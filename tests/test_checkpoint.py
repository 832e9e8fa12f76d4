"""Checkpointing: bit-exact roundtrip, atomicity, keep-k, async, elastic."""
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, latest_step,
                              restore_checkpoint, save_checkpoint)


def _tree(key):
    k1, k2 = jax.random.split(key)
    return {"a": jax.random.normal(k1, (8, 16)),
            "b": {"c": jnp.arange(5, dtype=jnp.int32),
                  "d": jax.random.normal(k2, (3,)).astype(jnp.bfloat16)}}


def test_roundtrip_bit_exact(tmp_path):
    tree = _tree(jax.random.PRNGKey(0))
    save_checkpoint(tmp_path, 7, tree)
    like = jax.tree_util.tree_map(jnp.zeros_like, tree)
    restored, step = restore_checkpoint(tmp_path, like)
    assert step == 7
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_keep_k_gc(tmp_path):
    tree = _tree(jax.random.PRNGKey(1))
    for s in range(6):
        save_checkpoint(tmp_path, s, tree, keep=2)
    names = sorted(p.name for p in tmp_path.iterdir())
    assert names == ["step_00000004", "step_00000005"]


def test_latest_ignores_partial(tmp_path):
    tree = _tree(jax.random.PRNGKey(2))
    save_checkpoint(tmp_path, 3, tree)
    # simulate a crash mid-write: tmp dir + incomplete final dir
    (tmp_path / "step_00000009.tmp").mkdir()
    (tmp_path / "step_00000010").mkdir()   # no manifest -> incomplete
    assert latest_step(tmp_path) == 3


def test_async_manager_and_wait(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3, async_write=True)
    tree = _tree(jax.random.PRNGKey(3))
    mgr.save(11, tree)
    mgr.wait()
    assert mgr.latest_step() == 11
    restored, step = mgr.restore(jax.tree_util.tree_map(
        jnp.zeros_like, tree))
    np.testing.assert_array_equal(restored["a"], tree["a"])


def test_elastic_restore_with_sharding(tmp_path):
    """Restore with explicit shardings (the elastic path: the restart
    mesh may differ from the save mesh)."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(AxisType.Auto,))
    tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    save_checkpoint(tmp_path, 1, tree)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    restored, _ = restore_checkpoint(tmp_path, tree, shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_array_equal(restored["w"], tree["w"])


def test_save_is_atomic_under_failure(tmp_path, monkeypatch):
    """If serialization dies mid-way, the previous checkpoint survives
    and the partial write is invisible to latest_step."""
    tree = _tree(jax.random.PRNGKey(4))
    save_checkpoint(tmp_path, 1, tree)

    calls = {"n": 0}
    orig = np.save

    def failing_save(path, arr):
        calls["n"] += 1
        if calls["n"] > 2:
            raise IOError("disk died")
        orig(path, arr)

    monkeypatch.setattr(np, "save", failing_save)
    with pytest.raises(IOError):
        save_checkpoint(tmp_path, 2, tree)
    monkeypatch.setattr(np, "save", orig)
    assert latest_step(tmp_path) == 1
    restored, _ = restore_checkpoint(tmp_path, jax.tree_util.tree_map(
        jnp.zeros_like, tree))
    np.testing.assert_array_equal(restored["a"], tree["a"])


# ---------------------------------------------------------------------------
# PR 6: integrity (CRC + treedef) and corruption fallback
# ---------------------------------------------------------------------------

from repro.checkpoint.checkpoint import (CheckpointCorruptError,  # noqa: E402
                                         complete_steps)
from repro.resilience import corrupt_checkpoint  # noqa: E402


def _zeros_like(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


@pytest.mark.parametrize("mode", ["truncate_leaf", "bad_manifest"])
def test_corrupt_latest_falls_back_to_previous(tmp_path, mode):
    """Acceptance: latest checkpoint damaged -> restore falls back to the
    newest step that passes full CRC verification."""
    t2 = _tree(jax.random.PRNGKey(5))
    t3 = jax.tree_util.tree_map(lambda x: x + 1, t2)
    save_checkpoint(tmp_path, 2, t2)
    save_checkpoint(tmp_path, 3, t3)
    corrupt_checkpoint(tmp_path, mode=mode)       # hits latest (step 3)
    restored, step = restore_checkpoint(tmp_path, _zeros_like(t2))
    assert step == 2
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(t2["a"]))


def test_crc_catches_silent_bitflip(tmp_path):
    """A flipped byte in the payload leaves shape/dtype intact — only
    the per-leaf CRC32 catches it."""
    t1 = _tree(jax.random.PRNGKey(6))
    t2 = jax.tree_util.tree_map(lambda x: x * 2, t1)
    save_checkpoint(tmp_path, 1, t1)
    save_checkpoint(tmp_path, 2, t2)
    leaf = tmp_path / "step_00000002" / "000.npy"
    raw = bytearray(leaf.read_bytes())
    raw[-1] ^= 0xFF                               # corrupt payload byte
    leaf.write_bytes(bytes(raw))
    restored, step = restore_checkpoint(tmp_path, _zeros_like(t1))
    assert step == 1                              # CRC rejected step 2
    with pytest.raises(CheckpointCorruptError, match="crc"):
        restore_checkpoint(tmp_path, _zeros_like(t1), step=2)


def test_explicit_step_corrupt_raises_no_fallback(tmp_path):
    t = _tree(jax.random.PRNGKey(7))
    save_checkpoint(tmp_path, 1, t)
    save_checkpoint(tmp_path, 2, t)
    corrupt_checkpoint(tmp_path, step=2, mode="truncate_leaf")
    with pytest.raises(CheckpointCorruptError):
        restore_checkpoint(tmp_path, _zeros_like(t), step=2)


def test_all_checkpoints_corrupt_raises(tmp_path):
    t = _tree(jax.random.PRNGKey(8))
    save_checkpoint(tmp_path, 1, t)
    corrupt_checkpoint(tmp_path, step=1, mode="bad_manifest")
    with pytest.raises(CheckpointCorruptError):
        restore_checkpoint(tmp_path, _zeros_like(t))


def test_leaf_count_mismatch_is_friendly_valueerror(tmp_path):
    """Satellite: restoring into a structurally different target is a
    caller bug — a ValueError naming the path and both leaf counts, and
    never a silent fallback."""
    t = _tree(jax.random.PRNGKey(9))              # 3 leaves
    save_checkpoint(tmp_path, 4, t)
    wrong = {"only": jnp.zeros((2, 2))}           # 1 leaf
    with pytest.raises(ValueError) as ei:
        restore_checkpoint(tmp_path, wrong)
    msg = str(ei.value)
    assert "step_00000004" in msg and "3" in msg and "1" in msg


def test_treedef_mismatch_is_valueerror(tmp_path):
    """Same leaf count, different structure: the stored treedef is
    validated against the restore target."""
    t = {"a": jnp.zeros((2,)), "b": jnp.ones((3,))}
    save_checkpoint(tmp_path, 1, t)
    wrong = {"x": jnp.zeros((2,)), "y": jnp.ones((3,))}
    with pytest.raises(ValueError, match="different structure"):
        restore_checkpoint(tmp_path, wrong)


def test_manager_sweeps_stale_tmp_dirs(tmp_path):
    (tmp_path / "step_00000005.tmp").mkdir(parents=True)
    (tmp_path / "step_00000005.tmp" / "000.npy").write_bytes(b"junk")
    mgr = CheckpointManager(tmp_path, keep=2)
    assert not (tmp_path / "step_00000005.tmp").exists()
    t = _tree(jax.random.PRNGKey(10))
    mgr.save(1, t)
    mgr.wait()
    assert mgr.latest_step() == 1


def test_async_wait_propagates_writer_error(tmp_path):
    """Satellite: an exception in the async writer thread surfaces on
    the next wait() instead of being lost."""
    target = tmp_path / "ckpt"
    target.write_text("i am a file, not a directory")
    mgr = CheckpointManager(target, keep=2, async_write=True)
    t = {"w": jnp.ones((2, 2))}
    mgr.save(1, t)
    with pytest.raises(Exception) as ei:
        mgr.wait()
    assert "ckpt" in str(ei.value) or isinstance(
        ei.value, (OSError, NotADirectoryError, FileExistsError))
    mgr.wait()                                    # error raised once


def test_complete_steps_newest_first(tmp_path):
    t = {"w": jnp.ones((2,))}
    for s in (1, 5, 3):
        save_checkpoint(tmp_path, s, t)
    (tmp_path / "step_00000007").mkdir()          # incomplete
    assert complete_steps(tmp_path) == [5, 3, 1]
