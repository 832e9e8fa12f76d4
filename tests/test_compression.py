"""Error-feedback int8 compression: quantization error bounded, error
feedback contracts (time-averaged gradient preserved), psum form works."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:            # fallback: deterministic parametrize shim
    from _propshim import given, settings, st

from repro.distributed.compression import (compressed_psum, ef_compress_grads,
                                           init_ef_state)


@given(seed=st.integers(0, 2**16), scale=st.floats(1e-3, 1e3))
@settings(max_examples=20, deadline=None)
def test_single_step_quantization_error_bounded(seed, scale):
    g = jax.random.normal(jax.random.PRNGKey(seed), (64,)) * scale
    tree = {"g": g}
    ef = init_ef_state(tree)
    deq, ef2 = ef_compress_grads(tree, ef)
    err = jnp.max(jnp.abs(deq["g"] - g))
    step = jnp.max(jnp.abs(g)) / 127.0
    assert float(err) <= float(step) * 0.51 + 1e-6


def test_error_feedback_preserves_average_gradient():
    """Sum over T steps of dequantized grads ~= sum of true grads —
    the EF contraction property that keeps training unbiased."""
    key = jax.random.PRNGKey(0)
    g_const = jax.random.normal(key, (32,)) * 0.01   # small => coarse quant
    tree = {"g": g_const}
    ef = init_ef_state(tree)
    total = jnp.zeros_like(g_const)
    for t in range(50):
        deq, ef = ef_compress_grads(tree, ef)
        total = total + deq["g"]
    avg = total / 50
    np.testing.assert_allclose(avg, g_const, rtol=0.02, atol=1e-5)
    # and the residual is bounded (no drift)
    assert float(jnp.max(jnp.abs(ef["g"]))) <= \
        float(jnp.max(jnp.abs(g_const))) + 1e-6


def test_compressed_psum_on_mesh():
    from jax.sharding import AxisType, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(AxisType.Auto,))
    x = jax.random.normal(jax.random.PRNGKey(1), (8,))

    f = jax.shard_map(
        functools.partial(compressed_psum, axis_name="data"),
        mesh=mesh, in_specs=P(None), out_specs=P(None))
    got = f(x)
    # 1 device: psum is identity; error is pure quantization
    err = jnp.max(jnp.abs(got - x))
    assert float(err) <= float(jnp.max(jnp.abs(x))) / 127.0 * 0.51 + 1e-6


# ---------------------------------------------------------------------------
# PR 6: shared-grid determinism / shard symmetry
# ---------------------------------------------------------------------------

def test_compressed_psum_shard_symmetric_and_deterministic():
    """All shards quantize onto the pmax-agreed grid BEFORE the int32
    psum, so the collective is invariant to which shard holds the
    largest gradient and to reduction grouping.  (vmap with an axis
    name runs the real pmax/psum collectives across the stacked axis.)"""
    from repro.distributed.compression import _psum_int8

    big = jnp.array([10.0, -5.0, 2.5, 0.1])
    small = jnp.array([0.01, -0.02, 0.005, 0.0])
    shards = jnp.stack([big, small])

    out = jax.vmap(lambda x: compressed_psum(x, "i"), axis_name="i")(shards)
    # every shard sees the identical replicated sum
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))

    # matches the shared-grid math exactly
    scale = float(jnp.max(jnp.abs(shards)) / 127.0 + 1e-12)
    q = np.clip(np.round(np.asarray(shards) / scale), -127, 127)
    expected = (q[0] + q[1]) * scale
    np.testing.assert_allclose(np.asarray(out[0]), expected, rtol=1e-6)

    # shard order must not matter (symmetry)
    out_rev = jax.vmap(lambda x: compressed_psum(x, "i"),
                       axis_name="i")(shards[::-1])
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out_rev[0]))

    # error vs the true sum is bounded by one shared-grid LSB per shard
    true = np.asarray(big + small)
    assert np.max(np.abs(expected - true)) <= 2 * scale * 0.51 + 1e-6

    # and the payload path really is the int8 collective helper
    direct = jax.vmap(
        lambda x: _psum_int8(
            jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8),
            jnp.float32(scale), "i"),
        axis_name="i")(shards)
    np.testing.assert_allclose(np.asarray(direct[0]), expected, rtol=1e-6)
