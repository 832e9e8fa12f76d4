"""Elastic restart demo: checkpoint on one mesh, resume on ANOTHER.

Phase 1 (4 host devices, (data=2, model=2) mesh): train a small LM,
checkpoint.  Phase 2 (run again with 8 devices, (data=4, model=2) mesh):
auto-resume — the checkpoint carries no mesh assumptions, so the restore
reshard s onto whatever the restart sees; training continues bit-exactly
(stateless data pipeline).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/elastic_restart.py --phase 1 --ckpt /tmp/elastic
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/elastic_restart.py --phase 2 --ckpt /tmp/elastic
"""
import argparse
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.data import LMDataConfig, lm_batch
from repro.distributed.sharding import use_rules
from repro.models.transformer import (ModelConfig, init_params, loss_fn,
                                      param_specs)
from repro.optim import adamw, constant
from repro.train import Trainer, TrainerConfig


def build(ckpt: str, mesh):
    cfg = ModelConfig(name="elastic", n_layers=2, d_model=32, n_heads=4,
                      kv_heads=2, d_ff=64, vocab=32, dtype=jnp.float32)
    data = LMDataConfig(vocab=32, seq_len=32, global_batch=8, seed=11)
    with use_rules(mesh=mesh):
        specs = param_specs(cfg)
        params = init_params(jax.random.PRNGKey(0), cfg)
        tr = Trainer(
            loss_fn=lambda p, b: loss_fn(p, cfg, b), params=params,
            optimizer=adamw(constant(3e-3)), mesh=mesh, param_specs=specs,
            batch_fn=lambda s: lm_batch(data, s),
            config=TrainerConfig(total_steps=20, ckpt_every=10,
                                 ckpt_dir=ckpt, log_every=5))
    return tr


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", type=int, required=True)
    ap.add_argument("--ckpt", required=True)
    args = ap.parse_args()

    n = len(jax.devices())
    mesh = jax.make_mesh((n // 2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    print(f"phase {args.phase}: {n} devices, mesh "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")

    tr = build(args.ckpt, mesh)
    if args.phase == 1:
        tr.cfg.total_steps = 10
        with use_rules(mesh=mesh):
            tr.run()
        print(f"phase 1 done at step {tr.step}; loss "
              f"{tr.last_loss:.4f}")
    else:
        # try_resume reshards the whole bundle (params, optimizer state,
        # step) onto THIS mesh via CheckpointManager.restore(shardings=...)
        # — no hand-resharding needed.
        assert tr.try_resume(), "no checkpoint found"
        print(f"resumed at step {tr.step} onto the NEW mesh")
        with use_rules(mesh=mesh):
            tr.run()
        print(f"phase 2 done at step {tr.step}; loss "
              f"{tr.last_loss:.4f}")
        # oracle: a straight 20-step run must match.  NOT bit-exact:
        # phase 1 ran its first 10 steps on a different mesh, and
        # all-reduce grouping differs (fp32 reduction order) — the
        # difference is pure float non-associativity, ~1e-5.
        import numpy as np
        import tempfile
        ref = build(tempfile.mkdtemp(), mesh)
        with use_rules(mesh=mesh):
            ref.run()
        for a, b in zip(jax.tree_util.tree_leaves(tr.params),
                        jax.tree_util.tree_leaves(ref.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)
        print("elastic resume == straight run: OK "
              "(up to cross-mesh reduction order)")


if __name__ == "__main__":
    main()
