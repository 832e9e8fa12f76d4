"""Training launcher: --arch <id> on the current host's mesh.

    PYTHONPATH=src python -m repro.launch.train --arch glm4-9b \
        --steps 50 [--reduced/--full] [--ckpt DIR] [--microbatches N] \
        [--grad-compression int8_ef]

Default is the REDUCED same-family config (this container is CPU-only;
the full configs need the production cluster — their step functions are
exactly what ``repro.launch.dryrun`` lowers for the 256/512-chip
meshes).  The loop is the production Trainer: sharded params, gradient
accumulation, async atomic checkpoints, auto-resume, restore-and-replay
on failure.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.data import (DetectionDataConfig, LMDataConfig, detection_batch,
                        lm_batch)
from repro.distributed.sharding import use_rules
from repro.launch.mesh import make_host_mesh
from repro.launch.platform import device_summary, enable_compile_cache
from repro.models import registry as reg
from repro.models.registry import reduced_config
from repro.models.resnet_dcn import ResNetDCNConfig
from repro.optim import default_optimizer_for, warmup_cosine
from repro.train import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=reg.names())
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--ckpt", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", choices=["int8_ef"], default=None)
    ap.add_argument("--lam", type=float, default=0.0,
                    help="Eq. 5 lambda (DCN archs)")
    ap.add_argument("--full", action="store_true",
                    help="use the FULL config (needs a real cluster)")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    print(f"device {device_summary()}, compile cache {cache_dir}")
    arch = reg.get(args.arch)
    cfg = arch.config if args.full else reduced_config(arch)
    mesh = make_host_mesh()
    print(f"arch={args.arch} ({'full' if args.full else 'reduced'}), "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    if isinstance(cfg, ResNetDCNConfig):
        from repro.models import resnet_dcn as R
        dcfg = DetectionDataConfig(img_size=cfg.img_size,
                                   global_batch=args.global_batch,
                                   num_classes=cfg.num_classes)
        lam = args.lam or (0.005 if cfg.offset_bound else 0.0)
        loss = lambda p, b: R.train_loss(p, cfg, b, lam=lam)  # noqa: E731
        batch_fn = lambda s: detection_batch(dcfg, s)          # noqa: E731
        with use_rules(mesh=mesh):
            from repro.models.layers import spec_tree
            specs = spec_tree(R.model_def(cfg))
            params = R.init_params(jax.random.PRNGKey(0), cfg)
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    else:
        from repro.models.transformer import (init_params, loss_fn,
                                              param_specs)
        dcfg = LMDataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                            global_batch=args.global_batch,
                            codebooks=cfg.codebooks)
        loss = lambda p, b: loss_fn(p, cfg, b)                 # noqa: E731
        batch_fn = lambda s: lm_batch(dcfg, s)                 # noqa: E731
        with use_rules(mesh=mesh):
            specs = param_specs(cfg)
            params = init_params(jax.random.PRNGKey(0), cfg)
        n_params = cfg.param_count()

    opt = default_optimizer_for(
        args.arch, n_params, warmup_cosine(3e-3, 10, args.steps))
    trainer = Trainer(
        loss_fn=loss, params=params, optimizer=opt, mesh=mesh,
        param_specs=specs, batch_fn=batch_fn,
        config=TrainerConfig(total_steps=args.steps,
                             ckpt_every=args.ckpt_every,
                             ckpt_dir=args.ckpt, log_every=10,
                             microbatches=args.microbatches,
                             grad_compression=args.grad_compression))
    if trainer.try_resume():
        print(f"resumed from step {trainer.step}")
    history = trainer.run()
    for h in history:
        print(h)


if __name__ == "__main__":
    main()
