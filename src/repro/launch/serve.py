"""Serving launcher: continuous batching for --arch <id>.

LM archs run the token slot engine:

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --requests 12 [--slots 4] [--cache-len 128] [--ckpt DIR]

DCL detection archs run the shape-bucketed engine (PR 7) — calibrated
int8_chain by default, with deadlines / admission control / the
per-request degradation ladder live:

    PYTHONPATH=src python -m repro.launch.serve --arch resnet50_dcn \
        --requests 12 [--buckets 64,128] [--quant int8_chain] \
        [--deadline 30] [--shed-policy reject_new] [--telemetry OUT.json]

The paper's production path at published widths (ResNet-50, 12 DCLs,
B=2, 512x512 images) on a TPU:

    PYTHONPATH=src python -m repro.launch.serve \
        --arch resnet50_dcn_bounded --full --buckets 512

Reduced same-family config unless ``--full``; weights come from a
seeded init or, with ``--ckpt``, from a checkpoint produced by
``repro.launch.train``.  Every result line is read against the device
line printed first.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.launch.platform import device_summary, enable_compile_cache
from repro.models import registry as reg
from repro.models.registry import reduced_config
from repro.models.resnet_dcn import ResNetDCNConfig
from repro.serve import (DCLServeConfig, DCLServingEngine, LADDER,
                         Request, ServeConfig, ServingEngine)


def serve_detection(cfg: ResNetDCNConfig, args) -> None:
    from repro.models import resnet_dcn as R
    from repro.quant.calibrate import calibrate_resnet_dcn

    if cfg.offset_bound is None:
        cfg = dataclasses.replace(cfg, offset_bound=2.0)
    cfg = dataclasses.replace(cfg, use_kernel=True)
    buckets = tuple(int(b) for b in args.buckets.split(",")) \
        if args.buckets else (cfg.img_size if args.full else 64,)
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    if args.ckpt:
        from repro.checkpoint import restore_checkpoint
        restored, step = restore_checkpoint(args.ckpt, {"params": params})
        params = restored["params"]
        print(f"restored params from step {step}")

    rng = np.random.RandomState(0)
    table = None
    if args.quant in ("int8_chain", "int8"):
        t0 = time.monotonic()
        table = calibrate_resnet_dcn(
            params, cfg,
            [rng.randn(2, b, b, 3).astype(np.float32) for b in buckets])
        print(f"calibrated scale table in {time.monotonic() - t0:.1f}s "
              f"({sorted(k for k in table if k != '_meta')})")

    engine = DCLServingEngine(
        params, cfg,
        DCLServeConfig(buckets=buckets, slots=args.slots,
                       quant=args.quant,
                       queue_capacity=args.queue_capacity,
                       shed_policy=args.shed_policy,
                       default_deadline=args.deadline),
        scale_table=table)
    for uid in range(args.requests):
        b = buckets[uid % len(buckets)]
        engine.submit(rng.randn(b, b, 3).astype(np.float32))

    t0 = time.monotonic()
    engine.run_until_drained()
    dt = time.monotonic() - t0
    ok = [r for r in engine.completed if r.outcome == "ok"]
    lats = sorted(r.latency_s() for r in ok)
    print(f"served {len(ok)}/{len(engine.completed)} requests in "
          f"{engine.steps} batched steps ({dt:.1f}s, "
          f"{len(ok) / max(dt, 1e-9):.2f} req/s, compiles included)")
    if lats:
        print(f"  p50 latency {lats[len(lats) // 2] * 1e3:.0f} ms, "
              f"max {lats[-1] * 1e3:.0f} ms")
    print(f"  counters: {engine.counters}")
    if args.telemetry:
        from repro.resilience import dump_telemetry
        dump_telemetry(args.telemetry, engine.telemetry())
        print(f"  telemetry -> {args.telemetry}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=reg.names())
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--ckpt", default=None)
    # DCL detection engine knobs
    ap.add_argument("--full", action="store_true",
                    help="serve the FULL (published-width) config")
    ap.add_argument("--buckets", default=None,
                    help="comma-separated square shape buckets (default: "
                         "the config's image size with --full, else 64)")
    ap.add_argument("--quant", default="int8_chain", choices=LADDER)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline in seconds")
    ap.add_argument("--queue-capacity", type=int, default=64)
    ap.add_argument("--shed-policy", default="reject_new",
                    choices=("reject_new", "shed_oldest"))
    ap.add_argument("--telemetry", default=None,
                    help="write engine telemetry JSON here")
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    print(f"device {device_summary()}, compile cache {cache_dir}")
    arch = reg.get(args.arch)
    cfg = arch.config if args.full else reduced_config(arch)
    if isinstance(cfg, ResNetDCNConfig):
        serve_detection(cfg, args)
        return
    if cfg.codebooks > 1:
        raise SystemExit("the slot engine tracks one token per slot; "
                         "multi-codebook decoding (musicgen) needs a "
                         "(slots, codebooks) token state — not wired yet")

    from repro.models.transformer import init_params
    params = init_params(jax.random.PRNGKey(0), cfg)
    if args.ckpt:
        from repro.checkpoint import restore_checkpoint
        bundle = {"params": params}
        restored, step = restore_checkpoint(args.ckpt, bundle)
        params = restored["params"]
        print(f"restored params from step {step}")

    engine = ServingEngine(params, cfg,
                           ServeConfig(slots=args.slots,
                                       cache_len=args.cache_len))
    rng = np.random.RandomState(0)
    for uid in range(args.requests):
        prompt = rng.randint(0, cfg.vocab,
                             rng.randint(4, 12)).astype(np.int32)
        engine.submit(Request(uid=uid, prompt=prompt,
                              max_new_tokens=args.max_new_tokens))

    t0 = time.monotonic()
    steps = 0
    while engine.queue or any(r is not None for r in engine.active):
        engine.step()
        steps += 1
    dt = time.monotonic() - t0
    toks = sum(len(r.output) for r in engine.completed)
    print(f"served {len(engine.completed)} requests / {toks} tokens in "
          f"{steps} batched steps ({dt:.1f}s, {toks / dt:.1f} tok/s, "
          f"compiles included)")
    for r in engine.completed[:3]:
        print(f"  req {r.uid}: {r.output[:8]}...")


if __name__ == "__main__":
    main()
