#!/usr/bin/env python3
"""Finds the knee of a configuration under open-loop traffic (not part
of a run).

    python bench/sweep.py --workload r50dcn_b2.offline --rates 10,12,14 \
        --seconds 20 --seed 1

In one process, serves the named cell's configuration with Poisson
arrivals at each rate and prints one JSON line per rate: the latency
percentiles and the queue after each step in the window's first and
second halves.  The knee is the highest rate at which the queue does
not grow over the window; an open-loop traffic file takes about four
fifths of it.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    root = bench_run.ROOT
    sys.path.insert(0, str(root))
    sys.path.insert(0, str(root / "src"))
    import jax
    from bench.checks import percentile
    spec, cell, cfg = bench_run.load_spec(root, args.workload)
    device = bench_run.check_device(cell["chips"])
    bench_run.enable_compile_cache()
    family = importlib.import_module(f"bench.families.{cfg['family']}")
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        system = bench_run.build(family, cfg, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = {"loop": "open", "arrivals": "poisson", "rate_per_s": rate}
        result, _, _ = bench_run.run_cell(
            root, spec, cell, cfg, args.seed, args.seconds, False,
            device=device, system=system, traffic=traffic)
        rec = system.record
        lat = sorted(rec.latencies_s)
        # The queue while requests still arrive (the drain after the
        # last arrival is left out).
        end = rec.window[0] + args.seconds
        queued = [q for (t0, _, _), q in zip(rec.steps, rec.queued)
                  if t0 <= end]
        half = len(queued) // 2
        print(json.dumps({
            "rate_per_s": rate, "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "p50_ms": percentile(lat, 0.5) * 1e3,
            "p95_ms": percentile(lat, 0.95) * 1e3,
            "window_s": rec.window_s, "steps": len(rec.steps),
            "queued_first_half": sum(queued[:half]) / max(half, 1),
            "queued_second_half": sum(queued[half:])
            / max(len(queued) - half, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
