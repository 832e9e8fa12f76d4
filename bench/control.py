#!/usr/bin/env python3
"""Readings that the limits of a cell are set from (not part of a run).

    python bench/control.py --workload <name> --seeds 1-12 \
        --control-seeds 1-3 --seconds 3 [--out FILE]

In one process, for each seed: builds the cell's configuration from the
seed, serves a short window of the cell's own traffic at its own size,
and prints the compared numbers of the program and, for the control
seeds, of the control (the plain reference in the program's place, one
precision below the configuration's) on the same sample.  Each line is
one JSON object; the limits in ``bench/configs/<config>.json`` are set
between the program's largest reading and the control's smallest.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys

import run as bench_run


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += range(int(lo), int(hi or lo) + 1)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    root = bench_run.ROOT
    sys.path.insert(0, str(root))
    sys.path.insert(0, str(root / "src"))
    spec, cell, cfg = bench_run.load_spec(root, args.workload)
    device = bench_run.check_device(cell["chips"])
    bench_run.enable_compile_cache()
    family = importlib.import_module(f"bench.families.{cfg['family']}")
    out = open(args.out, "a") if args.out else sys.stdout
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        import jax
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            system = bench_run.build(family, cfg, seed)
        result, numbers, control = bench_run.run_cell(
            root, spec, cell, cfg, seed, args.seconds, False, device=device,
            system=system, with_control=seed in args.control_seeds)
        line = {"workload": args.workload, "seed": seed,
                "correct": result["correct"], "program": numbers,
                "control": control, "attempted": result["attempted"]}
        print(json.dumps(line), file=out, flush=True)
        del system
    return 0


if __name__ == "__main__":
    sys.exit(main())
