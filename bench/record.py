#!/usr/bin/env python3
"""Records a cell's window under the profiler and reduces it to the
program's spans (not part of a run).

    python bench/record.py --workload <name> --seed <n> --seconds <s> \
        [--out DIR]

Builds the cell's configuration from the seed, warms it up, and serves
``--seconds`` of its traffic under ``jax.profiler`` as ``bench/run.py
--trace 1`` does (a window that ends with the step that crosses it;
``--seconds 0.001`` is one step).  Prints the stall table
(``bench/spans.py``) on standard error and one JSON line: the
benchmark's trace reduction (``bench/trace.py``) and the spans'
(``readings``, ``spans``, ``idle_gaps``, ``stalls``).  ``--out`` keeps
the ``.xplane.pb`` there.
"""
from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import shutil
import sys
import tempfile

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    root = bench_run.ROOT
    sys.path.insert(0, str(root))
    sys.path.insert(0, str(root / "src"))
    spec, cell, cfg = bench_run.load_spec(root, args.workload)
    bench_run.check_device(cell["chips"])
    bench_run.enable_compile_cache()

    import jax
    from bench import spans, trace, traffic as traffic_mod
    family = importlib.import_module(f"bench.families.{cfg['family']}")
    traffic = traffic_mod.load(root, cell["traffic"])
    rec = bench_run.Record()
    tmp = tempfile.mkdtemp(prefix="bench-record-")
    try:
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            system = bench_run.build(family, cfg, args.seed)
            images = traffic_mod.Images(args.seed,
                                        traffic.get("sizes", [cfg["bucket"]]))
            bench_run.warm_up(system, images, cfg["slots"])
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
            try:
                bench_run.drive(system, traffic, images, args.seconds,
                                system.tap, -1, rec)
            finally:
                jax.profiler.stop_trace()
        path = max(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        reduced = spans.reduce_file(path)
        spans.log_stalls(reduced)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            shutil.copy(path, os.path.join(args.out, os.path.basename(path)))
        print(json.dumps({"workload": args.workload, "seed": args.seed,
                          "trace": trace.reduce_file(path),
                          "readings": spans.readings(reduced), **reduced}),
              flush=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
