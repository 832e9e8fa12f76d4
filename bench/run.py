#!/usr/bin/env python3
"""Runs one benchmark cell once and prints its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic mix are found by name in
``BENCHMARK.json``: the configuration file names the model family
(``bench/families/<family>.py``), the traffic file the generator's
parameters (``bench/traffic/<name>.json``), and every metric is read by
``bench/metrics/<metric>.py``.  A new cell, configuration, traffic mix
or metric is a new file and a new entry; nothing here changes.

A run: refuses any device but a TPU (or fewer chips than the cell asks
for); builds the configuration from the seed (weights on the device,
calibration from the plain reference); warms the cell's one shape
through the engine; drives ``submit``/``step`` for ``--seconds``;
checks a seeded sample of what the window served against the plain
reference; prints each compared number beside its limit on standard
error, and one JSON line on standard output.  ``--trace 1`` runs the
same window under the profiler and reports the per-layer metrics.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# The persistent compilation cache lives at one fixed path inside the
# checkout, so the second run of a cell finds every program.
CACHE_DIR = ROOT / ".jax_cache"
SAMPLE_EXTRA = 8          # requests checked besides the tapped batch
TAP_STEP_CHOICES = 4      # the tapped step is one of the window's first


class Refused(Exception):
    """The run cannot report: wrong device, missing program, bad spec."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_spec(root: pathlib.Path, workload: str):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    cell = cells[workload]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((root / config["file"]).read_text())
    return spec, cell, cfg


def metrics_for(spec, cell, trace: bool) -> list[dict]:
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in spec[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def load_reader(root: pathlib.Path, name: str):
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_name = "bench_metric_" + "".join(c if c.isalnum() else "_"
                                         for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_device(chips: int) -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise Refused(f"no TPU: JAX runs on {d.platform!r} "
                      f"({d.device_kind}); this benchmark reports from a "
                      f"TPU only")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees "
                      f"{len(devices)} {d.device_kind}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": chips}


def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # The eager forward is hundreds of small programs that each compile
    # in under a second: keep them all, and evict none (a size limit
    # from the environment evicts the warm-up's own programs).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileCounter:
    """Counts compilations (and persistent-cache misses) inside a
    ``with`` block."""

    def __enter__(self):
        import jax
        self.compiles = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class GcPauses:
    """Counts the garbage collector's pauses inside a ``with`` block."""

    def __enter__(self):
        self.count, self.seconds, self.longest, self._t = 0, 0.0, 0.0, None
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            d = time.monotonic() - self._t
            self.count += 1
            self.seconds += d
            self.longest = max(self.longest, d)


class Tap:
    """The engine's tap, armed for the one step whose layers are checked."""

    def __init__(self):
        self.armed = False
        self.seen: dict = {}

    def __call__(self, name, x):
        if self.armed:
            self.seen[name] = x


class Record:
    """What one window did, for the metric readers."""

    def __init__(self):
        self.setup_s = 0.0
        self.window_s = 0.0
        self.steps: list[tuple[float, float, int]] = []  # start, end, served
        self.queued: list[int] = []          # requests queued after each step
        self.latencies_s: list[float] = []   # per request due; inf: not ok
        self.images_ok = 0
        self.queue_wait = (0.0, 0)           # seconds summed, requests
        self.work_per_step: list[dict] = []
        self.peaks: dict = {}
        self.trace: dict | None = None


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def drive(system, traffic, images, seconds, tap, tap_step, rec):
    """The measured window.  Returns the requests due in it."""
    from bench import traffic as traffic_mod
    engine = system.engine
    clock = time.monotonic
    due, reqs, done_at = [], [], {}
    pending: list = []
    steps = 0
    tap.seen, tap.uids = {}, []

    def submit(i, when):
        with annotate("bench/submit"):
            r = engine.submit(images(i))
        reqs.append(r)
        due.append(when)
        pending.append(r)

    def step():
        nonlocal steps
        tap.armed = steps == tap_step
        t0 = clock()
        with annotate("bench/step"):
            engine.step()
        t1 = clock()
        tap.armed = False
        with annotate("bench/fetch"):
            served = 0
            for r in list(pending):
                if r.done:
                    pending.remove(r)
                    done_at[id(r)] = t1
                    served += r.outcome == "ok"
        rec.steps.append((t0, t1, served))
        rec.queued.append(len(engine.queue))
        if steps == tap_step:
            tap.uids = [r.uid for r in reqs if done_at.get(id(r)) == t1]
        steps += 1

    q0 = system.queue_wait()
    t_start = clock()
    with annotate("bench/window"), CompileCounter() as compiles, \
            GcPauses() as pauses:
        if traffic["loop"] == "closed":
            end, i = t_start + seconds, 0
            while clock() < end:
                while len(engine.queue) < traffic["queued"]:
                    submit(i, clock())
                    i += 1
                step()
            # Requests left queued when the window closed were not due:
            # they belong to no step of the window.
            in_window = [k for k, r in enumerate(reqs) if id(r) in done_at]
        else:
            arrivals = t_start + traffic_mod.gaps(traffic, seconds,
                                                  system.seed)
            i, lateness = 0, []
            while i < len(arrivals) or len(engine.queue):
                now = clock()
                while i < len(arrivals) and arrivals[i] <= now:
                    lateness.append(now - arrivals[i])
                    submit(i, arrivals[i])
                    i += 1
                if len(engine.queue):
                    step()
                elif i < len(arrivals):
                    with annotate("bench/wait"):
                        time.sleep(max(0.0, arrivals[i] - clock()))
            in_window = list(range(len(reqs)))
            lateness.sort()
            log(f"generator lateness: median "
                f"{lateness[len(lateness) // 2] * 1e3:.3f} ms, max "
                f"{lateness[-1] * 1e3:.3f} ms over {len(lateness)} sends")
    t_end = rec.steps[-1][1] if traffic["loop"] == "closed" else clock()
    q1 = system.queue_wait()
    rec.window_s = t_end - t_start
    rec.queue_wait = (q1[0] - q0[0], q1[1] - q0[1])
    rec.window = (t_start, t_end)
    due_reqs = [reqs[k] for k in in_window]
    for k in in_window:
        r = reqs[k]
        ok = r.outcome == "ok"
        rec.images_ok += ok
        rec.latencies_s.append(done_at[id(r)] - due[k] if ok
                               else float("inf"))
    log(f"window: {rec.window_s:.3f} s, {len(rec.steps)} steps, "
        f"{len(due_reqs)} requests due, {rec.images_ok} served ok; "
        f"compilations in the window: {compiles.compiles} "
        f"(persistent-cache misses {compiles.misses})")
    step_s = sorted(e - s for s, e, _ in rec.steps)
    log(f"step seconds: min {step_s[0]:.4f}, median "
        f"{step_s[len(step_s) // 2]:.4f}, p90 "
        f"{step_s[int(0.9 * (len(step_s) - 1))]:.4f}, max {step_s[-1]:.4f}; "
        f"garbage collections in the window: {pauses.count} "
        f"({pauses.seconds:.4f} s, longest {pauses.longest:.4f} s)")
    return due_reqs


def warm_up(system, images, slots):
    """Serves two full batches: every shape the window uses (a partial
    batch is padded to the same static shape)."""
    engine = system.engine
    for i in range(2 * slots):
        engine.submit(images(-1 - i))
    while len(engine.queue):
        engine.step()


def sample_of(reqs, tap, seed):
    """The tapped batch's requests and ``SAMPLE_EXTRA`` others, drawn
    from the seed among those served ok."""
    from bench.traffic import rng
    ok = [r for r in reqs if r.outcome == "ok"]
    tapped = [r for r in ok if r.uid in set(getattr(tap, "uids", []))]
    rest = [r for r in ok if r not in tapped]
    pick = rng(seed, 4).choice(len(rest), size=min(SAMPLE_EXTRA, len(rest)),
                               replace=False)
    return tapped + [rest[j] for j in sorted(pick)]


def build(family, cfg, seed):
    """The configuration built from the seed, with its tap."""
    tap = Tap()
    system = family.System(cfg, seed, tap=tap, log=log)
    system.tap, system.seed = tap, seed
    return system


def run_cell(root, spec, cell, cfg, seed, seconds, trace, *, device,
             system=None, with_control=False, traffic=None):
    """One run of ``cell``.  Returns (result line, compared numbers,
    the control's numbers on the same sample or None).  ``system``
    reuses an already built configuration (its seed then stands);
    ``traffic`` replaces the cell's traffic mix (the knee sweep)."""
    import jax
    import numpy as np
    from bench import peaks as peaks_mod
    from bench import trace as trace_mod
    from bench import traffic as traffic_mod

    family = importlib.import_module(f"bench.families.{cfg['family']}")
    traffic = traffic or traffic_mod.load(root, cell["traffic"])
    rec = Record()
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        if system is None:
            system = build(family, cfg, seed)
        seed, tap = system.seed, system.tap
        images = traffic_mod.Images(seed,
                                    traffic.get("sizes", [cfg["bucket"]]))
        t0 = time.monotonic()
        warm_up(system, images, cfg["slots"])
        log(f"set-up phase warm_up: {time.monotonic() - t0:.3f} s")
        # Everything alive now (the program's compiled-op caches, the
        # weights) lives as long as the server: a full collection in the
        # window would scan it all.  Freeze it, as a long-running server
        # does, so collections scan only what the window allocates.
        gc.collect()
        gc.freeze()
        rec.setup_s = time.monotonic() - T_START
        log(f"set-up: {rec.setup_s:.3f} s")
        tap_step = int(traffic_mod.rng(seed, 5).integers(TAP_STEP_CHOICES))
        tmp = None
        if trace:
            tmp = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host TraceMe events only
            jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            reqs = drive(system, traffic, images, seconds, tap, tap_step,
                         rec)
        finally:
            if trace:
                jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(
        stats.get("peak_bytes_in_use", 0)))
    rec.peaks = peaks_mod.lookup(device["kind"])
    rec.work_per_step = [dict(w, ops=w["ops"] * cfg["slots"],
                              bytes=w["bytes"] * cfg["slots"])
                         for w in system.work_per_image()]
    if trace:
        try:
            rec.trace = trace_mod.reduce_dir(tmp)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        device.update(busy_s=rec.trace["busy_s"],
                      window_s=rec.trace["window_s"])

    sample = sample_of(reqs, tap, seed)
    imgs = [np.asarray(r.image, np.float32) for r in sample]
    numbers = system.numbers(imgs, [r.result for r in sample], tap.seen)
    control = None
    if with_control:
        control = system.numbers(imgs, *system.control(imgs, tap.seen))
    numbers["off_rung"] = float(sum(
        r.outcome != "ok" or r.ladder != cfg["rung"] or r.retries > 0
        for r in reqs))
    limits = dict(cfg["limits"], off_rung=0.0)
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(f"checked {len(sample)} of {len(reqs)} requests and the "
        f"{len(tap.seen) // 2} DCLs of one served batch")

    metrics = {}
    for m in metrics_for(spec, cell, trace):
        value = load_reader(root, m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": len(reqs),
              "failed": sum(r.outcome != "ok" for r in reqs),
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = rec.trace["breakdown"]
    result["checks"] = checks
    system.record = rec
    return result, numbers, control


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        spec, cell, cfg = load_spec(ROOT, args.workload)
        if importlib.util.find_spec("repro") is None:
            raise Refused(f"the program (src/repro) is not in {ROOT}")
        device = check_device(cell["chips"])
    except (Refused, OSError, KeyError, ValueError) as e:
        log(f"bench: cannot run: {e}")
        return 2
    enable_compile_cache()
    result, _, _ = run_cell(ROOT, spec, cell, cfg, args.seed, args.seconds,
                            bool(args.trace), device=device)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
