#!/usr/bin/env python3
"""Smoke run of the bounded-DCL detector on a TPU, through its serving
entry points, at published widths.

    python chip_smoke.py             # one chip (device 0)
    python chip_smoke.py --chips 4   # spatial height sharding, 4 chips

One chip: ``resnet50_dcn_bounded`` (ResNet-50, widths 256-2048, 12 DCLs,
offset bound B=2) from a seeded init is calibrated on seeded 512x512
images and served by ``DCLServingEngine`` on its production rung,
``int8_chain`` (8 requests, 4 slots).  Every DCL of the first served
batch is compared, on the input the engine fed it, with the XLA
reference lowering of the same layer (``platform='xla_ref'``); every
served result with the chain fake-quant reference (``use_kernel=False``,
same scales); one fp32-kernel batch with the fp32 reference.  The
compiled DCL programs must contain the Pallas kernel
(``tpu_custom_call``).

``--chips 4``: a 1024x1024 bucket served height-sharded over four chips
(``spatial_shards=((1024, 4),)``, entry rung ``int8``) against the same
seeded requests on the unsharded ``int8`` path on device 0.

The whole run (both engines and every reference) is under
``jax.default_matmul_precision("highest")``: the Pallas kernels fix
their own MXU precision, and the XLA convolutions around them must not
round to bf16 on one side of a comparison only.

Exits non-zero, printing no result, when JAX finds no TPU or any phase
fails.  The last line of a passing run is one JSON object naming the
device.  Times printed here are smoke figures: first calls include
compilation, and nothing is repeated enough to be a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SMOKE_RES = 512           # the config's published input resolution
SPATIAL_RES = 1024        # megapixel bucket of the spatial path
SPATIAL_SHARDS = 4
SEED = 0
REQUESTS = 8              # two full batches: one compiles, one is steady
SLOTS = 4
SPATIAL_REQUESTS = 2      # one batch per spatial engine

# Tolerances, each with its reason.
#
# int8 datapaths end to end (served int8_chain vs the chain fake-quant
# reference; spatial int8 vs unsharded int8): both sides quantize onto
# the same calibrated grids, but an fp32 value that lands within an ulp
# of a .5 rounding boundary rounds either way, and each such one-step
# flip perturbs the next layers enough to flip more: over twelve int8
# DCLs the flips cascade, so end to end the two can differ by far more
# than one step.  The cascade's size is measured, not guessed: the
# reference is run again on inputs perturbed by 1e-6 (about ten fp32
# ulps of the unit-variance images — the size of the rounding
# differences between two correct implementations), and the relative
# L2 error of that run against the unperturbed one is the noise floor.
# The served results may differ from the reference by at most
# INT8_FLOOR_FACTOR times the worst floor (and INT8_MIN_L2 in any
# case).  This end-to-end bound is loose by nature: it catches faults
# outside the DCLs and gross ones inside.  The sharp check is per layer
# (below): every DCL of a served batch against the reference, fed the
# input the engine fed it.
INT8_FLOOR_SEEDS = 3
INT8_FLOOR_NOISE = 1e-6
INT8_FLOOR_FACTOR = 4.0
INT8_MIN_L2 = 1e-3
# One served chained DCL layer against the XLA reference lowering on
# the same input: at most one step of the int8 output grid (an fp32
# value within an ulp of a rounding boundary), at no more than 0.5% of
# the outputs (the same bound as tests/test_chain.py).
LAYER_MAX_STEPS = 1
LAYER_MAX_FRACTION = 5e-3
# fp32 kernel vs fp32 reference end to end: the same arithmetic summed
# in a different order (~1e-6 relative per DCL), amplified by at most
# ~100x through twelve GroupNormed blocks.
FP32_E2E_REL = 1e-3


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def rel_delta(got, want) -> float:
    """max |got - want| / max |want|."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def l2_delta(pairs) -> float:
    """||got - want||_2 / ||want||_2 over every (got, want) pair."""
    import numpy as np
    num = den = 0.0
    for got, want in pairs:
        d = np.asarray(got, np.float64) - np.asarray(want, np.float64)
        num += float(np.sum(d * d))
        den += float(np.sum(np.asarray(want, np.float64) ** 2))
    return (num / max(den, 1e-300)) ** 0.5


def int8_noise_floor(params, cfg, table, x) -> float:
    """Worst relative L2 change of ``cfg``'s forward when its input moves
    by ``INT8_FLOOR_NOISE`` — the rounding-flip cascade of an int8
    datapath that has nothing wrong with it."""
    import numpy as np
    fwd = jitted_forward(cfg, table)
    base, _ = fwd(params, x)
    worst = 0.0
    for seed in range(INT8_FLOOR_SEEDS):
        noise = np.random.RandomState(seed).randn(*x.shape)
        out, _ = fwd(params, (x + INT8_FLOOR_NOISE * noise).astype(x.dtype))
        worst = max(worst, l2_delta((out[k], base[k])
                                    for k in ("cls", "box")))
    return worst


def check_int8_agreement(pairs, floor, label) -> None:
    pairs = list(pairs)
    l2 = l2_delta(pairs)
    worst = max(rel_delta(g, w) for g, w in pairs)
    tol = max(INT8_FLOOR_FACTOR * floor, INT8_MIN_L2)
    print(f"{label}: ||delta|| / ||ref|| = {l2:.3e} (tolerance {tol:.3e} = "
          f"max({INT8_FLOOR_FACTOR:g} x noise floor {floor:.3e}, "
          f"{INT8_MIN_L2:.0e})); max |delta| / max |ref| = {worst:.3e}",
          flush=True)
    check(l2 <= tol, f"{label}: {l2:.3e} > {tol:.3e}")


class Phases:
    """Times each phase and prints it.  A failed check is recorded and
    the run goes on to the next phase (one chip call reports every
    check); any failure makes the run exit non-zero.  Any other
    exception ends the run at once."""

    def __init__(self):
        self.seconds: dict[str, float] = {}
        self.failures: list[str] = []

    def run(self, name: str, fn, *args, **kw):
        t0 = time.perf_counter()
        out = None
        try:
            out = fn(*args, **kw)
        except SmokeFailure as e:
            self.failures.append(f"{name}: {e}")
            print(f"phase {name} FAILED: {e}", flush=True)
        dt = time.perf_counter() - t0
        self.seconds[name] = dt
        print(f"phase {name}: {dt:.2f} s (first calls include compilation)",
              flush=True)
        return out


def seeded_images(seed: int, n: int, res: int):
    import numpy as np
    rng = np.random.RandomState(seed)
    return [rng.randn(res, res, 3).astype(np.float32) for _ in range(n)]


def serve(engine, images):
    """Serve ``images`` to completion; returns the requests and the wall
    seconds of each engine step."""
    reqs = [engine.submit(img) for img in images]
    step_s = []
    while len(engine.queue):
        t0 = time.perf_counter()
        engine.step()
        step_s.append(time.perf_counter() - t0)
    return reqs, step_s


def check_served(engine, reqs, *, rung: str) -> None:
    """Every request came back ok on its entry rung, with no retry and
    no degradation."""
    for r in reqs:
        check(r.outcome == "ok", f"request {r.uid}: outcome {r.outcome} "
                                 f"({r.error})")
        check(r.retries == 0 and not r.degraded and r.ladder == rung,
              f"request {r.uid}: retries={r.retries} degraded={r.degraded} "
              f"ladder={r.ladder} (entry rung {rung})")
    counters = engine.counters
    check("retries" not in counters and "degraded_batches" not in counters,
          f"engine counters show retries/degradation: {counters}")
    print(f"{len(reqs)} requests ok on {rung}, no retry, no degradation; "
          f"counters {counters}", flush=True)


def batches_of(reqs, slots):
    import numpy as np
    for i in range(0, len(reqs), slots):
        chunk = reqs[i:i + slots]
        x = np.zeros((slots,) + np.asarray(chunk[0].image).shape,
                      np.float32)
        for j, r in enumerate(chunk):
            x[j] = r.image
        yield chunk, x


def jitted_forward(cfg, table=None):
    """``R.forward`` under one jit (the engine's own forward is eager):
    returns the head outputs and every tapped DCL input/output."""
    import jax
    from repro.models import resnet_dcn as R

    def run(params, images):
        taps = {}
        out, _ = R.forward(params, cfg, images, quant_scales=table,
                           tap=lambda n, a: taps.__setitem__(n, a))
        return {k: out[k] for k in ("cls", "box")}, taps
    return jax.jit(run)


def calibration_forward(params, cfg, images, *, tap):
    """The ``forward=`` of ``calibrate_resnet_dcn``: one compiled program
    of the fp32 reference instead of the eager sweep, feeding the same
    taps."""
    _, taps = jitted_forward(cfg)(params, images)
    for name, act in taps.items():
        tap(name, act)


def compare_to_forward(reqs, slots, params, cfg, table, *, label):
    """Compare each served int8 result with ``R.forward`` under ``cfg``,
    against that forward's own noise floor."""
    fwd = jitted_forward(cfg, table)
    pairs, floor = [], 0.0
    for chunk, x in batches_of(reqs, slots):
        out, _ = fwd(params, x)
        floor = max(floor, int8_noise_floor(params, cfg, table, x))
        for j, r in enumerate(chunk):
            pairs += [(r.result[k], out[k][j]) for k in ("cls", "box")]
    check_int8_agreement(pairs, floor, label)


def dcl_call(layer, cfg, name, x, table, *, quant):
    """DCL ``name`` of the model (its parameters ``layer``), called the
    way ``resnet_dcn`` calls it."""
    from repro.models.layers import dcl_apply
    y, _ = dcl_apply(layer, x, stride=dcl_strides(cfg)[name],
                     offset_bound=cfg.offset_bound,
                     use_kernel=cfg.use_kernel, quant=quant,
                     quant_scales=table.get(name),
                     shard_spatial=cfg.shard_spatial)
    return y


def dcl_strides(cfg):
    from repro.serve import bucket_layer_dims
    return {k: d["stride"] for k, d in bucket_layer_dims(cfg, SMOKE_RES)
            .items()}


def assert_kernel_compiled(fn, args, label, *, also=()):
    import jax
    text = jax.jit(fn).lower(*args).compile().as_text()
    for needle in ("tpu_custom_call", *also):
        check(needle in text, f"{label}: compiled program has no {needle}")
    print(f"{label}: compiled program contains "
          f"{', '.join(('tpu_custom_call', *also))}", flush=True)


def one_chip(phases: Phases) -> None:
    import jax
    import numpy as np
    from repro.configs.resnet50_dcn import CONFIG_BOUNDED
    from repro.launch.platform import platform_scope
    from repro.models import resnet_dcn as R
    from repro.quant.calibrate import calibrate_resnet_dcn
    from repro.serve import DCLServeConfig, DCLServingEngine

    cfg = dataclasses.replace(CONFIG_BOUNDED, use_kernel=True)
    params = phases.run("init", R.init_params, jax.random.PRNGKey(SEED),
                        cfg)
    calib = np.stack(seeded_images(SEED + 1, SLOTS, SMOKE_RES))
    table = phases.run("calibrate", calibrate_resnet_dcn, params,
                       dataclasses.replace(cfg, use_kernel=False), [calib],
                       forward=calibration_forward)
    # The first served batch's DCL inputs and outputs, as the engine
    # computed them: the layer checks below hold each served layer to
    # the reference on the input the engine fed it.
    served = {}
    engine = phases.run(
        "engine_start", DCLServingEngine, params, cfg,
        DCLServeConfig(buckets=(SMOKE_RES,), slots=SLOTS,
                       quant="int8_chain"),
        scale_table=table, tap=served.setdefault)
    images = seeded_images(SEED + 2, REQUESTS, SMOKE_RES)
    reqs, step_s = phases.run("serve_int8_chain", serve, engine, images)
    print(f"served {len(reqs)} requests in {len(step_s)} steps; step "
          f"seconds {[round(t, 3) for t in step_s]}", flush=True)
    if len(step_s) > 1:
        print(f"steady per-request latency (smoke figure, not a "
              f"benchmark): {min(step_s[1:]) * 1e3:.1f} ms, every request "
              f"of a batch of {SLOTS} retiring together", flush=True)
    phases.run("serving_checks", check_served, engine, reqs,
               rung="int8_chain")
    phases.run("chain_reference", compare_to_forward, reqs, SLOTS,
               params, dataclasses.replace(cfg, quant="int8_chain",
                                           use_kernel=False), table,
               label="int8_chain served vs chain fake-quant reference")

    fp32_kernel = dataclasses.replace(cfg, quant="none", use_kernel=True)
    fp32_ref = dataclasses.replace(cfg, quant="none", use_kernel=False)
    x = np.stack(images[:SLOTS])

    def fp32_batch():
        got, _ = jitted_forward(fp32_kernel)(params, x)
        want, _ = jitted_forward(fp32_ref)(params, x)
        worst = max(rel_delta(got[k], want[k]) for k in ("cls", "box"))
        l2 = l2_delta((got[k], want[k]) for k in ("cls", "box"))
        print(f"fp32_kernel vs fp32 reference (one batch): max |delta| / "
              f"max |ref| = {worst:.3e} (tolerance {FP32_E2E_REL:.0e}); "
              f"||delta|| / ||ref|| = {l2:.3e}", flush=True)
        check(worst <= FP32_E2E_REL, f"fp32: {worst:.3e}")
    phases.run("fp32_kernel", fp32_batch)

    # Every DCL of the first served batch, its engine-computed output
    # against the XLA reference lowering of the same layer on the same
    # input; the compiled programs of one stride-1 (c3) and one stride-2
    # (first c5) layer must hold the Pallas kernel.
    chain_cfg = dataclasses.replace(cfg, quant="int8_chain")
    names = sorted(n for n in served if "/" not in n)

    def layers():
        check(len(names) == len(dcl_strides(cfg)),
              f"the engine tapped DCLs {names}, the model has "
              f"{sorted(dcl_strides(cfg))}")
        for name in ("s1b1", "s3b0"):
            xin, layer = served[name], params[name]["dcl"]
            assert_kernel_compiled(
                lambda v, p, n=name: dcl_call(p, chain_cfg, n, v, table,
                                              quant="int8_chain").values,
                (xin, layer), f"DCL {name} int8_chain {tuple(xin.shape)}")
            assert_kernel_compiled(
                lambda v, p, n=name: dcl_call(p, fp32_kernel, n, v, table,
                                              quant="none"),
                (xin, layer), f"DCL {name} fp32_kernel {tuple(xin.shape)}")
        bad = []
        for name in names:
            with platform_scope("xla_ref"):
                want = dcl_call(params[name]["dcl"], chain_cfg, name,
                                served[name], table, quant="int8_chain")
            # The engine's output is the int8 plane times its scale;
            # dividing by the same scale recovers the int8 values.
            got = np.rint(np.asarray(served[f"{name}/out"], np.float64)
                          / np.asarray(want.scale, np.float64))
            steps = np.abs(got - np.asarray(want.values, np.float64))
            frac = np.count_nonzero(steps) / steps.size
            print(f"DCL {name} {tuple(served[name].shape)} served "
                  f"int8_chain vs xla_ref on the served input: max "
                  f"{int(steps.max())} int8 steps (tolerance "
                  f"{LAYER_MAX_STEPS}), {frac:.2e} of outputs differ "
                  f"(tolerance {LAYER_MAX_FRACTION:.0e})", flush=True)
            if steps.max() > LAYER_MAX_STEPS or frac > LAYER_MAX_FRACTION:
                bad.append(name)
        check(not bad, f"served DCLs {bad} differ from the reference")
    phases.run("layer_checks", layers)


def four_chips(phases: Phases) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs.resnet50_dcn import CONFIG_BOUNDED
    from repro.distributed.sharding import use_rules
    from repro.models import resnet_dcn as R
    from repro.quant.calibrate import calibrate_resnet_dcn
    from repro.serve import DCLServeConfig, DCLServingEngine

    n_dev = len(jax.devices())
    check(n_dev >= SPATIAL_SHARDS,
          f"--chips {SPATIAL_SHARDS} needs {SPATIAL_SHARDS} devices, JAX "
          f"sees {n_dev}")
    cfg = dataclasses.replace(CONFIG_BOUNDED, use_kernel=True)
    slots = SPATIAL_REQUESTS
    params = phases.run("init", R.init_params,
                        jax.random.PRNGKey(SEED), cfg)
    calib = np.stack(seeded_images(SEED + 1, slots, SPATIAL_RES))
    table = phases.run("calibrate", calibrate_resnet_dcn, params,
                       dataclasses.replace(cfg, use_kernel=False), [calib],
                       forward=calibration_forward)
    images = seeded_images(SEED + 2, slots, SPATIAL_RES)

    sharded = phases.run(
        "engine_start_spatial", DCLServingEngine, params, cfg,
        DCLServeConfig(buckets=(SPATIAL_RES,), slots=slots,
                       quant="int8_chain",
                       spatial_shards=((SPATIAL_RES, SPATIAL_SHARDS),)),
        scale_table=table)
    s_reqs, s_steps = phases.run("serve_spatial_int8", serve, sharded,
                                 images)
    phases.run("spatial_serving_checks", check_served, sharded, s_reqs,
               rung="int8")
    unsharded = phases.run(
        "engine_start_device0", DCLServingEngine, params, cfg,
        DCLServeConfig(buckets=(SPATIAL_RES,), slots=slots, quant="int8"),
        scale_table=table)
    u_reqs, u_steps = phases.run("serve_device0_int8", serve, unsharded,
                                 images)
    phases.run("device0_serving_checks", check_served, unsharded, u_reqs,
               rung="int8")
    print(f"step seconds: spatial {[round(t, 3) for t in s_steps]}, "
          f"device 0 {[round(t, 3) for t in u_steps]} (smoke figures, "
          f"first steps include compilation)", flush=True)
    def spatial_vs_device0():
        floor = int8_noise_floor(
            params, dataclasses.replace(cfg, quant="int8", use_kernel=False),
            table, np.stack(images))
        check_int8_agreement(
            [(a.result[k], b.result[k]) for a, b in zip(s_reqs, u_reqs)
             for k in ("cls", "box")], floor,
            f"spatial int8 ({SPATIAL_SHARDS} chips) vs unsharded int8 "
            f"(device 0)")
    phases.run("spatial_vs_device0", spatial_vs_device0)

    # The sharded DCL itself: the halo exchange is a collective permute
    # in the compiled program, and the output lives on all four chips in
    # height slabs — nothing is computed on device 0 alone.
    mesh = Mesh(np.asarray(jax.devices()[:SPATIAL_SHARDS]), ("model",))
    name = "s1b1"
    _, taps = jitted_forward(dataclasses.replace(cfg, use_kernel=False))(
        params, np.stack(images))
    xin, layer = taps[name], params[name]["dcl"]
    scfg = dataclasses.replace(cfg, quant="int8", shard_spatial=True)
    ucfg = dataclasses.replace(cfg, quant="int8")

    def sharded_layer():
        with use_rules(mesh=mesh):
            assert_kernel_compiled(
                lambda v, p: dcl_call(p, scfg, name, v, table,
                                      quant="int8"),
                (xin, layer), f"spatial DCL {name} {tuple(xin.shape)}",
                also=("collective-permute",))
            y = dcl_call(layer, scfg, name, xin, table, quant="int8")
        want = dcl_call(layer, ucfg, name, xin, table, quant="int8")
        devs = {sh.device for sh in y.addressable_shards}
        rows = sorted({sh.data.shape[1] for sh in y.addressable_shards})
        same = bool(np.array_equal(np.asarray(y), np.asarray(want)))
        print(f"spatial DCL {name}: output on {len(devs)} devices, shard "
              f"rows {rows} of {y.shape[1]}; bit-identical to the "
              f"unsharded int8 layer: {same}", flush=True)
        check(len(devs) == SPATIAL_SHARDS
              and rows == [y.shape[1] // SPATIAL_SHARDS],
              f"spatial DCL output is not split over {SPATIAL_SHARDS} "
              f"devices: {len(devs)} devices, rows {rows}")
        check(same, f"spatial DCL {name} differs from the unsharded layer")
    phases.run("spatial_layer", sharded_layer)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the spatial-sharding path")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.platform import device_summary, enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e}); run it from the repository checkout",
              file=sys.stderr)
        return 2
    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is "
              f"{jax.default_backend()!r}); this smoke run needs a TPU",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    device = device_summary()
    print(f"device {device}; compile cache {cache_dir}", flush=True)

    phases = Phases()
    try:
        with jax.default_matmul_precision("highest"):
            if args.chips == 4:
                four_chips(phases)
            else:
                one_chip(phases)
    except SmokeFailure as e:
        phases.failures.append(str(e))
    if phases.failures:
        for f in phases.failures:
            print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
        return 1
    print("phase seconds: " + ", ".join(
        f"{k} {v:.1f}" for k, v in phases.seconds.items()), flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
