"""Shared body of the chaos integration test (PR 6 capstone).

Trains the miniature sharded QAT ``resnet_dcn`` twice on a forced
4-device mesh — once fault-free, once under a seeded random
:class:`FaultPlan` covering four fault classes (non-finite gradients, a
corrupted latest checkpoint, an injected device loss, a data-pipeline
hiccup) — and reports both loss trajectories plus the injection
telemetry.  The chaos run must complete every step with no unhandled
exception and land within tolerance of the fault-free run (the only
legitimate divergence is the one skipped non-finite step).

Entry modes mirror ``tests/_sharded_checks.py``: in-process when the
pytest process already sees >= 4 devices (the CI ``chaos`` job),
otherwise once in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` printing JSON on
the last stdout line.  If ``REPRO_CHAOS_TELEMETRY`` is set, the chaos
telemetry (plan, fired injections, trainer health counters, losses) is
also written there as JSON — the artifact the CI job uploads.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

if __name__ == "__main__":       # subprocess mode: force the devices
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

CHAOS_SEED = 20260808
TOTAL_STEPS = 8


def _losses(history):
    return [h["loss"] for h in history if "loss" in h]


def run_checks() -> dict:
    assert jax.device_count() >= 4, jax.devices()
    from repro.data import DetectionDataConfig, detection_batch
    from repro.distributed.sharding import use_rules
    from repro.models import resnet_dcn as R
    from repro.models.layers import spec_tree
    from repro.optim import constant, sgd
    from repro.resilience import ChaosHooks, FaultPlan
    from repro.train import Trainer, TrainerConfig

    mesh = jax.make_mesh((4,), ("data",),
                         axis_types=(AxisType.Auto,))
    cfg = R.ResNetDCNConfig(
        stage_sizes=(1, 1, 1, 1), widths=(16, 32, 64, 128), stem_width=8,
        num_dcn=2, num_classes=4, img_size=32, offset_bound=2.0,
        use_kernel=True, shard_batch=True, quant="qat")
    data = DetectionDataConfig(img_size=32, global_batch=4, num_classes=4,
                               seed=5)
    with use_rules(mesh=mesh):
        param_specs = spec_tree(R.model_def(cfg))

    def make_trainer(ckpt_dir, hooks=None):
        tr = Trainer(
            loss_fn=lambda p, b: R.train_loss(p, cfg, b, lam=0.1),
            params=R.init_params(jax.random.PRNGKey(0), cfg),
            optimizer=sgd(constant(0.05), momentum=0.9), mesh=mesh,
            param_specs=param_specs,
            batch_fn=lambda s: {k: jnp.asarray(v) for k, v in
                                detection_batch(data, s).items()},
            config=TrainerConfig(total_steps=TOTAL_STEPS, ckpt_every=1,
                                 ckpt_dir=ckpt_dir, log_every=1,
                                 max_retries=5),
            fault_hook=hooks.fault_hook if hooks else None,
            batch_hook=hooks.batch_hook if hooks else None)
        if hooks is not None:
            hooks.bind(tr)
        return tr

    out: dict = {"device_count": jax.device_count(),
                 "total_steps": TOTAL_STEPS}

    # -- fault-free oracle --------------------------------------------
    with tempfile.TemporaryDirectory() as tmp:
        tr_free = make_trainer(tmp)
        hist_free = tr_free.run()
    out["losses_free"] = _losses(hist_free)

    # -- skip-only oracle ---------------------------------------------
    # The one legitimate numeric divergence under chaos is the skipped
    # non-finite step: every other fault (device loss, corrupt
    # checkpoint, data hiccup) must recover bit-exactly.  So the parity
    # target is a run that sees ONLY the nonfinite_grads event — the
    # chaos run must land on the same trajectory to the last bit.
    nf_events = tuple(e for e in FaultPlan.random(
        CHAOS_SEED, total_steps=TOTAL_STEPS,
        kinds=("nonfinite_grads", "ckpt_corrupt", "step_crash",
               "data_hiccup"), min_step=2).events
        if e.kind == "nonfinite_grads")
    oracle_hooks = ChaosHooks(FaultPlan(events=nf_events, seed=CHAOS_SEED))
    with tempfile.TemporaryDirectory() as tmp:
        tr_oracle = make_trainer(tmp, oracle_hooks)
        hist_oracle = tr_oracle.run()
    out["losses_oracle"] = _losses(hist_oracle)

    # -- chaos run: same model/data/optimizer, seeded fault schedule --
    # min_step=2 guarantees >= 2 complete checkpoints exist before the
    # corruption event (ckpt_every=1), so the CRC fallback has a
    # previous step to land on.
    plan = FaultPlan.random(
        CHAOS_SEED, total_steps=TOTAL_STEPS,
        kinds=("nonfinite_grads", "ckpt_corrupt", "step_crash",
               "data_hiccup"),
        min_step=2)
    # The chaos run is traced (ISSUE 8): fault firings land as fault/*
    # instant events among the train/step spans, and the trainer's
    # health counters live in its metrics registry — both are asserted
    # on by test_chaos.py and uploaded as CI artifacts below.
    from repro.obs import Tracer, tracer_scope

    hooks = ChaosHooks(plan)
    tracer = Tracer(enabled=True)
    with tempfile.TemporaryDirectory() as tmp:
        tr = make_trainer(tmp, hooks)
        with tracer_scope(tracer):
            hist = tr.run()
    out["losses_chaos"] = _losses(hist)
    out["trace_event_names"] = sorted({e["name"] for e in tracer.events})
    out["trace_span_names"] = sorted({s.name for s in tracer.spans})
    out["steps_completed"] = tr.step
    out["plan"] = plan.summary()
    out["fired"] = hooks.fired
    out["fired_kinds"] = sorted({f["kind"] for f in hooks.fired})
    out["telemetry"] = dict(tr.telemetry)
    out["events"] = [h["event"] for h in hist if "event" in h]
    out["final_loss_free"] = out["losses_free"][-1]
    out["final_loss_chaos"] = out["losses_chaos"][-1]
    out["final_loss_oracle"] = out["losses_oracle"][-1]

    path = os.environ.get("REPRO_CHAOS_TELEMETRY")
    if path:
        from repro.obs import dump_telemetry as _dump
        # registry= attaches the trainer's metric snapshot; the span
        # trace rides in a companion JSONL (both CI artifacts, rendered
        # by repro.launch.obs_report).
        _dump(path, hooks.telemetry(), extra={
            "seed": CHAOS_SEED,
            "trainer_telemetry": out["telemetry"],
            "losses_free": out["losses_free"],
            "losses_chaos": out["losses_chaos"],
            "steps_completed": tr.step}, registry=tr.metrics)
        root, _ = os.path.splitext(path)
        tracer.export_jsonl(root + "-trace.jsonl")
    return out


if __name__ == "__main__":
    print(json.dumps(run_checks()))
