"""Serve chaos capstone (PR 7): the DCL serving engine under a seeded
fault schedule covering all four serve-relevant fault classes — a
kernel dispatch failure (degradation ladder), a slow step (deadline
expiry), a malformed request, and a bucket-miss storm.

The engine must retire EVERY submitted request with a typed outcome (no
crash, no hung slot, nothing left pending), degraded requests must
report their ladder rung in per-request telemetry, and requests the
faults never touched must be bit-exact against a fault-free run of the
same traffic.  If ``REPRO_SERVE_TELEMETRY`` is set, the chaos plan +
engine telemetry is written there — the artifact the CI ``chaos-serve``
job uploads.
"""
import os

import jax
import numpy as np
import pytest

from repro.kernels import ops
from repro.models import resnet_dcn as R
from repro.obs import Tracer, tracer_scope
from repro.quant.calibrate import calibrate_resnet_dcn
from repro.resilience import ChaosHooks, FaultEvent, FaultPlan
from repro.serve import DCLServeConfig, DCLServingEngine, OUTCOMES

from _fakeclock import FakeClock

CHAOS_SEED = 20260808
BUCKET = 32
N_REQUESTS = 10
SLOW_STALL_S = 1.0      # fake-clock stall injected by slow_step
TIGHT_DEADLINE_S = 0.5  # two requests carry this; the stall expires them


@pytest.fixture(scope="module")
def model():
    cfg = R.ResNetDCNConfig(
        stage_sizes=(1, 1, 1, 1), widths=(16, 32, 64, 128), stem_width=8,
        num_dcn=2, num_classes=4, img_size=BUCKET, offset_bound=2.0,
        use_kernel=True)
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    table = calibrate_resnet_dcn(
        params, cfg, [rng.randn(2, BUCKET, BUCKET, 3).astype(np.float32)])
    return cfg, params, table


def _plan():
    """Seeded schedule: the slow-step placement comes from the seed; the
    admission faults are armed in plan order (consumed per submit)."""
    rng = np.random.default_rng(CHAOS_SEED)
    slow_at = int(rng.integers(1, 3))     # engine step 1 or 2
    return FaultPlan(events=(
        FaultEvent(step=slow_at, kind="slow_step", mode=str(SLOW_STALL_S)),
        FaultEvent(step=0, kind="malformed_request"),
        FaultEvent(step=0, kind="bucket_miss_storm", mode="2"),
        FaultEvent(step=0, kind="dispatch_fault"),
    ), seed=CHAOS_SEED)


def _run(model, hooks=None):
    cfg, params, table = model
    clock = FakeClock()
    if hooks is not None:
        hooks.sleep = clock.advance       # deterministic stall
    eng = DCLServingEngine(
        params, cfg,
        # max_retries=0: a one-shot dispatch fault degrades the batch
        # instead of being absorbed by a same-rung replay
        DCLServeConfig(buckets=(BUCKET,), slots=2, max_retries=0),
        scale_table=table, clock=clock,
        step_hook=hooks.serve_step_hook if hooks else None,
        admit_hook=hooks.admit_hook if hooks else None)
    rng = np.random.RandomState(CHAOS_SEED % 2**31)
    imgs = [rng.randn(BUCKET, BUCKET, 3).astype(np.float32)
            for _ in range(N_REQUESTS)]
    for uid, img in enumerate(imgs):
        deadline = TIGHT_DEADLINE_S if uid >= N_REQUESTS - 2 else None
        eng.submit(img, deadline=deadline)
    if hooks is not None:
        with ops.dispatch_hook_scope(hooks.dispatch_hook):
            eng.run_until_drained()
    else:
        eng.run_until_drained()
    return eng


def test_serve_chaos_every_request_typed_and_undisturbed_bit_exact(model):
    free = _run(model)
    assert all(r.outcome == "ok" for r in free.completed)
    free_by_uid = {r.uid: r for r in free.completed}

    # The chaos run is traced (ISSUE 8): the engine and ChaosHooks both
    # resolve the process-global tracer at use time, so the scope below
    # captures serve spans AND fault/* instant events — while every
    # bit-exactness assertion beneath must still hold (tracing cannot
    # perturb outcomes).
    tracer = Tracer(enabled=True)
    hooks = ChaosHooks(_plan())
    with tracer_scope(tracer):
        eng = _run(model, hooks)

    # every injected fault appears in the trace as a fault/* event,
    # nested among the serve spans
    event_names = {e["name"] for e in tracer.events}
    assert {"fault/slow_step", "fault/malformed_request",
            "fault/bucket_miss_storm",
            "fault/dispatch_fault"} <= event_names
    span_names = {s.name for s in tracer.spans}
    assert "serve/step" in span_names
    assert "model/dcl" in span_names

    # every admitted request retired with a typed outcome; nothing hung
    assert len(eng.completed) == N_REQUESTS
    assert len(eng.queue) == 0
    by_uid = {r.uid: r for r in eng.completed}
    for r in eng.completed:
        assert r.done and r.outcome in OUTCOMES, (r.uid, r.outcome)
        assert r.outcome != "pending" and r.outcome != "failed"

    # all four fault kinds actually fired
    assert {f["kind"] for f in hooks.fired} == {
        "slow_step", "malformed_request", "bucket_miss_storm",
        "dispatch_fault"}

    # admission faults: uid 0 malformed, uids 1-2 the bucket-miss storm
    assert by_uid[0].outcome == "malformed"
    assert by_uid[1].outcome == "unbucketable"
    assert by_uid[2].outcome == "unbucketable"

    # the dispatch fault degraded the first served batch one rung, and
    # the rung is recorded per request in telemetry
    degraded = [r for r in eng.completed if r.degraded]
    assert degraded, "dispatch fault should have degraded a batch"
    for r in degraded:
        assert r.outcome == "ok" and r.ladder == "int8" and r.retries == 1
    tel = eng.telemetry()
    for rec in tel["requests"]:
        if rec["degraded"]:
            assert rec["ladder"] == "int8"
    assert eng.counters["degraded_batches"] == 1
    # ...without touching ops' process-global warn-once fallback
    assert ops._FALLBACK_WARNED == set()

    # the slow step expired the tight-deadline requests (typed, swept)
    expired = [r for r in eng.completed
               if r.outcome == "deadline_exceeded"]
    assert {r.uid for r in expired} <= {N_REQUESTS - 2, N_REQUESTS - 1}
    assert expired, "slow_step should have expired a tight deadline"
    for r in expired:
        assert r.result is None

    # undisturbed requests are bit-exact vs the fault-free run
    undisturbed = [r for r in eng.completed
                   if r.outcome == "ok" and not r.degraded
                   and r.retries == 0 and r.ladder == "int8_chain"]
    assert undisturbed, "some requests must be untouched by the plan"
    for r in undisturbed:
        ref = free_by_uid[r.uid]
        assert np.array_equal(r.result["cls"], ref.result["cls"]), r.uid
        assert np.array_equal(r.result["box"], ref.result["box"]), r.uid

    path = os.environ.get("REPRO_SERVE_TELEMETRY")
    if path:
        from repro.resilience import dump_telemetry
        dump_telemetry(path, tel, extra={
            "seed": CHAOS_SEED,
            "chaos": hooks.telemetry(),
            "undisturbed_uids": sorted(r.uid for r in undisturbed)})
        # companion trace artifact (CI uploads both; obs_report renders)
        root, _ = os.path.splitext(path)
        tracer.export_jsonl(root + "-trace.jsonl")
