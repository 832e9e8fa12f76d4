"""DCL detection serving engine: buckets, deadlines, admission control,
retry/backoff, and the per-request degradation ladder."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.models import resnet_dcn as R
from repro.obs import Tracer, tracer_scope
from repro.quant.calibrate import calibrate_resnet_dcn
from repro.resilience import KernelDispatchFault
from repro.serve import (DCLServeConfig, DCLServingEngine, OUTCOMES,
                         resolve_bucket)

from _fakeclock import FakeClock

BUCKET = 32


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


@pytest.fixture
def clean_dispatch():
    prev_hook = ops.set_dispatch_hook(None)
    prev_deg = ops.set_degradation(True)
    ops.reset_fallback_warnings()
    yield
    ops.set_dispatch_hook(prev_hook)
    ops.set_degradation(prev_deg)
    ops.reset_fallback_warnings()


def _engine(model, **kw):
    cfg, params, table = model
    kw.setdefault("buckets", (BUCKET,))
    kw.setdefault("slots", 2)
    extra = {k: kw.pop(k) for k in ("clock", "sleep", "step_hook",
                                    "admit_hook") if k in kw}
    return DCLServingEngine(params, cfg, DCLServeConfig(**kw),
                            scale_table=table, **extra)


def _img(seed, side=BUCKET):
    return np.random.RandomState(seed).randn(side, side, 3) \
        .astype(np.float32)


# -- bucket resolution ----------------------------------------------------

def test_resolve_bucket_strict_miss_names_resolution_and_nearest():
    with pytest.raises(ValueError) as ei:
        resolve_bucket(96, 96, (64, 128))
    msg = str(ei.value)
    assert "96x96" in msg
    assert "64x64" in msg and "128x128" in msg
    assert "strict_buckets=False" in msg


def test_resolve_bucket_pad_up_and_overflow():
    assert resolve_bucket(96, 80, (64, 128), strict=False) == 128
    assert resolve_bucket(64, 64, (64, 128), strict=False) == 64
    with pytest.raises(ValueError) as ei:
        resolve_bucket(200, 200, (64, 128), strict=False)
    assert "exceeds the largest" in str(ei.value)


def test_unbucketable_request_is_typed_not_raised(model):
    eng = _engine(model)
    r = eng.submit(_img(0, side=20))
    assert r.outcome == "unbucketable"
    assert "nearest" in r.error
    assert eng.counters["unbucketable"] == 1
    # the engine keeps serving
    eng.submit(_img(1))
    done = eng.run_until_drained()
    assert [q.outcome for q in done] == ["unbucketable", "ok"]


def test_strict_buckets_false_pads_up(model):
    eng = _engine(model, strict_buckets=False)
    small = _img(2)[:24, :28]
    r = eng.submit(small)
    eng.run_until_drained()
    assert r.outcome == "ok" and r.bucket == BUCKET
    # padding is explicit zero-fill: bit-exact vs a hand-padded submit
    padded = np.zeros((BUCKET, BUCKET, 3), np.float32)
    padded[:24, :28] = small
    eng2 = _engine(model)
    r2 = eng2.submit(padded)
    eng2.run_until_drained()
    assert np.array_equal(r.result["cls"], r2.result["cls"])


# -- datapath correctness -------------------------------------------------

def test_fp32_ref_rung_matches_direct_forward(model):
    cfg, params, _ = model
    eng = DCLServingEngine(params, cfg,
                           DCLServeConfig(buckets=(BUCKET,), slots=2,
                                          quant="fp32_ref"))
    r = eng.submit(_img(3))
    eng.run_until_drained()
    assert r.outcome == "ok" and r.ladder == "fp32_ref"
    ref_cfg = dataclasses.replace(cfg, quant="none", use_kernel=False)
    batch = np.zeros((2, BUCKET, BUCKET, 3), np.float32)
    batch[0] = _img(3)
    out, _ = R.forward(params, ref_cfg, jnp.asarray(batch))
    assert np.array_equal(r.result["cls"], np.asarray(out["cls"])[0])


def test_int8_chain_default_serves_and_reports_rung(model):
    eng = _engine(model)
    assert eng.scfg.quant == "int8_chain"
    for i in range(5):
        eng.submit(_img(10 + i))
    done = eng.run_until_drained()
    assert all(r.outcome == "ok" for r in done)
    assert all(r.ladder == "int8_chain" and not r.degraded for r in done)
    assert eng.counters == {"ok": 5}
    tel = eng.telemetry()
    assert tel["served_per_bucket"] == {str(BUCKET): 5}
    assert set(tel["plans"][str(BUCKET)]) == {"s2b0", "s3b0"}
    assert {"hits", "misses", "size"} <= set(tel["plan_cache"])
    assert all(r["outcome"] in OUTCOMES for r in tel["requests"])


def test_tap_sees_every_served_dcl_layer(model):
    """The engine's ``tap`` receives each DCL's input and output of the
    served batch; re-running a layer on its tapped input reproduces the
    tapped output (the served layers can be checked one by one)."""
    from repro.models.layers import dcl_apply
    from repro.serve import bucket_layer_dims
    cfg, params, table = model
    seen = []
    eng = DCLServingEngine(params, cfg,
                           DCLServeConfig(buckets=(BUCKET,), slots=2),
                           scale_table=table,
                           tap=lambda n, a: seen.append((n, a)))
    r = eng.submit(_img(20))
    eng.run_until_drained()
    assert r.outcome == "ok" and r.ladder == "int8_chain"
    taps = dict(seen)
    assert [n for n, _ in seen] == ["s2b0", "s2b0/out", "s3b0", "s3b0/out"]
    dims = bucket_layer_dims(cfg, BUCKET)
    for name in ("s2b0", "s3b0"):
        y, _ = dcl_apply(params[name]["dcl"], taps[name],
                         stride=dims[name]["stride"],
                         offset_bound=cfg.offset_bound, use_kernel=True,
                         quant="int8_chain", quant_scales=table[name])
        assert taps[f"{name}/out"].shape == y.shape
        assert np.array_equal(np.asarray(taps[f"{name}/out"]),
                              np.asarray(y.dequantize(cfg.dtype)))


# -- deadlines ------------------------------------------------------------

def test_deadline_checked_at_admission_and_in_queue(model):
    clock = FakeClock()
    eng = _engine(model, clock=clock)
    # expired the moment it arrives
    r0 = eng.submit(_img(20), deadline=-1.0)
    assert r0.outcome == "deadline_exceeded"
    # expires while queued behind nothing — swept by the next step
    r1 = eng.submit(_img(21), deadline=5.0)
    r2 = eng.submit(_img(22))
    clock.advance(10.0)
    eng.run_until_drained()
    assert r1.outcome == "deadline_exceeded"
    assert "expired in queue" in r1.error
    assert r2.outcome == "ok"


def test_slow_step_drops_result_past_deadline(model):
    clock = FakeClock()
    eng = _engine(model, clock=clock,
                  step_hook=lambda step, ctx: clock.advance(1.0))
    r = eng.submit(_img(23), deadline=0.5)
    eng.run_until_drained()
    assert r.outcome == "deadline_exceeded"
    assert "result dropped" in r.error
    assert r.result is None


# -- admission queue ------------------------------------------------------

def test_reject_new_backpressure(model):
    eng = _engine(model, queue_capacity=2)
    r0, r1, r2 = (eng.submit(_img(30 + i)) for i in range(3))
    assert r2.outcome == "rejected" and "capacity 2" in r2.error
    eng.run_until_drained()
    assert r0.outcome == "ok" and r1.outcome == "ok"


def test_shed_oldest_sacrifices_queue_head(model):
    eng = _engine(model, queue_capacity=2, shed_policy="shed_oldest")
    r0, r1, r2 = (eng.submit(_img(40 + i)) for i in range(3))
    assert r0.outcome == "shed" and "shed by request" in r0.error
    eng.run_until_drained()
    assert r1.outcome == "ok" and r2.outcome == "ok"
    assert eng.counters == {"shed": 1, "ok": 2}


# -- retries, backoff, degradation ladder ---------------------------------

def test_transient_fault_is_retried_without_degrading(model, clean_dispatch):
    calls = {"n": 0}

    def fail_once(ctx):
        if ctx.get("op") == "deform_conv_chain" and calls["n"] == 0:
            calls["n"] += 1
            raise KernelDispatchFault("transient")

    eng = _engine(model, max_retries=2)
    with ops.dispatch_hook_scope(fail_once):
        r = eng.submit(_img(50))
        eng.run_until_drained()
    assert r.outcome == "ok"
    assert r.retries == 1 and not r.degraded
    assert r.ladder == "int8_chain"


def test_retry_backoff_is_exponential(model, clean_dispatch):
    sleeps = []

    def chain_fail(ctx):
        if ctx.get("op") == "deform_conv_chain":
            raise KernelDispatchFault("persistent")

    eng = _engine(model, max_retries=2, retry_backoff=0.05,
                  sleep=sleeps.append)
    with ops.dispatch_hook_scope(chain_fail):
        r = eng.submit(_img(51))
        eng.run_until_drained()
    # two same-rung replays back off 0.05, 0.10; then the rung drops
    assert sleeps == [0.05, 0.1]
    assert r.outcome == "ok" and r.degraded and r.ladder == "int8"


def test_ladder_is_per_request_across_two_engines(model, clean_dispatch):
    """Two engines in one process keep independent ladders and never
    touch ops' global warn-once fallback state."""
    def chain_fail(ctx):
        if ctx.get("op") == "deform_conv_chain":
            raise KernelDispatchFault("persistent chain fault")

    eng_a = _engine(model, max_retries=0)
    eng_b = _engine(model, max_retries=0)
    with ops.dispatch_hook_scope(chain_fail):
        ra = eng_a.submit(_img(60))
        rb = eng_b.submit(_img(61))
        eng_a.run_until_drained()
        eng_b.run_until_drained()
    for r in (ra, rb):
        assert r.outcome == "ok"
        assert r.degraded and r.ladder == "int8" and r.retries == 1
    assert eng_a.counters["degraded_batches"] == 1
    assert eng_b.counters["degraded_batches"] == 1
    # the global warn-once fallback never fired — degradation was
    # recorded per request, not per process
    assert ops._FALLBACK_WARNED == set()
    # with the fault gone, fresh requests are back on the top rung
    ra2 = eng_a.submit(_img(62))
    eng_a.run_until_drained()
    assert ra2.ladder == "int8_chain" and not ra2.degraded


def test_malformed_request_is_typed_not_raised(model):
    eng = _engine(model)
    r = eng.submit(np.full((5,), np.nan, np.float32))
    assert r.outcome == "malformed"
    assert "(H, W, 3)" in r.error
    r2 = eng.submit("not an image at all")
    assert r2.outcome == "malformed"
    eng.submit(_img(70))
    done = eng.run_until_drained()
    assert done[-1].outcome == "ok"


# -- deadline-aware scheduling + spatial buckets (ISSUE 10) ---------------

def test_pick_bucket_orders_by_deadline_then_age():
    from repro.serve.admission import (AdmissionConfig, AdmissionQueue,
                                       DetRequest)
    q = AdmissionQueue(AdmissionConfig())
    q.queue.append(DetRequest(0, None, bucket=64, submitted_at=0.0))
    q.queue.append(DetRequest(1, None, bucket=128, deadline=9.0,
                              submitted_at=1.0))
    # blind head-of-line order would starve the tight-deadline bucket
    assert q.head_bucket() == 64
    assert q.pick_bucket(slots=4, now=2.0) == 128
    # ties on deadline (both None) break by oldest submit
    q2 = AdmissionQueue(AdmissionConfig())
    q2.queue.append(DetRequest(0, None, bucket=128, submitted_at=5.0))
    q2.queue.append(DetRequest(1, None, bucket=64, submitted_at=3.0))
    assert q2.pick_bucket(slots=4, now=9.0) == 64
    assert AdmissionQueue(AdmissionConfig()).pick_bucket(
        slots=4, now=0.0) is None


def test_pick_bucket_prefers_full_batches_and_honors_window():
    from repro.serve.admission import (AdmissionConfig, AdmissionQueue,
                                       DetRequest)
    q = AdmissionQueue(AdmissionConfig())
    q.queue.append(DetRequest(0, None, bucket=128, deadline=1.0,
                              submitted_at=0.0))
    for i in (1, 2):
        q.queue.append(DetRequest(i, None, bucket=64, submitted_at=2.0))
    # 64 fills all slots -> preferred over the more urgent partial 128
    assert q.pick_bucket(slots=2, now=3.0) == 64
    # with room for both, deadline order reasserts itself
    assert q.pick_bucket(slots=3, now=3.0) == 128
    # partials: held inside the window, eligible after it
    q3 = AdmissionQueue(AdmissionConfig())
    q3.queue.append(DetRequest(0, None, bucket=64, submitted_at=10.0))
    assert q3.pick_bucket(slots=2, now=10.5, batch_window=1.0) is None
    assert q3.pick_bucket(slots=2, now=11.0, batch_window=1.0) == 64
    assert q3.pick_bucket(slots=2, now=10.5) == 64   # window off


def test_urgent_bucket_served_before_older_lax_bucket(model):
    clock = FakeClock()
    eng = _engine(model, buckets=(BUCKET, 64), clock=clock)
    r_lax = eng.submit(_img(90))                     # older, no deadline
    clock.advance(1.0)
    r_tight = eng.submit(_img(91, side=64), deadline=100.0)
    eng.step()
    assert r_tight.outcome == "ok" and r_lax.outcome == "pending"
    eng.run_until_drained()
    assert r_lax.outcome == "ok"


def test_full_batch_preferred_over_urgent_partial(model):
    eng = _engine(model, buckets=(BUCKET, 64))       # slots=2
    r1, r2 = eng.submit(_img(92)), eng.submit(_img(93))
    ru = eng.submit(_img(94, side=64), deadline=50.0)
    eng.step()
    assert r1.outcome == "ok" and r2.outcome == "ok"
    assert ru.outcome == "pending"
    eng.run_until_drained()
    assert ru.outcome == "ok"


def test_batch_window_holds_partial_batches(model):
    clock = FakeClock()
    eng = _engine(model, batch_window=2.0, clock=clock)
    r = eng.submit(_img(95))
    assert eng.step() == 0 and r.outcome == "pending"
    clock.advance(1.0)
    assert eng.step() == 0                  # still inside the window
    clock.advance(1.0)
    eng.step()
    assert r.outcome == "ok"
    # a full batch never waits on the window
    r1, r2 = eng.submit(_img(96)), eng.submit(_img(97))
    eng.step()
    assert r1.outcome == "ok" and r2.outcome == "ok"


def test_serve_config_validates_window_and_spatial_shards():
    with pytest.raises(ValueError) as ei:
        DCLServeConfig(buckets=(32,), batch_window=-1.0)
    assert "batch_window" in str(ei.value)
    with pytest.raises(ValueError) as ei:
        DCLServeConfig(buckets=(32,), spatial_shards=((48, 2),))
    assert "not in buckets" in str(ei.value)
    with pytest.raises(ValueError):
        DCLServeConfig(buckets=(32,), spatial_shards=((32, 0),))
    cfg = DCLServeConfig(buckets=(32, 64), spatial_shards=((32, 2),))
    assert cfg.spatial_shards_for(32) == 2
    assert cfg.spatial_shards_for(64) == 1


def test_spatial_shards_beyond_devices_rejected_at_init(model):
    too_many = jax.device_count() + 1
    with pytest.raises(ValueError) as ei:
        _engine(model, spatial_shards=((BUCKET, too_many),))
    assert f"spatial_shards={too_many}" in str(ei.value)
    assert "available device" in str(ei.value)


def test_telemetry_reports_scheduler_config(model):
    eng = _engine(model, batch_window=0.5)
    tel = eng.telemetry()["engine"]
    assert tel["batch_window"] == 0.5
    assert tel["spatial_shards"] == []


# -- program spans --------------------------------------------------------

def test_one_step_span_tree_and_no_device_sync(model, monkeypatch):
    """One step: serve/step > serve/batch, serve/forward (> one
    model/block per block, a model/dcl in each DCN block), serve/fetch,
    serve/retire; and the host never blocks on the device inside it."""
    cfg = model[0]
    eng = _engine(model)
    for i in range(2):
        eng.submit(_img(40 + i))
    syncs = []
    real_sync = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: syncs.append(1) or real_sync(x))
    tr = Tracer(enabled=True)
    with tracer_scope(tr):
        assert eng.step() == 2
    assert syncs == []
    spans = sorted(tr.spans, key=lambda s: s.span_id)       # start order
    kids = {}
    for s in spans:
        kids.setdefault(s.parent_id, []).append(s)
    (step,) = kids[None]
    assert step.name == "serve/step"
    assert [s.name for s in kids[step.span_id]] == [
        "serve/batch", "serve/forward", "serve/fetch", "serve/retire"]
    forward = kids[step.span_id][1]
    assert forward.attrs == {"rung": "int8_chain"}
    blocks = [s for s in kids[forward.span_id] if s.name == "model/block"]
    assert len(blocks) == cfg.total_blocks
    dcls = [s for s in spans if s.name == "model/dcl"]
    assert len(dcls) == sum(cfg.is_dcn(i) for i in range(cfg.total_blocks))
    assert {d.parent_id for d in dcls} <= {b.span_id for b in blocks}
    assert "kernel/dispatch" not in {s.name for s in spans}
