"""Unified observability subsystem (ISSUE 8): tracer spans, metric
histograms, Prometheus round-trip, the dispatch recorder + divergence
report, and the obs_report renderers."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (DEFAULT_LATENCY_BUCKETS, DispatchRecorder,
                       DivergenceTracker, Histogram, MetricsRegistry,
                       NOOP_SPAN, Tracer, dump_telemetry,
                       modeled_dispatch_bytes, parse_prometheus_text,
                       tracer_scope)
from repro.obs.divergence import key_from_context

from _fakeclock import FakeClock


# -- tracer ------------------------------------------------------------

def test_span_nesting_and_ordering_with_fake_clock():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    with tr.span("outer", layer=1) as outer:
        clock.advance(1.0)
        with tr.span("inner") as inner:
            clock.advance(0.25)
        clock.advance(1.0)
    assert inner.parent_id == outer.span_id
    assert outer.parent_id is None
    assert inner.duration == pytest.approx(0.25)
    assert outer.duration == pytest.approx(2.25)
    # end order: inner closes first
    assert [s.name for s in tr.spans] == ["inner", "outer"]
    assert outer.attrs == {"layer": 1}


def test_events_parent_to_open_span():
    tr = Tracer(clock=FakeClock())
    with tr.span("parent") as p:
        tr.event("ping", n=1)
    tr.event("orphan")
    assert tr.events[0]["parent_id"] == p.span_id
    assert tr.events[1]["parent_id"] is None


def test_disabled_tracer_is_noop_and_allocates_no_spans():
    tr = Tracer(enabled=False)
    s1 = tr.span("a", x=1)
    s2 = tr.span("b")
    assert s1 is NOOP_SPAN and s2 is NOOP_SPAN  # one shared instance
    with s1:
        tr.event("nothing")
    assert tr.spans == [] and tr.events == []


class _AnnotationLog:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each enter
    and exit, in order."""

    def __init__(self):
        self.log = []
        outer = self

        class Annotation:
            def __init__(self, name, **attrs):
                self.name, self.attrs = name, attrs

            def __enter__(self):
                outer.log.append(("enter", self.name, self.attrs))
                return self

            def __exit__(self, *exc):
                outer.log.append(("exit", self.name))
                return False

        self.cls = Annotation


@pytest.fixture
def annotations(monkeypatch):
    rec = _AnnotationLog()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec.cls)
    return rec


@pytest.mark.parametrize("enabled", [True, False])
def test_profiler_tracer_mirrors_spans_as_annotations(annotations, enabled):
    tr = Tracer(clock=FakeClock(), enabled=enabled, profiler=True)
    with tr.span("serve/step", step=3):
        with tr.span("model/gn", layer="s2b3/gn1"):
            pass
        sp = tr.span("serve/fetch").start()
        sp.end()
        sp.end()                                   # a second end: no-op
    assert annotations.log == [
        ("enter", "serve/step", {"step": 3}),
        ("enter", "model/gn", {"layer": "s2b3/gn1"}),
        ("exit", "model/gn"),
        ("enter", "serve/fetch", {}),
        ("exit", "serve/fetch"),
        ("exit", "serve/step")]
    if enabled:
        assert [s.name for s in tr.spans] == ["model/gn", "serve/fetch",
                                              "serve/step"]
    else:
        assert tr.spans == [] and tr.events == []  # keeps nothing


def test_plain_disabled_tracer_enters_no_annotation(annotations):
    tr = Tracer(enabled=False)
    with tr.span("serve/step") as sp:
        pass
    assert sp is NOOP_SPAN and annotations.log == []


def test_default_tracer_mirrors_to_the_profiler_and_keeps_nothing():
    from repro.obs import trace as trace_mod
    default = trace_mod._tracer
    assert default.profiler and not default.enabled


def test_model_spans_only_on_concrete_arrays():
    """Eager, the forward opens its model/* spans; under jax.jit, and
    under an eager jax.grad of the params on concrete images, it opens
    none (they would time tracing, not work)."""
    from repro.models import resnet_dcn as R
    cfg = R.ResNetDCNConfig(stage_sizes=(1, 1), widths=(16, 32),
                            stem_width=8, num_dcn=1, num_classes=2,
                            img_size=16)
    params = R.init_params(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((1, 16, 16, 3), jnp.float32)
    eager = Tracer(enabled=True)
    with tracer_scope(eager):
        R.forward(params, cfg, x)
    names = [s.name for s in eager.spans]
    assert names.count("model/block") == 2
    assert names.count("model/dcl") == 1
    assert {"model/stem", "model/head", "model/conv", "model/gn"} \
        <= set(names)
    traced = Tracer(enabled=True)
    with tracer_scope(traced):
        jax.jit(lambda p, a: R.forward(p, cfg, a)[0]["cls"])(params, x)
    assert traced.spans == []
    grad = Tracer(enabled=True)
    with tracer_scope(grad):
        jax.grad(lambda p: R.forward(p, cfg, x)[0]["cls"].sum())(params)
    assert grad.spans == []


def test_tracer_scope_restores_previous():
    from repro.obs import get_tracer
    prev = get_tracer()
    inner = Tracer(enabled=True)
    with tracer_scope(inner):
        assert get_tracer() is inner
    assert get_tracer() is prev


def test_trace_exports(tmp_path):
    clock = FakeClock()
    tr = Tracer(clock=clock)
    with tr.span("work", kind="demo"):
        clock.advance(0.5)
        tr.event("mark", at="mid")
    p = tr.export_jsonl(tmp_path / "trace.jsonl")
    recs = [json.loads(line) for line in p.read_text().splitlines()]
    assert {r["type"] for r in recs} == {"span", "event"}
    chrome = tr.to_chrome()
    phs = {e["ph"] for e in chrome["traceEvents"]}
    assert phs == {"X", "i"}
    x = next(e for e in chrome["traceEvents"] if e["ph"] == "X")
    assert x["dur"] == pytest.approx(0.5e6)   # microseconds


# -- metrics -----------------------------------------------------------

def test_histogram_quantiles_within_one_bucket_width():
    rng = np.random.default_rng(7)
    samples = np.abs(rng.lognormal(mean=-4.0, sigma=1.5, size=500))
    h = Histogram("lat")
    for s in samples:
        h.observe(float(s))
    exact = sorted(samples)
    for q in (0.5, 0.9, 0.99):
        idx = min(len(exact) - 1, max(0, math.ceil(q * len(exact)) - 1))
        ex = exact[idx]
        got = h.quantile(q)
        assert abs(got - ex) <= h.bucket_width(ex) + 1e-12, (q, got, ex)


def test_histogram_edge_cases():
    h = Histogram("lat")
    assert math.isnan(h.quantile(0.5))
    h.observe(1e9)                               # overflow bucket
    assert h.quantile(0.99) == h.bounds[-1]      # clamped
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_counter_and_gauge_labels():
    reg = MetricsRegistry()
    c = reg.counter("req_total")
    c.inc(outcome="ok")
    c.inc(outcome="ok")
    c.inc(outcome="shed")
    assert c.value(outcome="ok") == 2
    assert c.value(outcome="missing") == 0
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(3, q="a")
    assert g.value(q="a") == 3
    with pytest.raises(ValueError):
        reg.gauge("req_total")                   # kind conflict


def test_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("jobs_total", "jobs").inc(5, kind="batch")
    reg.gauge("depth").set(2.5)
    h = reg.histogram("lat_seconds", "latency")
    for v in (0.001, 0.002, 0.002, 0.4):
        h.observe(v, op="fwd")
    text = reg.prometheus_text()
    parsed = parse_prometheus_text(text)
    assert parsed[("jobs_total", (("kind", "batch"),))] == 5
    assert parsed[("depth", ())] == 2.5
    assert parsed[("lat_seconds_count", (("op", "fwd"),))] == 4
    assert parsed[("lat_seconds_sum", (("op", "fwd"),))] == \
        pytest.approx(0.405)
    # cumulative bucket counts are monotone and end at the total
    buckets = sorted(
        ((float(dict(k[1])["le"]), v) for k, v in parsed.items()
         if k[0] == "lat_seconds_bucket" and dict(k[1])["le"] != "+Inf"))
    counts = [v for _, v in buckets]
    assert counts == sorted(counts) and counts[-1] == 4
    assert parsed[("lat_seconds_bucket",
                   (("le", "+Inf"), ("op", "fwd")))] == 4


def test_snapshot_and_dump_telemetry_round_trip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("n").inc()
    reg.histogram("lat").observe(0.01, op="x")
    rec = {"arr": np.arange(3), "scalar": np.float32(1.5),
           "i": np.int64(7)}
    p = dump_telemetry(tmp_path / "t.json", rec, extra={"k": 1},
                       registry=reg)
    loaded = json.loads(p.read_text())
    assert loaded["arr"] == [0, 1, 2]
    assert loaded["scalar"] == 1.5 and loaded["i"] == 7 and loaded["k"] == 1
    snap = loaded["metrics"]
    assert snap["counters"]["n"]["values"][0]["value"] == 1
    hv = snap["histograms"]["lat"]["values"][0]
    assert hv["count"] == 1 and hv["labels"] == {"op": "x"}
    assert hv["p50"] == pytest.approx(0.01, rel=0.3)


def test_serve_bench_percentiles_match_histogram_at_bucket_resolution():
    """The serving bench now reports p50/p99 from the fixed-bucket
    histogram; parity with the retained-sample percentile it replaced
    is one bucket width (satellite of ISSUE 8)."""
    from benchmarks.serve_bench import _percentile

    rng = np.random.default_rng(3)
    lats = sorted(float(v) for v in
                  np.abs(rng.normal(0.05, 0.02, size=48)) + 1e-4)
    h = Histogram("serve_bench_latency_seconds")
    for v in lats:
        h.observe(v, bucket="32", quant="int8_chain")
    for q in (0.50, 0.99):
        exact = _percentile(lats, q)
        got = h.quantile(q, bucket="32", quant="int8_chain")
        assert abs(got - exact) <= h.bucket_width(exact), (q, got, exact)


# -- divergence + dispatch recorder ------------------------------------

def test_key_from_context_and_modeled_bytes():
    ctx = dict(op="deform_conv", precision="fp32", dataflow="zero_copy",
               shape=(1, 16, 16, 32), offset_bound=2.0, kernel_size=3,
               stride=1, dilation=1, m=32, cores=1)
    key = key_from_context(ctx)
    assert key.dtype == "fp32" and key.quant == "none" and key.cores == 1
    assert "deform_conv[1x16x16x32]" in key.label()
    b_fp32 = modeled_dispatch_bytes(ctx)
    assert b_fp32 and b_fp32 > 0
    b_int8 = modeled_dispatch_bytes({**ctx, "precision": "int8"})
    assert b_int8 and b_int8 < b_fp32        # int8 band is cheaper
    assert modeled_dispatch_bytes({"op": "x"}) is None  # unpriceable
    assert key_from_context({"op": "x", "shape": (1, 2)}) is None


def test_divergence_pair_flags_model_inversion():
    t = DivergenceTracker()
    ok = t.record_pair("fwd", modeled_ratio=1.8, measured_ratio=1.5)
    bad = t.record_pair("bwd_mc_128c", modeled_ratio=1.92,
                        measured_ratio=0.8, note="ROADMAP anomaly")
    assert not ok["anomalous"]
    assert bad["anomalous"] and bad["divergence"] == pytest.approx(2.4)
    rep = t.report()
    assert [p["name"] for p in rep["pairs"]] == ["fwd", "bwd_mc_128c"]


def test_dispatch_recorder_times_real_kernel_dispatch():
    from repro.kernels import ops

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, 8, 8, 8), jnp.float32)
    offs = jax.random.normal(jax.random.fold_in(key, 1), (1, 8, 8, 18),
                             jnp.float32)
    wgt = jax.random.normal(jax.random.fold_in(key, 2), (9, 8, 8),
                            jnp.float32) * 0.1
    reg = MetricsRegistry()
    tracer = Tracer(enabled=True)
    tracker = DivergenceTracker()
    rec = DispatchRecorder(registry=reg, tracer=tracer, tracker=tracker)
    with ops.dispatch_hook_scope(rec):
        out = ops.deform_conv(x, offs, wgt, offset_bound=2.0)
    assert out.shape == (1, 8, 8, 8)
    c = reg.counter("kernel_dispatch_total")
    assert c.value(op="deform_conv", quant="none", outcome="ok") == 1
    h = reg.histogram("kernel_dispatch_seconds")
    assert h.count(op="deform_conv", quant="none") == 1
    assert h.sum(op="deform_conv", quant="none") > 0
    spans = [s for s in tracer.spans if s.name == "kernel/dispatch"]
    assert len(spans) == 1 and spans[0].attrs["outcome"] == "ok"
    rows = tracker.report()["dispatches"]
    assert len(rows) == 1
    assert rows[0]["modeled_bytes"] and rows[0]["implied_gbps"] > 0


def test_dispatch_recorder_chains_and_survives_chaos_raise():
    """next_hook (the chaos seam) runs FIRST; its raise aborts the
    dispatch before any timing starts, and ops degrades (opted in with
    ``degradation_scope(True)``) as before."""
    from repro.kernels import ops

    calls = []

    def chaos_hook(context):
        calls.append(context["op"])
        raise RuntimeError("injected")

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, 8, 8, 8), jnp.float32)
    offs = jnp.zeros((1, 8, 8, 18), jnp.float32)
    wgt = jax.random.normal(jax.random.fold_in(key, 2), (9, 8, 8),
                            jnp.float32) * 0.1
    reg = MetricsRegistry()
    rec = DispatchRecorder(registry=reg, next_hook=chaos_hook)
    ops._FALLBACK_WARNED.discard(("deform_conv", "fp32"))
    try:
        with ops.dispatch_hook_scope(rec), ops.degradation_scope(True):
            out = ops.deform_conv(x, offs, wgt, offset_bound=2.0)
    finally:
        ops._FALLBACK_WARNED.discard(("deform_conv", "fp32"))
    assert out.shape == (1, 8, 8, 8)         # degraded, not crashed
    assert calls == ["deform_conv"]
    # the injected abort happened before timing: nothing recorded
    assert reg.histogram("kernel_dispatch_seconds").count(
        op="deform_conv", quant="none") == 0


# -- trainer clock seam ------------------------------------------------

def test_trainer_step_timing_on_fake_clock(tmp_path):
    from repro.optim import constant, sgd
    from repro.train import Trainer, TrainerConfig

    class TickClock(FakeClock):
        """Advances 1s per read: each step's (t0, t1) pair -> dt == 1."""

        def __call__(self):
            v = self.t
            self.t += 1.0
            return v

    tr = Trainer(
        loss_fn=lambda p, b: (jnp.sum((p["w"] - b) ** 2), {}),
        params={"w": jnp.zeros((2,))},
        optimizer=sgd(constant(0.1)), mesh=None, param_specs=None,
        batch_fn=lambda s: jnp.ones((2,)),
        config=TrainerConfig(total_steps=3, ckpt_every=100,
                             ckpt_dir=str(tmp_path), log_every=1),
        clock=TickClock())
    tr.run()
    assert tr.step_seconds == [1.0, 1.0, 1.0]
    assert tr.median_step_sec(skip_first=1) == 1.0
    # telemetry is now a registry view with the legacy dict shape
    assert tr.telemetry == {"skipped": 0, "recovered": 0, "retries": 0,
                            "preempted": False}
    h = tr.metrics.histogram("train_step_seconds")
    assert h.count() == 3 and h.sum() == pytest.approx(3.0)


# -- obs_report --------------------------------------------------------

def test_obs_report_renders_all_three_artifacts(tmp_path):
    from repro.launch import obs_report

    clock = FakeClock()
    tr = Tracer(clock=clock)
    with tr.span("serve/step", bucket=32):
        clock.advance(0.2)
        tr.event("fault/slow_step")
    trace_path = tr.export_jsonl(tmp_path / "trace.jsonl")

    reg = MetricsRegistry()
    reg.counter("serve_requests_total").inc(5, outcome="ok", bucket="32")
    h = reg.histogram("serve_latency_seconds")
    for v in (0.01, 0.02, 0.03):
        h.observe(v, bucket="32", outcome="ok")
    metrics_path = dump_telemetry(tmp_path / "tel.json", {"x": 1},
                                  registry=reg)

    t = DivergenceTracker()
    t.record_pair("dcl_bwd_megacore_128c/bwd_megacore_split",
                  modeled_ratio=1.92, measured_ratio=0.8)
    div_path = tmp_path / "div.json"
    div_path.write_text(json.dumps({"divergence": t.report()}))

    rows = obs_report.summarize_trace(obs_report.load_trace(trace_path))
    assert any("serve/step" in r for r in rows)
    assert any("fault/slow_step" in r for r in rows)

    rows = obs_report.summarize_metrics(
        obs_report.load_metrics(metrics_path))
    assert any("serve_requests_total" in r and "5" in r for r in rows)
    assert any("serve_latency_seconds" in r for r in rows)

    rows = obs_report.summarize_divergence(
        obs_report.load_divergence(div_path))
    anomaly = [r for r in rows if "dcl_bwd_megacore_128c" in r]
    assert anomaly and "ANOMALOUS" in anomaly[0]

    assert obs_report.main(["--trace", str(trace_path),
                            "--metrics", str(metrics_path),
                            "--divergence", str(div_path)]) == 0


def test_obs_report_loads_engine_telemetry_with_legacy_counters(tmp_path):
    """Engine telemetry dumps carry a legacy top-level ``counters``
    view ({outcome: n}); load_metrics must descend into the embedded
    ``metrics`` snapshot rather than mistake the doc for a bare one."""
    from repro.launch import obs_report

    reg = MetricsRegistry()
    reg.counter("serve_requests_total").inc(6, outcome="ok", bucket="32")
    path = dump_telemetry(tmp_path / "serve-tel.json",
                          {"counters": {"ok": 6}, "steps": 2},
                          registry=reg)
    rows = obs_report.summarize_metrics(obs_report.load_metrics(path))
    assert any("serve_requests_total" in r and "6" in r for r in rows)


def test_kernel_bench_divergence_records_flag_mc128_anomaly():
    """The known-bad 128c Megacore backward configuration produces an
    anomalous divergence pair from the bench records (satellite)."""
    from benchmarks.kernel_bench import divergence_records

    recs = [{"name": "dcl_bwd_megacore_128c",
             "us_bwd_mc_zero_copy": 1000.0, "us_bwd_mc_baseline": 800.0,
             "hbm_bwd_per_core_ratio": 1.92}]
    rep = divergence_records(recs)
    pair = rep["pairs"][0]
    assert pair["name"] == "dcl_bwd_megacore_128c/bwd_megacore_split"
    assert pair["anomalous"]
