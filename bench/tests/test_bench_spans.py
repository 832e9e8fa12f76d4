"""The reduction of the program's spans through the host-to-device flow
links (``bench/spans.py``)."""
import pathlib

import pytest

from bench import spans, trace

MS = 1_000_000
TESTDATA = pathlib.Path(__file__).parents[1] / "testdata"
NO_SPANS = TESTDATA / "r50dcn_b2_offline_one_step.xplane.pb"
WITH_SPANS = TESTDATA / "r50dcn_b2_offline_one_step_spans.xplane.pb"


class Ev:
    def __init__(self, name, start, end, **stats):
        self.name, self.start_ns, self.end_ns = name, start * MS, end * MS
        self.stats = list(stats.items())


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def _profile(with_spans=True):
    """Two steps.  The multiply is enqueued on the Python thread inside
    ``model/gn``; the DCL's program is enqueued later from a worker
    thread, while the Python thread is already in ``serve/fetch``, and
    reaches ``model/dcl`` through the worker's second flow; one program
    has a flow id no host event carries."""
    python = [
        Ev("bench/window", 0, 100),
        Ev("bench/step", 0, 40),
        Ev("serve/step", 0, 40, step=0),
        Ev("serve/forward", 1, 30, rung="int8_chain"),
        Ev("model/gn", 2, 10, layer="stem/gn"),
        Ev("PjitFunction(multiply)", 2.2, 9),
        Ev("PJRT_LoadedExecutable_Execute linkage", 3.1, 3.2, _p=1),
        Ev("model/dcl", 12, 25, layer="s2b0/dcl"),
        Ev("PjitFunction(dcl)", 13, 20),
        Ev("PJRT_LoadedExecutable_Execute linkage", 13.1, 13.2, _p=2),
        Ev("serve/fetch", 30, 39),
        Ev("bench/step", 50, 90),
        Ev("serve/step", 50, 90, step=1),
        Ev("serve/forward", 51, 80, rung="int8_chain"),
        Ev("model/conv", 52, 60, layer="s2b0/conv1"),
        Ev("PjitFunction(conv)", 53, 58),
        Ev("PJRT_LoadedExecutable_Execute linkage", 53.1, 53.2, _p=3),
        Ev("serve/fetch", 80, 89),
    ]
    if not with_spans:
        python = [ev for ev in python if not spans.is_program_span(ev.name)]
    native = [
        Ev("PJRT_LoadedExecutable_Execute", 3.2, 8.9, _c=1),
        Ev("DoEnqueueProgram", 4, 5, _p=101),
        Ev("PJRT_LoadedExecutable_Execute", 13.2, 19.9, _c=2),
        Ev("tpu::System::Execute", 14, 15, _p=7),
        Ev("PJRT_LoadedExecutable_Execute", 53.2, 57.9, _c=3),
        Ev("DoEnqueueProgram", 54, 55, _p=103),
    ]
    worker = [
        Ev("tpu::System::Execute=>IssueSequencedEvent", 31, 33, _c=7),
        Ev("DoEnqueueProgram", 31.5, 32, _p=102),
    ]
    ops = [
        Ev("%multiply.1 = f32[4] multiply(f32[4] %a)", 5, 8),
        Ev("%pad.1 = s8[4] pad(s8[4] %b)", 33, 35),
        Ev("%dcl.1 = s8[4] custom-call(s8[4] %pad.1)", 35, 38),
        Ev("%convolution.1 = f32[4] convolution(f32[4] %c)", 55, 58),
        Ev("%add.1 = f32[4] add(f32[4] %d)", 60, 62),
    ]
    modules = [
        Ev("jit_multiply(1)", 5, 8, _c=101),
        Ev("jit_dcl(2)", 33, 38, _c=102),
        Ev("jit_conv(3)", 55, 58, _c=103),
        Ev("jit_add(4)", 60, 62, _c=999),
    ]
    return Profile([
        Plane("/host:CPU", [Line("python", python), Line("main/1", native),
                            Line("pjrt-tpu-tasks/2", worker)]),
        Plane("/device:TPU:0", [Line(trace.OPS_LINE, ops),
                                Line(trace.MODULES_LINE, modules)])])


def test_programs_go_to_spans_through_the_flows():
    r = spans.reduce_profile(_profile())
    s = r["spans"]
    assert s["model/gn"]["device_s"] == pytest.approx(0.003)
    # Enqueued from the worker while the Python thread was in
    # serve/fetch: the second flow puts it in model/dcl.
    assert s["model/dcl"]["device_s"] == pytest.approx(0.005)
    assert s["model/dcl"]["programs"] == 1
    assert s["serve/fetch"]["programs"] == 0
    assert s["model/conv"]["device_s"] == pytest.approx(0.003)
    assert r["unattributed"] == {"programs": 1,
                                 "device_s": pytest.approx(0.002)}
    assert r["programs"] == 4 and r["programs_in_steps"] == 3
    assert sum(v["device_s"] for v in s.values()) \
        + r["unattributed"]["device_s"] == pytest.approx(r["busy_s"])
    assert sum(v["device_s"] for v in s.values()) <= r["busy_s"]


def test_host_time_self_time_and_readings():
    r = spans.reduce_profile(_profile())
    s = r["spans"]
    assert s["serve/forward"]["count"] == 2
    assert s["serve/forward"]["host_s"] == pytest.approx(0.058)
    # 29 ms each, less gn (8) and dcl (13) in the first, conv (8) in
    # the second.
    assert s["serve/forward"]["host_self_s"] == pytest.approx(0.029)
    assert s["serve/step"]["host_self_s"] == pytest.approx(
        0.080 - 0.058 - 0.018)
    assert spans.readings(r) == pytest.approx({
        "programs_per_step": 1.5, "forward_host_ms": 29.0,
        "fetch_wait_ms": 9.0, "gn_device_ms": 1.5, "conv_device_ms": 1.5,
        "dcl_layer_ms": 2.5})


def test_idle_gaps_name_the_program_span():
    r = spans.reduce_profile(_profile())
    idle = dict(r["idle_gaps"])
    assert idle == pytest.approx({
        "bench/step > model/gn > PjitFunction(multiply)": 0.005,  # 0-5
        "bench/step > model/dcl": 0.025,                          # 8-33
        "bench/window": 0.017,                                    # 38-55
        "bench/step > model/conv": 0.002,                         # 58-60
        "bench/step > serve/fetch": 0.038})                       # 62-100
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["spans"]["model/dcl"]["idle_s"] == pytest.approx(0.025)


def test_stalls_name_the_span_and_the_runtime():
    (first, second, third) = spans.reduce_profile(_profile())["stalls"]
    assert first["dispatch"] == "PjitFunction(dcl)"
    assert first["seconds"] == pytest.approx(0.007)
    assert first["path"] == ("serve/step > serve/forward > "
                             "model/dcl[s2b0/dcl]")
    assert first["runtime"][0] == "PJRT_LoadedExecutable_Execute"
    assert first["workers"] is None
    assert {second["dispatch"], third["dispatch"]} == {
        "PjitFunction(multiply)", "PjitFunction(conv)"}


def test_program_spans_leave_the_benchmark_reduction_unchanged():
    with_spans = trace.reduce_profile(_profile())
    without = trace.reduce_profile(_profile(with_spans=False))
    for key in ("window_s", "busy_s", "kernel_s", "steps"):
        assert with_spans[key] == without[key]
    assert with_spans["breakdown"]["device_ops"] \
        == without["breakdown"]["device_ops"]


def test_without_program_spans_idle_gaps_read_as_the_benchmark_reads():
    r = spans.reduce_profile(_profile(with_spans=False))
    assert r["spans"] == {} and spans.readings(r) == {}
    assert dict(r["idle_gaps"]) == pytest.approx(dict(
        trace.reduce_profile(_profile(with_spans=False))
        ["breakdown"]["idle_gaps"]))


def test_a_recorded_trace_without_program_spans():
    pd = _load(NO_SPANS)
    r = spans.reduce_profile(pd)
    t = trace.reduce_profile(pd)
    assert r["spans"] == {}
    assert r["unattributed"]["programs"] == 0
    assert r["programs"] == _modules(pd)
    assert r["busy_s"] == pytest.approx(t["busy_s"])
    assert dict(r["idle_gaps"]) == pytest.approx(
        dict(t["breakdown"]["idle_gaps"]))


def test_a_recorded_trace_with_program_spans():
    """One served step on the chip: nearly all of its device time lies
    in a program span, and every program of the step was launched in
    the engine's step."""
    pd = _load(WITH_SPANS)
    r = spans.reduce_profile(pd)
    assert r["steps"] == 1
    in_spans = sum(v["device_s"] for v in r["spans"].values())
    assert in_spans >= 0.99 * r["busy_s"]
    assert r["unattributed"]["device_s"] <= 0.01 * r["busy_s"]
    readings = spans.readings(r)
    assert readings["programs_per_step"] == _modules(pd)
    assert readings["gn_device_ms"] + readings["conv_device_ms"] \
        + readings["dcl_layer_ms"] <= r["busy_s"] * 1e3
    assert {"serve/batch", "serve/forward", "serve/fetch", "model/stem",
            "model/block", "model/conv", "model/gn", "model/dcl",
            "model/head"} <= set(r["spans"])


def _load(path):
    import jax
    return jax.profiler.ProfileData.from_file(str(path))


def _modules(pd) -> int:
    return sum(len(list(line.events)) for plane in pd.planes
               if plane.name.startswith("/device:TPU:")
               for line in plane.lines if line.name == trace.MODULES_LINE)
