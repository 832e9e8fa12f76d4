"""The reduction from a profiler trace to busy time, kernel time, steps
and the breakdown."""
import glob
import pathlib

import pytest

from bench import trace

MS = 1_000_000


class Ev:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.end_ns = name, start, end


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Profile:
    def __init__(self, planes):
        self.planes = planes


def _profile():
    host = Line("python", [
        Ev("bench/window", 0, 100 * MS),
        Ev("bench/step", 0, 40 * MS),
        Ev("PjitFunction(conv)", 1 * MS, 20 * MS),
        Ev("bench/step", 50 * MS, 90 * MS),
        Ev("bench/wait", 40 * MS, 50 * MS),
        Ev("bench/step", 95 * MS, 120 * MS),     # ends after the window
    ])
    ops = Line(trace.OPS_LINE, [
        Ev("%convolution.1 = f32[4] convolution(f32[4] %a)", 5 * MS, 15 * MS),
        Ev("%fusion.2 = f32[4] fusion(f32[4] %b)", 10 * MS, 20 * MS),
        Ev("%dcl_kernel.1 = s8[4] custom-call(s8[4] %pad.0)", 60 * MS,
           80 * MS),
        Ev("%fusion.4 = f32[4] fusion(f32[4] %c)", 98 * MS, 110 * MS),
    ])
    modules = Line(trace.MODULES_LINE, [
        Ev("jit_conv(123)", 5 * MS, 20 * MS),
        Ev("jit_dcl(456)", 60 * MS, 80 * MS),
        Ev("jit_head(789)", 98 * MS, 110 * MS),
    ])
    return Profile([Plane("/host:CPU", [host]),
                    Plane("/device:TPU:0", [ops, modules])])


def test_busy_kernel_steps_and_window():
    r = trace.reduce_profile(_profile())
    assert r["window_s"] == pytest.approx(0.100)
    # Busy: 5-20 (overlap merged), 60-80, 98-100 (clipped to the window).
    assert r["busy_s"] == pytest.approx(0.037)
    assert r["kernel_s"] == pytest.approx(0.020)
    assert r["steps"] == 2
    ops = dict(r["breakdown"]["device_ops"])
    assert ops == pytest.approx({"jit_conv": 0.015, "jit_dcl": 0.020,
                                 "jit_head": 0.002})


def test_idle_named_by_what_the_host_did():
    idle = dict(trace.reduce_profile(_profile())["breakdown"]["idle_gaps"])
    assert idle["bench/step > PjitFunction(conv)"] == pytest.approx(0.005)
    assert idle["bench/wait"] == pytest.approx(0.040)    # 20-60 ms
    assert idle["bench/step"] == pytest.approx(0.018)    # 80-98 ms
    assert sum(idle.values()) == pytest.approx(0.100 - 0.037)


def test_no_device_is_an_error():
    p = _profile()
    p.planes = p.planes[:1]
    with pytest.raises(ValueError, match="no device"):
        trace.reduce_profile(p)


RECORDED = sorted(glob.glob(str(pathlib.Path(__file__).parents[1]
                                / "testdata" / "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED)
def test_a_trace_recorded_on_the_chip(path):
    r = trace.reduce_file(path)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 < r["kernel_s"] <= r["busy_s"]
    assert r["steps"] >= 1
    assert 0 < len(r["breakdown"]["device_ops"]) <= trace.TOP
    assert 0 < len(r["breakdown"]["idle_gaps"]) <= trace.TOP
    idle = sum(v for _, v in r["breakdown"]["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-9
