"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

* The window is the host annotation ``bench/window``; everything is
  clipped to it.
* Busy time is the union of the intervals in which an operation ran on
  a device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  averaged over the devices.
* Kernel time is the summed device time of the Pallas kernels: ops
  whose HLO instruction is a custom call (the only custom calls of the
  served model).
* Steps are the host annotations ``bench/step`` that lie in the window.
* ``breakdown``: the ten programs (the ``XLA Modules`` line, named
  without their hash) that took the most device time, and the idle
  time of the device summed by what the host was doing in each gap: the
  innermost host event on the annotated thread that covers the gap's
  middle, under the ``bench/`` annotation around it.
"""
from __future__ import annotations

import glob
import os

WINDOW = "bench/window"
STEP = "bench/step"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def is_kernel(name: str) -> bool:
    """A Pallas kernel: the op's HLO text (its event name) is a custom
    call, ``%<kernel>.<n> = <shape> custom-call(...)``."""
    return " custom-call(" in name


def module_name(name: str) -> str:
    """``jit_conv_general_dilated(9597674177905061397)`` without the hash."""
    return name.split("(", 1)[0]


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_dir(log_dir: str) -> dict:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return reduce_file(max(files, key=os.path.getmtime))


def reduce_file(path: str) -> dict:
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(path))


def reduce_profile(pd) -> dict:
    host_lines, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            host_lines += [[(e.name, e.start_ns, e.end_ns)
                            for e in line.events] for line in plane.lines]
        elif plane.name.startswith("/device:TPU:"):
            lines = {line.name: [(e.name, e.start_ns, e.end_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            if lines.get(OPS_LINE):
                devices.append(lines)
    annotated = [evs for evs in host_lines
                 if any(n == WINDOW for n, _, _ in evs)]
    if not annotated:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    thread = sorted(annotated[0], key=lambda e: (e[1], -e[2]))
    w0, w1 = next((s, e) for n, s, e in thread if n == WINDOW)
    steps = sum(1 for n, s, e in thread if n == STEP and s >= w0 and e <= w1)
    if not devices:
        raise ValueError("the trace has no device operations")

    busy_ns, kernel_ns, by_module, gaps = 0.0, 0.0, {}, []
    for lines in devices:
        spans = []
        for name, s, e in lines[OPS_LINE]:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            spans.append((s, e))
            if is_kernel(name):
                kernel_ns += e - s
        for name, s, e in lines.get(MODULES_LINE, []):
            s, e = max(s, w0), min(e, w1)
            if e > s:
                mod = module_name(name)
                by_module[mod] = by_module.get(mod, 0.0) + (e - s)
        merged = union(spans)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    n_dev = len(devices)
    idle = _idle_by_host_activity(thread, gaps)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns / n_dev * 1e-9,
        "kernel_s": kernel_ns / n_dev * 1e-9,
        "steps": steps,
        "breakdown": {
            "device_ops": [[k, v / n_dev * 1e-9] for k, v in sorted(
                by_module.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[k, v / n_dev * 1e-9] for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])[:TOP]],
        },
    }


def _idle_by_host_activity(thread, gaps) -> dict:
    """Idle nanoseconds per name of what the host thread was doing."""
    mids = sorted(((s + e) / 2, e - s) for s, e in gaps)
    out: dict[str, float] = {}
    stack: list = []
    j = 0
    for mid, length in mids:
        while j < len(thread) and thread[j][1] <= mid:
            while stack and stack[-1][2] <= thread[j][1]:
                stack.pop()
            stack.append(thread[j])
            j += 1
        while stack and stack[-1][2] <= mid:
            stack.pop()
        outer = next((n for n, _, _ in reversed(stack)
                      if n.startswith("bench/") and n != WINDOW), None)
        inner = stack[-1][0] if stack else "no host event"
        name = inner if outer in (None, inner) else f"{outer} > {inner}"
        out[name] = out.get(name, 0.0) + length
    return out
