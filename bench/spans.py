"""The program's own spans in a profiler trace, reduced through the
host-to-device flow links (``bench/record.py`` prints it).

The program opens spans around its work (``repro.obs.trace``: the
engine's ``serve/*``, the model's ``model/*``), and the profiler keeps
them as annotations on the Python thread, on the host clock.

* **Programs.** Each device program (an event of a device's ``XLA
  Modules`` line) carries a flow id (stat ``_c``); the host event that
  enqueued it carries the same id as ``_p``.  Where that event lies on
  the annotated thread (the line with ``bench/window``, or the
  runtime's own line of the same thread, which the annotated line's
  flows lead to), the program goes to the innermost program span open
  there at that event's start.  A program enqueued from a runtime
  worker thread is followed one hop further: the innermost event
  around the enqueue, on the worker's line, that carries a ``_c`` links
  to the event on the annotated thread that issued it.  A program with
  no such link is ``unattributed``; none is placed by time (a device
  program starts up to 0.3 ms before its enqueue on the host clock).
* **Device time** of a program is the union of its operations (the
  ``XLA Ops`` inside it) clipped to the window, as ``bench/trace.py``
  counts busy time, so the spans' device seconds sum to at most busy.
* **Host time** of a span is its duration; its self time leaves out its
  child spans.  Idle device time goes to the innermost program span the
  host was in at the middle of each gap.  ``idle_gaps`` names each gap
  as ``bench/trace.py`` does, with that span put after the ``bench/``
  annotation: ``bench/step > model/gn > PjitFunction(multiply)``.
* **Stalls**: the five longest host dispatches (the outermost events on
  the annotated line that are neither an annotation of the benchmark
  nor a program span) with the span path they lie in and what the
  runtime's line and the ``pjrt-tpu-tasks`` worker lines spent the most
  time in during each.

Only spans and programs of the window (``bench/window``) count; per-step
readings are over its ``bench/step`` annotations.
"""
from __future__ import annotations

import statistics
import sys

from bench.trace import (MODULES_LINE, OPS_LINE, STEP, TOP, WINDOW,
                         _idle_by_host_activity, union)

PROGRAM_SPANS = ("serve/", "model/")
WORKERS = "pjrt-tpu-tasks"
STALLS = 5


class Ev:
    """One trace event: name, host or device interval, flow ids, attrs."""

    __slots__ = ("name", "s", "e", "p", "c", "attrs")

    def __init__(self, name, s, e, stats=()):
        self.name, self.s, self.e = name, s, e
        self.p = self.c = None
        self.attrs = {}
        for k, v in stats:
            if k == "_p":
                self.p = v
            elif k == "_c":
                self.c = v
            elif not k.startswith("_"):
                self.attrs[k] = v


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_SPANS)


def label(ev: Ev) -> str:
    layer = ev.attrs.get("layer")
    return f"{ev.name}[{layer}]" if layer else ev.name


def innermost(intervals, times):
    """For each time, the stack (outermost first) of the nested
    ``intervals`` (``Ev``) open at it."""
    ivs = sorted(intervals, key=lambda ev: (ev.s, -ev.e))
    out = [()] * len(times)
    stack: list = []
    j = 0
    for k in sorted(range(len(times)), key=times.__getitem__):
        t = times[k]
        while j < len(ivs) and ivs[j].s <= t:
            while stack and stack[-1].e <= ivs[j].s:
                stack.pop()
            stack.append(ivs[j])
            j += 1
        while stack and stack[-1].e <= t:
            stack.pop()
        out[k] = tuple(stack)
    return out


def self_time(events, lo, hi) -> dict:
    """Seconds per event name in [lo, hi] in which that event was the
    innermost one open on its line."""
    out: dict[str, float] = {}
    evs = [ev for ev in events if ev.e > lo and ev.s < hi]
    cuts = sorted({lo, hi} | {x for ev in evs for x in (ev.s, ev.e)
                              if lo < x < hi})
    mids = [(a + b) / 2 for a, b in zip(cuts, cuts[1:])]
    for (a, b), stack in zip(zip(cuts, cuts[1:]), innermost(evs, mids)):
        if stack:
            out[stack[-1].name] = out.get(stack[-1].name, 0.0) \
                + (b - a) * 1e-9
    return out


def top_of(d: dict):
    k = max(d, key=d.get) if d else None
    return [k, d[k]] if k else None


def read(pd):
    """Host lines by name and device lines, as ``Ev`` lists."""
    host, devices = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host[line.name] = [Ev(e.name, e.start_ns, e.end_ns, e.stats)
                                   for e in line.events]
        elif plane.name.startswith("/device:TPU:"):
            lines = {line.name: [Ev(e.name, e.start_ns, e.end_ns,
                                    e.stats if line.name == MODULES_LINE
                                    else ())
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            if lines.get(OPS_LINE):
                devices.append(lines)
    return host, devices


def reduce_file(path: str) -> dict:
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(path))


def reduce_profile(pd) -> dict:
    host, devices = read(pd)
    annotated = next((n for n, evs in host.items()
                      if any(ev.name == WINDOW for ev in evs)), None)
    if annotated is None:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    if not devices:
        raise ValueError("the trace has no device operations")
    thread = host[annotated]
    window = next(ev for ev in thread if ev.name == WINDOW)
    w0, w1 = window.s, window.e
    steps = [ev for ev in thread
             if ev.name == STEP and ev.s >= w0 and ev.e <= w1]
    spans = [ev for ev in thread if is_program_span(ev.name)
             and ev.s >= w0 and ev.e <= w1]

    # The annotated thread's lines: its own, and the runtime line its
    # flows lead to.
    out_ids = {ev.p for ev in thread if ev.p is not None}
    own = {annotated} | {n for n, evs in host.items()
                         if any(ev.c in out_ids for ev in evs)}
    producer = {ev.p: (n, ev) for n, evs in host.items() for ev in evs
                if ev.p is not None}
    issuers = {n: [ev for ev in evs if ev.c is not None]
               for n, evs in host.items() if n not in own}

    # Each program's launch: a host time on the annotated thread.
    programs = [(d, m) for d, lines in enumerate(devices)
                for m in lines.get(MODULES_LINE, [])]
    launch: list = [None] * len(programs)
    hop: dict[str, list] = {}
    for k, (_, m) in enumerate(programs):
        line, ev = producer.get(m.c, (None, None))
        if line in own:
            launch[k] = ev.s
        elif line is not None:
            hop.setdefault(line, []).append((k, ev.s))
    for line, asks in hop.items():
        stacks = innermost(issuers[line], [t for _, t in asks])
        for (k, _), stack in zip(asks, stacks):
            line2, ev2 = producer.get(stack[-1].c, (None, None)) \
                if stack else (None, None)
            if line2 in own:
                launch[k] = ev2.s
    linked = [k for k, t in enumerate(launch) if t is not None]
    paths = dict(zip(linked, innermost(spans, [launch[k] for k in linked])))
    step_of = dict(zip(linked, innermost(steps, [launch[k] for k in linked])))

    # Device time of each program: the union of its ops in the window.
    device_s = [0.0] * len(programs)
    busy_ns, gaps = 0.0, []
    for d, lines in enumerate(devices):
        mods = [(k, m) for k, (dd, m) in enumerate(programs) if dd == d]
        ops = [(max(o.s, w0), min(o.e, w1)) for o in lines[OPS_LINE]]
        ops = [(s, e) for s, e in ops if e > s]
        owner = innermost([m for _, m in mods], [(s + e) / 2 for s, e in ops])
        index = {id(m): k for k, m in mods}
        per: dict[int, list] = {}
        for iv, stack in zip(ops, owner):
            if stack:
                per.setdefault(index[id(stack[-1])], []).append(iv)
        for k, ivs in per.items():
            device_s[k] = sum(e - s for s, e in union(ivs)) * 1e-9
        merged = union(ops)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    n_dev = len(devices)

    table: dict[str, dict] = {}

    def row(name):
        return table.setdefault(name, {
            "count": 0, "host_s": 0.0, "host_self_s": 0.0, "durations": [],
            "device_s": 0.0, "programs": 0, "idle_s": 0.0})

    for ev, stack in zip(spans, innermost(spans, [ev.s for ev in spans])):
        r = row(ev.name)
        r["count"] += 1
        r["host_s"] += (ev.e - ev.s) * 1e-9
        r["host_self_s"] += (ev.e - ev.s) * 1e-9
        r["durations"].append((ev.e - ev.s) * 1e-9)
        parent = stack[-2] if len(stack) > 1 and stack[-1] is ev else None
        if parent is not None:
            row(parent.name)["host_self_s"] -= (ev.e - ev.s) * 1e-9
    unattributed = {"programs": 0, "device_s": 0.0}
    in_steps = 0
    for k in range(len(programs)):
        if device_s[k] == 0.0 and not (w0 <= programs[k][1].s < w1):
            continue                     # a program outside the window
        if launch[k] is None:
            unattributed["programs"] += 1
            unattributed["device_s"] += device_s[k] / n_dev
            continue
        path = paths[k]
        if path:
            r = row(path[-1].name)
            r["programs"] += 1
            r["device_s"] += device_s[k] / n_dev
        if step_of[k] and any(ev.name == "serve/step" for ev in path):
            in_steps += 1

    # Idle gaps: named by bench/trace.py from what the host was doing
    # at each gap's middle, grouped by the program span open there.
    by_span: dict = {}
    for gap, stack in zip(gaps, innermost(spans,
                                          [(s + e) / 2 for s, e in gaps])):
        by_span.setdefault(stack[-1].name if stack else None, []).append(gap)
    events = sorted(((ev.name, ev.s, ev.e) for ev in thread),
                    key=lambda e: (e[1], -e[2]))
    idle: dict[str, float] = {}
    for span, group in by_span.items():
        for name, ns in _idle_by_host_activity(events, group).items():
            key = with_span(name, span)
            idle[key] = idle.get(key, 0.0) + ns / n_dev * 1e-9
            if span:
                row(span)["idle_s"] += ns / n_dev * 1e-9

    for r in table.values():
        r["host_median_s"] = statistics.median(r.pop("durations")) \
            if r["count"] else 0.0
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns / n_dev * 1e-9,
        "steps": len(steps),
        "programs": sum(1 for k, (_, m) in enumerate(programs)
                        if device_s[k] > 0.0 or w0 <= m.s < w1),
        "programs_in_steps": in_steps,
        "unattributed": unattributed,
        "spans": table,
        "idle_gaps": [[k, v] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:TOP]],
        "stalls": stalls(host, annotated, own, spans, w0, w1),
    }


def with_span(name: str, span) -> str:
    """An idle-gap name of ``bench/trace.py``, ``<bench/ annotation> >
    <host event>`` or ``<host event>``, with ``span`` put after the
    annotation (not twice where the host event is the span itself)."""
    if span is None:
        return name
    outer, sep, inner = name.partition(" > ")
    if not (sep and outer.startswith("bench/")):
        outer, inner = "", name
    return " > ".join(p for p in (outer, span if span != inner else "",
                                  inner) if p)


def stalls(host, annotated, own, spans, w0, w1) -> list[dict]:
    """The longest host dispatches of the window, with the span path
    each lies in and what the runtime's threads did during it."""
    thread = [ev for ev in host[annotated] if ev.s >= w0 and ev.e <= w1
              and not ev.name.startswith("bench/")
              and not is_program_span(ev.name)]
    outermost = [ev for ev, stack in zip(thread,
                                         innermost(thread,
                                                   [ev.s for ev in thread]))
                 if stack and stack[0] is ev]
    longest = sorted(outermost, key=lambda ev: ev.s - ev.e)[:STALLS]
    paths = innermost(spans, [ev.s for ev in longest])
    runtime = [evs for n, evs in host.items() if n in own and n != annotated]
    workers = [evs for n, evs in host.items() if n.startswith(WORKERS)]

    def busiest(lines, ev):
        total: dict[str, float] = {}
        for evs in lines:
            for k, v in self_time(evs, ev.s, ev.e).items():
                total[k] = total.get(k, 0.0) + v
        return top_of(total)

    return [{"dispatch": ev.name, "seconds": (ev.e - ev.s) * 1e-9,
             "at_s": (ev.s - w0) * 1e-9,
             "path": " > ".join(label(sp) for sp in path),
             "runtime": busiest(runtime, ev),
             "workers": busiest(workers, ev)}
            for ev, path in zip(longest, paths)]


def readings(r: dict) -> dict:
    """Per-step readings of a reduction: programs launched in the
    engine's step, the host time of the forward and of the fetch, and
    the device time of GroupNorm, the XLA convs and the DCL layers."""
    n = r["steps"]
    spans = r["spans"]

    def device_ms(name):
        return spans[name]["device_s"] / n * 1e3 if name in spans else None

    def median_ms(name):
        return spans[name]["host_median_s"] * 1e3 if name in spans else None

    if not n or "serve/step" not in spans:
        return {}
    out = {"programs_per_step": r["programs_in_steps"] / n,
           "forward_host_ms": median_ms("serve/forward"),
           "fetch_wait_ms": median_ms("serve/fetch"),
           "gn_device_ms": device_ms("model/gn"),
           "conv_device_ms": device_ms("model/conv"),
           "dcl_layer_ms": device_ms("model/dcl")}
    return {k: v for k, v in out.items() if v is not None}


def log_stalls(r: dict, out=sys.stderr) -> None:
    for s in r["stalls"]:
        rt = s["runtime"] or ["-", 0.0]
        wk = s["workers"] or ["-", 0.0]
        print(f"stall {s['seconds'] * 1e3:9.3f} ms at {s['at_s']:8.3f} s "
              f"{s['dispatch']} in {s['path'] or '-'}; runtime: {rt[0]} "
              f"{rt[1] * 1e3:.3f} ms; {WORKERS}: {wk[0]} "
              f"{wk[1] * 1e3:.3f} ms", file=out, flush=True)
