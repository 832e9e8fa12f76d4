"""Render the ISSUE-8 observability artifacts for humans / CI logs.

Summarizes any of the three artifact families:

* ``--trace trace.jsonl`` — a :meth:`repro.obs.Tracer.export_jsonl`
  dump: top span names by total time (count / total / mean / max) and
  the instant-event counts (fault firings show up here);
* ``--metrics file.json`` — a :meth:`MetricsRegistry.snapshot`, either
  bare or embedded under a ``"metrics"`` key of a
  :func:`~repro.obs.dump_telemetry` file: counters, gauges, and the
  per-label histogram latency table (count / p50 / p99 / mean);
* ``--divergence file.json`` — a divergence report
  (:meth:`DivergenceTracker.report`), bare or under a ``"divergence"``
  key (``BENCH_kernels.json``): per-dispatch-key
  aggregates and the modeled-vs-measured ratio pairs, anomalies
  flagged.

Pure-stdlib and side-effect free until ``main()`` prints — the
``summarize_*`` functions return row lists so tests assert on content.
"""
from __future__ import annotations

import argparse
import json
import pathlib

__all__ = ["load_metrics", "load_divergence", "load_trace", "main",
           "summarize_divergence", "summarize_metrics", "summarize_trace"]


# -- loaders (tolerant of the wrapped artifact shapes) ------------------

def load_trace(path) -> list[dict]:
    records = []
    for line in pathlib.Path(path).read_text().splitlines():
        line = line.strip()
        if line:
            records.append(json.loads(line))
    return records


def load_metrics(path) -> dict:
    """A registry snapshot — bare, or under ``"metrics"`` of a
    telemetry dump."""
    doc = json.loads(pathlib.Path(path).read_text())
    # telemetry dumps can carry legacy top-level "counters" views (the
    # serve engine's {outcome: n} dict), so the embedded snapshot wins
    if isinstance(doc.get("metrics"), dict):
        return doc["metrics"]
    if "histograms" in doc or "counters" in doc:
        return doc
    raise ValueError(f"{path} holds neither a metrics snapshot nor a "
                     f"telemetry dump with a 'metrics' key")


def load_divergence(path) -> dict:
    """A divergence report — bare, or under ``"divergence"`` of
    ``BENCH_kernels.json``."""
    doc = json.loads(pathlib.Path(path).read_text())
    if "dispatches" in doc or "pairs" in doc:
        return doc
    if isinstance(doc.get("divergence"), dict):
        return doc["divergence"]
    raise ValueError(f"{path} holds neither a divergence report nor a "
                     f"document with a 'divergence' key")


# -- summaries ----------------------------------------------------------

def summarize_trace(records: list[dict], *, top: int = 10) -> list[str]:
    """Top span names by total duration + event counts."""
    spans: dict[str, dict] = {}
    events: dict[str, int] = {}
    for r in records:
        if r.get("type") == "span" and r.get("dur_s") is not None:
            s = spans.setdefault(r["name"],
                                 {"n": 0, "total": 0.0, "max": 0.0})
            s["n"] += 1
            s["total"] += r["dur_s"]
            s["max"] = max(s["max"], r["dur_s"])
        elif r.get("type") == "event":
            events[r["name"]] = events.get(r["name"], 0) + 1
    rows = [f"{'span':<28}{'n':>6}{'total_s':>10}{'mean_ms':>10}"
            f"{'max_ms':>10}"]
    ranked = sorted(spans.items(), key=lambda kv: -kv[1]["total"])[:top]
    for name, s in ranked:
        rows.append(f"{name:<28}{s['n']:>6}{s['total']:>10.3f}"
                    f"{s['total'] / s['n'] * 1e3:>10.2f}"
                    f"{s['max'] * 1e3:>10.2f}")
    if events:
        rows.append("")
        rows.append(f"{'event':<28}{'n':>6}")
        for name in sorted(events):
            rows.append(f"{name:<28}{events[name]:>6}")
    return rows


def _fmt_labels(labels: dict) -> str:
    if not labels:
        return "-"
    return ",".join(f"{k}={v}" for k, v in sorted(labels.items()))


def summarize_metrics(snapshot: dict) -> list[str]:
    """Counters/gauges + the per-label histogram latency table."""
    rows: list[str] = []
    for section in ("counters", "gauges"):
        for name, m in sorted(snapshot.get(section, {}).items()):
            for v in m.get("values", []):
                rows.append(f"{name}{{{_fmt_labels(v['labels'])}}} = "
                            f"{v['value']:g}")
    hists = snapshot.get("histograms", {})
    if hists:
        rows.append("")
        rows.append(f"{'histogram':<30}{'labels':<34}{'n':>6}"
                    f"{'p50_ms':>9}{'p99_ms':>9}{'mean_ms':>9}")
        for name, m in sorted(hists.items()):
            for v in m.get("values", []):
                n = v["count"]
                mean = v["sum"] / n if n else float("nan")
                rows.append(
                    f"{name:<30}{_fmt_labels(v['labels']):<34}{n:>6}"
                    f"{v['p50'] * 1e3:>9.2f}{v['p99'] * 1e3:>9.2f}"
                    f"{mean * 1e3:>9.2f}")
    return rows


def summarize_divergence(report: dict) -> list[str]:
    """Per-dispatch-key aggregate table + the named ratio pairs."""
    rows: list[str] = []
    disp = report.get("dispatches", [])
    if disp:
        rows.append(f"{'dispatch key':<44}{'n':>5}{'best_ms':>9}"
                    f"{'modeled_MB':>12}{'implied_GBps':>13}")
        for d in disp:
            mb = d.get("modeled_bytes")
            gbps = d.get("implied_gbps")
            rows.append(
                f"{d['key']:<44}{d['n']:>5}{d['best_s'] * 1e3:>9.2f}"
                f"{(mb / 1e6 if mb else float('nan')):>12.2f}"
                f"{(gbps if gbps is not None else float('nan')):>13.2f}")
    pairs = report.get("pairs", [])
    if pairs:
        if disp:
            rows.append("")
        rows.append(f"{'pair':<44}{'modeled':>9}{'measured':>10}"
                    f"{'diverge':>9}  flag")
        for p in pairs:
            rows.append(
                f"{p['name']:<44}{p['modeled_ratio']:>8.2f}x"
                f"{p['measured_ratio']:>9.2f}x{p['divergence']:>8.2f}x"
                f"  {'ANOMALOUS' if p.get('anomalous') else 'ok'}")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="summarize repro.obs trace/metrics/divergence "
                    "artifacts (CI)")
    ap.add_argument("--trace", help="span/event JSONL "
                    "(Tracer.export_jsonl)")
    ap.add_argument("--metrics", help="metrics snapshot JSON, bare or "
                    "a dump_telemetry file with a 'metrics' key")
    ap.add_argument("--divergence", help="divergence report JSON, bare "
                    "or a document with a 'divergence' key")
    ap.add_argument("--top", type=int, default=10,
                    help="span names to show (default 10)")
    args = ap.parse_args(argv)
    if not (args.trace or args.metrics or args.divergence):
        ap.error("pass at least one of --trace/--metrics/--divergence")

    def emit(title: str, rows: list[str]) -> None:
        print(f"== {title} ==")
        for row in rows or ["(empty)"]:
            print(row)
        print()

    if args.trace:
        emit(f"trace {args.trace}",
             summarize_trace(load_trace(args.trace), top=args.top))
    if args.metrics:
        emit(f"metrics {args.metrics}",
             summarize_metrics(load_metrics(args.metrics)))
    if args.divergence:
        emit(f"divergence {args.divergence}",
             summarize_divergence(load_divergence(args.divergence)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
