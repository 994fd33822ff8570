#!/usr/bin/env python
"""Summarize a crosscoder_tpu Chrome trace-event file without Perfetto.

``python scripts/trace_report.py <trace.json>`` prints one table row per
span name — count, total ms, SELF ms (a span less the spans nested in it
on its own thread: for ``produce``, the producer's host work once its
device calls ``serve_gather`` / ``harvest_dispatch`` / ``harvest`` are
taken out), p50/p99/max — plus the refill-bubble
fraction (total ``refill_wait`` time over total ``step`` time: the
fraction of train-loop step wall-clock spent blocked on batch
production), so a trace captured on an air-gapped pod answers "where did
the time go" from the terminal. Exits nonzero on malformed input
(unreadable file, non-trace JSON, events missing required fields), so CI
and drivers can gate on trace validity.

Where the job closed a profile window, its ``device_scopes.json`` (device
self time by XLA module and by the program's own ``jax.named_scope``s, written
beside the window's xplane by ``obs/device_scopes.py``) is printed as a second
table: the newest under ``<the trace's directory>/profile``, or the file
``--device-scopes`` names.

Accepts both Chrome trace-event container forms: the JSON-object form
``{"traceEvents": [...]}`` (what :class:`crosscoder_tpu.obs.trace.SpanTracer`
writes) and the bare JSON-array form.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def load_trace(path: str) -> tuple[list[dict], int]:
    """Parse + validate; returns (events, dropped_event_count); raises
    ValueError on anything malformed."""
    try:
        with open(path) as f:
            data = json.load(f)
    except OSError as e:
        raise ValueError(f"cannot read {path}: {e}")
    except json.JSONDecodeError as e:
        raise ValueError(f"{path} is not valid JSON: {e}")
    dropped = 0
    if isinstance(data, dict):
        events = data.get("traceEvents")
        if events is None:
            raise ValueError(
                f"{path}: JSON object without a 'traceEvents' key — not a "
                "Chrome trace-event file"
            )
        dropped = int(data.get("dropped_events", 0) or 0)
    elif isinstance(data, list):
        events = data
    else:
        raise ValueError(f"{path}: top-level JSON must be an object or array")
    if not isinstance(events, list):
        raise ValueError(f"{path}: traceEvents must be an array")
    for i, ev in enumerate(events):
        if not isinstance(ev, dict) or "ph" not in ev:
            raise ValueError(f"{path}: event {i} is not an object with 'ph'")
        if ev["ph"] == "X":
            for field in ("name", "ts", "dur"):
                if field not in ev:
                    raise ValueError(
                        f"{path}: complete event {i} missing {field!r}"
                    )
            if not isinstance(ev["ts"], (int, float)) or not isinstance(
                    ev["dur"], (int, float)):
                raise ValueError(f"{path}: event {i} ts/dur must be numbers")
    return events, dropped


def load_events(path: str) -> list[dict]:
    """Back-compat/test surface: just the validated event list."""
    return load_trace(path)[0]


def _pct(sorted_vals: list[float], q: float) -> float:
    return sorted_vals[min(len(sorted_vals) - 1, int(len(sorted_vals) * q))]


def self_ms(events: list[dict]) -> dict[str, float]:
    """Total self time (ms) per span name: each span's duration less what
    its direct children cover, a child being a span that lies inside it on
    the same thread (the tracer records nesting by time, not by id). Only
    ratios and differences of ``ts`` are used, so any clock origin does."""
    by_thread: dict[tuple, list[dict]] = {}
    for ev in events:
        if ev.get("ph") == "X":
            by_thread.setdefault((ev.get("pid"), ev.get("tid")), []).append(ev)
    out: dict[str, float] = {}
    for spans in by_thread.values():
        stack: list[dict] = []          # the open spans, outermost first
        for ev in sorted(spans, key=lambda e: (e["ts"], -e["dur"])):
            while stack and stack[-1]["ts"] + stack[-1]["dur"] <= ev["ts"]:
                stack.pop()
            if stack and ev["ts"] + ev["dur"] <= stack[-1]["ts"] + stack[-1]["dur"]:
                out[stack[-1]["name"]] -= ev["dur"] / 1e3
            out[ev["name"]] = out.get(ev["name"], 0.0) + ev["dur"] / 1e3
            stack.append(ev)
    return out


def summarize(events: list[dict]) -> tuple[list[dict], float | None]:
    """Per-span-name stats (ms) + the bubble fraction (None when the trace
    has no ``step`` spans to attribute against)."""
    by_name: dict[str, list[float]] = {}
    for ev in events:
        if ev.get("ph") == "X":
            by_name.setdefault(ev["name"], []).append(ev["dur"] / 1e3)  # µs→ms
    own = self_ms(events)
    rows = []
    for name in sorted(by_name, key=lambda n: -sum(by_name[n])):
        durs = sorted(by_name[name])
        rows.append({
            "span": name,
            "count": len(durs),
            "total_ms": sum(durs),
            "self_ms": own[name],
            "p50_ms": _pct(durs, 0.50),
            "p99_ms": _pct(durs, 0.99),
            "max_ms": durs[-1],
        })
    step_total = sum(by_name.get("step", []))
    wait_total = sum(by_name.get("refill_wait", []))
    bubble = None
    if step_total > 0:
        # refill_wait and step are disjoint intervals of the same loop
        # iteration (the trainer opens them sequentially), so the ratio is
        # "blocked on batch production per unit of step dispatch time"
        bubble = wait_total / (step_total + wait_total)
    return rows, bubble


def device_scopes_file(trace: str, named: str | None) -> Path | None:
    """The file ``--device-scopes`` names, else the newest one under the
    profile directory beside the trace (``<obs_dir>/profile``, the default)."""
    if named:
        return Path(named)
    found = sorted(Path(trace).resolve().parent.glob(
        "profile/plugins/profile/*/device_scopes.json"),
        key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def print_device_scopes(path: Path) -> None:
    reading = json.loads(path.read_text())
    steps = reading["steps"]
    rows = sorted(((seconds, module, scope)
                   for module, row in reading["by_scope"].items()
                   for scope, seconds in row.items()), reverse=True)
    total = sum(r[0] for r in rows) or 1.0
    print(f"\ndevice time by scope: window of {steps} steps on "
          f"{reading['n_devices']} device(s), busy {reading['busy_s']:.4f} s, "
          f"{100 * reading['scoped_share']:.1f}% of self time under a registered "
          f"scope ({path})")
    hdr = f"{'module':<32} {'scope':<30} {'ms/step':>10} {'share':>7}"
    print(hdr)
    print("-" * len(hdr))
    for seconds, module, scope in rows:
        print(f"{module[:32]:<32} {scope:<30} "
              f"{1e3 * seconds / max(steps, 1):>10.3f} {100 * seconds / total:>6.2f}%")
    if reading.get("longest"):
        print("\nlongest ops (a fusion is credited whole to the scope it names):")
        for module, op, scope, seconds in reading["longest"]:
            print(f"{module[:32]:<32} {scope:<30} "
                  f"{1e3 * seconds / max(steps, 1):>10.3f}  {op[:60]}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="path to trace.json")
    ap.add_argument("--device-scopes", default=None,
                    help="a profile window's device_scopes.json (default: the "
                         "newest under <the trace's directory>/profile)")
    args = ap.parse_args(argv)
    try:
        events, dropped = load_trace(args.trace)
    except ValueError as e:
        print(f"trace_report: MALFORMED TRACE: {e}", file=sys.stderr)
        return 2
    rows, bubble = summarize(events)
    if not rows:
        print("trace_report: no complete ('X') span events in trace",
              file=sys.stderr)
        return 1
    hdr = (f"{'span':<16} {'count':>7} {'total_ms':>12} {'self_ms':>12} "
           f"{'p50_ms':>10} {'p99_ms':>10} {'max_ms':>10}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(f"{r['span']:<16} {r['count']:>7} {r['total_ms']:>12.2f} "
              f"{r['self_ms']:>12.2f} {r['p50_ms']:>10.3f} {r['p99_ms']:>10.3f} {r['max_ms']:>10.3f}")
    if bubble is not None:
        print(f"\nrefill_bubble_frac: {bubble:.4f}  "
              f"(refill_wait / (step + refill_wait) totals)")
    scopes_file = device_scopes_file(args.trace, args.device_scopes)
    if scopes_file is not None:
        print_device_scopes(scopes_file)
    if dropped:
        print(f"WARNING: trace truncated — {dropped} events dropped at the "
              f"tracer's in-memory cap", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
