"""What the host did MORE of in the slowest log interval: over the window's
rows after the profiler's cycles, (sum of ``plus`` - sum of ``minus``) of the
row with the largest ``perf/interval_s``, less the median of the same over
all those rows, times ``scale``.

With ``plus``/``minus`` the host's self time (producer and main thread),
read it beside the slowest cycle's excess wall: about 0 says the host was
parked as usual and the DEVICE was slow; about the excess wall says the HOST
was away. args as ``interval_spans`` (no ``per``); None under 3 usable rows.
"""

from __future__ import annotations

import statistics

from benchmarks.reducers import interval_spans


def reduce(obs: dict, args: dict):
    rows = [r for r in interval_spans.usable(interval_spans.quiet_rows(obs), args)
            if "perf/interval_s" in r]
    if len(rows) < 3:
        return None
    slowest = max(rows, key=lambda r: r["perf/interval_s"])
    median = statistics.median(interval_spans.value(r, args) for r in rows)
    return (interval_spans.value(slowest, args) - median) * args.get("scale", 1.0)
