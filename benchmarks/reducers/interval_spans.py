"""Span time per log interval, from the ``perf/span/*`` totals the program
publishes at each log step (``obs/__init__.py`` ``publish_interval``): the
mean over the window's rows AFTER the profiler's cycles of
(sum of ``plus`` keys - sum of ``minus`` keys) / ``per``, times ``scale``.

args: ``plus`` and ``minus`` (logged keys; one absent from a row counts 0
there: no span of that name ended in the interval), ``per``
(``"perf/interval_steps"``, or absent for "per interval"), ``scale``.
Nothing to read (None) where no row carries the first ``plus`` key: a
program that publishes no span totals.
"""

from __future__ import annotations


def quiet_rows(obs: dict) -> list[dict]:
    """The window's log rows after the profiler's: its ``traced_steps / spc``
    cycles and the short one after the stop, as the runner's ``skip``. Where
    none is left (the runner refuses such a run: under 3 cycles after the
    profiler's) the window's rows as they are, so the line still has a value."""
    rows = list(obs.get("window_rows") or [])
    if not obs.get("traced_steps") or not obs.get("spc"):
        return rows
    return rows[int(obs["traced_steps"]) // int(obs["spc"]) + 1:] or rows


def value(row: dict, args: dict) -> float:
    """(plus - minus) / per of one row, unscaled."""
    total = (sum(row.get(k, 0.0) for k in args["plus"])
             - sum(row.get(k, 0.0) for k in args.get("minus", [])))
    return total / row[args["per"]] if args.get("per") else total


def usable(rows: list[dict], args: dict) -> list[dict]:
    per = args.get("per")
    return [r for r in rows if args["plus"][0] in r and (not per or r.get(per))]


def reduce(obs: dict, args: dict):
    rows = usable(quiet_rows(obs), args)
    if not rows:
        return None
    return sum(value(r, args) for r in rows) / len(rows) * args.get("scale", 1.0)
