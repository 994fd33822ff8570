"""Thread-safe host-side span tracing in Chrome trace-event format.

The framework's hot loops (train step dispatch, buffer refill, checkpoint
save) run across several host threads — the main loop, the prefetch
worker, the checkpoint writer, watchdog runners — and until now their
timing lived in scattered ``time.perf_counter`` deltas that never left the
process. :class:`SpanTracer` gives every one of those paths the same
primitive: a context-manager span that

- records a Chrome trace-event "complete" (``ph: "X"``) entry with
  microsecond ``ts``/``dur`` and the recording thread's ``tid``, so the
  resulting ``trace.json`` opens directly in Perfetto / ``chrome://tracing``
  (and summarizes offline via ``scripts/trace_report.py``);
- wraps the body in :class:`jax.profiler.TraceAnnotation`, so when a
  device profile window is captured (:mod:`crosscoder_tpu.obs.profiler`)
  the HOST spans line up with the DEVICE timeline in xprof — the
  correlation that turns "the step got slower" into "the step got slower
  because the refill drain ran under it";
- adds its duration to a per-name total that the Trainer takes and resets
  at every log step (:meth:`SpanTracer.take_interval`), so span time rides
  the ordinary metrics stream as ADDITIVE per-interval totals
  (``perf/span/<name>_s`` / ``_n``) — a span is credited to the interval
  it ends in.

Every ``ts`` is ``time.perf_counter_ns() / 1e3`` with no private epoch
(Perfetto takes any origin), so the span file joins anything else stamped
with ``time.perf_counter()`` in the same process — a harness's own log
stamps, for one — without an offset.

Library code records spans through the module-level :func:`span` /
:func:`instant` hooks, which delegate to a process-global tracer that
defaults to :class:`NullTracer` — a shared no-op context manager, so with
observability off (the default) a span site costs one global load and one
attribute call, touches no lock, allocates nothing, and transfers nothing.
:func:`crosscoder_tpu.obs.acquire` installs a real tracer for the JOB —
whichever of ``make_buffer`` and ``Trainer.__init__`` runs first creates
the plane, so set-up (``calibrate``, ``first_fill``, ``init_state``) is
traced too — and ``Trainer.close()`` restores the null tracer.

The span taxonomy, with each span's thread and cause, is the table in
docs/OBSERVABILITY.md (``lint-span-taxonomy`` holds every literal span
name to it).
"""

from __future__ import annotations

import json
import os
import threading
import time
from pathlib import Path
from typing import Any


class _NullSpan:
    """Shared no-op context manager — the entire off-path cost of a span."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """The off-state tracer: every operation is a no-op."""

    enabled = False

    def span(self, name: str, /, **args: Any) -> _NullSpan:
        return _NULL_SPAN

    def instant(self, name: str, /, **args: Any) -> None:
        return None

    def complete(self, name: str, t0_ns: int, t1_ns: int, /, **args: Any) -> None:
        return None

    def flush(self) -> None:
        return None

    def close(self) -> None:
        return None


class _Span:
    """One live span: times the body and registers the event on exit.

    The ``jax.profiler.TraceAnnotation`` wrap is what correlates this host
    span with the device timeline inside a captured profile window.
    """

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._ann = None

    def __enter__(self) -> "_Span":
        ann_cls = self._tracer._annotation_cls
        if ann_cls is not None:
            self._ann = ann_cls(self._name)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> bool:
        dur_ns = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._record(self._name, self._t0, dur_ns, self._args)
        return False


class SpanTracer:
    """Collects trace events in memory; ``flush``/``close`` writes the
    Chrome trace-event JSON file (``{"traceEvents": [...]}`` — the object
    form Perfetto and ``chrome://tracing`` both load).

    Thread-safe: spans may open/close concurrently on any thread; each
    event carries its recording thread's id so Perfetto renders one track
    per thread (main loop, batch-prefetch, ckpt-writer, watchdog).

    Beside the event list the tracer keeps per-name totals (nanoseconds
    and count) of the spans that ENDED since the last
    :meth:`take_interval` — what the Trainer publishes at each log step.
    """

    enabled = True

    # events kept in memory (~300 B each → ~150 MB at the cap); beyond it
    # new events are DROPPED and counted — the drop count is written into
    # the trace (instant event + "dropped_events" top-level key) so a
    # truncated trace can never read as a complete one
    MAX_EVENTS = 500_000

    def __init__(self, path: str | Path,
                 process_name: str = "crosscoder_tpu") -> None:
        self.path = Path(path)
        self.dropped = 0
        self._lock = threading.Lock()
        self._totals: dict[str, list[int]] = {}     # name -> [ns, count]
        self._pid = os.getpid()
        self._events: list[dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": self._pid, "tid": 0,
            "args": {"name": process_name},
        }]
        try:
            import jax

            self._annotation_cls = jax.profiler.TraceAnnotation
        except Exception:   # profiler API moved / jax absent: spans still record
            self._annotation_cls = None

    # -- recording ------------------------------------------------------
    def span(self, name: str, /, **args: Any) -> _Span:
        return _Span(self, name, args)

    def instant(self, name: str, /, **args: Any) -> None:
        ev: dict[str, Any] = {
            "name": name, "ph": "i", "s": "t",
            "ts": time.perf_counter_ns() / 1e3,
            "pid": self._pid, "tid": threading.get_ident() & 0xFFFFFFFF,
        }
        if args:
            ev["args"] = args
        with self._lock:
            if len(self._events) < self.MAX_EVENTS:
                self._events.append(ev)
            else:
                self.dropped += 1

    def complete(self, name: str, t0_ns: int, t1_ns: int, /, **args: Any) -> None:
        """Record a span from two ``perf_counter_ns`` readings the caller
        took itself — for a span that straddles loop iterations
        (``log_interval``) and so cannot be a ``with`` block. No
        ``TraceAnnotation``: the profiler cannot be told of a span after
        the fact."""
        self._record(name, t0_ns, t1_ns - t0_ns, args)

    def _record(self, name: str, t0_ns: int, dur_ns: int,
                args: dict[str, Any]) -> None:
        ev: dict[str, Any] = {
            "name": name, "ph": "X", "cat": "host",
            "ts": t0_ns / 1e3,
            "dur": dur_ns / 1e3,
            "pid": self._pid, "tid": threading.get_ident() & 0xFFFFFFFF,
        }
        if args:
            ev["args"] = args
        with self._lock:
            if len(self._events) < self.MAX_EVENTS:
                self._events.append(ev)
            else:
                self.dropped += 1
            tot = self._totals.get(name)
            if tot is None:
                self._totals[name] = [dur_ns, 1]
            else:
                tot[0] += dur_ns
                tot[1] += 1

    def take_interval(self) -> dict[str, tuple[float, int]]:
        """``{name: (seconds, count)}`` of the spans that ended since the
        last call, and reset — totals, so they add up (over names against
        the interval's wall, over intervals against the run's)."""
        with self._lock:
            totals, self._totals = self._totals, {}
        return {name: (ns / 1e9, n) for name, (ns, n) in totals.items()}

    # -- inspection / output -------------------------------------------
    def events(self) -> list[dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def flush(self) -> Path:
        """Write (atomically) everything recorded so far; safe to call
        repeatedly — the file always holds a complete, valid trace."""
        with self._lock:
            payload = {"traceEvents": list(self._events),
                       "displayTimeUnit": "ms"}
            if self.dropped:
                payload["dropped_events"] = self.dropped
        self.path.parent.mkdir(parents=True, exist_ok=True)
        tmp = self.path.with_name(self.path.name + ".tmp")
        tmp.write_text(json.dumps(payload))
        os.replace(tmp, self.path)
        return self.path

    def close(self) -> None:
        self.flush()


# ---------------------------------------------------------------------------
# process-global tracer hooks (what library call sites use)

_TRACER: NullTracer | SpanTracer = NullTracer()


def get_tracer() -> NullTracer | SpanTracer:
    return _TRACER


def set_tracer(tracer: NullTracer | SpanTracer) -> NullTracer | SpanTracer:
    """Install ``tracer`` as the process-global tracer; returns the one it
    replaces (so Observability.close can restore it)."""
    global _TRACER
    prev = _TRACER
    _TRACER = tracer
    return prev


def span(name: str, /, **args: Any):
    """Record a span on the process-global tracer (no-op by default)."""
    return _TRACER.span(name, **args)


def instant(name: str, /, **args: Any) -> None:
    """Record an instant event on the process-global tracer."""
    return _TRACER.instant(name, **args)
