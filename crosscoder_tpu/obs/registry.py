"""Typed metrics registry: counters, gauges, histograms.

Generalizes :class:`crosscoder_tpu.utils.logging.ResilienceCounters` (a
lock + monotone int dict) to the three shapes performance telemetry needs,
under the same two contracts that made the resilience channel safe to
merge into the reference's metric stream:

- **thread-safe from any thread** — the train loop, the prefetch worker,
  the checkpoint writer, and watchdog runners all record concurrently;
- **an untouched registry snapshots to ``{}``** — a run that never records
  a perf metric logs exactly the surface it logged before the registry
  existed (the property tests/test_resilience.py pinned for the
  resilience channel, now extended to ``perf/*``/``comm/*``).

Unlike ResilienceCounters (whose short keys are auto-prefixed
``resilience/`` at snapshot), registry keys are FULL metric names — the
caller picks the namespace (``perf/``, ``comm/``, ...), and
``scripts/check_metric_keys.py`` lints every constant key against the
documented namespaces (docs/OBSERVABILITY.md).

Shapes and their snapshot forms:

- ``count(k)``: monotone counter → ``{k: int}`` (zero counts are dropped);
- ``gauge(k, v)``: last-value gauge → ``{k: float}``;
- ``observe(k, v)``: bounded histogram (last ``HIST_CAP`` observations)
  → ``{k_p50, k_p99, k_max, k_n}`` — the tail-attribution shape for
  bubble/stall hunting, where a mean would average the spike away.

Span durations are not recorded here: the tracer keeps them as additive
per-log-interval totals (``obs/trace.py`` ``take_interval``), which the
plane publishes beside this registry's snapshot.
"""

from __future__ import annotations

import threading


class MetricsRegistry:
    HIST_CAP = 4096     # observations kept per histogram (ring buffer)

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counts: dict[str, int] = {}
        self._gauges: dict[str, float] = {}
        self._hists: dict[str, list[float]] = {}
        self._hist_pos: dict[str, int] = {}

    # -- recording ------------------------------------------------------
    def count(self, key: str, n: int = 1) -> None:
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + n

    def gauge(self, key: str, value: float) -> None:
        with self._lock:
            self._gauges[key] = float(value)

    def observe(self, key: str, value: float) -> None:
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = []
                self._hist_pos[key] = 0
            if len(h) < self.HIST_CAP:
                h.append(float(value))
            else:                       # ring overwrite: keep the newest CAP
                h[self._hist_pos[key]] = float(value)
                self._hist_pos[key] = (self._hist_pos[key] + 1) % self.HIST_CAP
            self._counts[f"{key}_n"] = self._counts.get(f"{key}_n", 0) + 1

    # -- reading --------------------------------------------------------
    def get_count(self, key: str) -> int:
        with self._lock:
            return self._counts.get(key, 0)

    def get_gauge(self, key: str) -> float | None:
        with self._lock:
            return self._gauges.get(key)

    def snapshot(self) -> dict[str, float]:
        """Flat scalar view for the metrics stream; ``{}`` when untouched."""
        with self._lock:
            out: dict[str, float] = {k: v for k, v in self._counts.items() if v}
            out.update(self._gauges)
            for k, h in self._hists.items():
                if not h:
                    continue
                s = sorted(h)
                out[f"{k}_p50"] = s[len(s) // 2]
                out[f"{k}_p99"] = s[min(len(s) - 1, (len(s) * 99) // 100)]
                out[f"{k}_max"] = s[-1]
            return out
