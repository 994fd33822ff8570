"""Unified telemetry plane (``cfg.obs``; docs/OBSERVABILITY.md).

One object — :class:`Observability` — owns the three telemetry channels
and their lifecycle. It belongs to the JOB, not to the Trainer:
:func:`acquire` is called by whichever of ``make_buffer`` and
``Trainer.__init__`` runs first with ``cfg.obs == "on"`` and creates the
plane (keyed by the resolved ``obs_dir``); the later caller gets the same
object, so calibration and the first fill are traced into the same file
as the loop. ``Trainer.close()`` closes it.

- a :class:`~crosscoder_tpu.obs.trace.SpanTracer` installed as the
  process-global tracer, so the span sites in the buffer, checkpointer,
  and watchdog light up without those objects growing constructor
  parameters; every span is stamped with ``time.perf_counter_ns`` (one
  clock with any harness around the job), and span time reaches the log
  stream as per-log-interval totals (:meth:`Observability.publish_interval`:
  ``perf/span/<name>_s``/``_n``, ``perf/interval_s``/``_steps``);
- a :class:`~crosscoder_tpu.obs.registry.MetricsRegistry` whose snapshot
  the Trainer merges into the metrics stream (``perf/*`` and ``comm/*``
  keys) exactly like the resilience counters — the resilience channel is
  now simply the oldest of the registry's siblings;
- compile/comm observability: step-variant compilations are reported
  (variant key, wall time; the HLO cost analysis is kept per key,
  ``compile_cache.record_cost``) via
  :func:`crosscoder_tpu.utils.compile_cache.observed`, and each compiled
  step's collectives are accounted through
  :mod:`crosscoder_tpu.parallel.comm_model` into
  ``comm/predicted_wire_bytes`` — logged next to the measured host↔device
  transfer counters (``comm/h2d_transfers``/``comm/d2h_transfers``), so
  drift between the PR-2 wire-byte model and the program actually running
  is visible in every log line.

Off by default: with ``cfg.obs == "off"`` nothing constructs this
object, every library span site hits the shared
:class:`~crosscoder_tpu.obs.trace.NullTracer` no-op, the compiled step
HLO is byte-identical to a build without the plane, and zero additional
host↔device transfers occur (regression-tested in tests/test_obs.py).
"""

from __future__ import annotations

import os
import sys
from typing import Any

from crosscoder_tpu.obs import trace
from crosscoder_tpu.obs.registry import MetricsRegistry
from crosscoder_tpu.obs.trace import NullTracer, SpanTracer


def resolved_dir(cfg: Any) -> str:
    """Where the job's telemetry lands: ``cfg.obs_dir``, or ``obs/`` under
    the checkpoint directory."""
    return cfg.obs_dir or os.path.join(cfg.checkpoint_dir, "obs")


# the live planes by resolved directory: one per job
_PLANES: dict[str, "Observability"] = {}


def acquire(cfg: Any, mesh: Any | None = None) -> "Observability | None":
    """The job's telemetry plane: created by the first caller, returned to
    every later one with the same resolved ``obs_dir`` until it is closed;
    None (and nothing constructed) unless ``cfg.obs == "on"``. ``mesh``
    (the comm accounting's) is taken from the first caller that has one."""
    if cfg.obs != "on":
        return None
    key = resolved_dir(cfg)
    plane = _PLANES.get(key)
    if plane is None:
        plane = _PLANES[key] = Observability(cfg, mesh=mesh)
    elif plane.mesh is None:
        plane.mesh = mesh
    return plane


def count(key: str, n: int = 1) -> None:
    """Bump a counter in every live plane's registry — for library code
    that holds no plane (a choice made while a program is traced). With
    ``obs`` off there is no plane and this is a no-op."""
    for plane in tuple(_PLANES.values()):
        plane.registry.count(key, n)


def gauge(key: str, value: float) -> None:
    """Set a last-value gauge in every live plane's registry (a no-op with
    ``obs`` off, as :func:`count`)."""
    for plane in tuple(_PLANES.values()):
        plane.registry.gauge(key, value)


class Observability:
    def __init__(self, cfg: Any, mesh: Any | None = None) -> None:
        self.cfg = cfg
        self.out_dir = resolved_dir(cfg)
        self.registry = MetricsRegistry()
        # per-process trace file: on a multi-host pod with a shared
        # checkpoint_dir, every process traces its own host threads
        try:
            import jax

            idx = jax.process_index()
        except Exception:
            idx = 0
        name = "trace.json" if idx == 0 else f"trace.p{idx}.json"
        self.tracer = SpanTracer(os.path.join(self.out_dir, name))
        self._prev_tracer = trace.set_tracer(self.tracer)
        self.mesh = mesh
        # the last published log interval's perf/span/* and perf/interval_*
        # keys; replaced whole at every log step, so a span name that did
        # not occur in an interval is absent from its line, not stale
        self._interval: dict[str, float] = {}
        self._closed = False

    # -- span time per log interval --------------------------------------
    def publish_interval(self, t0_ns: int, t1_ns: int, steps: int) -> None:
        """Close the log interval ``[t0_ns, t1_ns]`` of ``steps`` steps:
        record its ``log_interval`` span and turn the span totals since
        the last log step into the keys the next :meth:`snapshot` carries.
        ``perf/refill_bubble_frac`` is ``refill_wait`` over the interval."""
        self.tracer.complete("log_interval", t0_ns, t1_ns, steps=steps)
        totals = self.tracer.take_interval()
        wall_s = max(totals.pop("log_interval")[0], 1e-9)
        out = {"perf/interval_s": wall_s, "perf/interval_steps": float(steps)}
        for name, (s, n) in totals.items():
            out[f"perf/span/{name}_s"] = s
            out[f"perf/span/{name}_n"] = float(n)
        out["perf/refill_bubble_frac"] = totals.get("refill_wait", (0.0, 0))[0] / wall_s
        self._interval = out

    def snapshot(self) -> dict[str, float]:
        """The registry's scalars plus the last published interval's."""
        return {**self.registry.snapshot(), **self._interval}

    # -- compile/comm observability -------------------------------------
    def observe_step(self, key: str, jit_fn: Any, *,
                     disk_scope: Any = None) -> Any:
        """Wrap a jitted step variant so its compilation is measured and
        reported (utils.compile_cache.observed). ``disk_scope`` keys the
        persistent AOT tier when ``cfg.compile_cache_dir`` is set."""
        from crosscoder_tpu.utils import compile_cache

        return compile_cache.observed(jit_fn, key, self,
                                      disk_scope=disk_scope)

    def on_compile(self, key: str, compiled: Any, wall_s: float) -> None:
        """Report one compile event + the compiled program's collective
        accounting. Never raises: a cost-analysis/HLO-parsing failure
        degrades to the wall-time-only report."""
        from crosscoder_tpu.utils import compile_cache

        r = self.registry
        r.count("perf/compiles")
        r.observe("perf/compile_s", wall_s)
        # (the cost stays per KEY, for the tuner: a last-value gauge of
        # whichever variant compiled last meant nothing with two variants)
        flops = compile_cache.record_cost(key, compiled)["flops"]
        try:
            self._account_comm(compiled)
        except Exception:
            pass
        print(f"[crosscoder_tpu] obs: compiled {key} in {wall_s:.2f}s"
              + (f" ({flops / 1e9:.2f} GFLOP/step)" if flops else ""),
              file=sys.stderr, flush=True)

    def _account_comm(self, compiled: Any) -> None:
        """Predicted per-device ICI wire bytes of the compiled step (the
        PR-2 analytical model applied to the program ACTUALLY running),
        logged as ``comm/*`` gauges next to the measured transfer
        counters."""
        from crosscoder_tpu.parallel import comm_model

        hlo = compiled.as_text()
        by_op = comm_model.collective_bytes(hlo)
        n_dev = int(self.mesh.size) if self.mesh is not None else 1
        model_axis = (int(self.mesh.shape.get("model", 1))
                      if self.mesh is not None else 1)
        profile = comm_model.CommProfile(
            "train_step", n_dev, model_axis, by_op
        )
        self.registry.gauge("comm/predicted_wire_bytes",
                            comm_model.wire_bytes(profile))
        self.registry.gauge("comm/collective_output_bytes",
                            float(profile.total_bytes))
        self.registry.gauge("comm/collectives_per_step",
                            float(by_op.get("count", 0)))

    # -- lifecycle ------------------------------------------------------
    def flush(self) -> None:
        self.tracer.flush()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if _PLANES.get(self.out_dir) is self:
            del _PLANES[self.out_dir]
        trace.set_tracer(self._prev_tracer)
        self.tracer.close()


__all__ = [
    "Observability",
    "acquire",
    "count",
    "gauge",
    "MetricsRegistry",
    "NullTracer",
    "SpanTracer",
    "trace",
]
