"""Windowed device profiling: jax.profiler traces for exactly N steps.

``cfg.profile_dir`` has always captured a fixed early window (steps 10-14
of the stretch — right for "is the compiled step sane", useless for "what
happened at step 48 200"). This module generalizes it:

- ``cfg.profile_steps="start:stop"`` captures a ``jax.profiler`` device
  trace around exactly the ABSOLUTE steps ``[start, stop)`` — e.g.
  ``"48190:48200"`` brackets a reproducible stall;
- ``SIGUSR1`` (installed by the Trainer when observability or a profiler
  window is configured) captures an on-demand window of
  ``SIG_WINDOW_STEPS`` steps starting at the next step — the "the run is
  slow RIGHT NOW, show me" trigger, usable on a live pod without a
  restart (``kill -USR1 <pid>`` on every process; each host writes its
  own trace);
- with neither set, a non-empty ``profile_dir`` keeps the legacy relative
  window (``LEGACY_START``..``+LEGACY_LEN`` of each stretch), so existing
  workflows and tests see identical behavior.

Around ``stop_trace`` the caller must force device completion first
(the trainer syncs by fetching the loss scalar); :meth:`after_step`
takes that sync as a callable so the profiler never invents its own
device round-trip on the fast path.

While a window closes, per-device HBM stats (``jax.local_devices()``
``memory_stats``) land in the registry as ``perf/hbm_*`` gauges — absent
on backends that report none (CPU), populated on TPU.

Two spans put the window on the span clock (docs/OBSERVABILITY.md):
``profile_window`` from ``start_trace`` to the return of ``stop_trace``,
and ``profile_stop`` around the sync and ``stop_trace`` themselves — the
seconds the stop holds the loop are a named span, not a long step.

A closed window is then READ, off the loop: a short-lived thread
``profile-reader`` (span ``profile_read``) turns the window's xplane into
device time by the program's own scopes (:mod:`.device_scopes`, imported
there and only there), writes ``device_scopes.json`` beside it and sets the
``perf/device/*`` gauges, which every later log line carries.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time
from typing import Any, Callable

from crosscoder_tpu.obs import trace


def parse_profile_steps(spec: str) -> tuple[int, int] | None:
    """``"start:stop"`` → (start, stop), validated; ``""`` → None."""
    if not spec:
        return None
    parts = spec.split(":")
    if len(parts) != 2 or not all(p.strip().lstrip("-").isdigit() for p in parts):
        raise ValueError(
            f"profile_steps must be 'start:stop' (two integers), got {spec!r}"
        )
    start, stop = int(parts[0]), int(parts[1])
    if start < 0 or stop <= start:
        raise ValueError(
            f"profile_steps needs 0 <= start < stop, got {spec!r}; the "
            f"window captures steps [start, stop)"
        )
    return start, stop


class ProfilerWindow:
    """One run's profiling driver; the trainer calls ``before_step`` /
    ``after_step`` around every loop iteration (both O(1) no-ops when no
    window is configured or pending)."""

    LEGACY_START = 10       # the historical profile_dir window, kept
    LEGACY_LEN = 5
    SIG_WINDOW_STEPS = 5    # steps captured per SIGUSR1

    def __init__(self, cfg: Any, registry: Any | None = None) -> None:
        self.out_dir = cfg.profile_dir or os.path.join(
            cfg.obs_dir or os.path.join(cfg.checkpoint_dir, "obs"), "profile"
        )
        self.registry = registry
        self._window = parse_profile_steps(cfg.profile_steps)
        self._legacy = self._window is None and bool(cfg.profile_dir)
        self._resolved: tuple[int, int] | None = self._window
        self._pending_sig = 0           # SIGUSR1-requested steps
        self._active = False
        self._t_start_ns = 0            # perf_counter_ns at start_trace
        self.windows_captured = 0
        self._reader: threading.Thread | None = None
        self._prev_handler: Any = None

    @property
    def configured(self) -> bool:
        """True when this run can ever capture (a window or legacy dir)."""
        return self._window is not None or self._legacy

    # -- stretch/loop hooks --------------------------------------------
    def begin_stretch(self, start: int) -> None:
        """Resolve stretch-relative windows (the legacy profile_dir
        behavior); absolute ``profile_steps`` windows are left alone, so a
        rollback re-entering the loop does not re-arm a window already
        captured."""
        if self._legacy:
            self._resolved = (start + self.LEGACY_START,
                              start + self.LEGACY_START + self.LEGACY_LEN)

    def request_window(self, n_steps: int | None = None) -> None:
        """Arm an on-demand window starting at the next step (the SIGUSR1
        path; also callable directly)."""
        self._pending_sig = n_steps or self.SIG_WINDOW_STEPS

    def before_step(self, step: int) -> None:
        if self._active:
            return
        if self._resolved is not None and step > self._resolved[0]:
            # the window's start step already passed without firing (a
            # restore/rollback landed beyond it): discard it — a stale
            # window must not block SIGUSR1 on-demand capture forever
            self._resolved = None
        if self._pending_sig and self._resolved is None:
            # on-demand window starts at THIS step; a still-pending
            # configured window takes precedence (the signal request
            # stays armed and fires after it)
            self._resolved = (step, step + self._pending_sig)
            self._pending_sig = 0
        if self._resolved is not None and step == self._resolved[0]:
            import jax

            self._t_start_ns = time.perf_counter_ns()
            jax.profiler.start_trace(self.out_dir)
            self._active = True

    def after_step(self, step: int, sync: Callable[[], Any] | None = None) -> None:
        if self._active and self._resolved is not None \
                and step >= self._resolved[1] - 1:
            self._stop(sync)
            # a one-shot window is consumed; a later SIGUSR1 can re-arm
            self._resolved = None

    def stop_if_active(self, sync: Callable[[], Any] | None = None) -> None:
        """End an in-flight capture (rollback / loop exit) — a dangling
        start_trace would make the next window's start raise."""
        if self._active:
            self._stop(sync)
            self._resolved = None

    def _stop(self, sync: Callable[[], Any] | None) -> None:
        import jax

        with trace.span("profile_stop"):
            if sync is not None:
                sync()          # device execution must have LANDED in the trace
            jax.profiler.stop_trace()
        trace.get_tracer().complete(
            "profile_window", self._t_start_ns, time.perf_counter_ns())
        self._active = False
        self.windows_captured += 1
        if self.registry is not None:
            self.registry.count("perf/profile_windows")
            self.record_memory_gauges()
        self.join_reader()      # (a window closed while the last is still read)
        self._reader = threading.Thread(
            target=self._read_window, name="profile-reader", daemon=True)
        self._reader.start()

    # -- device time by scope ------------------------------------------
    def _read_window(self) -> None:
        """The closed window's xplane → ``perf/device/*`` (never raises: a
        file this reader cannot make sense of costs the job a warning)."""
        try:
            with trace.span("profile_read"):
                from crosscoder_tpu.obs import device_scopes

                device_scopes.publish(self.out_dir, self.registry)
        except Exception as e:
            print(f"[crosscoder_tpu] obs: profile window not read: {e!r}",
                  file=sys.stderr, flush=True)

    def join_reader(self, timeout: float | None = 120.0) -> None:
        """Wait for the reader of the last window (the trainer's ``finally``:
        its span and its file land before the plane closes)."""
        if self._reader is not None:
            self._reader.join(timeout)
            self._reader = None

    # -- device memory gauges ------------------------------------------
    def record_memory_gauges(self) -> None:
        """Per-process HBM occupancy into the registry (max over local
        devices — the OOM-relevant number). Backends without memory_stats
        (CPU) record nothing."""
        if self.registry is None:
            return
        import jax

        in_use, limit, peak = 0, 0, 0
        for d in jax.local_devices():
            stats = getattr(d, "memory_stats", lambda: None)()
            if not stats:
                continue
            in_use = max(in_use, stats.get("bytes_in_use", 0))
            limit = max(limit, stats.get("bytes_limit", 0))
            peak = max(peak, stats.get("peak_bytes_in_use", 0))
        if in_use or limit or peak:
            self.registry.gauge("perf/hbm_bytes_in_use", in_use)
            self.registry.gauge("perf/hbm_peak_bytes", peak)
            if limit:
                self.registry.gauge("perf/hbm_bytes_limit", limit)

    # -- SIGUSR1 --------------------------------------------------------
    def install_sigusr1(self) -> bool:
        """Arm-on-signal; main thread only (signal module requirement).
        Returns True when installed. The previous disposition is restored
        by :meth:`uninstall_sigusr1` (the trainer's ``finally``)."""
        if threading.current_thread() is not threading.main_thread():
            return False

        def _on_sig(signum, frame):
            self.request_window()

        self._prev_handler = signal.signal(signal.SIGUSR1, _on_sig)
        return True

    def uninstall_sigusr1(self) -> None:
        if self._prev_handler is not None:
            signal.signal(signal.SIGUSR1, self._prev_handler)
            self._prev_handler = None
