"""Metrics logging: the reference's wandb+print surface, made optional.

The reference hard-requires wandb (``wandb.init`` at ``trainer.py:26``,
``wandb.log`` + ``print`` at ``trainer.py:65-67``). Here the logger is a
small strategy object selected by ``cfg.log_backend``:

- ``wandb``: same behavior as the reference when wandb is importable and a
  project is configured;
- ``jsonl``: append one JSON object per log call to
  ``<checkpoint_dir>/metrics.jsonl`` — the zero-dependency default for
  air-gapped TPU pods;
- ``null``: drop everything (benchmarks);
- ``auto``: wandb if usable, else jsonl.

The logged scalar set is exactly the reference's 9-key comparison surface
(``trainer.py:51-61``): loss, l2_loss, l1_loss, l0_loss, l1_coeff, lr,
explained_variance, explained_variance_A, explained_variance_B — with
``explained_variance_{i}`` generalized beyond two sources.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path
from typing import Any

_LETTERS = "ABCDEFGH"


class ResilienceCounters:
    """Monotone recovery counters (``resilience/*`` metric channel).

    The resilience subsystem (:mod:`crosscoder_tpu.resilience`) bumps these
    from whichever thread detected/recovered a fault — the train loop
    (rollbacks), the watchdog executor (harvest retries/timeouts), the
    checkpoint restore path (corrupt-artifact skips) — so every recovery
    is visible in the ordinary metrics stream instead of only in stderr.
    ``snapshot`` returns the nonzero counters under ``resilience/<name>``
    keys; an untouched instance snapshots to ``{}``, so runs with no
    faults log exactly the reference's scalar surface.

    The observability plane generalizes this shape to counters/gauges/histograms
    (:class:`crosscoder_tpu.obs.registry.MetricsRegistry`,
    the ``perf/*``/``comm/*`` channels — docs/OBSERVABILITY.md); the
    resilience counters stay a separate instance because they must exist
    (and stay zero-cost) even when ``cfg.obs`` is off.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counts: dict[str, int] = {}

    def bump(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n

    def get(self, name: str) -> int:
        with self._lock:
            return self._counts.get(name, 0)

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            return {f"resilience/{k}": v for k, v in self._counts.items() if v}


def source_tag(i: int) -> str:
    """Source index → metric-name suffix: A/B for the reference pair
    (``explained_variance_A``/``_B``, reference trainer.py:58-60), letters
    through H, then the bare index. Shared by the trainer metrics and the
    CE eval so their key schemes cannot drift."""
    return _LETTERS[i] if i < len(_LETTERS) else str(i)


class MetricsLogger:
    def __init__(self, cfg) -> None:
        self.cfg = cfg
        backend = cfg.log_backend
        self._wandb = None
        if backend == "wandb" and not cfg.wandb_project:
            raise ValueError("log_backend='wandb' requires cfg.wandb_project")
        if backend in ("auto", "wandb") and cfg.wandb_project:
            try:
                import wandb  # type: ignore

                wandb.init(project=cfg.wandb_project, entity=cfg.wandb_entity or None)
                self._wandb = wandb
                backend = "wandb"
            except Exception as e:  # offline pod, no creds, not installed
                if cfg.log_backend == "wandb":
                    raise
                print(f"[crosscoder_tpu] wandb unavailable ({e}); falling back to jsonl", file=sys.stderr)
                backend = "jsonl"
        elif backend == "auto":
            backend = "jsonl"
        self.backend = backend
        self._file = None
        if backend == "jsonl":
            path = Path(cfg.checkpoint_dir)
            path.mkdir(parents=True, exist_ok=True)
            self._file = open(path / "metrics.jsonl", "a", buffering=1)
        self._n_logs = 0
        self._skipped_keys: set[str] = set()

    def log(self, metrics: dict[str, Any], step: int) -> None:
        # non-scalar values (a caller handing the un-expanded per-source
        # array, a None) must not kill the train loop at the log point:
        # skip them with a one-time-per-key warning instead of raising
        scalars: dict[str, float] = {}
        for k, v in metrics.items():
            try:
                scalars[k] = float(v)
            except (TypeError, ValueError):
                if k not in self._skipped_keys:
                    self._skipped_keys.add(k)
                    print(f"[crosscoder_tpu] MetricsLogger: skipping "
                          f"non-scalar metric {k!r} ({type(v).__name__}); "
                          f"further occurrences silent",
                          file=sys.stderr, flush=True)
        if self.backend == "wandb" and self._wandb is not None:
            self._wandb.log(scalars, step=step)
        elif self._file is not None:
            self._file.write(json.dumps({"step": step, "time": time.time(), **scalars}) + "\n")
        # human echo goes to STDERR (stdout belongs to executables — the
        # bench's "exactly one JSON line on stdout" contract broke the
        # moment it constructed a non-null logger), at a configurable
        # cadence (cfg.log_print_every; 0 = never)
        every = getattr(self.cfg, "log_print_every", 1)
        if self.backend != "null" and every and self._n_logs % every == 0:
            print({"step": step, **{k: round(v, 6) for k, v in scalars.items()}},
                  file=sys.stderr)
        self._n_logs += 1

    def close(self) -> None:
        if self._wandb is not None:
            self._wandb.finish()
        if self._file is not None:
            self._file.close()
