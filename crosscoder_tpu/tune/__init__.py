"""Telemetry-driven autotuner (docs/TUNING.md).

The knob count has grown every PR — refill watermarks and dispatch
batching, quantized-plane block widths, serve bucket ladders, fleet
bucket caps — and every deployment scenario shipped hand-tuned defaults.
This package closes the loop with a two-stage search:

- **Stage 1 (static, no execution)** — :mod:`crosscoder_tpu.tune.lattice`
  enumerates the valid knob lattice straight from ``config.py``'s own
  validation rules (a candidate IS a constructed ``CrossCoderConfig``;
  anything ``__post_init__`` rejects is pruned, not special-cased) and
  prices each candidate with the analytical cost model the repo already
  carries: HLO cost-analysis FLOPs/bytes of the compiled step
  (:func:`crosscoder_tpu.utils.compile_cache.cost_of` via ``aot_get``
  lowering), the PR-2 wire-byte predictor for the DP gradient sync
  (:func:`crosscoder_tpu.parallel.comm_model.wire_bytes`), and the
  docs/SCALING.md refill/harvest cost models for the data-plane knobs.
- **Stage 2 (measured)** — :mod:`crosscoder_tpu.tune.calibrate` runs the
  top-K candidates as short calibration windows through the real Trainer,
  scoring with the mean ``step`` span (PR 5's tracer) and the refill
  bubble fraction, with every candidate mechanically gated by the
  contracts engine — a tuned config that violates a contract is
  discarded (counted under ``tune/rejected_contract``), not shipped.

The winner is pinned as a reproducible ``TUNED.json``
(:mod:`crosscoder_tpu.tune.artifact`) that ``--tuned <path>`` loads back
through config resolution, and the elastic controller / fleet policy
consult per-topology cached artifacts on a remesh instead of carrying
stale knobs across a shape change.
"""

from crosscoder_tpu.tune.artifact import (TunedArtifact, apply_tuned,
                                          cached_artifact, config_hash,
                                          load_tuned, on_remesh,
                                          topology_key)
from crosscoder_tpu.tune.autotune import tune
from crosscoder_tpu.tune.calibrate import contracts_gate, measure_window
from crosscoder_tpu.tune.lattice import (Candidate, default_axes,
                                         enumerate_lattice, price_candidate,
                                         rank_candidates)

__all__ = [
    "TunedArtifact",
    "apply_tuned",
    "cached_artifact",
    "config_hash",
    "load_tuned",
    "on_remesh",
    "topology_key",
    "tune",
    "contracts_gate",
    "measure_window",
    "Candidate",
    "default_axes",
    "enumerate_lattice",
    "price_candidate",
    "rank_candidates",
]
