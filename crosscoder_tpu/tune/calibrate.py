"""Stage-2 autotuner: measured calibration windows + the contracts gate.

Stage 1 ranks on a model; stage 2 believes only what it measures. Each
surviving candidate runs a short window through the REAL Trainer (the
production step, refill engine, prefetch worker — nothing mocked),
scored with the PR-5 telemetry the run would log anyway: the
mean ``step`` span and the refill bubble fraction. Before any
candidate is measured it passes the contracts gate — its step lowering
is checked against the full HLO rule set plus one tune-specific
identity: the candidate must lower byte-identically to its projection
onto :data:`~crosscoder_tpu.tune.lattice.STEP_FIELDS`, the exact
assumption stage-1 pricing used to share one compile across the
data-plane sub-lattice. A candidate that violates any contract is
discarded (``tune/rejected_contract``), never shipped.
"""

from __future__ import annotations

import dataclasses
import tempfile
import time
from typing import Any

from crosscoder_tpu.tune.lattice import STEP_FIELDS

# memo: projection-config JSON → lowered baseline text, so gating a 2^k
# data-plane lattice lowers the shared projection once, not k times
_PROJECTION_TEXTS: dict[str, str] = {}


def _field_defaults(cfg_type) -> dict[str, Any]:
    out: dict[str, Any] = {}
    for f in dataclasses.fields(cfg_type):
        if f.default is not dataclasses.MISSING:
            out[f.name] = f.default
        elif f.default_factory is not dataclasses.MISSING:  # type: ignore
            out[f.name] = f.default_factory()  # type: ignore
    return out


def _step_projection_cfg(cfg: Any, knobs: dict[str, Any]):
    """``cfg`` with every NON-step tuned knob reset to its dataclass
    default (the present-but-off state): the config whose compiled step
    the candidate claimed to share during stage-1 pricing. Step-relevant
    knobs and every untuned field carry over verbatim — fields like
    ``num_tokens`` bake schedule constants into the program and must not
    drift between the pair."""
    defaults = _field_defaults(type(cfg))
    reset = {k: defaults[k] for k in knobs
             if k not in STEP_FIELDS and k in defaults}
    return cfg.replace(**reset)


def contracts_gate(cfg: Any, knobs: dict[str, Any] | None = None
                   ) -> tuple[bool, list]:
    """Run the full HLO contract rule set over one candidate's lowered
    step. With ``knobs`` (the candidate's tuned assignment) the context
    also carries the tune-specific identity pair — candidate vs the same
    config with its data-plane knobs at defaults, the exact assumption
    stage-1 pricing used to share compiles. Returns
    ``(ok, error_findings)``; ``ok`` is False on ANY error-severity
    finding — including a crashed harness, which the engine itself
    converts into a finding (a candidate the gate cannot check is a
    candidate that does not ship)."""
    from crosscoder_tpu.analysis.contracts import hlo_rules
    from crosscoder_tpu.analysis.contracts.engine import run_rules

    ctx = hlo_rules.StepContext()
    text, n_leaves = hlo_rules.lower_step(cfg)
    quant_off = not (cfg.quant_encoder or cfg.quant_grads)
    ctx.texts["tune:candidate"] = text
    ctx.meta["tune:candidate"] = hlo_rules.VariantMeta(
        n_donated_leaves=n_leaves, quant_off=quant_off)
    ctx.jaxpr_consts["tune:candidate"] = hlo_rules.step_jaxpr_consts(cfg)

    proj = _step_projection_cfg(cfg, knobs or {})
    if proj is not cfg and proj.to_dict() != cfg.to_dict():
        import json as _json

        sig = _json.dumps(proj.to_dict(), sort_keys=True, default=str)
        base_text = _PROJECTION_TEXTS.get(sig)
        if base_text is None:
            base_text = _PROJECTION_TEXTS[sig] = (
                hlo_rules.lower_step_text(proj))
        ctx.texts["tune:step_projection"] = base_text
        ctx.meta["tune:step_projection"] = hlo_rules.VariantMeta(
            n_donated_leaves=n_leaves, quant_off=quant_off)
        ctx.jaxpr_consts["tune:step_projection"] = []
        # the stage-1 cost-sharing assumption, checked mechanically: the
        # candidate's data-plane knobs must not change the step program
        ctx.identity_pairs.append(
            ("tune:step_projection", "tune:candidate", "tune-data-plane"))

    report = run_rules(hlo_rules.HLO_RULES, ctx)
    errors = [f for f in report.findings if f.severity == "error"]
    return not errors, errors


def measure_window(cfg: Any, *, steps: int = 6, warmup: int = 2,
                   n_devices: int = 1) -> dict[str, float]:
    """One short calibration window through the real Trainer.

    The window runs with ``obs="on"`` regardless of the candidate's own
    obs setting (the telemetry IS the measurement; obs overhead is flat
    across candidates so the ranking is unbiased) into throwaway
    checkpoint/obs dirs, logging nothing. Scoring: the mean ``step``
    span over the post-warmup steps, inflated by the measured refill
    bubble — ``effective_ms = step_ms / (1 - bubble)`` — so a candidate
    whose data-plane knobs starve the step loop loses even when its
    device program is fast. Score is acts/s/chip on the effective rate.
    """
    import jax

    from crosscoder_tpu.train.trainer import Trainer

    with tempfile.TemporaryDirectory(prefix="tune_cal_") as tmp:
        run_cfg = cfg.replace(
            obs="on", obs_dir="", checkpoint_dir=tmp, log_backend="null",
            save_every=10**9, num_tokens=10**12,
        )
        tr = Trainer(run_cfg)
        try:
            m = None
            for _ in range(max(1, warmup)):
                m = tr.step(full_metrics=False)
            jax.block_until_ready(m["loss"])
            tr._obs.tracer.take_interval()      # reset the span totals
            t0 = time.perf_counter()
            for _ in range(max(1, steps)):
                m = tr.step(full_metrics=False)
            jax.block_until_ready(m["loss"])
            wall_s = max(1e-9, time.perf_counter() - t0)
            spans = tr._obs.tracer.take_interval()
        finally:
            tr.close()
    blocked_s = spans.get("refill_wait", (0.0, 0))[0]
    step_s, n = spans.get("step", (wall_s, max(1, steps)))
    step_ms = 1e3 * step_s / max(1, n)
    bubble = min(0.95, max(0.0, blocked_s / wall_s))
    effective_ms = step_ms / (1.0 - bubble)
    score = cfg.batch_size * 1e3 / (effective_ms * max(1, n_devices))
    return {
        "step_ms": step_ms,
        "bubble_frac": bubble,
        "effective_step_ms": effective_ms,
        "acts_per_sec_chip": score,
        "wall_s": wall_s,
        "steps": float(steps),
        "score": score,
    }
