"""The repo's one subject-LM block (``crosscoder_tpu/models/lm.py``): sandwich
RMSNorms, soft-capped attention with a window on even blocks, a gated MLP.
The departures of the block from a configuration's source are in the file's
``assumed``."""

from __future__ import annotations

from typing import Any

from benchmarks.reference.lm_ref import resid_pre  # noqa: F401 — the plain reference

# The hooked activations against the float32 reference, as the relative
# Frobenius error over a seeded sample: 14 blocks each round their
# activations to bf16, which measures about 1e-2 on a v5e (PERF.md); a wrong
# position, mask or scale gives O(1).
HARVEST_RTOL = 3e-2


def lm_config(config: dict, overrides: dict | None = None) -> Any:
    """``lm.LMConfig`` from the published keys in a configuration file."""
    from crosscoder_tpu.models import lm

    a = config["assumed"]
    kw = dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"], attn_softcap=a["attn_softcap"],
        final_softcap=a["final_softcap"], sliding_window=a["sliding_window"],
        query_pre_attn_scalar=a["query_pre_attn_scalar"], dtype=a["lm_dtype"],
    )
    kw.update(overrides or {})
    return lm.LMConfig(**kw)


def flops_per_token(lm_cfg: Any, n_layers: int, seq_len: int) -> float:
    """Forward FLOPs of ``n_layers`` blocks for one token of a ``seq_len``
    causal sequence: the seven projections, and attention over the causal
    half of the score matrix (QK^T and PV)."""
    D, F = lm_cfg.d_model, lm_cfg.d_ff
    qd, kd = lm_cfg.n_heads * lm_cfg.head_dim, lm_cfg.n_kv_heads * lm_cfg.head_dim
    proj = 2 * (D * qd + 2 * D * kd + qd * D + 3 * D * F)
    attn = 2 * 2 * qd * (seq_len + 1) / 2
    return float(n_layers * (proj + attn))
