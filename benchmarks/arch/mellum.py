"""Mellum2 (JetBrains; ``model_type`` "mellum"): a pre-norm block, GQA with
a 3:1 table of window and full layers that rotate differently (plain RoPE /
static YaRN), every MLP a sparse-expert layer with top-k routing. The
program runs it through ``crosscoder_tpu/models/lm.py`` (layer table, block
style) and ``crosscoder_tpu/ops/moe.py``; the plain reference is
``benchmarks/reference/mellum_ref.py``."""

from __future__ import annotations

from typing import Any

from benchmarks.reference.mellum_ref import resid_pre  # noqa: F401 — the plain reference

# The hooked activations against the float32 reference, as the relative
# Frobenius error over one seeded 4096-token sequence a model. What is
# measured is the top-k router's discontinuity, not rounding: the bf16 stream
# moves a router logit by about 4e-3, the 8th and the 9th of 64 gates lie
# 7e-2 apart on average, so about one token in ten a layer swaps its last
# expert, and a swap moves that token's expert output by a third. That reads
# 6.4e-2 … 7.7e-2 on a v5e over PR 29's 19 seeds (PERF.md §6), where rounding
# alone would read under 1e-2. The limit stands 29% above the largest
# reading and under the planted faults measured on the chip at the cell's
# widths (PERF.md §6, PR 29): the full layer's attention_factor dropped 0.116,
# its YaRN frequencies replaced by the plain ones 0.19, top-7 for top-8 0.22,
# the weights rounded to 8-bit floats (the precision below the stated one)
# 0.26, no renormalisation of the gates 0.67, the window ignored 0.74. A
# bf16 router softmax reads 0.077 (seed 21, where the program reads 0.074): it perturbs the logits by as much as the
# bf16 stream already does, and NO limit on this number can tell it from the
# program; the router's precision is held by the CPU tests instead
# (tests/test_mellum.py, tests/test_moe.py: the reference's expert choice
# exactly, float32 gates under a bf16 model).
HARVEST_RTOL = 0.1

# The CPU tests' tiny sizes (``overrides``: ``LMConfig`` keywords for the
# common fields) mean, for this architecture's own fields: the layer table is
# the END of the published period at depths under a whole period (n_layers 2
# -> one window layer, then the full layer: both kinds inside the tiny hook
# depth) and its start otherwise; every layer stays sparse, with TINY_EXPERTS
# experts of width d_ff // 4, TINY_TOP_K of them a token (top-k < experts);
# the RoPE parameters stay the published ones at the tiny head size.
TINY_EXPERTS, TINY_TOP_K = 8, 2


def lm_config(config: dict, overrides: dict | None = None) -> Any:
    """``lm.LMConfig`` from the published keys in a configuration file."""
    from crosscoder_tpu.models import lm

    a = config["assumed"]
    rp = config["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    assert full["rope_type"] == "yarn" and sliding["rope_type"] == "default", rp
    kw = dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], rope_theta=float(sliding["rope_theta"]),
        rms_eps=config["rms_norm_eps"], attn_softcap=0.0, final_softcap=0.0,
        sliding_window=config["sliding_window"],
        query_pre_attn_scalar=float(config["head_dim"]), dtype=a["lm_dtype"],
        layer_types=tuple(config["layer_types"]),
        mlp_types=tuple(config["mlp_layer_types"]), block_style="prenorm",
        rope=((lm.FULL, lm.Rope(
            theta=float(full["rope_theta"]), yarn_factor=float(full["factor"]),
            original_max_position=full["original_max_position_embeddings"],
            beta_fast=float(full["beta_fast"]), beta_slow=float(full["beta_slow"]),
            attention_factor=full["attention_factor"])),),
        n_experts=config["num_experts"], experts_per_tok=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        # the output head is after the hook: a harvest job does not hold it
        tie_embeddings=not a["output_head_held"],
    )
    if overrides:
        kw.update(overrides)
        n, table = kw["n_layers"], tuple(config["layer_types"])
        kw.update(
            layer_types=table[-n:] if n < len(table) else (table * n)[:n],
            mlp_types=(lm.SPARSE,) * n, n_experts=TINY_EXPERTS,
            experts_per_tok=TINY_TOP_K, d_expert=max(kw["d_ff"] // 4, 1))
    return lm.LMConfig(**kw)


def _mean_keys(kind: str, seq_len: int, window: int) -> float:
    """Keys a query attends to, averaged over the positions of a causal
    sequence: ``(S + 1) / 2`` on a full layer, the mean of ``min(pos + 1,
    window)`` on a window layer."""
    if kind == "full_attention" or window >= seq_len:
        return (seq_len + 1) / 2
    return (window * (window + 1) / 2 + (seq_len - window) * window) / seq_len


def expert_flops_per_token(lm_cfg: Any, n_layers: int) -> float:
    """The routed experts' three products for one token: ``experts_per_tok``
    experts, gate, up and down of ``d_model x d_expert`` each."""
    return float(n_layers * lm_cfg.experts_per_tok * 3 * 2 * lm_cfg.d_model * lm_cfg.d_expert)


def flops_per_token(lm_cfg: Any, n_layers: int, seq_len: int) -> float:
    """Forward FLOPs of the first ``n_layers`` blocks for one token of a
    ``seq_len`` causal sequence: the four attention projections, the router,
    the routed experts, and QK^T and PV over the keys the layer's kind sees."""
    D = lm_cfg.d_model
    qd, kd = lm_cfg.n_heads * lm_cfg.head_dim, lm_cfg.n_kv_heads * lm_cfg.head_dim
    proj = 2 * (D * qd + 2 * D * kd + qd * D) + 2 * D * lm_cfg.n_experts
    attn = sum(2 * 2 * qd * _mean_keys(kind, seq_len, lm_cfg.sliding_window)
               for kind in lm_cfg.layer_types[:n_layers])
    return float(n_layers * proj + attn) + expert_flops_per_token(lm_cfg, n_layers)


def expert_share_of_flops(lm_cfg: Any, n_layers: int, seq_len: int) -> float:
    """The experts' part of ``flops_per_token`` (``moe_experts_peak_share``
    scales the harvest's needed FLOPs a step by it)."""
    return expert_flops_per_token(lm_cfg, n_layers) / flops_per_token(lm_cfg, n_layers, seq_len)
