"""Xing4.0-29B-A4B (XingChen-AGI; ``model_type`` "xing4_0"): a pre-norm block
on FOUR residual streams a token — each sublayer reads one vector from them
by an input-dependent map and writes its output back through a second while a
4 x 4 doubly stochastic matrix (20 Sinkhorn iterations a token and sublayer)
mixes them (mHC, arXiv:2512.24880); latent attention (two low-rank paths with
a norm in the middle of each, a score head of 128 + 64 rotary dims whose
rotary key all 32 heads share, a value head of 128, static YaRN); two dense
layers, then 64 sigmoid-scored experts (top-4 by score plus a bias, gated by
the unbiased scores, times 2) beside one shared expert. A chip holds a SHARE
of each sparse layer's experts (the configuration's ``deployment``). The
program runs it through ``crosscoder_tpu/models/lm.py``, ``ops/mhc.py``,
``ops/flash_attention.py`` (the latent instance) and ``ops/moe.py``; the plain
reference is ``benchmarks/reference/xing_ref.py``."""

from __future__ import annotations

import math
from typing import Any

from benchmarks.reference.xing_ref import resid_pre  # noqa: F401 — the plain reference

# The hooked activations (the MEAN of the four streams entering the hook's
# block) against the float32 reference given the same share, as the relative
# Frobenius error over one seeded 4096-token sequence a model. As for Mellum2
# and Laguna the bf16 program's own reading is not rounding but the top-k
# router's discontinuity under a bf16 stream: entering the blocks it reads
# 0.004, 0.005 after the two dense layers, then 0.021, 0.032, 0.045, 0.055
# after the four sparse ones (scripts/probes/_xing_faults.py --by-layer, seed
# 21, on a v5e) — a quarter of the routed experts reaches this chip's stream,
# twice Laguna's eighth, at gates times 2. The two sides of the limit,
# measured on the chip at the cell's widths (seed 21; PERF.md §6 has every
# reading): below, the program 0.0622 (0.0597-0.0639 over the thirteen runs
# of the cell's two sets and its traced run); above, the weights rounded to
# 8-bit floats (float8_e4m3, the precision below the stated one) 0.2256. The
# limit stands 1.56 times over the first's largest and 2.3 times under the second, and under fifteen of the eighteen planted faults: one
# Sinkhorn iteration 0.122 (the nearest), the latent norms dropped 0.166, the
# choice bias dropped 0.170, softmax for sigmoid 0.209, routed scale 1.0
# 0.233, top-3 0.255, the maps' input-dependent part dropped 0.377, YaRN
# dropped 0.392, m² dropped 0.432, another rank's experts 0.537, a rotary key
# a head 0.544, sigma for two sigma 0.591, the shared expert dropped 0.708,
# the streams summed at the hook 3.0. Three read under it and are held by the
# CPU tests in float32 (tests/test_xing_faults.py CPU_ONLY): rows before columns
# 0.065 (after 20 iterations the order is Sinkhorn's remainder), the norm
# before the read 0.080, the bias used in the gates 0.061 (the fixture's bias
# is a hundredth).
HARVEST_RTOL = 0.1

# The CPU tests' tiny sizes (``overrides``: ``LMConfig`` keywords for the
# common fields) mean, for this architecture's own fields: the layer table is
# ONE leading dense layer, then sparse ones (n_layers 2 -> both MLP kinds
# inside the tiny hook depth; the published table leads with two); one
# key/value head a query head; of the given ``head_dim`` the trailing half is
# rotary and the leading half is the head's own part and the value head's
# size; the query rank is half and the key/value rank a quarter of the width;
# TINY_EXPERTS experts of width d_ff // 4 of which rank 0 of TINY_RANKS holds
# its share, TINY_TOP_K a token, the shared expert as wide as a routed one;
# the stream count, the Sinkhorn iterations, eps, clamp, the RoPE parameters
# and the scale's m² stay the published ones.
TINY_EXPERTS, TINY_RANKS, TINY_TOP_K = 16, 4, 4


def _mscale(factor: float, m: float) -> float:
    """DeepSeek's ``yarn_get_mscale``."""
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def lm_config(config: dict, overrides: dict | None = None) -> Any:
    """``lm.LMConfig`` from the published keys in a configuration file."""
    from crosscoder_tpu.models import lm

    a, dep, rs = config["assumed"], config["deployment"], config["rope_scaling"]
    assert rs["type"] == "yarn" and config["scoring_func"] == "sigmoid", config
    assert config["topk_method"] == "noaux_tc" and config["n_group"] == config["topk_group"] == 1
    assert config["num_key_value_heads"] == config["num_attention_heads"]
    assert config["hidden_act"] == "silu" and not config["attention_bias"]
    assert config["moe_layer_freq"] == 1
    assert dep["experts_held"] == config["n_routed_experts"], dep
    n, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    m2 = _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    kw = dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"], n_layers=n,
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        d_ff=config["intermediate_size"], rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"], attn_softcap=0.0, final_softcap=0.0,
        sliding_window=0, dtype=a["lm_dtype"], block_style="prenorm",
        n_experts=dep["published_n_routed_experts"],
        experts_per_tok=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        # the output head is after the hook: a harvest job does not hold it
        tie_embeddings=not a["output_head_held"],
        d_shared_expert=config["n_shared_experts"] * config["moe_intermediate_size"],
        routed_scale=float(config["routed_scaling_factor"]), router="sigmoid_bias",
        experts_held=dep["experts_held"], expert_rank=dep["rank"],
        embed_std=float(a["weights"]["embed_std"]),
        n_streams=config["hc_mult"], hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"],
        hc_clamp=(config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]),
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_rope_dim=config["qk_rope_head_dim"], v_head_dim=config["v_head_dim"],
    )
    if overrides:
        kw.update(overrides)
        dense = 1
        hd, D = kw["head_dim"], kw["d_model"]
        kw.update(
            n_kv_heads=kw["n_heads"], sliding_window=0,
            qk_rope_dim=hd // 2, v_head_dim=hd - hd // 2,
            q_lora_rank=D // 2, kv_lora_rank=D // 4,
            n_experts=TINY_EXPERTS, experts_held=TINY_EXPERTS // TINY_RANKS,
            expert_rank=0, experts_per_tok=TINY_TOP_K,
            d_expert=max(kw["d_ff"] // 4, 1), d_shared_expert=max(kw["d_ff"] // 4, 1))
    n = kw["n_layers"]
    kw.update(
        layer_types=(lm.FULL,) * n,
        mlp_types=tuple(lm.DENSE if i < dense else lm.SPARSE for i in range(n)),
        # scores · head_dim^-0.5 · m²  ==  scores · (head_dim / m⁴)^-0.5
        query_pre_attn_scalar=kw["head_dim"] / m2 ** 2,
        rope=((lm.FULL, lm.Rope(
            theta=float(config["rope_theta"]), yarn_factor=float(rs["factor"]),
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            attention_factor=(_mscale(rs["factor"], rs["mscale"])
                              / _mscale(rs["factor"], rs["mscale_all_dim"])))),))
    return lm.LMConfig(**kw)


def _sparse_layers(lm_cfg: Any, n_layers: int) -> int:
    return sum(t == "sparse" for t in lm_cfg.mlp_types[:n_layers])


def expert_flops_per_token(lm_cfg: Any, n_layers: int) -> float:
    """The HELD routed experts' three products for one token, as the even
    expectation: of a token's ``experts_per_tok`` routed rows the share
    ``held / published`` meets an expert this chip holds (4 · 16 / 64 = 1 row
    a sparse layer at the cell's sizes; what a seed's routers really send is
    the gauge ``harvest/moe_local_row_share``)."""
    held = (lm_cfg.experts_held or lm_cfg.n_experts) / lm_cfg.n_experts
    return float(_sparse_layers(lm_cfg, n_layers) * lm_cfg.experts_per_tok * held
                 * 3 * 2 * lm_cfg.d_model * lm_cfg.d_expert)


def attn_core_flops_per_token(lm_cfg: Any, n_layers: int, seq_len: int) -> float:
    """QK^T over the score head (its own part and the rotary part: 192 dims,
    not the lane padding) and PV over the value head, for the mean number of
    keys a query of a ``seq_len`` causal sequence sees."""
    keys = (seq_len + 1) / 2
    return float(n_layers * lm_cfg.n_heads * 2 * (lm_cfg.head_dim + lm_cfg.v_head_dim) * keys)


def flops_per_token(lm_cfg: Any, n_layers: int, seq_len: int) -> float:
    """Forward FLOPs this chip's share of the first ``n_layers`` blocks needs
    for one token of a ``seq_len`` causal sequence: per layer the latent
    projections (q_a, q_b, kv_a, kv_b, o), the attention cores, the two stream
    maps' products (``vec(X) phi``, the weighted read, the mixing and the
    write); the dense MLP, or the router (at the model's width), the shared
    expert and the held routed experts."""
    D, H, n = lm_cfg.d_model, lm_cfg.n_heads, lm_cfg.n_streams
    rq, rkv, dr, dv = (lm_cfg.q_lora_rank, lm_cfg.kv_lora_rank, lm_cfg.qk_rope_dim,
                       lm_cfg.v_head_dim)
    qd = H * lm_cfg.head_dim
    proj = 2 * (D * rq + rq * qd + D * (rkv + dr) + rkv * (qd - H * dr) + rkv * H * dv + H * dv * D)
    maps = 2 * (2 * n * D * (n * n + 2 * n) + 2 * n * D + 2 * n * n * D + 2 * n * D)
    total = n_layers * (proj + maps) + attn_core_flops_per_token(lm_cfg, n_layers, seq_len)
    for i in range(n_layers):
        if lm_cfg.mlp_types[i] == "dense":
            total += 3 * 2 * D * lm_cfg.d_ff
        else:
            total += 2 * D * lm_cfg.n_experts + 3 * 2 * D * lm_cfg.d_shared_expert
    return float(total) + expert_flops_per_token(lm_cfg, n_layers)


def expert_share_of_flops(lm_cfg: Any, n_layers: int, seq_len: int) -> float:
    """The held routed experts' part of ``flops_per_token``
    (``xing_held_experts_peak_share`` scales the harvest's needed FLOPs a
    step by it)."""
    return expert_flops_per_token(lm_cfg, n_layers) / flops_per_token(lm_cfg, n_layers, seq_len)


def attn_core_share_of_flops(lm_cfg: Any, n_layers: int, seq_len: int) -> float:
    """The attention cores' part of ``flops_per_token``
    (``mla_attn_kernel_peak_share``)."""
    return (attn_core_flops_per_token(lm_cfg, n_layers, seq_len)
            / flops_per_token(lm_cfg, n_layers, seq_len))


def mhc_bytes_per_token(lm_cfg: Any, n_layers: int) -> float:
    """HBM bytes the two stream-map kernels NEED for one token, two sublayers
    a layer: the read takes the n streams in and gives the read vector out,
    ``(n + 1) · C`` values; the write takes the streams and y in and gives the
    streams out, ``(2n + 1) · C`` (the maps themselves, 20 floats a token, and
    phi, read once a call, are not counted)."""
    n, item = lm_cfg.n_streams, 2 if lm_cfg.dtype == "bf16" else 4
    return float(n_layers * 2 * ((n + 1) + (2 * n + 1)) * lm_cfg.d_model * item)


def mhc_bytes_over_flops(lm_cfg: Any, n_layers: int, seq_len: int) -> float:
    """``mhc_bytes_per_token`` over ``flops_per_token``: what turns the
    harvest's needed FLOPs a step into the stream maps' needed bytes a step
    (``mhc_hbm_roofline``; ``op_peak_share`` multiplies the one by the
    other and divides by the HBM peak)."""
    return mhc_bytes_per_token(lm_cfg, n_layers) / flops_per_token(lm_cfg, n_layers, seq_len)
