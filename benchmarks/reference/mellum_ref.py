"""Mellum2's forward to a residual hook in plain float32.

Written out from the published config (``model_type`` "mellum"; the
equations are ISSUE 29's, each departure from the source is in the
configuration file's ``assumed``). For layer ``l`` on the stream ``h``:

    h <- h + Attn_l(RMSNorm(h; w1_l));   h <- h + MoE_l(RMSNorm(h; w2_l))

- RMSNorm: ``x * rsqrt(mean(x^2) + eps) * w`` (plain ``w``); no post-norm, no
  soft-cap, no embedding scale.
- Attention: 32 query heads over 4 key/value heads of 128, no bias;
  split-half RoPE with the LAYER KIND's frequencies; scores ``q k^T /
  sqrt(128)``; query ``i`` sees key ``j`` iff ``j <= i`` and, on a
  ``sliding_attention`` layer, ``i - j < sliding_window``.
- RoPE: ``sliding_attention`` rotates by ``theta^(-2i/d)``; ``full_attention``
  by static YaRN (below), its cos and sin times ``attention_factor``.
- MoE: ``g = softmax(x Wr)`` over all experts; the ``k`` largest (ties to
  the lowest index), their gates divided by their sum; the sum over the
  chosen experts of ``gate * (SiLU(x Wg_e) * (x Wu_e)) Wd_e``.

A Python loop over layers, inside it over key/value heads and over experts
(each expert on every token, masked by its gate: a token it was not chosen
for has gate 0), so that the float32 copies of one head group or one expert
are all that is live beside the model: one layer's experts in float32 would
be 1.6 GB. Float32 weights, highest matmul precision; no scan, no kernel, no
grouping. Shares no code with ``crosscoder_tpu``: the config object and the
parameter tree are read as data (field and leaf names only).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def yarn_inv_freq(theta: float, dim: int, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The ``dim // 2`` YaRN frequencies: the plain ones, those divided by
    ``factor``, and a linear ramp between the two over the pairs whose
    wavelengths the original context held between ``beta_fast`` and
    ``beta_slow`` times."""
    i = np.arange(dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / dim)
    inter = extra / factor

    def cd(rotations: float) -> float:
        return dim * math.log(original_max / (2 * math.pi * rotations)) / (2 * math.log(theta))

    low = max(math.floor(cd(beta_fast)), 0)
    high = min(math.ceil(cd(beta_slow)), dim - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rope_tables(cfg: Any, kind: str, seq_len: int) -> tuple[np.ndarray, np.ndarray]:
    """cos and sin ``[S, head_dim // 2]`` of one layer kind, in float32."""
    d = cfg.head_dim
    spec = dict(cfg.rope).get(kind)
    if spec is None or not spec.yarn_factor:
        theta = cfg.rope_theta if spec is None else spec.theta
        inv, factor = theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d), 1.0
    else:
        inv = yarn_inv_freq(spec.theta, d, spec.yarn_factor, spec.original_max_position,
                            spec.beta_fast, spec.beta_slow)
        factor = spec.attention_factor
    ang = np.arange(seq_len, dtype=np.float32)[:, None] * inv.astype(np.float32)[None, :]
    return ((np.cos(ang) * factor).astype(np.float32),
            (np.sin(ang) * factor).astype(np.float32))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate(x, cos, sin):
    """x [B, S, heads, d]; cos, sin [S, d/2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@jax.jit
def _attend_group(q, k, v, mask):
    """One key/value head and the query heads it serves: q [B, S, g, d],
    k, v [B, S, d], mask [S, S] -> [B, S, g, d]."""
    scores = jnp.einsum("bqgd,bsd->bgqs", q, k) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bgqs,bsd->bqgd", probs, v)


def attention(x, lp: dict, cfg: Any, kind: str):
    B, S, _ = x.shape
    H, KV, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cos, sin = rope_tables(cfg, kind, S)
    q = _rotate((x @ lp["wq"]).reshape(B, S, H, d), cos, sin)
    k = _rotate((x @ lp["wk"]).reshape(B, S, KV, d), cos, sin)
    v = (x @ lp["wv"]).reshape(B, S, KV, d)
    pos = np.arange(S)
    mask = pos[:, None] >= pos[None, :]
    if kind == "sliding_attention":
        mask &= pos[:, None] - pos[None, :] < cfg.sliding_window
    g = H // KV
    heads = [_attend_group(q[:, :, j * g:(j + 1) * g], k[:, :, j], v[:, :, j], mask)
             for j in range(KV)]
    return jnp.concatenate(heads, axis=2).reshape(B, S, H * d) @ lp["wo"]


def routing(x, w_router, top_k: int, norm_topk_prob: bool):
    """x [T, D] -> chosen experts [T, k] and their gates [T, k] (float32)."""
    g = jax.nn.softmax(x @ w_router, axis=-1)
    gates, chosen = jax.lax.top_k(g, top_k)     # ties: the lower index first
    if norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return chosen, gates


@jax.jit
def _expert_term(x, chosen, gates, w_gate_up, w_down, layer, e):
    """Expert ``e`` of layer ``layer`` on every token, times its gate (0
    where it was not chosen). ``layer`` and ``e`` index the stored leaves as
    traced numbers: one compiled function for every expert."""
    w_gu = w_gate_up[layer, e].astype(jnp.float32)
    f = w_gu.shape[-1] // 2
    gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
    y = (jax.nn.silu(x @ w_gu[:, :f]) * (x @ w_gu[:, f:])) @ w_down[layer, e].astype(jnp.float32)
    return gate[:, None] * y


def moe(x, lp: dict, layer_leaves: dict, layer: int, cfg: Any):
    """x [B, S, D]; ``lp`` holds the layer's float32 router; the expert
    weights are read from the stored leaves one expert at a time."""
    B, S, D = x.shape
    x2 = x.reshape(B * S, D)
    chosen, gates = routing(x2, lp["router"], cfg.experts_per_tok, cfg.norm_topk_prob)
    out = jnp.zeros_like(x2)
    for e in range(cfg.n_experts):
        out = out + _expert_term(x2, chosen, gates, layer_leaves["we_gate_up"],
                                 layer_leaves["we_down"], np.int32(layer), np.int32(e))
    return out.reshape(B, S, D)


@jax.jit
def _layer_leaves(leaves: dict, layer):
    return {k: leaves[k][layer].astype(jnp.float32)
            for k in ("attn_norm", "pre_ffw_norm", "wq", "wk", "wv", "wo", "router")}


def resid_pre(params: dict, tokens: jax.Array, cfg: Any, hook_layer: int) -> jax.Array:
    """[B, S, d] float32: the residual stream entering block ``hook_layer``."""
    leaves = params["layers"]
    with jax.default_matmul_precision("highest"):
        resid = params["embed"][tokens].astype(jnp.float32)
        for layer in range(hook_layer):
            lp = _layer_leaves(leaves, np.int32(layer))
            resid = resid + attention(_rms(resid, lp["attn_norm"], cfg.rms_eps), lp, cfg,
                                      cfg.layer_types[layer])
            resid = resid + moe(_rms(resid, lp["pre_ffw_norm"], cfg.rms_eps), lp, leaves,
                                layer, cfg)
        return resid
