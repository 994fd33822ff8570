"""The subject model's forward to a residual hook in plain float32.

The block is the one ``crosscoder_tpu/models/lm.py`` runs (the repo's only
subject-LM block), written out from its description: embedding scaled by
sqrt(d), then per block a sandwich of RMSNorms ((1 + w) scale) around causal
softmax attention (split-half RoPE, logits scaled by
query_pre_attn_scalar**-0.5 and soft-capped, even blocks limited to a sliding
window) and around a gated MLP (tanh-GELU gate). ``hook_resid_pre`` of block
L is the stream after blocks 0..L-1. A Python loop over layers, float32
weights, highest matmul precision; no scan, no cache, no kernels.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    S, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d // 2, dtype=jnp.float32) * 2.0 / d))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _block(resid, lp, cfg: Any, layer: int):
    B, S, _ = resid.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = _rms(resid, lp["attn_norm"], cfg.rms_eps)
    q = _rope((x @ lp["wq"]).reshape(B, S, H, hd), cfg.rope_theta)
    k = _rope((x @ lp["wk"]).reshape(B, S, KV, hd), cfg.rope_theta)
    v = (x @ lp["wv"]).reshape(B, S, KV, hd)
    k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
    logits = jnp.einsum("bqhd,bshd->bhqs", q * cfg.query_pre_attn_scalar ** -0.5, k)
    if cfg.attn_softcap:
        logits = cfg.attn_softcap * jnp.tanh(logits / cfg.attn_softcap)
    pos = jnp.arange(S)
    mask = pos[:, None] >= pos[None, :]
    if layer % 2 == 0 and cfg.sliding_window:
        mask &= pos[:, None] - pos[None, :] < cfg.sliding_window
    probs = jax.nn.softmax(jnp.where(mask[None, None], logits, -1e30), axis=-1)
    a = jnp.einsum("bhqs,bshd->bqhd", probs, v).reshape(B, S, H * hd) @ lp["wo"]
    resid = resid + _rms(a, lp["post_attn_norm"], cfg.rms_eps)
    x = _rms(resid, lp["pre_ffw_norm"], cfg.rms_eps)
    m = (jax.nn.gelu(x @ lp["w_gate"], approximate=True) * (x @ lp["w_up"])) @ lp["w_down"]
    return resid + _rms(m, lp["post_ffw_norm"], cfg.rms_eps)


def resid_pre(params: dict, tokens: jax.Array, cfg: Any, hook_layer: int) -> jax.Array:
    """[B, S, d] float32: the residual stream entering block ``hook_layer``."""
    with jax.default_matmul_precision("highest"):
        resid = params["embed"][tokens].astype(jnp.float32) * math.sqrt(cfg.d_model)
        block = jax.jit(_block, static_argnums=(2, 3))
        for layer in range(hook_layer):
            lp = {k: v[layer].astype(jnp.float32) for k, v in params["layers"].items()}
            resid = block(resid, lp, cfg, layer % 2)   # only parity matters
        return resid
