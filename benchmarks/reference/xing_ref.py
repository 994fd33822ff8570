"""Xing4.0-29B-A4B's forward to a residual hook in plain float32.

Written out from the published config (``model_type`` "xing4_0"; the
equations are ISSUE 35's, each thing the config does not say is in the
configuration file's ``assumed``). ``RMS(x; w) = x * rsqrt(mean(x^2) + eps)
* w``; ``C`` the model width, ``n = hc_mult`` streams a token.

*Streams.* A token's state is ``X [n, C]``; after the embedding every
``X[i]`` is the embedding. Each sublayer ``F`` of each layer (attention, then
MLP) has its own ``phi [n C, n^2 + 2n]``, ``alpha = (a_pre, a_post, a_res)``
and ``bias = (b_pre | b_post | B_res row-major)``:

    z      = rsqrt(mean(vec(X)^2) + eps) * (vec(X) phi)
    h_pre  = sigmoid(a_pre z[:n] + b_pre);  h_post = 2 sigmoid(a_post z[n:2n] + b_post)
    A      = clip(a_res mat(z[2n:]) + B_res, clamp_min, clamp_max)
    M      = exp(A); iters times: M <- M / (colsum(M) + hc_eps); M <- M / (rowsum(M) + hc_eps)
    u      = RMS(sum_i h_pre[i] X[i]; w);  y = F(u);  X'[i] = sum_j M[i, j] X[j] + h_post[i] y

*Attention* (H heads): ``c_q = RMS(u Wqa; w_qa)``; per head ``q = [c_q
Wq_nope | rot(c_q Wq_rope)]``; ``[c_kv | k_r] = u Wkva``; ``c_kv <- RMS(c_kv;
w_kva)``; per head ``k = [c_kv Wk_nope | rot(k_r)]`` — ONE rotary key for all
heads — and ``v = c_kv Wv``; rotation by static YaRN at the rotary width
(split-half pairs); scores ``q k^T * scale`` with ``scale =
query_pre_attn_scalar^-0.5`` (the configuration folds YaRN's m^2 into it);
causal softmax; ``y = concat_h(P v) Wo``.

*MLP.* A dense layer: ``(SiLU(u Wg) * (u Wu)) Wd``. A sparse layer: ``s =
sigmoid(u Wr)`` over ALL the model's experts; the ``k`` largest of ``s +
bias`` (ties to the lowest index); gates ``s_e / (sum_chosen s + 1e-20) *
routed_scale`` from the UNBIASED scores; ``y = sum_e gate_e E_e(u) + S(u)``.
*The share*: the tree holds experts ``[rank * held, (rank + 1) * held)``; the
sum runs over the experts that are chosen AND held, plus ``S(u)``; nothing
stands in for the absent ones.

*The hook*: the MEAN of the streams entering block ``hook_layer``.

Python loops over layers, heads, held experts (each on every token, masked
by its gate) and Sinkhorn iterations; float32 weights, highest matmul
precision; no scan, no kernel, no grouping. Shares no code with
``crosscoder_tpu`` nor with the other references: the config object and the
parameter tree are read as data (field and leaf names only). The tree's
layers are stacked by the SHAPE of their leaves, (query heads, MLP kind) in
order of first appearance.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

_EXPERT_LEAVES = ("we_gate_up", "we_down")


def stack_and_slot(cfg: Any, layer: int) -> tuple[int, int]:
    """Which stack of leaves holds ``layer``, and at which place."""
    shapes = [(cfg.n_heads, cfg.mlp_types[i]) for i in range(cfg.n_layers)]
    order = list(dict.fromkeys(shapes))
    return order.index(shapes[layer]), shapes[:layer].count(shapes[layer])


def rope_tables(cfg: Any, seq_len: int) -> tuple[np.ndarray, np.ndarray]:
    """cos and sin ``[S, qk_rope_dim / 2]`` of static YaRN at the rotary
    width: the plain frequencies, those divided by ``factor``, and a linear
    ramp between the two over the pairs whose wavelengths the original
    context held between ``beta_fast`` and ``beta_slow`` times."""
    spec = dict(cfg.rope)["full_attention"]
    dim = cfg.qk_rope_dim
    i = np.arange(dim // 2, dtype=np.float64)
    freq = spec.theta ** (-2.0 * i / dim)
    if spec.yarn_factor:
        def cd(rotations: float) -> float:
            return (dim * math.log(spec.original_max_position / (2 * math.pi * rotations))
                    / (2 * math.log(spec.theta)))

        low = max(math.floor(cd(spec.beta_fast)), 0)
        high = min(math.ceil(cd(spec.beta_slow)), dim - 1)
        ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
        freq = freq / spec.yarn_factor * ramp + freq * (1.0 - ramp)
    ang = np.arange(seq_len, dtype=np.float32)[:, None] * freq.astype(np.float32)[None, :]
    return ((np.cos(ang) * spec.attention_factor).astype(np.float32),
            (np.sin(ang) * spec.attention_factor).astype(np.float32))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, cos, sin):
    """x [B, S, d]; cos, sin [S, d/2]: pairs ``(j, j + d/2)`` rotate."""
    half = cos.shape[-1]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def sinkhorn(M, iters: int, eps: float):
    """M [..., n, n] positive: columns, then rows, ``iters`` times."""
    for _ in range(iters):
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + eps)
        M = M / (jnp.sum(M, axis=-1, keepdims=True) + eps)
    return M


def stream_maps(X, phi, alpha, bias, cfg: Any):
    """X [B, S, n, C] -> h_pre [B, S, n], h_post [B, S, n], M [B, S, n, n]."""
    B, S, n, C = X.shape
    flat = X.reshape(B, S, n * C)
    z = (flat @ phi) * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + cfg.rms_eps)
    h_pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] + bias[n:2 * n])
    A = alpha[2] * z[..., 2 * n:].reshape(B, S, n, n) + bias[2 * n:].reshape(n, n)
    M = sinkhorn(jnp.exp(jnp.clip(A, cfg.hc_clamp[0], cfg.hc_clamp[1])),
                 cfg.hc_sinkhorn_iters, cfg.hc_eps)
    return h_pre, h_post, M


@jax.jit
def _attend_head(q, k, v, scale):
    """One head: q, k [B, S, d], v [B, S, dv] -> [B, S, dv], causal."""
    S = q.shape[1]
    pos = np.arange(S)
    scores = jnp.einsum("bqd,bsd->bqs", q, k) * scale
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    return jnp.einsum("bqs,bsd->bqd", jax.nn.softmax(scores, axis=-1), v)


def attention(u, lp: dict, cfg: Any):
    B, S, _ = u.shape
    dr, dv, r = cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    dn = cfg.head_dim - dr
    cos, sin = rope_tables(cfg, S)
    c_q = _rms(u @ lp["wq_a"], lp["q_a_norm"], cfg.rms_eps)
    ckv = u @ lp["wkv_a"]
    c_kv = _rms(ckv[..., :r], lp["kv_a_norm"], cfg.rms_eps)
    k_rope = rotate(ckv[..., r:], cos, sin)                 # ONE key, all heads
    scale = cfg.query_pre_attn_scalar ** -0.5
    heads = []
    for h in range(cfg.n_heads):
        q = jnp.concatenate([c_q @ lp["wq_nope"][:, h * dn:(h + 1) * dn],
                             rotate(c_q @ lp["wq_rope"][:, h * dr:(h + 1) * dr], cos, sin)], -1)
        k = jnp.concatenate([c_kv @ lp["wk_nope"][:, h * dn:(h + 1) * dn], k_rope], -1)
        heads.append(_attend_head(q, k, c_kv @ lp["wv"][:, h * dv:(h + 1) * dv], scale))
    return jnp.concatenate(heads, axis=-1) @ lp["wo"]


def gated_mlp(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def routing(u, w_router, bias, top_k: int, norm_topk_prob: bool, routed_scale: float):
    """u [T, D] -> chosen experts [T, k] (of all the model's) and their
    gates [T, k]: chosen by score + bias, gated by the score."""
    s = jax.nn.sigmoid(u @ w_router)
    _, chosen = jax.lax.top_k(s + bias, top_k)          # ties: the lower index first
    gates = jnp.take_along_axis(s, chosen, axis=-1)
    if norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return chosen, gates * routed_scale


@jax.jit
def _expert_term(u, chosen, gates, w_gate_up, w_down, slot, held, e):
    """The held expert at place ``held`` of the stored leaves — expert ``e``
    of the model — on every token, times its gate (0 where it was not
    chosen). Traced numbers: one compiled function for every expert."""
    w_gu = w_gate_up[slot, held].astype(jnp.float32)
    f = w_gu.shape[-1] // 2
    gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
    y = gated_mlp(u, w_gu[:, :f], w_gu[:, f:], w_down[slot, held].astype(jnp.float32))
    return gate[:, None] * y


def mlp(u, lp: dict, stack: dict, slot: int, cfg: Any):
    """u [B, S, D]: the layer's MLP by its leaves."""
    if "router" not in lp:
        return gated_mlp(u, lp["w_gate"], lp["w_up"], lp["w_down"])
    B, S, D = u.shape
    u2 = u.reshape(B * S, D)
    chosen, gates = routing(u2, lp["router"], lp["router_bias"], cfg.experts_per_tok,
                            cfg.norm_topk_prob, cfg.routed_scale)
    n_held = stack["we_down"].shape[1]
    first = cfg.expert_rank * n_held
    out = gated_mlp(u2, lp["ws_gate"], lp["ws_up"], lp["ws_down"])      # the shared expert
    for held in range(n_held):
        out = out + _expert_term(u2, chosen, gates, stack["we_gate_up"], stack["we_down"],
                                 np.int32(slot), np.int32(held), np.int32(first + held))
    return out.reshape(B, S, D)


@jax.jit
def _layer_leaves(stack: dict, slot):
    return {k: v[slot].astype(jnp.float32) for k, v in stack.items()
            if k not in _EXPERT_LEAVES}


def sublayer(X, lp: dict, site: str, norm: str, fn, cfg: Any):
    """Read, norm, ``fn``, write: X [B, S, n, C] -> X'."""
    h_pre, h_post, M = stream_maps(X, lp[f"hc_{site}_phi"], lp[f"hc_{site}_alpha"],
                                   lp[f"hc_{site}_bias"], cfg)
    u = _rms(jnp.einsum("bsn,bsnc->bsc", h_pre, X), lp[norm], cfg.rms_eps)
    y = fn(u)
    return jnp.einsum("bsij,bsjc->bsic", M, X) + h_post[..., None] * y[:, :, None, :]


def streams(params: dict, tokens: jax.Array, cfg: Any, n_layers: int,
            means: list | None = None) -> jax.Array:
    """[B, S, n, C] float32: the streams entering block ``n_layers`` (and,
    appended to ``means``, their mean as it enters each block before it)."""
    stacks = params["layers"]
    if isinstance(stacks, dict):
        stacks = (stacks,)
    e = params["embed"][tokens].astype(jnp.float32)
    X = jnp.repeat(e[:, :, None, :], cfg.n_streams, axis=2)
    for layer in range(n_layers):
        if means is not None:
            means.append(jnp.mean(X, axis=2))
        c, slot = stack_and_slot(cfg, layer)
        lp = _layer_leaves(stacks[c], np.int32(slot))
        X = sublayer(X, lp, "attn", "attn_norm", lambda u: attention(u, lp, cfg), cfg)
        X = sublayer(X, lp, "ffn", "pre_ffw_norm",
                     lambda u: mlp(u, lp, stacks[c], slot, cfg), cfg)
    return X


def resid_pre(params: dict, tokens: jax.Array, cfg: Any, hook_layer: int) -> jax.Array:
    """[B, S, C] float32: what ``blocks.<hook_layer>.hook_resid_pre`` is on
    this model — the MEAN of the streams entering that block."""
    with jax.default_matmul_precision("highest"):
        return jnp.mean(streams(params, tokens, cfg, hook_layer), axis=2)


def logits(params: dict, tokens: jax.Array, cfg: Any) -> jax.Array:
    """[B, S, V] float32: the whole forward (CPU tests only) — after the last
    layer the learned read ``sum_i sigmoid(a z[i] + b[i]) X[i]`` with ``z``
    as above for ``phi_h [n C, n]``, the final norm, the untied head."""
    with jax.default_matmul_precision("highest"):
        X = streams(params, tokens, cfg, cfg.n_layers)
        B, S, n, C = X.shape
        flat = X.reshape(B, S, n * C)
        z = (flat @ params["hc_head_phi"]) * jax.lax.rsqrt(
            jnp.mean(flat * flat, axis=-1, keepdims=True) + cfg.rms_eps)
        h = jax.nn.sigmoid(params["hc_head_alpha"] * z + params["hc_head_bias"])
        x = _rms(jnp.einsum("bsn,bsnc->bsc", h, X),
                 params["final_norm"].astype(jnp.float32), cfg.rms_eps)
        return x @ params["unembed"].astype(jnp.float32).T
