"""Laguna-S-2.1's forward to a residual hook in plain float32.

Written out from the published config (``model_type`` "laguna"; the
equations are ISSUE 33's, each thing the config does not say is in the
configuration file's ``assumed``). ``RMSNorm(h; w) = h * rsqrt(mean(h^2) +
eps) * w``; no post-norm, no soft-cap, no embedding scale. For layer ``l``:

    h <- h + Attn_l(RMSNorm(h; w1_l));   h <- h + MLP_l(RMSNorm(h; w2_l))

- Attention: ``H_l`` query heads (the layer's own count: 48 on a full layer,
  72 on a window layer) over 8 key/value heads of 128, no bias. RoPE by the
  layer's kind: a window layer rotates the whole head by ``theta^(-2i/128)``;
  a full layer rotates the FIRST ``rotary_factor * 128`` dims of each head
  (split-half pairs inside them, the rest pass) by static YaRN computed at
  that width, cos and sin times ``attention_factor``. Scores ``q k^T /
  sqrt(128)``; query ``i`` sees key ``j`` iff ``j <= i`` and, on a window
  layer, ``i - j < sliding_window``. Gate: ``g = sigmoid(u Wg)``, one scalar
  a head and position from the block's normed input ``u``, times the
  attended head; then ``Wo``.
- MLP, a dense layer: ``(SiLU(u Wgate) * (u Wup)) Wdown``.
- MLP, a sparse layer: ``p = softmax(u Wr)`` over ALL the model's experts;
  the ``k`` largest (ties to the lowest index); gates ``p_e / sum p`` over
  the chosen, times ``routed_scale``; ``sum_e gate_e E_e(u) + S(u)`` with
  ``E_e`` and the shared ``S`` both ``(SiLU(u Wg) * (u Wu)) Wd``.
- The share: the tree holds experts ``[rank * held, (rank + 1) * held)`` of
  each sparse layer. The router keeps its whole width and its ``k``; the sum
  runs over the experts that are chosen AND held, plus ``S(u)``. That goes on
  to the next layer. Nothing stands in for the absent experts.

Python loops over layers, key/value head groups and held experts (each on
every token, masked by its gate), float32 weights, highest matmul precision;
no scan, no kernel, no grouping. Shares no code with ``crosscoder_tpu`` nor
with the other references: the config object and the parameter tree are
read as data (field and leaf names only). The tree's layers are stacked by
the SHAPE of their leaves, (query heads, MLP kind) in order of first
appearance, one dict of ``[layers of that shape, ...]`` leaves a shape
(``params["layers"]`` is that dict itself where there is one shape).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

_EXPERT_LEAVES = ("we_gate_up", "we_down")


def heads_of(cfg: Any, layer: int) -> int:
    by_layer = getattr(cfg, "heads_by_layer", None)
    return cfg.n_heads if by_layer is None else by_layer[layer]


def stack_and_slot(cfg: Any, layer: int) -> tuple[int, int]:
    """Which stack of leaves holds ``layer``, and at which place."""
    shapes = [(heads_of(cfg, i), cfg.mlp_types[i]) for i in range(cfg.n_layers)]
    order = list(dict.fromkeys(shapes))
    return order.index(shapes[layer]), shapes[:layer].count(shapes[layer])


def inv_freq(spec: Any, head_dim: int) -> tuple[np.ndarray, float]:
    """The frequencies of the pairs that rotate (``rotary_factor * head_dim
    / 2`` of them) and what cos and sin are multiplied by. YaRN: the plain
    frequencies, those divided by ``factor``, and a linear ramp between the
    two over the pairs whose wavelengths the original context held between
    ``beta_fast`` and ``beta_slow`` times, all at the rotated width."""
    dim = int(head_dim * spec.rotary_factor)
    i = np.arange(dim // 2, dtype=np.float64)
    plain = spec.theta ** (-2.0 * i / dim)
    if not spec.yarn_factor:
        return plain, 1.0

    def cd(rotations: float) -> float:
        return (dim * math.log(spec.original_max_position / (2 * math.pi * rotations))
                / (2 * math.log(spec.theta)))

    low = max(math.floor(cd(spec.beta_fast)), 0)
    high = min(math.ceil(cd(spec.beta_slow)), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain / spec.yarn_factor * ramp + plain * (1.0 - ramp), spec.attention_factor


def rope_tables(cfg: Any, kind: str, seq_len: int) -> tuple[np.ndarray, np.ndarray]:
    """cos and sin ``[S, rotated dims // 2]`` of one layer kind, in float32."""
    spec = dict(cfg.rope).get(kind)
    if spec is None:
        d = cfg.head_dim
        freq, factor = cfg.rope_theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d), 1.0
    else:
        freq, factor = inv_freq(spec, cfg.head_dim)
    ang = np.arange(seq_len, dtype=np.float32)[:, None] * freq.astype(np.float32)[None, :]
    return ((np.cos(ang) * factor).astype(np.float32),
            (np.sin(ang) * factor).astype(np.float32))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, cos, sin):
    """x [B, S, heads, d]; cos, sin [S, r/2]: pairs ``(j, j + r/2)`` of the
    first ``r`` dims rotate, dims ``r..d`` pass."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


@jax.jit
def _attend_group(q, k, v, mask):
    """One key/value head and the query heads it serves: q [B, S, g, d],
    k, v [B, S, d], mask [S, S] -> [B, S, g, d]."""
    scores = jnp.einsum("bqgd,bsd->bgqs", q, k) / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bgqs,bsd->bqgd", probs, v)


def attention(u, lp: dict, cfg: Any, kind: str, n_heads: int):
    B, S, _ = u.shape
    KV, d = cfg.n_kv_heads, cfg.head_dim
    cos, sin = rope_tables(cfg, kind, S)
    q = rotate((u @ lp["wq"]).reshape(B, S, n_heads, d), cos, sin)
    k = rotate((u @ lp["wk"]).reshape(B, S, KV, d), cos, sin)
    v = (u @ lp["wv"]).reshape(B, S, KV, d)
    pos = np.arange(S)
    mask = pos[:, None] >= pos[None, :]
    if kind == "sliding_attention":
        mask &= pos[:, None] - pos[None, :] < cfg.sliding_window
    g = n_heads // KV
    a = jnp.concatenate(
        [_attend_group(q[:, :, j * g:(j + 1) * g], k[:, :, j], v[:, :, j], mask)
         for j in range(KV)], axis=2)                         # [B, S, H, d]
    if "w_attn_gate" in lp:
        a = a * jax.nn.sigmoid(u @ lp["w_attn_gate"])[..., None]
    return a.reshape(B, S, n_heads * d) @ lp["wo"]


def gated_mlp(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def routing(u, w_router, top_k: int, norm_topk_prob: bool, routed_scale: float):
    """u [T, D] -> chosen experts [T, k] (of all the model's) and their
    gates [T, k] (float32)."""
    p = jax.nn.softmax(u @ w_router, axis=-1)
    gates, chosen = jax.lax.top_k(p, top_k)     # ties: the lower index first
    if norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
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


def routed_part(u, lp: dict, stack: dict, slot: int, cfg: Any):
    """u [T, D] -> the held experts' part of the routed sum."""
    chosen, gates = routing(u, lp["router"], cfg.experts_per_tok, cfg.norm_topk_prob,
                            cfg.routed_scale)
    n_held = stack["we_down"].shape[1]
    first = cfg.expert_rank * n_held
    out = jnp.zeros_like(u)
    for held in range(n_held):
        out = out + _expert_term(u, chosen, gates, stack["we_gate_up"], stack["we_down"],
                                 np.int32(slot), np.int32(held), np.int32(first + held))
    return out


def mlp(u, lp: dict, stack: dict, slot: int, cfg: Any):
    """u [B, S, D]: the layer's MLP by its leaves."""
    if "router" not in lp:
        return gated_mlp(u, lp["w_gate"], lp["w_up"], lp["w_down"])
    B, S, D = u.shape
    u2 = u.reshape(B * S, D)
    out = routed_part(u2, lp, stack, slot, cfg)
    if "ws_gate" in lp:
        out = out + gated_mlp(u2, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out.reshape(B, S, D)


@jax.jit
def _layer_leaves(stack: dict, slot):
    return {k: v[slot].astype(jnp.float32) for k, v in stack.items()
            if k not in _EXPERT_LEAVES}


def resid_pre(params: dict, tokens: jax.Array, cfg: Any, hook_layer: int) -> jax.Array:
    """[B, S, d] float32: the residual stream entering block ``hook_layer``."""
    stacks = params["layers"]
    if isinstance(stacks, dict):
        stacks = (stacks,)
    with jax.default_matmul_precision("highest"):
        resid = params["embed"][tokens].astype(jnp.float32)
        for layer in range(hook_layer):
            c, slot = stack_and_slot(cfg, layer)
            lp = _layer_leaves(stacks[c], np.int32(slot))
            resid = resid + attention(_rms(resid, lp["attn_norm"], cfg.rms_eps), lp, cfg,
                                      cfg.layer_types[layer], heads_of(cfg, layer))
            resid = resid + mlp(_rms(resid, lp["pre_ffw_norm"], cfg.rms_eps), lp,
                                stacks[c], slot, cfg)
        return resid
