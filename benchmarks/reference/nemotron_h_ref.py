"""Nemotron-H's tower (Nemotron-Labs-TwoTower-30B-A3B) to a residual hook in
plain float32.

Written out from the published config (``model_type`` "nemotron_h"; each
thing the config does not say is in the configuration file's ``assumed``).
Every layer is ONE sublayer behind one pre-norm, named by its letter of
``hybrid_override_pattern``::

    h = h + f(RMSNorm(h; w))          RMSNorm(x; w) = x * rsqrt(mean(x^2) + eps) * w

- ``M``, the Mamba-2 mixer: ``[z | x | B | C | dt] = u W_in``; x | B | C
  through a causal depthwise conv of ``ssm_conv`` taps plus its bias, then
  SiLU; ``dt = softplus(dt + dt_bias)`` (no clamp), ``A = -exp(A_log)``; B
  and C of group ``g`` serve heads ``g·H/G … (g+1)·H/G − 1``; the SSM by
  its DEFINING RECURRENCE, token by token (``falcon_h1_ref.recurrence``);
  then ``y ⊙ silu(z)`` FIRST, an RMS over each of the G groups of the inner
  width, times the norm's weight, and ``W_out``.
- ``*``, attention: q, k, v projections (no bias), NO rotation; scores ``q
  k^T / sqrt(hd)``, causal; GQA, query head ``i`` on key/value head ``i //
  (H / KV)``; ``W_o``.
- ``E``, the expert layer: ``s = sigmoid(u W_r)`` over ALL the model's
  experts; the ``k`` largest of ``s + bias`` (ties to the lowest index);
  gates ``s_e / (sum_chosen s + 1e-20) * routed_scale`` from the UNBIASED
  scores; ``y = sum_e gate_e E_e(u) + S(u)`` with every expert and the
  shared one the UNGATED ``relu(u W_up)^2 W_down`` (a routed expert's
  ``W_up`` is held [out, in]). *The share*: the tree
  holds experts ``[rank * held, (rank + 1) * held)``; the sum runs over the
  experts that are chosen AND held, each computed on every token and masked
  by its gate, plus ``S(u)``; nothing stands in for the absent ones.

Python loops over layers, query heads and held experts; float32 weights,
highest matmul precision, one sequence at a time (a 4096-token sequence's
scores are 67 MB a head, the SSM state 2 MiB); no chunk, no kernel, no
grouping. ``resid_pre(..., act=jnp.float8_e4m3fn)`` is the same model
computed in the precision below the stated one (the limit's upper reading:
``benchmarks/arch/nemotron_h.py``). Shares no code with ``crosscoder_tpu``: the config object and the
parameter tree are read as data (field and leaf names only). The tree's
layers are stacked by the SHAPE of their leaves — (query heads, the mixer
slot, the FFN slot) in order of first appearance, attention of any kind one
slot.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.falcon_h1_ref import conv, recurrence

F32 = jnp.float32
_EXPERT_LEAVES = ("we_up", "we_down")


def stack_and_slot(cfg: Any, layer: int) -> tuple[int, int]:
    """Which stack of leaves holds ``layer``, and at which place."""
    def slot(kind: str) -> str:
        return kind if kind in ("mamba", "none") else "attention"

    shapes = [(cfg.n_heads, slot(cfg.layer_types[i]), cfg.mlp_types[i])
              for i in range(cfg.n_layers)]
    order = list(dict.fromkeys(shapes))
    return order.index(shapes[layer]), shapes[:layer].count(shapes[layer])


def rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


@jax.jit
def _attend_head(q, k, v):
    """One head of one sequence: q, k, v [S, hd] -> [S, hd], causal."""
    S, hd = q.shape
    pos = np.arange(S)
    scores = (q @ k.T) * hd ** -0.5
    scores = jnp.where(pos[:, None] >= pos[None, :], scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1) @ v


def attention(u: jax.Array, lp: dict, cfg: Any) -> jax.Array:
    """``[S, D]`` → ``[S, D]``: one query head at a time, no rotation."""
    S, H, KV, hd = u.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (u @ lp["wq"]).reshape(S, H, hd)
    k = (u @ lp["wk"]).reshape(S, KV, hd)
    v = (u @ lp["wv"]).reshape(S, KV, hd)
    rep = H // KV
    heads = [_attend_head(q[:, h], k[:, h // rep], v[:, h // rep]) for h in range(H)]
    return jnp.concatenate(heads, axis=-1) @ lp["wo"]


@functools.partial(jax.jit, static_argnames=("dims",))
def mamba(u: jax.Array, lp: dict, dims: tuple) -> jax.Array:
    """The mixer on ``[S, D]``; ``dims`` (H, P, G, N, eps)."""
    H, P, G, N, eps = dims
    S, di, gn = u.shape[0], H * P, G * N
    zxbcdt = u @ lp["ssm_in"]
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di: 2 * di + 2 * gn], zxbcdt[:, 2 * di + 2 * gn:]
    xbc = jax.nn.silu(conv(xbc, lp["ssm_conv_w"], lp["ssm_conv_b"]))
    y = recurrence(xbc[:, :di].reshape(S, H, P),
                   jax.nn.softplus(dt + lp["ssm_dt_bias"]), -jnp.exp(lp["ssm_A_log"]),
                   xbc[:, di: di + gn].reshape(S, G, N), xbc[:, di + gn:].reshape(S, G, N),
                   lp["ssm_D"])
    g = (y.reshape(S, di) * jax.nn.silu(z)).reshape(S, G, di // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(S, di) * lp["ssm_norm"]) @ lp["ssm_out"]


def relu2_mlp(u, w_up, w_down):
    return jnp.square(jax.nn.relu(u @ w_up)) @ w_down


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
def _expert_term(u, chosen, gates, w_up, w_down, slot, held, e):
    """The held expert at place ``held`` of the stored leaves — expert ``e``
    of the model — on every token, times its gate (0 where it was not
    chosen). Traced numbers: one compiled function for every expert."""
    gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), axis=-1)
    y = relu2_mlp(u, w_up[slot, held].astype(F32).T, w_down[slot, held].astype(F32))
    return gate[:, None] * y


def experts(u: jax.Array, lp: dict, stack: dict, slot: int, cfg: Any) -> jax.Array:
    """The expert layer on ``[S, D]``: the held share's routed part plus the
    shared expert."""
    chosen, gates = routing(u, lp["router"], lp["router_bias"], cfg.experts_per_tok,
                            cfg.norm_topk_prob, cfg.routed_scale)
    n_held = stack["we_down"].shape[1]
    first = cfg.expert_rank * n_held
    out = relu2_mlp(u, lp["ws_up"], lp["ws_down"])      # the shared expert
    for held in range(n_held):
        out = out + _expert_term(u, chosen, gates, stack["we_up"], stack["we_down"],
                                 np.int32(slot), np.int32(held), np.int32(first + held))
    return out


@jax.jit
def _layer_leaves(stack: dict, slot):
    return {k: v[slot].astype(F32) for k, v in stack.items() if k not in _EXPERT_LEAVES}


def rounded(x: jax.Array, dtype: Any) -> jax.Array:
    """``x`` rounded to ``dtype`` (saturating at its largest value), in
    ``x``'s own dtype. Op by op, NOT under one jit: XLA may drop a convert
    down and back up within one program (excess precision), and the TPU's
    compiler does — under jit this read 0.0 there."""
    big = float(jnp.finfo(dtype).max)
    low = jnp.clip(x, -big, big).astype(dtype)
    return low.astype(x.dtype)


def sublayer(h: jax.Array, layer: int, stacks: tuple, cfg: Any, act: Any = None) -> jax.Array:
    """Layer ``layer`` on one sequence's stream ``[S, D]``; ``act`` (None:
    float32 throughout) the dtype the sublayer's input, its output and the
    stream are rounded to."""
    c, slot = stack_and_slot(cfg, layer)
    lp = _layer_leaves(stacks[c], np.int32(slot))
    kind, ffn = cfg.layer_types[layer], cfg.mlp_types[layer]
    r = (lambda x: x) if act is None else functools.partial(rounded, dtype=act)
    if kind == "mamba":
        u = r(rms(h, lp["attn_norm"], cfg.rms_eps))
        dims = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state, cfg.rms_eps)
        f = mamba(u, lp, dims)
    elif kind != "none":
        f = attention(r(rms(h, lp["attn_norm"], cfg.rms_eps)), lp, cfg)
    else:
        u = r(rms(h, lp["pre_ffw_norm"], cfg.rms_eps))
        f = (experts(u, lp, stacks[c], slot, cfg) if ffn == "sparse"
             else relu2_mlp(u, lp["w_up"], lp["w_down"]))
    return r(h + r(f))


def resid_pre(params: dict, tokens: jax.Array, cfg: Any, hook_layer: int,
              act: Any = None) -> jax.Array:
    """``blocks.{hook_layer}.hook_resid_pre`` [B, S, D] float32. ``act`` (a
    dtype; None: float32) computes it in a lower precision: every weight,
    and each sublayer's input, its output and the stream, rounded to it
    (products and sums stay float32)."""
    if act is not None:
        params = jax.tree.map(functools.partial(rounded, dtype=act), params)
    stacks = params["layers"]
    if isinstance(stacks, dict):
        stacks = (stacks,)
    r = (lambda x: x) if act is None else functools.partial(rounded, dtype=act)
    with jax.default_matmul_precision("highest"):
        out = []
        for row in jnp.asarray(tokens):            # one sequence at a time
            h = r(params["embed"][row].astype(F32))
            for layer in range(hook_layer):
                h = sublayer(h, layer, stacks, cfg, act)
            out.append(h)
        return jnp.stack(out)


def logits(params: dict, tokens: jax.Array, cfg: Any) -> jax.Array:
    """[B, S, V] float32: the whole forward (CPU tests only): the last
    layer's stream, the final norm, the untied head."""
    h = resid_pre(params, tokens, cfg, cfg.n_layers)
    with jax.default_matmul_precision("highest"):
        x = rms(h, params["final_norm"].astype(F32), cfg.rms_eps)
        return x @ params["unembed"].astype(F32).T
