"""The crosscoder: a sparse dictionary tied across N model/layer sources.

Re-implements, TPU-first, the numeric contract of the reference
``CrossCoder`` module (reference ``crosscoder.py:24-130``):

- params ``W_enc [n, d_in, d_hidden]``, ``W_dec [d_hidden, n, d_in]``,
  ``b_enc [d_hidden]``, ``b_dec [n, d_in]`` — same leaf names as the torch
  ``state_dict`` so the checkpoint converter is trivial, but with the source
  axis ``n`` generalized from the reference's hardcoded 2
  (reference ``crosscoder.py:32``) to any ``n_models × n_hooked_layers``.
- init: ``W_dec`` rows drawn N(0,1) then rescaled to ``dec_init_norm`` per
  (latent, source) (reference ``crosscoder.py:36-53``); ``W_enc`` initialized
  as the transpose of ``W_dec`` (reference ``crosscoder.py:54-58``); biases 0.
- encode/decode as single einsums that XLA maps onto the MXU
  (reference ``crosscoder.py:69-89``), with fp32 accumulation.
- ``get_losses`` reproducing the reference's loss surface exactly
  (reference ``crosscoder.py:96-130``): summed-square-error L2 (mean over
  batch), explained variance overall and per source (eps 1e-8),
  **decoder-norm-weighted** L1 (reference ``crosscoder.py:123-126``), and L0.

Design notes (why this is not a torch translation):

- Everything is a pure function over a params pytree — no module object, no
  device state; ``jax.jit``/``pjit`` owns placement. Sharding is expressed
  separately (mesh + NamedSharding rules in the parallel layer) and
  propagates through these einsums, so the same code is the single-chip and
  the multi-chip kernel.
- Compute dtype (``enc_dtype``, usually bf16 for the MXU) is separated from
  loss dtype (always fp32, matching the reference's upcast at
  ``crosscoder.py:104``).
- Sparse activations (TopK / JumpReLU / BatchTopK) are first-class via
  :mod:`crosscoder_tpu.ops.activations`, with a Pallas kernel path for the
  TopK inner loop; the reference has only dense ReLU.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.ops import activations as act_ops
from crosscoder_tpu.utils.dtypes import dtype_of

Params = dict[str, jax.Array]


class LossOutput(NamedTuple):
    """Loss surface of one batch (shapes as the reference returns them,
    reference ``crosscoder.py:15-22``); all fp32."""

    l2_loss: jax.Array                    # scalar: mean over batch of summed sq err
    l1_loss: jax.Array                    # scalar: decoder-norm-weighted L1
    l0_loss: jax.Array                    # scalar: mean active latents
    explained_variance: jax.Array         # [batch]
    explained_variance_per_source: jax.Array  # [n_sources, batch] (ref: _A/_B pair)
    # jumprelu + cfg.l0_coeff only: the rectangle-kernel-STE L0 penalty
    # term (differentiable in θ; equals l0_loss in value). 0.0 elsewhere.
    l0_penalty: jax.Array | float = 0.0
    # AuxK only (cfg.aux_k > 0 and a dead_mask was passed): the
    # residual-normalized auxiliary reconstruction loss over dead latents,
    # and the [d_hidden] bool of latents that fired on this batch (the
    # trainer's steps_since_fired update). 0.0 / None elsewhere.
    aux_loss: jax.Array | float = 0.0
    fired: jax.Array | None = None


def init_params(key: jax.Array, cfg: CrossCoderConfig, dtype: jnp.dtype | None = None) -> Params:
    """Initialize crosscoder params.

    Matches the reference init semantics (reference ``crosscoder.py:33-62``):
    decoder rows are standard-normal rescaled so each (latent, source) row has
    norm ``dec_init_norm``; the encoder starts as the decoder transpose; biases
    start at zero. (The reference draws W_dec twice and keeps the second draw,
    ``crosscoder.py:36-49`` — RNG noise we deliberately do not replicate.)

    ``dtype`` defaults to ``cfg.enc_dtype`` (the reference stores params in
    the compute dtype, ``crosscoder.py:30-34``); the Trainer passes fp32 to
    keep master weights + Adam moments in fp32 and casts to ``enc_dtype``
    per-step inside the loss (mixed precision the TPU way, rather than the
    reference's all-bf16 torch Adam).
    """
    n, d_in, d_hidden = cfg.n_sources, cfg.d_in, cfg.dict_size
    dtype = dtype_of(cfg.enc_dtype) if dtype is None else dtype
    w = jax.random.normal(key, (d_hidden, n, d_in), dtype=jnp.float32)
    w = w / jnp.linalg.norm(w, axis=-1, keepdims=True) * cfg.dec_init_norm
    params: Params = {
        "W_dec": w.astype(dtype),
        "W_enc": jnp.transpose(w, (1, 2, 0)).astype(dtype),
        "b_enc": jnp.zeros((d_hidden,), dtype=dtype),
        "b_dec": jnp.zeros((n, d_in), dtype=dtype),
    }
    if cfg.activation == "jumprelu":
        # log-threshold parameterization keeps theta positive under Adam
        params["log_theta"] = jnp.full((d_hidden,), jnp.log(cfg.jumprelu_theta), dtype=jnp.float32)
    return params


@jax.named_scope("cc/encode")
def pre_acts(params: Params, x: jax.Array) -> jax.Array:
    """Encoder pre-activations: ``x @ W_enc + b_enc`` summed over sources.

    x: ``[..., n_sources, d_in]`` → ``[..., d_hidden]``. One einsum, contracted
    over both the source and feature axes (reference ``crosscoder.py:71-75``),
    with fp32 MXU accumulation.
    """
    h = jnp.einsum(
        "...nd,ndh->...h", x, params["W_enc"], preferred_element_type=jnp.float32
    )
    return (h + params["b_enc"].astype(jnp.float32)).astype(x.dtype)


def encode(params: Params, x: jax.Array, cfg: CrossCoderConfig, *, apply_activation: bool = True) -> jax.Array:
    """Latent activations ``[..., d_hidden]``.

    ``apply_activation=False`` returns raw pre-activations (the reference's
    ``apply_relu=False`` path, ``crosscoder.py:69-80``).
    """
    h = pre_acts(params, x)
    if not apply_activation:
        return h
    return act_ops.apply(h, cfg, params)


def calibrate_batchtopk_threshold(
    params: Params, cfg: CrossCoderConfig, batches
) -> float:
    """Mean per-batch BatchTopK threshold over representative batches —
    the fixed global threshold for EVAL (set it as
    ``cfg.batchtopk_threshold``; dispatch then uses
    :func:`crosscoder_tpu.ops.activations.batchtopk_fixed` so one
    example's activations never depend on the rest of its batch).

    ``batches``: iterable of ``[B, n_sources, d_in]`` activation batches
    (normalized exactly as training batches were).
    """
    import numpy as np

    @jax.jit
    def one(p, x):
        # cast like training does (fp32 masters -> enc_dtype): the order
        # statistic must come from the same bf16 pre-acts training saw.
        # params are a traced argument (not a closure) so the dictionary
        # weights are not baked into the executable as constants — same
        # trap documented at decoder.firing_rates / ce_eval.
        cp = cast_params(p, dtype_of(cfg.enc_dtype))
        hp = jax.nn.relu(pre_acts(cp, x.astype(dtype_of(cfg.enc_dtype))))
        return act_ops.batchtopk_threshold_of(hp, cfg.topk_k)

    vals = [float(jax.device_get(one(params, jnp.asarray(b)))) for b in batches]
    if not vals:
        raise ValueError("calibrate_batchtopk_threshold needs >= 1 batch")
    return float(np.mean(vals))


@jax.named_scope("cc/decode")
def decode(params: Params, f: jax.Array) -> jax.Array:
    """Reconstruction ``[..., n_sources, d_in]`` from latents ``[..., d_hidden]``
    (reference ``crosscoder.py:82-89``)."""
    y = jnp.einsum(
        "...h,hnd->...nd", f, params["W_dec"], preferred_element_type=jnp.float32
    )
    return (y + params["b_dec"].astype(jnp.float32)).astype(f.dtype)


def forward(params: Params, x: jax.Array, cfg: CrossCoderConfig) -> jax.Array:
    """encode → decode (reference ``crosscoder.py:91-94``)."""
    return decode(params, encode(params, x, cfg))


# apply-function cache keyed by the cfg's JSON identity. Consumers (CE eval,
# dashboards) close cfg into a function and pass that function as a STATIC
# jit argument with params/activations as array arguments; without this
# cache each call site would mint a fresh function object → a full retrace
# and recompile per eval/dashboard run, and the jit cache would retain
# every stale executable.
_APPLY_CACHE: dict[tuple[str, str], Any] = {}


def cached_apply(cfg: CrossCoderConfig, kind: str = "forward"):
    """A stable-identity ``apply(params, x)`` for this config.

    ``kind``: ``"forward"`` (encode→decode, the CE eval's reconstruction)
    or ``"encode"`` (latent activations, the dashboards' path).
    """
    import json

    if kind not in ("forward", "encode"):
        raise ValueError(f"kind must be forward|encode, got {kind!r}")
    key = (json.dumps(cfg.to_dict(), sort_keys=True, default=str), kind)
    fn = _APPLY_CACHE.get(key)
    if fn is None:
        if len(_APPLY_CACHE) > 32:
            # evict OLDEST only (dict preserves insertion order): clearing
            # everything would orphan functions still live as static jit
            # args and force a retrace of every active consumer
            _APPLY_CACHE.pop(next(iter(_APPLY_CACHE)))
        if kind == "forward":
            def fn(p: Params, x: jax.Array) -> jax.Array:
                return forward(p, x, cfg)
        else:
            def fn(p: Params, x: jax.Array) -> jax.Array:
                return encode(p, x, cfg)
        _APPLY_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# factored TopK decode (Pallas tier, round-5): forward through the k active
# rows only, backward through the SAME dense matmuls as the dense path.
#
# Why this split (all numbers v5e, B=4096, k=32, artifacts/TOPK_PROBE_r05 +
# GATHER_PROBE_r05): the decode FORWARD is the only dense matmul sparsity
# can actually remove — jnp.take of the k active W_dec rows + a [B,k,n,d]
# einsum costs 5.7-16 ms vs the 20-33 ms dense matmul at dict >= 2^16. The
# BACKWARD stays dense on purpose: a factored df (gather 8-16 ms + the
# [B,k]->[B,H] scatter 6-20 ms) loses to the dense matmul+mask at every
# size, and XLA's own scatter-add gradient for a gathered W_dec costs
# 42-76 ms. Gradients are therefore numerically IDENTICAL to the dense
# path (same matmuls, same straight-through mask) while the forward saves
# ~27 ms at 2^17. (vals, idx) come from the sparsify drain kernel — every
# general extractor measured is slower: lax.top_k 25-63 ms, approx_max_k
# inexact per row (79-97%), XLA scatter-compaction touches all B*H pairs.
# No reference counterpart (the reference decode is always dense,
# reference crosscoder.py:82-89).


@functools.cache
def _row_ops():
    """``ops/row_gather.py``'s entry points under ``jax.jit``. A job traces
    its step once a variant (with and without metrics), and a jitted
    callee's jaxpr is kept from one trace to the next: the kernels' bodies,
    two thirds of a variant's tracing on the chip's host (PERF.md §6,
    PR 32), are traced once a process."""
    import types

    from crosscoder_tpu.ops import row_gather

    return types.SimpleNamespace(
        weighted_sum=jax.jit(row_gather.weighted_sum, static_argnames=(
            "d", "name", "interpret", "out_dtype")),
        dots=jax.jit(row_gather.dots, static_argnames=("k", "name", "interpret")),
        grouped_sums=jax.jit(row_gather.grouped_sums, static_argnames=(
            "n_out", "name", "interpret")),
    )


def _select_decode(
    h: jax.Array, W_dec: jax.Array, k: int, rows: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """The factored tiers' shared forward tail: kernel mask → sparsify →
    k-row decode. Returns ``(f [B,H], vals [B,k], idx [B,k], recon [B,n,d]
    f32 without b_dec, W_dec as the backward wants it)``; the scopes name
    the two halves in a device trace. ``rows``: the k rows are fetched by
    DMA from ``W_dec`` in packed rows (``ops/row_gather.py``; the pack takes
    the place of the step's bf16 cast of ``W_dec`` and is the residual);
    else XLA's ``take``."""
    from crosscoder_tpu.ops import row_gather, topk_pallas

    with jax.named_scope("cc/select"):
        f = topk_pallas.topk(h, k)
        vals, idx = topk_pallas.sparsify(f, k)
    with jax.named_scope("cc/decode"):
        if rows:
            _, n, d = W_dec.shape
            W_dec = row_gather.packed_sources(
                W_dec, interpret=row_gather._INTERPRET)
            recon = _row_ops().weighted_sum(
                idx.reshape(-1), vals.astype(jnp.float32), W_dec, d=n * d,
                name="topk_rows_decode", interpret=row_gather._INTERPRET,
                out_dtype=jnp.float32).reshape(-1, n, d)
        else:
            w = jnp.take(W_dec, idx, axis=0)               # [B, k, n, d]
            recon = jnp.einsum("bk,bknd->bnd", vals, w,
                               preferred_element_type=jnp.float32)
    return f, vals, idx, recon, W_dec


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _factored_topk_forward(
    h: jax.Array, W_dec: jax.Array, k: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``(recon [B,n,d] f32 (no b_dec), vals [B,k], idx [B,k])`` from
    pre-acts ``h [B,H]``.

    Differentiable in ``h`` (straight-through mask) and ``W_dec`` (dense
    matmul), exactly as the dense TopK path. ``vals``/``idx`` carry NO
    gradient path — cotangents on them are ignored, which is only sound
    when nothing differentiable consumes them (the dispatch in get_losses
    guarantees l1_coeff == 0 on this path; metric-only uses are fine).
    """
    _, vals, idx, recon, _ = _select_decode(h, W_dec, k)
    return recon, vals, idx


def _factored_topk_fwd(h, W_dec, k):
    f, vals, idx, recon, _ = _select_decode(h, W_dec, k)
    # f is the residual: both backward matmuls consume the masked [B,H]
    # activations (dW_dec contraction + the straight-through mask on df)
    return (recon, vals, idx), (f, W_dec)


def _factored_topk_bwd(k, res, g):
    f, W_dec = res
    g_recon = g[0].astype(jnp.float32)                     # [B, n, d]
    # cotangents g[1], g[2] (vals, idx) are ignored — see docstring
    with jax.named_scope("cc/decode"):      # a custom vjp: JAX names nothing here
        dW_dec = jnp.einsum(
            "bh,bnd->hnd", f.astype(jnp.float32), g_recon,
            preferred_element_type=jnp.float32,
        ).astype(W_dec.dtype)
        df = jnp.einsum(
            "bnd,hnd->bh", g_recon, W_dec.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
    with jax.named_scope("cc/select"):
        dh = jnp.where(f > 0, df, 0.0).astype(f.dtype)
    return dh, dW_dec


_factored_topk_forward.defvjp(_factored_topk_fwd, _factored_topk_bwd)


# ---------------------------------------------------------------------------
# sparse backward plane (cfg.sparse_bwd; ops/sparse_grad.py): the factored
# tier with the dense backward matmuls replaced by O(B·k) scatter-
# accumulates. The dense factored backward (_factored_topk_bwd above) runs
# dW_dec [B,H]x[B,nd] + df [B,nd]x[H,nd] — and the encoder VJP behind it
# runs dW_enc [B,nd]x[B,H] — three matmuls that each multiply ~99.9%
# structural zeros at TopK(k=32), dict 2^17. With (vals, idx) in hand the
# same gradients are B·k-pair scatter/gathers:
#
#   d_vals[b,j] = <g[b], W_dec[idx[b,j]]>          (gather + [B,k,nd] einsum)
#   dW_dec[idx[b,j]] += vals[b,j] · g[b]           (scatter_add_rows)
#   dW_enc[:, :, idx[b,j]] += d_vals[b,j] · x[b]   (scatter_add_rows, with a
#   db_enc[idx[b,j]] += d_vals[b,j]                 ones column riding along)
#
# accumulated in f32 with deterministic within-block ordering (the kernel
# sorts pairs by destination, stable). Gradients equal the dense backward's
# up to f32 summation order — asserted in tests/test_sparse_grad.py,
# including the duplicate-index (two rows activating the same latent) case.
#
# Two variants, same split as the factored forward pair above:
# - _sparse_topk_step: owns encode AND decode (x, W_enc, b_enc, W_dec), so
#   ALL THREE backward matmuls disappear. Used on bare steps (no AuxK this
#   step) — the throughput-defining variant. dx is computed exactly (a
#   k-row gather of W_enc) and DCE'd by XLA when only params are
#   differentiated, which is every training step.
# - _sparse_topk_from_h: (h, W_dec) only, used when another consumer needs
#   the pre-acts differentiably (the AuxK ranking/gather). dh is scattered
#   back to [B, H] (the one scatter this variant keeps) and dW_enc flows
#   through the ordinary encoder VJP.
#
# Soundness gate is the factored tier's (l1_coeff == 0: no gradient path
# through (vals, idx) cotangents).


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _sparse_topk_step(
    x: jax.Array, W_enc: jax.Array, b_enc: jax.Array, W_dec: jax.Array,
    k: int, rows: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``(recon [B,n,d] f32 (no b_dec), vals [B,k], idx [B,k])`` from the
    batch ``x [B,n,d]`` — encode + TopK + factored decode in one
    custom-vjp scope so the backward never leaves factored form. ``rows``:
    every k-sparse product goes through rows fetched by DMA
    (``ops/row_gather.py``) — the decode and ``d_vals`` token-major, both
    weight gradients and ``db_enc`` in one latent-major pass."""
    return _encode_select_decode(x, W_enc, b_enc, W_dec, k, rows)[:3]


def _encode_select_decode(x, W_enc, b_enc, W_dec, k, rows):
    with jax.named_scope("cc/encode"):
        hf = jnp.einsum("bnd,ndh->bh", x, W_enc,
                        preferred_element_type=jnp.float32)
        h = (hf + b_enc.astype(jnp.float32)).astype(x.dtype)
    _, vals, idx, recon, w = _select_decode(h, W_dec, k, rows)
    return recon, vals, idx, w


def _sparse_topk_step_fwd(x, W_enc, b_enc, W_dec, k, rows):
    recon, vals, idx, w = _encode_select_decode(x, W_enc, b_enc, W_dec, k, rows)
    # residuals are FACTORED: (vals, idx) [B,k] replace the [B,H] masked
    # activations the dense backward keeps — ~H/k less residual memory;
    # W_dec as the decode read it (packed rows in the ``rows`` form).
    # (b_tok: zero-size dtype token — residual leaves must be arrays.)
    return (recon, vals, idx), (x, vals, idx, W_enc, w,
                                jnp.zeros((0,), b_enc.dtype))


def _sparse_topk_step_bwd(k, rows, res, g):
    from crosscoder_tpu.ops import row_gather, sparse_grad

    x, vals, idx, W_enc, W_dec, b_tok = res
    b_dtype = b_tok.dtype
    g_recon = g[0].astype(jnp.float32)                     # [B, n, d]
    # cotangents g[1], g[2] (vals, idx) are ignored — soundness gated on
    # l1_coeff == 0, exactly like _factored_topk_forward
    B, n, d = x.shape
    H = W_enc.shape[-1]
    nd = n * d
    g_flat = g_recon.reshape(B, nd)

    if rows:
        with jax.named_scope("cc/decode"):
            # d_vals through the same table and the same packed rows as the
            # decode, straight-through masked on the survivors and rounded
            # to the compute dtype, as the dense df is
            d_vals = _row_ops().dots(
                idx.reshape(-1), g_flat, W_dec, k=k, name="topk_rows_dvals",
                interpret=row_gather._INTERPRET)
            d_vals = jnp.where(vals > 0, d_vals, 0.0).astype(vals.dtype)
            # the cotangent of a bf16 reconstruction is bf16-valued: exact
            dW_dec, dW_enc, db_enc = _row_ops().grouped_sums(
                idx, vals, d_vals, g_flat.astype(x.dtype), x.reshape(B, nd),
                n_out=H, name="topk_rows_grads", interpret=row_gather._INTERPRET)
        dW_dec = dW_dec.reshape(H, n, d)
        # the kernel wrote it transposed: bf16 values in float32 words, the
        # form the optimizer reads (a cast here would be a pass of its own)
        dW_enc = dW_enc.reshape(n, d, H)
        db_enc = db_enc.astype(b_dtype)
    else:
        # d_vals through the k active decoder rows, straight-through masked
        # on the survivors (vals > 0; padded slots carry val 0 and drop out
        # — the same rule as the dense path's f > 0 mask)
        w = jnp.take(W_dec, idx, axis=0).astype(jnp.float32)   # [B, k, n, d]
        d_vals = jnp.einsum("bnd,bknd->bk", g_recon, w)
        d_vals = jnp.where(vals > 0, d_vals, 0.0)              # [B, k] f32

        # decoder gradient: B·k scatter-accumulate instead of [B,H]x[B,nd]
        dW_dec = sparse_grad.scatter_add_rows(
            vals.astype(jnp.float32), idx, g_flat, H
        ).reshape(H, n, d).astype(W_dec.dtype)

        # encoder gradients from the k-sparse dh: one scatter over the batch
        # rows, with a ones column appended (lane-padded to 128) so db_enc
        # rides the same accumulation instead of needing its own scatter
        x_flat = x.reshape(B, nd).astype(jnp.float32)
        ones_col = (jax.lax.broadcasted_iota(jnp.int32, (B, 128), 1) == 0
                    ).astype(jnp.float32)
        x_aug = jnp.concatenate([x_flat, ones_col], axis=1)    # [B, nd + 128]
        enc_grads = sparse_grad.scatter_add_rows(d_vals, idx, x_aug, H)
        dW_enc = jnp.transpose(
            enc_grads[:, :nd].reshape(H, n, d), (1, 2, 0)
        ).astype(W_enc.dtype)
        db_enc = enc_grads[:, nd].astype(b_dtype)

    # dx exactly (k-row gather of W_enc); XLA DCEs this whole branch when
    # only params are differentiated — i.e. on every training step
    we = jnp.take(W_enc, idx.reshape(-1), axis=2).reshape(n, d, B, k)
    dx = jnp.einsum("bk,ndbk->bnd", d_vals.astype(jnp.float32),
                    we.astype(jnp.float32)).astype(x.dtype)
    return dx, dW_enc, db_enc, dW_dec


_sparse_topk_step.defvjp(_sparse_topk_step_fwd, _sparse_topk_step_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sparse_topk_from_h(
    h: jax.Array, W_dec: jax.Array, k: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The (h, W_dec)-scoped sparse-backward variant: same forward as
    ``_factored_topk_forward``, backward with the dense dW_dec/df matmuls
    replaced by the scatter/gather pair. ``dh`` is materialized [B, H]
    (one scatter) because ``h`` has other consumers on this path (the
    AuxK ranking) — the full-step variant above avoids even that."""
    _, vals, idx, recon, _ = _select_decode(h, W_dec, k)
    return recon, vals, idx


def _sparse_topk_from_h_fwd(h, W_dec, k):
    out = _sparse_topk_from_h(h, W_dec, k)
    _, vals, idx = out
    # h_tok: zero-size dtype token (residual leaves must be arrays); the
    # dh shape is recoverable as (vals batch, W_dec rows)
    return out, (vals, idx, W_dec, jnp.zeros((0,), h.dtype))


def _sparse_topk_from_h_bwd(k, res, g):
    from crosscoder_tpu.ops import sparse_grad

    vals, idx, W_dec, h_tok = res
    h_shape = (vals.shape[0], W_dec.shape[0])
    h_dtype = h_tok.dtype
    g_recon = g[0].astype(jnp.float32)
    B = vals.shape[0]
    H, n, d = W_dec.shape
    w = jnp.take(W_dec, idx, axis=0).astype(jnp.float32)
    d_vals = jnp.einsum("bnd,bknd->bk", g_recon, w)
    d_vals = jnp.where(vals > 0, d_vals, 0.0)
    dW_dec = sparse_grad.scatter_add_rows(
        vals.astype(jnp.float32), idx, g_recon.reshape(B, n * d), H
    ).reshape(H, n, d).astype(W_dec.dtype)
    rows = jnp.arange(B)[:, None]
    dh = jnp.zeros(h_shape, h_dtype).at[rows, idx].add(
        d_vals.astype(h_dtype), mode="drop"
    )
    return dh, dW_dec


_sparse_topk_from_h.defvjp(_sparse_topk_from_h_fwd, _sparse_topk_from_h_bwd)


@jax.custom_vjp
def _sparse_aux_product(avals: jax.Array, aidx: jax.Array,
                        W_dec: jax.Array) -> jax.Array:
    """AuxK decode ``e_hat [B,n,d] f32`` with the SPARSE backward.

    Forward is byte-identical to the dense aux path (scatter the aux
    activations to [B, H], one MXU matmul — the measured-best forward at
    aux_k ≈ 8k, see the dense-decode note in get_losses); only the two
    backward matmuls are replaced: ``d_avals`` through the aux_k gathered
    rows, ``dW_dec`` through the scatter-accumulate plane.
    """
    B = avals.shape[0]
    H = W_dec.shape[0]
    rows = jnp.arange(B)[:, None]
    f_aux = jnp.zeros((B, H), avals.dtype).at[rows, aidx].add(avals)
    return jnp.einsum("bh,hnd->bnd", f_aux, W_dec,
                      preferred_element_type=jnp.float32)


def _sparse_aux_product_fwd(avals, aidx, W_dec):
    return _sparse_aux_product(avals, aidx, W_dec), (avals, aidx, W_dec)


def _sparse_aux_product_bwd(res, g):
    from crosscoder_tpu.ops import sparse_grad

    avals, aidx, W_dec = res
    gf = g.astype(jnp.float32)                             # [B, n, d]
    B = avals.shape[0]
    H, n, d = W_dec.shape
    w = jnp.take(W_dec, aidx, axis=0).astype(jnp.float32)  # [B, ak, n, d]
    d_avals = jnp.einsum("bnd,bknd->bk", gf, w).astype(avals.dtype)
    dW_dec = sparse_grad.scatter_add_rows(
        avals.astype(jnp.float32), aidx, gf.reshape(B, n * d), H
    ).reshape(H, n, d).astype(W_dec.dtype)
    return d_avals, None, dW_dec


_sparse_aux_product.defvjp(_sparse_aux_product_fwd, _sparse_aux_product_bwd)


def rows_live(cfg: CrossCoderConfig, batch: int | None) -> bool:
    """Whether the TopK step's k-sparse products go through rows fetched by
    DMA (``ops/row_gather.py``), from what is visible when the step is
    traced: TopK with no L1 objective (nothing differentiable may consume
    ``(vals, idx)``), the sparse backward plane not switched off, a known
    batch, a TPU backend with ONE device (a ``pallas_call`` is not
    partitioned over a mesh; or the interpreter), bf16 rows, and shapes
    BOTH kernel forms take — all four products or none: the decode and
    ``df`` alone pay ``sparsify``, the pack and a ``[B, H]`` scatter of
    ``dh`` for two 5.8 ms products and gain nothing (PERF.md §6, PR 32).
    ``get_losses`` adds what only it can see: no AuxK consumer of ``h`` on
    this step, and parameters already in the compute dtype."""
    if cfg.activation != "topk" or cfg.l1_coeff != 0:
        return False
    if batch is None or cfg.sparse_bwd == "off":
        return False
    from crosscoder_tpu.ops import row_gather

    shape = (batch, cfg.topk_k, cfg.n_sources * cfg.d_in, dtype_of(cfg.enc_dtype))
    return (row_gather.enabled() and row_gather.supported(*shape)
            and row_gather.grouped_supported(cfg.dict_size, *shape))


def use_sparse_bwd(cfg: CrossCoderConfig, batch: int | None = None) -> bool:
    """Dispatch for the sparse backward plane (``cfg.sparse_bwd``).

    Applies on top of the factored tier (callers AND the factored gate
    must agree — ``get_losses`` computes ``factored and use_sparse_bwd``).
    "off" never; "on" whenever sound (forced — CPU parity tests and
    forced A/Bs; where the row kernels are not live the gradients fall
    back to the XLA scatter inside scatter_add_rows, still sparse math);
    "auto" where the row kernels are live (:func:`rows_live`) — without
    them, a sparse backward IS the measured 42-76 ms XLA scatter the dense
    matmuls beat.
    Soundness: the factored tier's l1_coeff == 0 gate.
    """
    if cfg.activation != "topk":
        return False
    if cfg.sparse_bwd == "off" or cfg.l1_coeff != 0:
        return False
    return cfg.sparse_bwd == "on" or rows_live(cfg, batch)


def use_sparse_aux(cfg: CrossCoderConfig, batch: int) -> bool:
    """Sparse backward for the AuxK aux term. Requires the sparse plane
    forced ("on": "auto" answers for a step, and an AuxK step keeps the
    dense form) AND aux shapes under the pair cap (sparse_grad._MAX_PAIRS;
    aux_k ≈ 8k at batch 4096 is ~32× over it, and the XLA scatter
    materializes a [B·aux_k, n·d] f32 update matrix, so the support gate
    is hard even under forced "on" — unsupported aux falls back to the
    dense aux VJP, which is the measured-best dense path anyway)."""
    if cfg.aux_k <= 0 or not use_sparse_bwd(cfg):
        return False
    from crosscoder_tpu.ops import sparse_grad

    k_aux = min(cfg.aux_k, cfg.dict_size)
    return sparse_grad.supported(
        cfg.dict_size, cfg.n_sources * cfg.d_in, batch * k_aux)


def use_factored_decode(cfg: CrossCoderConfig, batch: int | None = None) -> bool:
    """Dispatch for the factored TopK decode tier.

    ``cfg.factored_decode``: "off" never; "on" whenever sound+supported;
    "auto" where the k rows can be fetched by DMA (:func:`rows_live`, which
    wants the step's ``batch``: 15 ns a row against the dense product's
    5.6–8 ms at dict 2^15, PERF.md §6, PR 32), and elsewhere from dict_size
    >= 2^17 — XLA's row gather costs ~17-20 ms flat (131k x 9 KB rows is
    instruction-rate-bound on v5e, ~74 GB/s effective), so it only beats
    the dense decode matmul once that matmul crosses ~30 ms (measured A/B:
    -8 ms at 2^17, +6 ms at 2^16).
    Soundness gate: l1_coeff must be 0 (see _factored_topk_forward).
    """
    if cfg.activation != "topk":
        return False
    mode = cfg.factored_decode
    if mode == "off" or cfg.l1_coeff != 0:
        return False
    from crosscoder_tpu.ops import activations as act_ops
    from crosscoder_tpu.ops import topk_pallas

    if not act_ops._default_use_pallas() and not topk_pallas._INTERPRET:
        return False
    probe = jax.ShapeDtypeStruct((1, cfg.dict_size), dtype_of(cfg.enc_dtype))
    if not topk_pallas.supported(probe, cfg.topk_k):
        return False
    if not topk_pallas.sparsify_supported(cfg.dict_size, cfg.topk_k):
        return False
    # sparse_bwd="on" forces the factored tier too (the sparse backward
    # plane extends it — the factored (vals, idx) ARE its inputs), so a
    # forced sparse backward at sub-2^17 dicts doesn't silently noop
    return (mode == "on" or cfg.sparse_bwd == "on"
            or cfg.dict_size >= 131072 or rows_live(cfg, batch))


def get_losses(
    params: Params,
    x: jax.Array,
    cfg: CrossCoderConfig,
    with_metrics: bool = True,
    dead_mask: jax.Array | None = None,
    track_fired: bool = False,
) -> LossOutput:
    """Full loss surface for a batch ``x: [batch, n_sources, d_in]``.

    ``with_metrics=False`` skips the metric-only reductions (l0 and the
    explained variances — several extra full passes over the batch/latents,
    ~13% of a TPU train step) and returns zeros in their slots; the
    objective terms (l2, weighted l1) are always computed. The trainer uses
    this off log-steps; numerics of the objective are identical.

    Numerics follow reference ``crosscoder.py:96-130`` exactly, with the
    fp32 upcast for all loss reductions (reference ``crosscoder.py:104``):

    - ``l2``: per-row sum of squared error over (source, d_in), mean over batch
    - explained variance: ``1 − l2_row / (total_variance_row + 1e-8)``, where
      total variance is about the batch mean
    - ``l1``: ``mean_b Σ_f acts[b,f] · Σ_n ‖W_dec[f,n]‖`` — the decoder-norm
      weighted form (reference ``crosscoder.py:123-126``), NOT plain Σ|acts|
    - ``l0``: mean count of strictly-positive latents
    """
    x = x.astype(dtype_of(cfg.enc_dtype))
    aux_active = dead_mask is not None and cfg.aux_k > 0
    # the step's form follows what this trace can see. An AuxK step keeps
    # the form it takes without the row kernels (``h`` has another
    # consumer there), and so do parameters that were not cast to the
    # compute dtype: packing them would round what the dense path does not
    rows = (not aux_active and rows_live(cfg, x.shape[0])
            and params["W_dec"].dtype == x.dtype == jnp.bfloat16)
    batch = x.shape[0] if rows else None
    factored = use_factored_decode(cfg, batch)
    l0_penalty: jax.Array | float = 0.0
    h = None            # pre-acts, kept when a later consumer (the
                        # JumpReLU L0 penalty, the AuxK ranking) needs
                        # them — shared explicitly rather than trusting
                        # CSE to dedupe a second encode matmul
    sparse_bwd = factored and use_sparse_bwd(cfg, batch)
    rows = rows and sparse_bwd
    if cfg.activation == "topk":
        from crosscoder_tpu import obs

        # which form this trace's k-sparse products took (forward: the
        # decode; backward: df and both weight gradients)
        obs.count("perf/cc_decode_rows_traces" if rows
                  else "perf/cc_decode_dense_traces")
        obs.count("perf/cc_bwd_rows_traces" if rows
                  else "perf/cc_bwd_dense_traces")
    if factored and sparse_bwd and not aux_active:
        # sparse backward plane, full-step scope: encode + TopK + factored
        # decode under ONE custom vjp — none of the three dense backward
        # matmuls survives. Forward numerics are the factored tier's
        # exactly (same einsum/kernel/gather chain). Aux-active steps fall
        # through to the (h, W_dec) scope below (the h-residual escape
        # hatch — the aux ranking consumes the pre-acts).
        recon_f32, vals, idx = _sparse_topk_step(
            x, params["W_enc"], params["b_enc"], params["W_dec"],
            cfg.topk_k, rows,
        )
        recon = (recon_f32 + params["b_dec"].astype(jnp.float32)).astype(x.dtype)
        f = None
    elif factored:
        # Pallas factored tier: kernel mask → sparsify → k-row decode;
        # backward identical to the dense path (see _factored_topk_forward)
        # — or, on sparse-backward AuxK steps, the (h, W_dec)-scoped sparse
        # variant (h must stay an explicit residual for the aux ranking)
        h = pre_acts(params, x)
        tier = _sparse_topk_from_h if sparse_bwd else _factored_topk_forward
        recon_f32, vals, idx = tier(h, params["W_dec"], cfg.topk_k)
        recon = (recon_f32 + params["b_dec"].astype(jnp.float32)).astype(x.dtype)
        f = None
    elif cfg.activation == "jumprelu" and cfg.l0_coeff > 0:
        h = pre_acts(params, x)
        f = act_ops.apply(h, cfg, params)
        recon = decode(params, f)
        l0_penalty = act_ops.jumprelu_l0(
            h, params["log_theta"], cfg.jumprelu_bandwidth
        )
    else:
        h = pre_acts(params, x)
        f = act_ops.apply(h, cfg, params)
        recon = decode(params, f)

    with jax.named_scope("cc/loss"):
        xf = x.astype(jnp.float32)
        rf = recon.astype(jnp.float32)
        err2 = jnp.square(rf - xf)                        # [B, n, d]
        l2_per_row = jnp.sum(err2, axis=(-2, -1))         # [B]
        l2_loss = jnp.mean(l2_per_row)

    # L1 is an objective term only when l1_coeff != 0 (TopK-style runs set it
    # to 0 and control sparsity structurally); off log-steps
    # (with_metrics=False) a zero-coeff L1 would be pure overhead — the
    # [H, n] decoder-norm reduce plus a full [B, H] weighted sweep, ~2-3 ms
    # of the bare TopK step at dict 2^15 — so it is gated exactly like the
    # other metric-only reductions and returns 0 in that slot.
    with jax.named_scope("cc/loss"):
        need_l1 = with_metrics or cfg.l1_coeff != 0
        if need_l1:
            dec_norms = jnp.linalg.norm(params["W_dec"].astype(jnp.float32), axis=-1)  # [H, n]
            total_dec_norm = jnp.sum(dec_norms, axis=-1)      # [H]
        if not need_l1:
            l1_loss = jnp.zeros((), jnp.float32)
        elif factored:
            # identical to the dense weighted L1: inactive latents contribute 0
            w_active = jnp.take(total_dec_norm, idx)          # [B, k]
            l1_loss = jnp.mean(jnp.sum(vals.astype(jnp.float32) * w_active, axis=-1))
        else:
            ff = f.astype(jnp.float32)
            l1_loss = jnp.mean(jnp.sum(ff * total_dec_norm[None, :], axis=-1))

    # --- AuxK (cfg.aux_k > 0; Gao et al. 2024 "Scaling and evaluating
    # sparse autoencoders", the standard TopK-SAE dead-latent recipe; no
    # reference counterpart — the reference's dense ReLU never faces mass
    # latent death). Reconstruct the MAIN reconstruction's residual
    # e = stop_grad(x − x̂) with the top aux_k latents among those the
    # trainer marked dead, decoded through W_dec without b_dec; the loss is
    # normalized by the residual's own power so cfg.aux_k_coeff stays
    # dimensionless as the residual shrinks. Raw (un-ReLU'd) pre-acts are
    # ranked/decoded — a dead latent's pre-act is usually ≤ 0, and ReLU
    # would zero exactly the gradient path this loss exists to provide.
    # Objective-relevant, so computed in the with_metrics=False step too.
    aux_loss: jax.Array | float = 0.0
    fired = None
    if track_fired or aux_active:
        # which latents fired this batch (the trainer's steps_since_fired
        # update). Tracked on EVERY step even when the aux loss itself is
        # amortized to every cfg.aux_every-th step — deadness must stay
        # current, or a revived latent would keep receiving aux gradient
        # for up to aux_every steps after coming back.
        d_hidden = params["W_dec"].shape[0]
        if factored:
            hits = jnp.zeros((d_hidden,), jnp.int32).at[idx.reshape(-1)].add(
                (vals.reshape(-1) > 0).astype(jnp.int32), mode="drop"
            )
            fired = hits > 0
        else:
            fired = jnp.any(f > 0, axis=0)
    if dead_mask is not None and cfg.aux_k > 0:
        d_hidden = params["W_dec"].shape[0]
        k_aux = min(cfg.aux_k, d_hidden)
        # Selection runs in the COMPUTE dtype with approx_max_k (the TPU
        # PartialReduce instruction) — an exact fp32 top_k here cost more
        # than the whole rest of the step at dict 2^15 (measured 498 vs
        # 79 ms, bench matrix): it materialized [B, H] fp32 and paid the
        # k=256 sort. Which near-top dead latent gets the aux gradient is
        # heuristic anyway; values are re-GATHERED from the pre-acts so
        # the encoder's gradient path is exact (the straight-through
        # treatment of ops.activations.topk), and non-dead slots (when fewer
        # dead than aux_k exist) are zeroed by the mask gather.
        h_all = h if h is not None else pre_acts(params, x)
        neg = jnp.asarray(jnp.finfo(h_all.dtype).min, h_all.dtype)
        ranked = jnp.where(dead_mask[None, :], jax.lax.stop_gradient(h_all), neg)
        if cfg.aux_exact_rank:
            # engine-parity mode: the torch oracle ranks exactly, so the
            # jax side must select the same latents (cfg.aux_exact_rank)
            _, aidx = jax.lax.top_k(ranked, k_aux)
        else:
            _, aidx = jax.lax.approx_max_k(ranked, k_aux, recall_target=0.95)
        aidx = jax.lax.stop_gradient(aidx)
        avals = jnp.take_along_axis(h_all, aidx, axis=-1)
        avals = jnp.where(jnp.take(dead_mask, aidx), avals, 0)
        e = jax.lax.stop_gradient(xf - rf)                # [B, n, d] fp32
        # dense decode of the scattered aux activations: at aux_k ≈ 8k a
        # per-example gather of the aux_k decoder rows materializes
        # [B, aux_k, n, d] — ~10 GB of HBM traffic at bench shapes
        # (measured 391 ms/step vs ~145 dense) — while B·aux_k/H ≈ 32
        # hits per dictionary row means every W_dec row is read anyway:
        # three MXU matmuls (fwd + the two VJPs) win outright.
        if use_sparse_aux(cfg, x.shape[0]):
            # sparse backward reuse (cfg.sparse_bwd): identical dense
            # forward, backward through the O(B·aux_k) scatter/gather
            # plane instead of the two [B,H]-sized VJP matmuls
            e_hat = _sparse_aux_product(
                avals.astype(x.dtype), aidx, params["W_dec"]
            )
        else:
            f_aux = jnp.zeros((x.shape[0], d_hidden), x.dtype).at[
                jnp.arange(x.shape[0])[:, None], aidx
            ].add(avals.astype(x.dtype))
            e_hat = jnp.einsum(
                "bh,hnd->bnd", f_aux, params["W_dec"],
                preferred_element_type=jnp.float32,
            )
        num = jnp.mean(jnp.sum(jnp.square(e_hat - e), axis=(-2, -1)))
        den = jnp.mean(jnp.sum(jnp.square(e), axis=(-2, -1)))
        # no dead latents → e_hat ≡ 0 and the ratio is a gradient-free
        # constant ≈ 1; gate it to 0 so loss/metrics don't carry the ghost
        aux_loss = jnp.where(jnp.any(dead_mask), num / (den + 1e-8), 0.0)

    if not with_metrics:
        zero = jnp.zeros((), jnp.float32)
        return LossOutput(
            l2_loss=l2_loss,
            l1_loss=l1_loss,
            l0_loss=zero,
            explained_variance=jnp.zeros_like(l2_per_row),
            explained_variance_per_source=jnp.zeros(
                (x.shape[-2], x.shape[0]), jnp.float32
            ),
            l0_penalty=l0_penalty,
            aux_loss=aux_loss,
            fired=fired,
        )

    with jax.named_scope("cc/loss"):
        eps = 1e-8
        centered = xf - jnp.mean(xf, axis=0, keepdims=True)
        tot_var = jnp.sum(jnp.square(centered), axis=(-2, -1))  # [B]
        explained_variance = 1.0 - l2_per_row / (tot_var + eps)

        # per-source EV (reference computes _A and _B separately,
        # crosscoder.py:115-121); vectorized over the source axis here
        l2_per_source = jnp.sum(err2, axis=-1)                # [B, n]
        var_per_source = jnp.sum(jnp.square(centered), axis=-1)  # [B, n]
        ev_per_source = 1.0 - l2_per_source / (var_per_source + eps)  # [B, n]

        if factored:
            l0_loss = jnp.mean(jnp.sum((vals > 0).astype(jnp.float32), axis=-1))
        else:
            l0_loss = jnp.mean(jnp.sum((f > 0).astype(jnp.float32), axis=-1))

    return LossOutput(
        l2_loss=l2_loss,
        l1_loss=l1_loss,
        l0_loss=l0_loss,
        explained_variance=explained_variance,
        explained_variance_per_source=jnp.transpose(ev_per_source),
        l0_penalty=l0_penalty,
        aux_loss=aux_loss,
        fired=fired,
    )


def cast_params(params: Params, dtype: jnp.dtype) -> Params:
    """Cast weight leaves to the compute dtype (``log_theta`` stays fp32 —
    its gradient path is the STE, not the MXU)."""
    return {
        k: (v if k == "log_theta" else v.astype(dtype)) for k, v in params.items()
    }


def training_loss(
    params: Params,
    x: jax.Array,
    l1_coeff: jax.Array | float,
    cfg: CrossCoderConfig,
    with_metrics: bool = True,
    l0_coeff: jax.Array | float | None = None,
    dead_mask: jax.Array | None = None,
    aux_coeff: jax.Array | float | None = None,
    track_fired: bool = False,
) -> tuple[jax.Array, LossOutput]:
    """Scalar training objective ``l2 + l1_coeff · l1`` (reference
    ``trainer.py:44``) plus the full loss surface as aux.

    Params may be fp32 masters; they are cast to ``cfg.enc_dtype`` here so
    the einsums hit the MXU in bf16 while gradients accumulate into fp32.
    """
    # The l1 metric/objective term is compiled out when with_metrics=False
    # AND cfg.l1_coeff == 0 (get_losses's need_l1 gate — a static decision).
    # The objective here multiplies the DYNAMIC ``l1_coeff`` argument, so a
    # direct caller passing a nonzero runtime coefficient against
    # cfg.l1_coeff == 0 would silently train l2 + coeff·0. Catch every
    # concretely-checkable disagreement; a traced coefficient can't be
    # inspected, but the production trainer derives it from cfg.l1_coeff's
    # schedule, so trace-time values always agree with the static gate.
    if not with_metrics and cfg.l1_coeff == 0:
        concrete: float | None = None
        if not isinstance(l1_coeff, jax.core.Tracer):
            # python numbers, numpy scalars (np.float32 is NOT a float
            # subclass), and concrete jax scalars all float(); anything
            # that can't is treated as unknowable, like a tracer
            try:
                concrete = float(l1_coeff)
            except (TypeError, ValueError):
                concrete = None
        if concrete is not None and concrete != 0.0:
            raise ValueError(
                f"training_loss got l1_coeff={concrete} but cfg.l1_coeff == 0 "
                "and with_metrics=False: the L1 term is compiled out on this "
                "path, so the sparsity penalty would be silently dropped. "
                "Set cfg.l1_coeff to the intended scale (the schedule-derived "
                "argument then agrees) or pass with_metrics=True."
            )
    losses = get_losses(
        cast_params(params, dtype_of(cfg.enc_dtype)), x, cfg, with_metrics,
        dead_mask=dead_mask, track_fired=track_fired,
    )
    # TopK-style runs control sparsity structurally and typically set
    # l1_coeff=0 in config; the objective shape is the same either way.
    # JumpReLU runs may add the paper's L0 objective via cfg.l0_coeff
    # (``l0_coeff`` overrides it — the trainer passes the warmed-up value).
    loss = losses.l2_loss + l1_coeff * losses.l1_loss
    if cfg.l0_coeff > 0:
        eff = cfg.l0_coeff if l0_coeff is None else l0_coeff
        loss = loss + eff * losses.l0_penalty
    if cfg.aux_k > 0 and dead_mask is not None:
        # AuxK term (``aux_coeff`` overrides cfg.aux_k_coeff — the trainer
        # passes the sparsity-warmup-scaled value, same ramp as l0_coeff)
        eff_aux = cfg.aux_k_coeff if aux_coeff is None else aux_coeff
        loss = loss + eff_aux * losses.aux_loss
    return loss, losses


def param_count(cfg: CrossCoderConfig) -> int:
    n, d, h = cfg.n_sources, cfg.d_in, cfg.dict_size
    count = 2 * n * d * h + h + n * d
    if cfg.activation == "jumprelu":
        count += h  # log_theta
    return count


def fold_scaling_factors(params: Params, factors: Any) -> Params:
    """Fold per-source activation-normalization factors into the weights.

    Mirrors the notebook's ``fold_activation_scaling_factor`` (reference
    ``nb:cell 27``): with per-source scale s (activations were trained on
    ``x·s``), an equivalent crosscoder over *raw* activations has
    ``W_enc[n] ·= s[n]``, ``W_dec[:, n] /= s[n]``, ``b_dec[n] /= s[n]``
    (``b_enc`` unchanged). After folding, analysis/evals can run on
    unnormalized model activations.
    """
    s = jnp.asarray(factors, dtype=jnp.float32)
    out = dict(params)
    out["W_enc"] = (params["W_enc"].astype(jnp.float32) * s[:, None, None]).astype(params["W_enc"].dtype)
    out["W_dec"] = (params["W_dec"].astype(jnp.float32) / s[None, :, None]).astype(params["W_dec"].dtype)
    out["b_dec"] = (params["b_dec"].astype(jnp.float32) / s[:, None]).astype(params["b_dec"].dtype)
    return out
