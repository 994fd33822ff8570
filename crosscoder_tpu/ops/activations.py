"""Encoder nonlinearities: dense ReLU (reference parity) plus the sparse
activations the reference lacks (TopK / BatchTopK / JumpReLU).

The reference supports only dense ReLU (reference ``crosscoder.py:76-77``).
The TPU build adds structural-sparsity activations as first-class options
(BASELINE.json config 2 calls for TopK(k=32) at dict_size 2^15), with:

- ``topk``: per-row TopK of the ReLU'd pre-activations. Gradients flow only
  through the surviving entries (the mask is a constant wrt the backward
  pass, which is the standard straight-through treatment).
- ``batchtopk``: TopK over the whole batch (k·batch entries globally), which
  equalizes feature usage across rows.
- ``jumprelu``: ``h · 1[h > θ]`` with the rectangle-kernel straight-through
  estimator for θ gradients (Rajamanoharan et al., 2024 parameterization with
  ``θ = exp(log_theta)``).

A Pallas TPU kernel for the TopK inner loop lives in
:mod:`crosscoder_tpu.ops.topk_pallas`; it is used automatically on TPU when
shapes are tile-aligned, with these dense versions as the fallback/oracle.
"""

from __future__ import annotations

import functools
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

if TYPE_CHECKING:
    from crosscoder_tpu.config import CrossCoderConfig


def relu(h: jax.Array) -> jax.Array:
    # jax.nn.relu, not jnp.maximum: its subgradient at exactly 0 is 0 (torch
    # ReLU convention, and what the Pallas topk backward's survivor mask
    # implements), where maximum would split the tie and pass 0.5·g.
    return jax.nn.relu(h)


def topk(h: jax.Array, k: int, *, use_pallas: bool | None = None) -> jax.Array:
    """Keep the k largest ReLU'd entries per row, zero elsewhere.

    ``h: [..., d_hidden]``. Ties broken by index (jax.lax.top_k semantics).
    """
    if use_pallas is None:
        use_pallas = _default_use_pallas()
    if use_pallas:
        from crosscoder_tpu.ops import topk_pallas

        if topk_pallas.supported(h, k):
            return topk_pallas.topk(h, k)
    return _topk_dense(h, k)


def _topk_dense(h: jax.Array, k: int) -> jax.Array:
    hp = relu(h)
    # Exact-k scatter of the top-k entries (a >=threshold mask would keep
    # extra entries on ties, which bf16 pre-acts make common).
    vals, idx = jax.lax.top_k(hp, k)                    # [..., k] sorted desc
    lead = hp.shape[:-1]
    flat_vals = vals.reshape(-1, k)
    flat_idx = idx.reshape(-1, k)
    rows = jnp.arange(flat_idx.shape[0])[:, None]
    out = jnp.zeros((flat_idx.shape[0], hp.shape[-1]), dtype=hp.dtype)
    out = out.at[rows, flat_idx].set(flat_vals, mode="drop", unique_indices=True)
    return out.reshape(*lead, hp.shape[-1])


def batchtopk(h: jax.Array, k: int, *, use_pallas: bool | None = None) -> jax.Array:
    """TopK over the flattened (batch × d_hidden) pre-acts, keeping
    ``k · batch`` entries globally (ties at the threshold all kept); at eval
    time this behaves like a global threshold (BatchTopK, Bussmann et al.
    2024).

    The global threshold — the (k·batch)-th largest ReLU'd value — is found
    by exact bit-pattern bisection (31 fused compare-and-count sweeps), not
    by sorting: ``lax.top_k`` over the flattened array is a 134M-element
    device sort at the production shape (4096 × 2^15) that XLA cannot tile,
    while each bisection sweep is a plain elementwise-compare + sum
    reduction that fuses and scales to any size.

    When the chunked Pallas kernels are live and the shape is supported
    (:func:`crosscoder_tpu.ops.topk_pallas.batchtopk_supported`), the
    bisection + mask run over VMEM-resident tiles instead — bit-identical
    output, same straight-through gradient.
    """
    if use_pallas is None:
        use_pallas = _default_use_pallas()
    if use_pallas:
        from crosscoder_tpu.ops import topk_pallas

        if (topk_pallas.batchtopk_kernel_enabled()
                and topk_pallas.batchtopk_supported(h, k)):
            return topk_pallas.batchtopk(h, k)
    hp = relu(h)
    thresh = batchtopk_threshold_of(hp, k)
    mask = (hp >= thresh) & (hp > 0)
    return hp * jax.lax.stop_gradient(mask.astype(hp.dtype))


def batchtopk_threshold_of(hp: jax.Array, k: int) -> jax.Array:
    """The (k·batch)-th largest of the ReLU'd pre-acts — THE BatchTopK
    threshold definition, shared by training dispatch and by eval
    calibration (:func:`crosscoder_tpu.models.crosscoder.
    calibrate_batchtopk_threshold`) so the two can never diverge."""
    n_rows = 1
    for s in hp.shape[:-1]:
        n_rows *= s
    kk = min(k * n_rows, hp.size)
    return _kth_largest_nonneg(hp, kk)


# thresholds evaluated per bisection pass (each pass = ONE fused read of
# the matrix producing T counts); 15 gives ceil(log_16(2^31)) = 8 passes
# for the full f32 pattern range vs classic bisection's 31 full reads
_BATCHTOPK_T = 15


def _kth_largest_nonneg(hp: jax.Array, kk: int) -> jax.Array:
    """Exact k-th largest value of a non-negative array as an f32 scalar.

    For non-negative IEEE-754 floats the int bit pattern is order-isomorphic
    to the value, so the exact k-th order statistic comes from integer
    bisection on the pattern — here MULTI-THRESHOLD bisection (the same
    trick as the width-chunked Pallas TopK kernel's pass structure,
    :mod:`crosscoder_tpu.ops.topk_pallas`): every pass counts
    ``x >= mid_j`` for T evenly spaced candidates in one fused
    compare-reduce over the matrix and narrows the range ~(T+1)×, so the
    whole search reads the matrix ~8 times instead of 31.
    Invariant: ``count(x >= lo) >= kk`` and ``count(x >= hi) < kk``.
    """
    hpf = hp.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(hpf, jnp.int32).reshape(-1)
    t = _BATCHTOPK_T
    jj = jnp.arange(t, dtype=jnp.int32)

    def body(_, carry):
        lo, hi = carry
        # T mids strictly inside (lo, hi), overflow-safe for the f32 range
        r1 = hi - lo - 1
        q, rem = r1 // t, r1 % t
        mids = lo + 1 + q * jj + (rem * jj) // t                    # [T]
        cnts = jnp.sum((bits[:, None] >= mids[None, :]).astype(jnp.int32),
                       axis=0)                                      # [T]
        num_ge = jnp.sum((cnts >= kk).astype(jnp.int32))            # prefix-true
        sel_lo = (jj == num_ge - 1).astype(jnp.int32)
        sel_hi = (jj == num_ge).astype(jnp.int32)
        new_lo = jnp.where(num_ge > 0, jnp.sum(mids * sel_lo), lo)
        new_hi = jnp.where(num_ge < t, jnp.sum(mids * sel_hi), hi)
        return new_lo, new_hi

    lo = jnp.int32(0)
    hi = jnp.maximum(jax.lax.bitcast_convert_type(jnp.max(hpf), jnp.int32), 0) + 1
    # worst-case passes for the full positive-f32 range at T=15 (+1 margin)
    n_passes = 1
    r = 0x7F800001
    while r > 1:
        r = -((1 - r) // t)
        n_passes += 1
    lo, hi = jax.lax.fori_loop(0, n_passes, body, (lo, hi))
    return jax.lax.bitcast_convert_type(lo, jnp.float32).astype(hp.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def jumprelu(h: jax.Array, log_theta: jax.Array, bandwidth: float) -> jax.Array:
    theta = jnp.exp(log_theta).astype(h.dtype)
    return h * (h > theta)


def _jumprelu_fwd(h, log_theta, bandwidth):
    theta = jnp.exp(log_theta).astype(h.dtype)
    return h * (h > theta), (h, theta)


def _jumprelu_bwd(bandwidth, res, g):
    h, theta = res
    hf = h.astype(jnp.float32)
    tf = theta.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    # d out / d h: pass-through where the unit is on (the jump itself gets no
    # gradient wrt h — standard JumpReLU STE choice)
    dh = gf * (hf > tf)
    # d out / d theta via rectangle kernel K(u)=1[|u|<=1/2] of width `bandwidth`:
    # ∂/∂θ ≈ −(θ/ε)·K((h−θ)/ε); chain through θ = exp(log_theta).
    rect = (jnp.abs(hf - tf) <= bandwidth / 2).astype(jnp.float32)
    dtheta_units = -(tf / bandwidth) * rect * gf
    dlog_theta = jnp.sum(
        dtheta_units * tf, axis=tuple(range(dtheta_units.ndim - 1))
    ).astype(jnp.float32)
    return dh.astype(h.dtype), dlog_theta


jumprelu.defvjp(_jumprelu_fwd, _jumprelu_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def jumprelu_l0(h: jax.Array, log_theta: jax.Array, bandwidth: float) -> jax.Array:
    """Differentiable-in-θ L0: ``mean_b Σ_f 1[h > θ_f]`` (the JumpReLU
    paper's sparsity objective — Rajamanoharan et al. 2024 eq. 10). The
    step function's θ-gradient uses the same rectangle-kernel STE as the
    activation: ``∂/∂θ ≈ −(1/ε)·K((h−θ)/ε)`` per element, averaged over
    the batch; ``h`` gets no gradient (the paper's pseudo-derivative)."""
    theta = jnp.exp(log_theta).astype(h.dtype)
    return jnp.mean(jnp.sum((h > theta).astype(jnp.float32), axis=-1))


def _jumprelu_l0_fwd(h, log_theta, bandwidth):
    theta = jnp.exp(log_theta).astype(h.dtype)
    val = jnp.mean(jnp.sum((h > theta).astype(jnp.float32), axis=-1))
    return val, (h, theta)


def _jumprelu_l0_bwd(bandwidth, res, g):
    h, theta = res
    hf = h.astype(jnp.float32)
    tf = theta.astype(jnp.float32)
    rect = (jnp.abs(hf - tf) <= bandwidth / 2).astype(jnp.float32)
    # d/dθ_f of mean_b Σ_f H(h−θ_f) ≈ −(1/ε)·mean_b rect[b,f];
    # chain through θ = exp(log_theta)
    batch_axes = tuple(range(rect.ndim - 1))
    dtheta = -(1.0 / bandwidth) * jnp.mean(rect, axis=batch_axes)
    dlog_theta = (g * dtheta * tf).astype(jnp.float32)
    return jnp.zeros_like(h), dlog_theta


jumprelu_l0.defvjp(_jumprelu_l0_fwd, _jumprelu_l0_bwd)


def batchtopk_fixed(h: jax.Array, threshold: float,
                    *, use_pallas: bool | None = None) -> jax.Array:
    """BatchTopK EVAL mode: a calibrated fixed global threshold, so one
    example's activations never depend on what else is in the batch
    (Bussmann et al. 2024 use the mean training threshold at inference).
    Calibrate with :func:`crosscoder_tpu.models.crosscoder.
    calibrate_batchtopk_threshold`. Dispatches to the Pallas emit sweep
    under the same gates as :func:`batchtopk` (bit-identical mask)."""
    if use_pallas is None:
        use_pallas = _default_use_pallas()
    if use_pallas:
        from crosscoder_tpu.ops import topk_pallas

        if (topk_pallas.batchtopk_kernel_enabled()
                and topk_pallas.batchtopk_supported(h, 1)):
            return topk_pallas.batchtopk_fixed(h, float(threshold))
    hp = relu(h)
    mask = (hp >= jnp.asarray(threshold, hp.dtype)) & (hp > 0)
    return hp * jax.lax.stop_gradient(mask.astype(hp.dtype))


@jax.named_scope("cc/select")
def apply(h: jax.Array, cfg: "CrossCoderConfig", params: dict | None = None) -> jax.Array:
    """Dispatch on ``cfg.activation``. Whichever activation it is, its ops
    carry the scope ``cc/select`` in the compiled program (the TopK family
    selects; ReLU and JumpReLU gate)."""
    if cfg.activation == "relu":
        return relu(h)
    if cfg.activation == "topk":
        return topk(h, cfg.topk_k)
    if cfg.activation == "batchtopk":
        if cfg.batchtopk_threshold > 0:
            return batchtopk_fixed(h, cfg.batchtopk_threshold)
        return batchtopk(h, cfg.topk_k)
    if cfg.activation == "jumprelu":
        if params is None or "log_theta" not in params:
            raise ValueError("jumprelu requires params['log_theta']")
        return jumprelu(h, params["log_theta"], cfg.jumprelu_bandwidth)
    raise ValueError(f"unknown activation {cfg.activation!r}")


# "auto": Pallas kernel on TPU when shapes allow, dense elsewhere.
# set_topk_impl("dense"/"pallas") forces one path — benchmarking both
# tiers at the training-step level and debugging kernel mismatches.
_TOPK_IMPL = "auto"


def set_topk_impl(impl: str) -> None:
    if impl not in ("auto", "pallas", "dense"):
        raise ValueError(f"impl must be auto|pallas|dense, got {impl!r}")
    global _TOPK_IMPL
    _TOPK_IMPL = impl


def _default_use_pallas() -> bool:
    if _TOPK_IMPL != "auto":
        return _TOPK_IMPL == "pallas"
    return _backend_is_tpu()


@functools.lru_cache(maxsize=1)
def _backend_is_tpu() -> bool:
    return jax.default_backend() == "tpu"
