"""Fused causal attention for the padded forward: one online-softmax kernel.

The XLA form of the padded attention
(:func:`crosscoder_tpu.ops.paged_attention.ragged_attention_reference`)
writes the f32 ``[B, H, S, S]`` scores to HBM and reads them back twice
(row max, row sum, exp-and-PV): at seq 1024 that traffic, not the matmuls,
is the attention's time on a v5e (PERF.md §5). This kernel keeps a
``[block, block]`` tile of scores in VMEM, folds it into a running
(max, sum, accumulator) and never computes a tile that lies wholly above
the diagonal (or wholly outside a binding sliding window).

Same mathematics at the same stated precision as the XLA form: operands
reach the MXU in their own dtype (bf16 in production) with f32
accumulation for QKᵀ and PV, ``q`` is scaled in its own dtype BEFORE the
kernel, the soft-cap, the running max and the running sum are f32, and
the probabilities are cast to ``v.dtype`` before PV. Online softmax
reassociates the row reduction and normalises after PV instead of before
it, so parity with the XLA form is a tolerance, not bit equality
(tests/test_flash_attention.py).

Layout: q/o are read and written as ``[B, S, H·hd]`` — the projection
einsums' own layout — one ``[block, hd]`` column band per (batch, head,
q-block) grid point, so no transpose op surrounds the call. The whole
sequence's K and V of one kv head sit in VMEM (``S·hd`` each, 256 KB at
1024 × 128 bf16) and are fetched once per (batch, kv head); the kv loop
runs INSIDE the kernel with a trip count computed from the q-block's
index, so skipped tiles cost nothing, not even a grid step.

Selection (:func:`enabled` and :func:`supported`) is made where a program
is traced, from what the code can observe: the backend is a TPU with one
device, and the shape fits. A ``pallas_call`` is not partitioned by the
SPMD partitioner, so a process that sees several devices keeps the XLA
form, which is partitioned (PERF.md §7). No environment gate, no config field.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one mask fill and one VMEM budget for both attention kernels
from crosscoder_tpu.ops.paged_attention import _VMEM_BUDGET_BYTES, NEG_INF

_LANES = 128

# test-only: route the kernel through the Pallas interpreter (and let it
# dispatch on the CPU backend) — same pattern as ops/paged_attention.
# Read at TRACE time.
_INTERPRET = False


def set_interpret(flag: bool) -> None:
    global _INTERPRET
    _INTERPRET = flag


def enabled() -> bool:
    """Whether the kernel may dispatch from this process: the interpreter
    (CPU tests), or a TPU backend with exactly one device."""
    return _INTERPRET or (
        jax.default_backend() == "tpu" and jax.device_count() == 1
    )


def block_for(seq_len: int) -> int:
    """The q and kv tile edge for a sequence length: the largest of
    512/256/128 that divides it, or 0 when none does. A constant of the
    shape, not a knob: 512 won against 256 and 128 on a v5e at seq 1024
    (PERF.md §6, PR 27)."""
    for b in (512, 256, 128):
        if seq_len % b == 0:
            return b
    return 0


def _vmem_bytes(seq_len: int, head_dim: int, block: int, itemsize: int,
                bands: int = 2) -> int:
    kv = bands * 2 * seq_len * head_dim * itemsize      # K, V (a shared key band): double-buffered
    qo = bands * 2 * block * head_dim * itemsize
    scratch = 2 * block * _LANES * 4 + block * head_dim * 4
    scores = 3 * block * block * 4                      # s, p and a mask's worth
    return kv + qo + scratch + scores


def supported(
    seq_len: int, n_heads: int, n_kv_heads: int, head_dim: int, dtype,
) -> bool:
    """Shapes the kernel handles within the shared VMEM budget: ``S`` a
    multiple of a tile edge, ``hd`` a multiple of the lane width (a head is
    one column band of ``[B, S, H·hd]``), whole GQA groups."""
    block = block_for(seq_len)
    if not block or head_dim % _LANES or n_heads % n_kv_heads:
        return False
    itemsize = jnp.dtype(dtype).itemsize
    return _vmem_bytes(seq_len, head_dim, block, itemsize) <= _VMEM_BUDGET_BYTES


def latent_supported(seq_len: int, d_nope: int, d_rope: int, d_v: int, dtype) -> bool:
    """Shapes the LATENT instance handles (:func:`flash_attention_latent`):
    a score head of ``d_nope`` dims of its own plus ``d_rope`` rotary dims
    whose key is shared by all heads, a value head of ``d_v``. The head's own
    part and the value head are one column band each (whole lanes, the same
    width); the rotary part is zero-padded to ONE lane tile and its key is a
    third whole-sequence band in VMEM."""
    block = block_for(seq_len)
    if not block or d_nope % _LANES or d_v != d_nope or not 0 < d_rope <= _LANES:
        return False
    itemsize = jnp.dtype(dtype).itemsize
    return _vmem_bytes(seq_len, d_nope, block, itemsize, bands=3) <= _VMEM_BUDGET_BYTES


_NT = (((1,), (1,)), ((), ()))     # [m, d] x [n, d] -> [m, n]
_NN = (((1,), (0,)), ((), ()))     # [m, n] x [n, d] -> [m, d]


def _kernel(q_ref, k_ref, v_ref, *rest, block: int, softcap: float, window: int,
            shared: bool = False):
    """One (batch, head, q-block) grid point: fold the kv tiles this
    q-block can see into (m, l, acc), then normalise. ``shared``: two more
    operands, a second query band and ONE key band all heads share, whose
    product joins the scores (the latent form's rotary part).

    ``m``/``l`` are kept lane-replicated ``[block, 128]`` (the layout the
    installed splash kernel uses), so a row statistic meets a score tile
    by a lane-aligned tile, not a cross-lane broadcast."""
    if shared:
        qs_ref, ks_ref, o_ref, m_ref, l_ref, acc_ref = rest
        qs = qs_ref[...]
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    i = pl.program_id(2)
    q_lo = i * block
    hd = q_ref.shape[-1]
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[...]

    def fold(j, masked: bool):
        k_lo = pl.multiple_of(j * block, block)
        s = jax.lax.dot_general(
            q, k_ref[pl.ds(k_lo, block), :], _NT,
            preferred_element_type=jnp.float32,
        )                                                   # [block, block]
        if shared:
            s = s + jax.lax.dot_general(
                qs, ks_ref[pl.ds(k_lo, block), :], _NT,
                preferred_element_type=jnp.float32)
        if softcap:
            s = softcap * jnp.tanh(s * (1.0 / softcap))
        if masked:
            qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = kpos <= qpos
            if window:
                keep &= qpos - kpos < window
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a row with nothing kept so far has m_next == NEG_INF and p == 1:
        # harmless, because its diagonal entry is always kept and the
        # diagonal tile comes last — alpha = exp(NEG_INF - real) = 0 then
        # wipes what such a tile left in l and acc
        p = jnp.exp(s - jnp.tile(m_next, (1, block // _LANES)))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_next
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[pl.ds(k_lo, block), :], _NN,
            preferred_element_type=jnp.float32,
        )                                                   # [block, hd]
        acc_ref[...] = jnp.tile(alpha, (1, hd // _LANES)) * acc_ref[...] + pv

    def run(lo, hi, masked: bool):
        def body(j, carry):
            fold(j, masked)
            return carry

        jax.lax.fori_loop(lo, hi, body, 0)

    # tiles are square, so the diagonal tile is j == i: tiles below it are
    # wholly causal; with a binding window the oldest tiles fall out and
    # the ones the window's edge crosses need the mask as well
    if window:
        j_lo = jnp.maximum(q_lo - window + 1, 0) // block
        f_lo = jnp.minimum(
            jnp.maximum(q_lo + block - 1 - window + block, 0) // block, i)
        run(j_lo, f_lo, True)
        run(f_lo, i, False)
    else:
        run(0, i, False)
    fold(i, True)

    inv_l = 1.0 / l_ref[...]
    o_ref[...] = (
        acc_ref[...] * jnp.tile(inv_l, (1, hd // _LANES))
    ).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float,
    softcap: float = 0.0,
    window: int = 0,
) -> jax.Array:
    """Causal soft-capped attention, fused. Same contract as
    :func:`crosscoder_tpu.ops.paged_attention.ragged_attention_reference`
    with ``lengths=None`` and a STATIC mask: ``window=0`` is causal,
    ``window=w`` causal within the last ``w`` positions (``w < S``: a window
    that cannot bind is the caller's 0; a caller with a traced ``is_local``
    picks between two instances with ``lax.cond``).

    ``q [B, S, H, hd]`` (unscaled), ``k``/``v [B, S, KV, hd]`` →
    ``[B, S, H·hd]``. The caller has checked :func:`supported`.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    block = block_for(S)
    kernel = functools.partial(
        _kernel, block=block, softcap=float(softcap), window=int(window))
    q_spec = pl.BlockSpec((None, block, hd), lambda b, h, i: (b, i, h))
    kv_spec = pl.BlockSpec((None, S, hd), lambda b, h, i: (b, 0, h // g))
    return pl.pallas_call(
        kernel,
        grid=(B, H, S // block),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H * hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block, _LANES), jnp.float32),       # running max
            pltpu.VMEM((block, _LANES), jnp.float32),       # running sum
            pltpu.VMEM((block, hd), jnp.float32),           # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        name="fused_causal_attention",
        interpret=_INTERPRET,
    )(
        # scaled in q's own dtype, as the XLA form scales it
        q.reshape(B, S, H * hd) * scale,
        k.reshape(B, S, KV * hd),
        v.reshape(B, S, KV * hd),
    )


def flash_attention_latent(
    q_nope: jax.Array, q_rope: jax.Array, k_nope: jax.Array, k_rope: jax.Array,
    v: jax.Array, *, scale: float,
) -> jax.Array:
    """Causal attention of the latent form, fused: scores ``(q_nope·k_nope +
    q_rope·k_rope) · scale`` with ONE rotary key a position for all heads,
    no window, no soft-cap. ``q_nope``/``k_nope [B, S, H, dn]``, ``q_rope
    [B, S, H, dr]``, ``k_rope [B, S, 1, dr]`` (both rotated), ``v [B, S, H,
    dv]`` → ``[B, S, H·dv]``. The caller has checked
    :func:`latent_supported`. The same kernel as :func:`flash_attention`
    with two operands more: the rotary dims zero-padded to a lane tile, the
    queries' a column band a head, the key's one band that every head's grid
    points share (it is never copied a head)."""
    B, S, H, dn = q_nope.shape
    dr = q_rope.shape[-1]
    block = block_for(S)
    pad = ((0, 0),) * 3 + ((0, _LANES - dr),)
    kernel = functools.partial(_kernel, block=block, softcap=0.0, window=0, shared=True)
    q_spec = pl.BlockSpec((None, block, dn), lambda b, h, i: (b, i, h))
    kv_spec = pl.BlockSpec((None, S, dn), lambda b, h, i: (b, 0, h))
    qs_spec = pl.BlockSpec((None, block, _LANES), lambda b, h, i: (b, i, h))
    ks_spec = pl.BlockSpec((None, S, _LANES), lambda b, h, i: (b, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, H, S // block),
        in_specs=[q_spec, kv_spec, kv_spec, qs_spec, ks_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, H * dn), q_nope.dtype),
        scratch_shapes=[
            pltpu.VMEM((block, _LANES), jnp.float32),       # running max
            pltpu.VMEM((block, _LANES), jnp.float32),       # running sum
            pltpu.VMEM((block, dn), jnp.float32),           # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        name="fused_causal_attention_latent",
        interpret=_INTERPRET,
    )(
        # scaled in q's own dtype, as the XLA form scales it
        q_nope.reshape(B, S, H * dn) * scale,
        k_nope.reshape(B, S, H * dn),
        v.reshape(B, S, H * dn),
        (jnp.pad(q_rope, pad) * scale).reshape(B, S, H * _LANES),
        jnp.pad(k_rope, pad).reshape(B, S, _LANES),
    )
