"""Sparse backward compute plane: scatter-accumulate row gradients.

The wide-dictionary TopK step is BACKWARD-bound: the factored Pallas tier
decodes through only the k active rows, but its backward "stays dense on
purpose" (models/crosscoder._factored_topk_bwd) because XLA's scatter-add
gradient for a gathered ``W_dec`` costs 42-76 ms at bench shapes — so
three of the step's large matmuls (``dW_dec`` [B,H]x[B,nd], ``df``
[B,nd]x[H,nd], ``dW_enc`` [B,nd]x[B,H]) each burn 20-33 ms at dict 2^17
multiplying ~99.9% structural zeros. This module is the hand-written
replacement: with at most ``k`` active latents per example, every one of
those gradients is the SAME primitive —

    out[dst[p]] += coeff[p] * rows[src[p]]        (P = B·k pairs)

an O(B·k·n·d) scatter-accumulate instead of an O(B·H·n·d) matmul
(Densifying Assumed-sparse Tensors, arXiv:1905.04035: accumulation
layout, not FLOPs, decides this shape of gradient).

Two implementations, one dispatch (the ops/quant.py discipline):

- **pure XLA** (``_scatter_add_rows_xla``): one flattened
  ``zeros.at[idx].add`` scatter — jittable anywhere, the CPU-test
  fallback and the oracle the kernel is pinned against. On TPU this is
  exactly the 42-76 ms XLA scatter the kernel exists to beat, so the
  model layer's "auto" gate never routes production steps here.
- **Pallas TPU kernel** (``_scatter_rows_kernel``): pairs are sorted by
  destination row (stable ``lax.sort``, so duplicate destinations — two
  examples activating the same latent, the scatter-add race case —
  accumulate in a DETERMINISTIC order), per-row-block pair ranges come
  from one ``searchsorted``, and the kernel walks each output row
  block's own pair range accumulating f32 in VMEM. Grid is
  ``(m_chunks, row_blocks)`` with the feature axis chunked so the
  ``rows`` operand block stays VMEM-resident across the row-block sweep
  (Ragged-Paged-Attention-style budgeted blocks + grid-tail handling,
  arXiv:2604.15464; same discipline as ops/topk_pallas).

HBM cost of the kernel at [B=4096, k=32, H=2^17, nd=4608]: one read of
the pair list (1.5 MB), ~``num_m`` reads of the cotangent rows (75 MB
f32), and one write of the [H, nd] f32 output (2.4 GB — the output
write is irreducible for a dense-layout gradient and is the same bytes
the dense matmul writes); vs the dense path's 2·B·H·nd ≈ 5 TFLOP
matmul. Hardware dispatch is gated on ``CROSSCODER_SPARSE_GRAD_PALLAS=1``
and must stay off: the kernel is interpret-verified only — the chip's
compiler REFUSES it as written (dynamic scalar reads of the pair list
from VMEM; tests/test_chip_compile.py holds the refusal as a strict
xfail and says why scalar-prefetching the list is not a local fix).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM budget shared with the other kernel modules (see topk_pallas).
_VMEM_BUDGET_BYTES = 13 << 20
# Output row-block height: f32 min sublane tile is 8; 256 matches the
# other kernels' row granularity. Shrunk (multiple-of-8) to divide n_out.
_ROW_BLOCK = 256
# Pair-list cap: dst/src/coeff are fully VMEM-resident ([1, P] int32 x2 +
# f32), so P is bounded by the budget share we give them (3 MB → 2^18
# pairs). B·k at bench shapes is 131072; AuxK at aux_k=256 (1M pairs)
# exceeds this — the model layer's aux gate checks it (see
# decode_grad_supported / the SCALING.md supported-shape matrix).
_MAX_PAIRS = 1 << 18

# test-only: route the kernel through the Pallas interpreter so the
# sparse-backward model path can run on CPU CI (same pattern as
# topk_pallas / quant). Read at TRACE time.
_INTERPRET = False


def set_interpret(flag: bool) -> None:
    global _INTERPRET
    _INTERPRET = flag


def kernel_enabled() -> bool:
    """Whether scatter_add_rows may dispatch to the Pallas kernel: the
    interpreter (CPU tests) or a real TPU with the opt-in env set (the
    shared ops/dispatch gate — ships interpret-verified, hardware-gated)."""
    from crosscoder_tpu.ops.dispatch import hw_kernel_enabled

    return hw_kernel_enabled("CROSSCODER_SPARSE_GRAD_PALLAS", _INTERPRET)


def _row_block(n_out: int) -> int:
    """Largest multiple-of-8 block height <= _ROW_BLOCK dividing n_out
    (0 when none exists — the caller's supported() gate rejects)."""
    rb = min(_ROW_BLOCK, n_out)
    rb -= rb % 8
    while rb >= 8 and n_out % rb:
        rb -= 8
    return rb if rb >= 8 else 0


def _m_chunk(m: int, n_rows: int, itemsize: int, rb: int, n_pairs: int) -> int:
    """Largest lane-aligned chunk of the feature axis whose working set
    (rows block + out block + resident pair arrays) fits the VMEM
    budget; 0 when even a 128-lane chunk does not fit."""
    pair_bytes = 12 * _pad_pairs(n_pairs)
    mc = min(m, 2048)
    mc -= mc % 128
    while mc >= 128:
        if m % mc == 0:
            used = n_rows * mc * itemsize + rb * mc * 4 + pair_bytes
            if used <= _VMEM_BUDGET_BYTES:
                return mc
        mc -= 128
    return 0


def _pad_pairs(n_pairs: int) -> int:
    return -(-max(n_pairs, 1) // 128) * 128


def supported(n_out: int, m: int, n_rows: int, n_pairs: int) -> bool:
    """Shapes the Pallas scatter-accumulate kernel handles: lane-aligned
    feature axis, a row-block height dividing the output rows, pair list
    under the VMEM-residency cap, and a feature chunk that fits the
    budget alongside the rows block."""
    if m < 128 or m % 128 or n_out < 8 or n_pairs < 1:
        return False
    if n_pairs > _MAX_PAIRS:
        return False
    rb = _row_block(n_out)
    if not rb:
        return False
    return _m_chunk(m, n_rows, 4, rb, n_pairs) > 0


def decode_grad_supported(dict_size: int, k: int, n_sources: int,
                          d_in: int, batch: int) -> bool:
    """The model-layer gate (mirrors topk_pallas.sparsify_supported's
    role): True when BOTH scatter calls of the factored-tier sparse
    backward are kernel-supported — ``dW_dec`` over ``m = n·d`` and the
    bias-augmented encoder call over ``m = n·d + 128`` (the extra
    128-lane block carries the ``db_enc`` ones column)."""
    m = n_sources * d_in
    n_pairs = batch * k
    return (
        supported(dict_size, m, batch, n_pairs)
        and supported(dict_size, m + 128, batch, n_pairs)
    )


# ---------------------------------------------------------------------------
# pure-XLA reference path
# ---------------------------------------------------------------------------


def _scatter_add_rows_xla(coeff: jax.Array, idx: jax.Array, rows: jax.Array,
                          n_out: int) -> jax.Array:
    """One flattened scatter-add: materializes the [P, m] update matrix,
    so it is only for fallback/oracle duty — the kernel's whole point is
    not doing this on the hot path."""
    B, k = coeff.shape
    updates = (coeff.astype(jnp.float32)[:, :, None]
               * rows.astype(jnp.float32)[:, None, :]).reshape(B * k, -1)
    out = jnp.zeros((n_out, rows.shape[-1]), jnp.float32)
    # negative indices would WRAP under .at[] (numpy semantics); route them
    # to the drop sentinel so both implementations share drop semantics
    flat = idx.reshape(-1)
    flat = jnp.where((flat >= 0) & (flat < n_out), flat, n_out)
    return out.at[flat].add(updates, mode="drop")


# ---------------------------------------------------------------------------
# Pallas kernel: sorted pairs -> per-row-block sequential accumulation
# ---------------------------------------------------------------------------


def _sorted_pairs(coeff: jax.Array, idx: jax.Array, n_out: int, rb: int):
    """Stable-sort the (dst, src, coeff) pair list by destination row and
    compute per-row-block [start, end) offsets.

    Stability makes duplicate destinations accumulate in original pair
    order (batch-major, then slot) — the deterministic within-block
    ordering the parity tests pin. Padding pairs carry the sentinel
    ``dst = n_out``: searchsorted places them past every block's range,
    so they are never visited.
    """
    B, k = coeff.shape
    P = B * k
    dst = idx.reshape(-1).astype(jnp.int32)
    # guard out-of-range destinations like scatter mode="drop" would:
    # route them to the sentinel row (never visited)
    dst = jnp.where((dst >= 0) & (dst < n_out), dst, n_out)
    src = jnp.arange(P, dtype=jnp.int32) // k           # batch row of pair p
    cf = coeff.reshape(-1).astype(jnp.float32)
    dst_s, src_s, cf_s = jax.lax.sort((dst, src, cf), num_keys=1,
                                      is_stable=True)
    pad = _pad_pairs(P) - P
    if pad:
        dst_s = jnp.concatenate([dst_s, jnp.full((pad,), n_out, jnp.int32)])
        src_s = jnp.concatenate([src_s, jnp.zeros((pad,), jnp.int32)])
        cf_s = jnp.concatenate([cf_s, jnp.zeros((pad,), jnp.float32)])
    bounds = jnp.arange(n_out // rb + 1, dtype=jnp.int32) * rb
    starts = jnp.searchsorted(dst_s, bounds, side="left").astype(jnp.int32)
    n_starts = starts.shape[0]
    spad = -(-n_starts // 128) * 128 - n_starts
    if spad:
        starts = jnp.concatenate(
            [starts, jnp.full((spad,), starts.shape[0], jnp.int32)]
        )
    return dst_s[None, :], src_s[None, :], cf_s[None, :], starts[None, :]


def _scatter_rows_kernel(dst_ref, src_ref, cf_ref, starts_ref, rows_ref,
                         out_ref, *, rb: int):
    """Grid ``(m_chunks, row_blocks)``: each step owns one [rb, mc] f32
    output block and walks ITS OWN slice of the dst-sorted pair list
    (``starts[r] .. starts[r+1]``), accumulating ``coeff · rows[src]``
    into the destination row. All pairs in the slice hit this block by
    construction, so the loop body is guard-free; accumulation order is
    the sorted order — deterministic, and ascending-destination within
    the block. The rows operand block is revisited across the row-block
    sweep (index constant in r), so it is DMA'd once per feature chunk.
    """
    r = pl.program_id(1)
    out_ref[:] = jnp.zeros_like(out_ref)
    s = starts_ref[0, r]
    e = starts_ref[0, r + 1]
    r0 = r * rb

    def body(p, _):
        d = dst_ref[0, p] - r0
        b = src_ref[0, p]
        c = cf_ref[0, p]
        row = rows_ref[pl.ds(b, 1), :].astype(jnp.float32)
        out_ref[pl.ds(d, 1), :] = out_ref[pl.ds(d, 1), :] + c * row
        return 0

    jax.lax.fori_loop(s, e, body, 0)


def _scatter_add_rows_pallas(coeff: jax.Array, idx: jax.Array,
                             rows: jax.Array, n_out: int,
                             interpret: bool) -> jax.Array:
    m = rows.shape[-1]
    n_rows = rows.shape[0]
    rb = _row_block(n_out)
    mc = _m_chunk(m, n_rows, jnp.dtype(rows.dtype).itemsize, rb,
                  coeff.size)
    assert rb and mc, (n_out, m, n_rows, coeff.size)
    dst_s, src_s, cf_s, starts = _sorted_pairs(coeff, idx, n_out, rb)
    num_m = m // mc
    num_r = n_out // rb
    p_lanes = dst_s.shape[-1]
    s_lanes = starts.shape[-1]

    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        )
    return pl.pallas_call(
        functools.partial(_scatter_rows_kernel, rb=rb),
        out_shape=jax.ShapeDtypeStruct((n_out, m), jnp.float32),
        grid=(num_m, num_r),
        in_specs=[
            pl.BlockSpec((1, p_lanes), lambda mi, r: (0, 0),
                         memory_space=pltpu.VMEM),     # dst (sorted)
            pl.BlockSpec((1, p_lanes), lambda mi, r: (0, 0),
                         memory_space=pltpu.VMEM),     # src
            pl.BlockSpec((1, p_lanes), lambda mi, r: (0, 0),
                         memory_space=pltpu.VMEM),     # coeff
            pl.BlockSpec((1, s_lanes), lambda mi, r: (0, 0),
                         memory_space=pltpu.VMEM),     # row-block starts
            pl.BlockSpec((n_rows, mc), lambda mi, r: (0, mi),
                         memory_space=pltpu.VMEM),     # cotangent rows
        ],
        out_specs=pl.BlockSpec((rb, mc), lambda mi, r: (r, mi),
                               memory_space=pltpu.VMEM),
        compiler_params=compiler_params,
        interpret=interpret,
    )(dst_s, src_s, cf_s, starts, rows)


def scatter_add_rows(coeff: jax.Array, idx: jax.Array, rows: jax.Array,
                     n_out: int, *, use_pallas: bool | None = None
                     ) -> jax.Array:
    """``out[n_out, m] f32`` with ``out[idx[b,j]] += coeff[b,j]·rows[b]``.

    ``coeff/idx: [B, k]``, ``rows: [B, m]`` (any float dtype; accumulation
    is f32). Out-of-range indices are dropped (scatter ``mode="drop"``
    semantics). Dispatches to the Pallas sorted-pair kernel when enabled
    and shape-supported, else the XLA scatter — both compute the same sum;
    they may differ by f32 association order on duplicate destinations
    (the kernel's order is deterministic run-to-run).
    """
    if coeff.shape != idx.shape or coeff.ndim != 2 or rows.ndim != 2:
        raise ValueError(
            f"scatter_add_rows wants coeff/idx [B, k] and rows [B, m], got "
            f"{coeff.shape}/{idx.shape}/{rows.shape}"
        )
    if coeff.shape[0] != rows.shape[0]:
        raise ValueError(
            f"coeff batch {coeff.shape[0]} != rows batch {rows.shape[0]}"
        )
    if use_pallas is None:
        use_pallas = kernel_enabled()
    if use_pallas and supported(n_out, rows.shape[-1], rows.shape[0],
                                coeff.size):
        # off-TPU forced-pallas callers (tests) always run the interpreter
        interpret = _INTERPRET or jax.default_backend() != "tpu"
        return _scatter_add_rows_pallas(coeff, idx, rows, n_out, interpret)
    return _scatter_add_rows_xla(coeff, idx, rows, n_out)
