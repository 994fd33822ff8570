"""Block-scaled symmetric int8 quantization for the data plane.

The replay buffer is the largest HBM tenant (``[buffer_size, n_sources,
d_in]`` bf16) and every hot byte path — device-buffer refill shards over
ICI, host↔device chunk transfers, and the data-parallel gradient
all-reduce — moves full-width bf16. EQuARX (PAPERS.md) shows a quantized
XLA all-reduce recovers ~2x collective bandwidth at negligible quality
loss; the same per-block int8 layout halves the replay store.

Layout: values quantize symmetrically per contiguous block of
``cfg.quant_block`` elements along the LAST axis (the feature axis for
activation rows, the flat vector for gradient shards):

    scale[..., b] = max(|x[..., b*B:(b+1)*B]|) / 127
    q[..., j]     = clip(round(x[..., j] / scale), -127, 127)  int8

so a ``[..., d]`` tensor stores as int8 ``[..., d]`` + f32 scales
``[..., d/B]`` — ``(1 + 4/B)/2`` of the bf16 bytes (0.508x at the default
B=256). Per-row-per-source granularity falls out of the row layout:
activation rows are ``[rows, n_sources, d_in]``, so every (row, source)
pair owns its own scale blocks and one outlier source cannot flatten the
other's resolution.

Two implementations, one dispatch:

- **pure XLA** (``quantize_blocks``/``dequantize_blocks``): reshape +
  block-max + divide/round, jittable anywhere (CPU tests, fused into the
  buffer's gather/scatter jits, inside shard_map collectives).
- **Pallas TPU kernel** (``_quantize_rows_kernel``): the XLA lowering is
  a reduce pass plus an elementwise pass over the matrix (two HBM
  round-trips); the kernel fuses block-amax, scale, and round into ONE
  pass over VMEM-resident row tiles. ``quantize_rows`` dispatches to it
  on TPU for supported shapes and falls back to XLA everywhere else
  (``set_interpret(True)`` runs the kernel in interpreter mode for CPU
  parity tests, same pattern as ops.topk_pallas).

Everything here is dtype-exact by construction on a given backend:
quantize → dequantize is deterministic, so the host- and device-store
buffer subclasses produce bit-identical serves from the same chunks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QMAX = 127.0

# -- pure-XLA reference path -------------------------------------------------


def n_blocks(d: int, block: int) -> int:
    if block <= 0 or d % block:
        raise ValueError(
            f"quant block {block} must be a positive divisor of the "
            f"quantized axis length {d}"
        )
    return d // block


def quantize_blocks(x: jax.Array, block: int) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-block int8 quantization over the last axis.

    ``x [..., d]`` (any float dtype) → ``(q int8 [..., d],
    scales f32 [..., d/block])``. All-zero blocks get scale 0 and
    quantize/dequantize to exact zeros.
    """
    nb = n_blocks(x.shape[-1], block)
    xb = x.astype(jnp.float32).reshape(*x.shape[:-1], nb, block)
    amax = jnp.max(jnp.abs(xb), axis=-1)                      # [..., nb]
    scale = amax / QMAX
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xb / safe[..., None]), -QMAX, QMAX)
    return q.astype(jnp.int8).reshape(x.shape), scale


def dequantize_blocks(
    q: jax.Array, scales: jax.Array, dtype: jnp.dtype = jnp.bfloat16
) -> jax.Array:
    """Inverse of :func:`quantize_blocks`: ``q [..., d]`` int8 + scales
    ``[..., d/block]`` → values ``[..., d]`` in ``dtype``."""
    nb = scales.shape[-1]
    block = q.shape[-1] // nb
    qb = q.astype(jnp.float32).reshape(*q.shape[:-1], nb, block)
    out = qb * scales.astype(jnp.float32)[..., None]
    return out.reshape(q.shape).astype(dtype)


def dequantize_np(q: np.ndarray, scales: np.ndarray, dtype) -> np.ndarray:
    """NumPy dequantize for the HOST replay store's serve path (the device
    paths stay in jnp). Same math as :func:`dequantize_blocks`."""
    nb = scales.shape[-1]
    block = q.shape[-1] // nb
    qb = q.astype(np.float32).reshape(*q.shape[:-1], nb, block)
    out = qb * scales.astype(np.float32)[..., None]
    return out.reshape(q.shape).astype(dtype)


def quantize_np(x: np.ndarray, block: int) -> tuple[np.ndarray, np.ndarray]:
    """NumPy quantize — the oracle the tests pin both jnp paths against.

    NB: uses round-half-away-from-zero? No — matches jnp/np.round
    (round-half-to-even) so CPU jnp and numpy agree bit-for-bit.
    """
    nb = n_blocks(x.shape[-1], block)
    xb = x.astype(np.float32).reshape(*x.shape[:-1], nb, block)
    amax = np.max(np.abs(xb), axis=-1)
    scale = (amax / QMAX).astype(np.float32)
    safe = np.where(scale > 0, scale, 1.0).astype(np.float32)
    q = np.clip(np.round(xb / safe[..., None]), -QMAX, QMAX)
    return q.astype(np.int8).reshape(x.shape), scale


# -- Pallas TPU kernel: fused block-amax + scale + round ---------------------
#
# One grid step owns a [rows_blk, width] tile in VMEM and produces the int8
# tile plus its [rows_blk, width/block] scale tile in a single pass — the
# XLA lowering reads the matrix twice (block-max reduce, then the
# elementwise divide/round). Profitable exactly where the buffer quantizes:
# harvest chunks of [C·S, n·d] rows at Gemma shapes, HBM-bandwidth-bound.

_INTERPRET = False


def set_interpret(flag: bool) -> None:
    """Interpreter mode for CPU parity tests (mirrors topk_pallas)."""
    global _INTERPRET
    _INTERPRET = flag


_ROW_BLK = 256          # int8 min tile sublane is 32; 256 keeps the VPU busy
_VMEM_BUDGET = 12 << 20


def rows_supported(n_rows: int, width: int, block: int) -> bool:
    """Gate for the Pallas rowwise quantize kernel."""
    if block % 128 or width % block:
        return False                      # lane alignment of the block split
    if n_rows % 32:
        return False                      # int8 min sublane tile
    rows = min(_ROW_BLK, n_rows)
    if n_rows % rows:
        return False                      # grid floors: a partial tail tile
                                          # would never be written
    # in f32 working copy + int8 out + f32 scales per tile
    if rows * width * (4 + 4 + 1) > _VMEM_BUDGET:
        return False
    return True


def _quantize_rows_kernel(x_ref, q_ref, s_ref, *, block: int):
    x = x_ref[...].astype(jnp.float32)                        # [R, W]
    rows, width = x.shape
    xb = x.reshape(rows, width // block, block)
    amax = jnp.max(jnp.abs(xb), axis=-1)                      # [R, nb]
    scale = amax / QMAX
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xb / safe[:, :, None]), -QMAX, QMAX)
    q_ref[...] = q.reshape(rows, width).astype(jnp.int8)
    s_ref[...] = scale


def _quantize_rows_pallas(x: jax.Array, block: int) -> tuple[jax.Array, jax.Array]:
    from jax.experimental import pallas as pl

    n_rows, width = x.shape
    rows_blk = min(_ROW_BLK, n_rows)
    grid = (n_rows // rows_blk,)
    return pl.pallas_call(
        functools.partial(_quantize_rows_kernel, block=block),
        grid=grid,
        in_specs=[pl.BlockSpec((rows_blk, width), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows_blk, width), lambda i: (i, 0)),
            pl.BlockSpec((rows_blk, width // block), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, width), jnp.int8),
            jax.ShapeDtypeStruct((n_rows, width // block), jnp.float32),
        ],
        interpret=_INTERPRET,
    )(x)


def quantize_rows(x: jax.Array, block: int) -> tuple[jax.Array, jax.Array]:
    """Quantize ``[..., d]`` rows, through the fused Pallas kernel when the
    backend and shape support it, else the XLA path. Semantically
    identical either way (the tests assert it in interpret mode).

    The TPU kernel dispatch is gated on ``CROSSCODER_QUANT_PALLAS=1``
    (conservative default: the kernel is interpret-verified and compiles
    for a v5e — tests/test_chip_compile.py — but has never been timed on
    one; flip the default once a real-TPU A/B lands — the XLA lowering is
    a correct two-pass fallback either way)."""
    from crosscoder_tpu.ops.dispatch import hw_kernel_enabled

    use_kernel = hw_kernel_enabled("CROSSCODER_QUANT_PALLAS", _INTERPRET)
    if use_kernel:
        lead = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
        if x.ndim >= 2 and rows_supported(lead, x.shape[-1], block):
            q, s = _quantize_rows_pallas(x.reshape(lead, x.shape[-1]), block)
            nb = x.shape[-1] // block
            return q.reshape(x.shape), s.reshape(*x.shape[:-1], nb)
    return quantize_blocks(x, block)


def store_bytes(shape: tuple[int, ...], block: int) -> int:
    """HBM/host bytes of a quantized store of this logical bf16 shape:
    int8 payload + f32 per-block scales (the budget-table helper)."""
    n = int(np.prod(shape))
    return n + 4 * (n // block)
