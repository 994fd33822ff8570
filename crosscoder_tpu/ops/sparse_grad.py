"""Sparse backward compute plane: scatter-accumulate row gradients.

The wide-dictionary TopK step is BACKWARD-bound: three of its large matmuls
(``dW_dec`` [B,H]x[B,nd], ``df`` [B,nd]x[H,nd], ``dW_enc`` [B,nd]x[B,H])
multiply ~99.9% structural zeros. With at most ``k`` active latents per
example every one of those gradients is the SAME primitive —

    out[dst[p]] += coeff[p] * rows[src[p]]        (P = B·k pairs)

an O(B·k·n·d) scatter-accumulate instead of an O(B·H·n·d) matmul
(Densifying Assumed-sparse Tensors, arXiv:1905.04035: accumulation
layout, not FLOPs, decides this shape of gradient).

On a chip that primitive is ``ops/row_gather.grouped_sums``: the pairs
sorted by destination, the rows fetched by DMA, the sums formed on the MXU
(PR 32; the sorted-pair scatter kernel that lived here was refused by the
chip's compiler — dynamic scalar reads of its pair list from VMEM, then 1.50
M of 1.00 M SMEM once scalar-prefetched — and never ran on one). What stays
here is the **pure XLA** form, one flattened ``zeros.at[idx].add``: jittable
anywhere, the oracle the kernel is pinned against, the form a forced
``sparse_bwd="on"`` takes where the row kernels are not live, and the AuxK
backward's (``models/crosscoder._sparse_aux_product``). On a TPU it is the
measured 42-76 ms scatter the dense matmuls beat, so ``sparse_bwd="auto"``
never routes a step here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Pair cap: the scatter materializes a [P, m] float32 update matrix (2^18
# pairs of 4608 columns: 4.8 GB). B·k at bench shapes is 131072; AuxK at
# aux_k=256 (1M pairs) exceeds this — the model layer's aux gate checks it.
_MAX_PAIRS = 1 << 18


def supported(n_out: int, m: int, n_pairs: int) -> bool:
    """Shapes the sparse backward takes: a lane-aligned feature axis, at
    least one row tile of outputs, and a pair list under the cap."""
    return m >= 128 and m % 128 == 0 and n_out >= 8 and 1 <= n_pairs <= _MAX_PAIRS


def scatter_add_rows(coeff: jax.Array, idx: jax.Array, rows: jax.Array,
                     n_out: int) -> jax.Array:
    """``out[n_out, m] f32`` with ``out[idx[b,j]] += coeff[b,j]·rows[b]``.

    ``coeff/idx: [B, k]``, ``rows: [B, m]`` (any float dtype; accumulation
    is f32). Out-of-range indices are dropped (scatter ``mode="drop"``
    semantics). One flattened scatter-add: it materializes the [P, m]
    update matrix.
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
    B, k = coeff.shape
    updates = (coeff.astype(jnp.float32)[:, :, None]
               * rows.astype(jnp.float32)[:, None, :]).reshape(B * k, -1)
    out = jnp.zeros((n_out, rows.shape[-1]), jnp.float32)
    # negative indices would WRAP under .at[] (numpy semantics): route them
    # to the drop sentinel
    flat = idx.reshape(-1)
    flat = jnp.where((flat >= 0) & (flat < n_out), flat, n_out)
    return out.at[flat].add(updates, mode="drop")
