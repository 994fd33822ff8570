"""The crosscoder's forward, loss and TopK selection in plain float32.

Follows the reference implementation's equations (crosscoder.py:69-130 of
the replicated repo): pre-activations ``sum_n x[b,n,:] @ W_enc[n] + b_enc``,
ReLU (or the k largest ReLU'd pre-activations per row), reconstruction
``f @ W_dec + b_dec``, ``l2`` = mean over rows of the summed squared error,
``l1`` = mean over rows of ``sum_f f * sum_n ||W_dec[f,n]||``, ``l0`` = mean
count of positive latents. No kernels, no mixed precision, no sharding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def losses(params: dict, x: jax.Array, k: int | None = None) -> dict:
    """x [B, n, d] (already scaled by the norm factors); k=None is ReLU."""
    with jax.default_matmul_precision("highest"):
        p = {n: jnp.asarray(v, jnp.float32) for n, v in params.items()}
        x = jnp.asarray(x, jnp.float32)
        h = jnp.einsum("bnd,ndh->bh", x, p["W_enc"]) + p["b_enc"]
        f = jnp.maximum(h, 0.0)
        idx = None
        if k is not None:
            vals, idx = jax.lax.top_k(f, k)
            rows = jnp.arange(f.shape[0])[:, None]
            f = jnp.zeros_like(f).at[rows, idx].set(vals)
        recon = jnp.einsum("bh,hnd->bnd", f, p["W_dec"]) + p["b_dec"]
        l2 = jnp.mean(jnp.sum((recon - x) ** 2, axis=(1, 2)))
        dec_norms = jnp.sum(jnp.linalg.norm(p["W_dec"], axis=-1), axis=-1)
        l1 = jnp.mean(jnp.sum(f * dec_norms[None, :], axis=-1))
        l0 = jnp.mean(jnp.sum(f > 0, axis=-1).astype(jnp.float32))
        return {"l2": l2, "l1": l1, "l0": l0, "pre_acts": h, "topk_idx": idx}
