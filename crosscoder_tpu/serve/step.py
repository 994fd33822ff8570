"""Device half of the serving path: one encode→TopK→diff program.

The request loop's whole device program after prefill is this function:
gather each request's LAST valid-token activation from the captured hook
plane, normalize it the way training rows were normalized, encode through
the crosscoder (the dense encode, ReLU and ``lax.top_k``), and gather each
selected latent's decoder-norm model-diff score. Three ``[B, k]`` arrays
come back — vals, idx, diff — and nothing else ever leaves the device,
so the serve inner loop is latency-shaped by construction
(docs/SERVING.md).

The diff score is :func:`crosscoder_tpu.analysis.decoder.relative_norms`
— ``‖dec_j‖ / (‖dec_i‖ + ‖dec_j‖)`` per latent, the reference's headline
model-diffing statistic — evaluated at the served indices: ≈0 means the
latent belongs to model i only, ≈0.5 shared, ≈1 model j only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from crosscoder_tpu.utils.dtypes import dtype_of


@functools.partial(
    jax.jit, static_argnames=("enc_dtype", "k", "pair")
)
@jax.named_scope("serve/encode_topk_diff")
def encode_topk_diff(
    params, captures, lengths, norm, *, enc_dtype: str, k: int,
    pair: tuple[int, int],
):
    """``(vals [B,k], idx [B,k] i32, diff [B,k])`` from captured hooks.

    - ``captures [B, S, n_sources, d_in]``: the paged/padded harvest
      output (pad positions irrelevant — only ``lengths-1`` is gathered);
    - ``lengths [B] i32``: valid token count per request;
    - ``norm [n_sources] f32``: per-source calibration factors (the
      replay buffer's ``sqrt(d_in)/mean_token_norm``; ones when the
      crosscoder was trained unnormalized).

    Row-local throughout: every per-request output depends only on that
    request's row, which is what makes bucket padding invisible and the
    served results bitwise-equal to a solo-request oracle
    (tests/test_serve.py).
    """
    from crosscoder_tpu.analysis import decoder
    from crosscoder_tpu.models import crosscoder

    last = (lengths - 1).astype(jnp.int32)
    x = jnp.take_along_axis(
        captures, last[:, None, None, None], axis=1
    )[:, 0]                                           # [B, n_sources, d_in]
    x = (x.astype(jnp.float32) * norm[:, None]).astype(dtype_of(enc_dtype))
    hp = jax.nn.relu(crosscoder.pre_acts(params, x))
    vals, idx = jax.lax.top_k(hp, k)
    idx = idx.astype(jnp.int32)
    r = decoder.relative_norms(params, pair)          # [d_hidden]
    diff = jnp.take(r, idx, axis=0)                   # [B, k]
    return vals, idx, diff


def diff_pair(n_sources: int, n_models: int) -> tuple[int, int]:
    """The source pair the diff score compares: model 0 vs model 1 at the
    first hooked layer under the model-major source ordering (source
    ``m * n_hooks + h``). Degenerates to ``(0, 0)`` for single-source
    configs (diff is then identically 0.5 — documented, not an error)."""
    n_hooks = max(1, n_sources // max(1, n_models))
    j = n_hooks if n_sources > n_hooks else 0
    return (0, j)

