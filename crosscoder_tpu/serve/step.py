"""Device half of the serving path: one fused encode→TopK→diff step.

The request loop's whole device program after prefill is this function:
gather each request's LAST valid-token activation from the captured hook
plane, normalize it the way training rows were normalized, encode through
the crosscoder (the fused encoder→TopK megakernel when live — no
``[B, dict]`` pre-act matrix, pinned by the ``hlo-serve-no-dense-preacts``
contract — else the dense encode + ``lax.top_k``), and gather each
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
    jax.jit, static_argnames=("enc_dtype", "k", "fused", "pair")
)
@jax.named_scope("serve/encode_topk_diff")
def encode_topk_diff(
    params, captures, lengths, norm, *, enc_dtype: str, k: int,
    fused: bool, pair: tuple[int, int],
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

    B = captures.shape[0]
    last = (lengths - 1).astype(jnp.int32)
    x = jnp.take_along_axis(
        captures, last[:, None, None, None], axis=1
    )[:, 0]                                           # [B, n_sources, d_in]
    x = (x.astype(jnp.float32) * norm[:, None]).astype(dtype_of(enc_dtype))
    if fused:
        from crosscoder_tpu.ops import fused_encoder_topk as fek

        vals, idx = fek.fused_topk_encode(
            x.reshape(B, -1),
            params["W_enc"].reshape(-1, params["W_enc"].shape[-1]),
            params["b_enc"], k,
        )
    else:
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


def lower_encode_text(cfg, batch: int | None = None, seq_len: int = 8) -> str:
    """StableHLO text of the serve encode step for the contracts plane
    (``hlo-serve-no-dense-preacts``): lowered abstractly from shape
    structs, fused dispatch resolved exactly as the engine resolves it."""
    from crosscoder_tpu.models import crosscoder

    B = cfg.batch_size if batch is None else batch
    n = cfg.n_sources
    dt = dtype_of(cfg.enc_dtype)
    params = jax.eval_shape(
        lambda key: crosscoder.init_params(key, cfg), jax.random.key(0)
    )
    captures = jax.ShapeDtypeStruct((B, seq_len, n, cfg.d_in), dt)
    lengths = jax.ShapeDtypeStruct((B,), jnp.int32)
    norm = jax.ShapeDtypeStruct((n,), jnp.float32)
    fused = crosscoder.use_fused_encoder(cfg, B)
    lowered = encode_topk_diff.lower(
        params, captures, lengths, norm, enc_dtype=cfg.enc_dtype,
        k=cfg.topk_k, fused=fused, pair=diff_pair(n, cfg.n_models),
    )
    return lowered.as_text()
