"""CE-recovered splicing eval — the repo's end-to-end fidelity metric.

Reproduces ``get_ce_recovered_metrics`` from the reference notebook
(nb:cell 29), the only quality metric with published numbers (SURVEY.md §6:
CE recovered ≈ 0.922 base / 0.926 IT on the published checkpoint):

per model m ∈ {A, B}:
  - ``ce_clean``:   CE of the untouched forward
  - ``ce_zero_abl``: CE with the hook activation zeroed (``zero_ablation_hook``)
  - ``ce_spliced``: CE with post-BOS hook activations replaced by the
    crosscoder reconstruction of BOTH models' streams (``splice_act_hook``
    keeps the BOS position clean)
  - ``ce_recovered = 1 − (spliced − clean) / (zero_abl − clean)``

The crosscoder must be **folded** first (``fold_scaling_factors``,
nb:cell 27) so it consumes raw — not norm-calibrated — activations.

TPU shape of the computation: ONE jitted program per chunk computes every
model's clean/zero-ablated/spliced CE and the crosscoder reconstruction,
returning a single ``[n_models, 3]`` array — one small fetch per chunk
instead of the reference's separate forwards with a host sync each
(nb:cell 29 runs ≥6 blocking round trips per chunk). Chunks are pipelined so the device computes chunk
k+1 while the host fetches chunk k's scalars. Reconstructor parameters
enter the program as ARGUMENTS, not closure constants (a closure would
bake the crosscoder weights into the compiled program — the jit-constant
trap fixed in dashboards).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.models import crosscoder as cc
from crosscoder_tpu.models import lm
from crosscoder_tpu.utils import pipeline
from crosscoder_tpu.utils.logging import source_tag


class Reconstructor(NamedTuple):
    """A reconstruction map ``apply(params, rows) -> rows`` plus its params.

    Splitting params from the function keeps large weights out of the jitted
    program's constants; ``params`` may be ``None`` for parameter-free
    oracles (identity, zero), which the tests use.
    """

    params: object
    apply: Callable[[object, jnp.ndarray], jnp.ndarray]


def crosscoder_reconstruct_fn(
    params: cc.Params, cfg: CrossCoderConfig
) -> Reconstructor:
    """rows ``[N, n_sources, d_in]`` → reconstructed rows, via the (folded)
    crosscoder (nb:cell 29: ``cc.decode(cc.encode(x))``). The apply function
    comes from :func:`crosscoder_tpu.models.crosscoder.cached_apply`, so
    repeated evals with the same config reuse one compiled program."""
    return Reconstructor(params=params, apply=cc.cached_apply(cfg, "forward"))


# wrapper identity per callable: without this, every eval call would mint
# a fresh lambda → fresh trace of _chunk_ces (apply is a static jit arg)
# and the jit cache would retain each stale executable — the exact trap
# the module docstring warns about, one layer up (ADVICE round-2)
_WRAPPER_CACHE: dict[int, tuple[Any, Reconstructor]] = {}


def _as_reconstructor(reconstruct) -> Reconstructor:
    if isinstance(reconstruct, Reconstructor):
        return reconstruct
    # bare callable: oracle tests and quick experiments. NB anything such a
    # callable closes over IS baked into the compiled program as constants —
    # real crosscoders must come through crosscoder_reconstruct_fn (params
    # as jit arguments, cached apply identity).
    cached = _WRAPPER_CACHE.get(id(reconstruct))
    # the keyed object must still be alive (ids recycle): keep a strong ref
    if cached is not None and cached[0] is reconstruct:
        return cached[1]
    rec = Reconstructor(params=None, apply=lambda _, rows: reconstruct(rows))
    if len(_WRAPPER_CACHE) > 32:
        _WRAPPER_CACHE.pop(next(iter(_WRAPPER_CACHE)))
    _WRAPPER_CACHE[id(reconstruct)] = (reconstruct, rec)
    return rec


@functools.partial(jax.jit, static_argnames=("lm_cfg", "hook_point", "apply"))
def _chunk_ces(
    mparams: tuple,
    rec_params,
    tok: jax.Array,
    lm_cfg: lm.LMConfig,
    hook_point: str,
    apply: Callable,
) -> jax.Array:
    """All CE numbers for one token chunk: ``[n_models, 3]`` with columns
    (clean, zero_abl, spliced). One device program; no host syncs inside."""
    n_models = len(mparams)
    clean, caches = [], []
    # one forward per model yields BOTH the clean logits and the hook
    # capture (the reference runs them separately, nb:cell 29)
    for p in mparams:
        logits, cache = lm.forward(p, tok, lm_cfg, capture=[hook_point])
        clean.append(lm.loss_fn(logits, tok))
        caches.append(cache[hook_point])
    acts = jnp.stack(caches, axis=2)[:, 1:]                # [B, S-1, n, d]
    B, Sm1 = acts.shape[0], acts.shape[1]
    rows = acts.reshape(-1, n_models, lm_cfg.d_model).astype(jnp.float32)
    recon = apply(rec_params, rows).reshape(B, Sm1, n_models, lm_cfg.d_model)

    per_model = []
    for m, p in enumerate(mparams):
        # splice_edit keeps BOS clean; pad recon back to S positions
        spliced_act = jnp.concatenate(
            [jnp.zeros_like(recon[:, :1, m]), recon[:, :, m]], axis=1
        )
        zero = lm.ce_loss(p, tok, lm_cfg, edits=[lm.Edit(hook_point, lm.zero_edit)])
        spliced = lm.ce_loss(
            p, tok, lm_cfg,
            edits=[lm.Edit(hook_point, lm.splice_edit, spliced_act)],
        )
        per_model.append(jnp.stack([clean[m], zero, spliced]))
    return jnp.stack(per_model)


def get_ce_recovered_metrics(
    tokens: np.ndarray,
    lm_cfg: lm.LMConfig,
    model_params: Sequence[lm.LMParams],
    hook_point: str,
    reconstruct,
    chunk: int = 4,
) -> dict[str, float]:
    """CE clean / zero-ablation / spliced / recovered, per model.

    ``reconstruct`` is a :class:`Reconstructor` (see
    :func:`crosscoder_reconstruct_fn`) or a bare callable mapping flattened
    post-BOS rows ``[N, n_models, d_in]`` to reconstructions; injecting it
    keeps the eval testable against exact oracles (identity ⇒ recovered=1,
    zero ⇒ recovered=0) independent of any trained crosscoder.
    """
    rec = _as_reconstructor(reconstruct)
    n_models = len(model_params)
    tokens = np.asarray(tokens)
    if tokens.shape[0] < 1:
        raise ValueError("need at least one token sequence")
    mparams = tuple(model_params)

    # seq-weighted accumulation over chunks; device results fetched with lag
    sums = np.zeros((n_models, 3), np.float64)
    total_seqs = 0

    def produced():
        for start in range(0, tokens.shape[0], chunk):
            tok = jnp.asarray(tokens[start: start + chunk])  # ragged tail kept
            yield tok.shape[0], _chunk_ces(
                mparams, rec.params, tok, lm_cfg, hook_point, rec.apply
            )

    def drain(item) -> None:
        nonlocal sums, total_seqs
        b, ces = item
        sums += b * np.asarray(jax.device_get(ces), np.float64)
        total_seqs += b

    pipeline.drive(produced(), drain)

    out: dict[str, float] = {}
    for m in range(n_models):
        tag = source_tag(m)
        clean, zero, spliced = (sums[m] / total_seqs).tolist()
        out[f"ce_clean_{tag}"] = clean
        out[f"ce_zero_abl_{tag}"] = zero
        out[f"ce_spliced_{tag}"] = spliced
        out[f"ce_diff_{tag}"] = spliced - clean
        out[f"ce_recovered_{tag}"] = 1.0 - (spliced - clean) / (zero - clean)
    return out
