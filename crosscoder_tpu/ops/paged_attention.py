"""Ragged paged attention: per-document attention over fixed-size KV pages.

The compute half of the paged harvest runtime (arXiv:2604.15464's Ragged
Paged Attention shape): queries and K/V arrive padded per document
``[D, S, ...]`` with ragged ``lengths``, K/V are viewed as a pool of
``page_size``-token pages addressed through a page table, and attention for
document ``d`` touches only its own ``ceil(len_d/page)`` pages — FLOPs and
KV reads proportional to real tokens squared, not ``S``\\ ².

Two implementations, one dispatch (the ops/quant.py discipline):

- **pure XLA** (:func:`ragged_attention_reference`): padded masked-softmax
  attention with the ragged length mask — jittable anywhere, the CPU
  fallback and the oracle the kernels are pinned against. It is the XLA
  form of the padded LM attention too (``models/lm._attn_core`` calls it
  wherever the fused dense kernel of :mod:`crosscoder_tpu.ops.flash_attention`
  may not dispatch), so on one backend the paged harvest's XLA path is
  bit-identical to the padded XLA forward at valid positions (the CPU
  parity gate); its attention cost is the padded cost — the paged runtime's
  XLA win comes from the packed-plane projections/MLP, ~93% of harvest FLOPs
  at Gemma-2-2B shapes.
- **Pallas TPU kernel** (:func:`_rpa_kernel`): grid ``(docs, kv_heads)``;
  the document's query block sits in VMEM, KV pages are DMA'd from the
  pool one page at a time driven by the scalar-prefetched page table, and
  an online-softmax (flash) accumulator folds each page in — the page
  loop is bounded by ``ceil(len_d/page)``, so short documents cost short
  loops. Online softmax reassociates the reduction, so kernel-vs-oracle
  parity is allclose (~1e-5 fp32), not bitwise — interpret-mode tests pin
  it (tests/test_paged_attention.py).

Hardware dispatch is gated on ``CROSSCODER_PAGED_ATTN_PALLAS=1``
(conservative default: interpret-verified, compiles for a v5e at
Gemma-2-2B heads — tests/test_chip_compile.py — never timed on one; the
page-table structure, not the constant, is the load-bearing part).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from crosscoder_tpu.ops.dispatch import hw_kernel_enabled

# THE attention mask fill: every dense/paged/kernel attention path (this
# module's, ops/flash_attention's) masks with this one constant
NEG_INF = -2.3819763e38

DISPATCH_ENV = "CROSSCODER_PAGED_ATTN_PALLAS"

# VMEM budget shared with the other kernel modules (see ops/topk_pallas).
_VMEM_BUDGET_BYTES = 13 << 20

# test-only: route the kernel through the Pallas interpreter so the paged
# model path can run on CPU CI (same pattern as topk_pallas / sparse_grad).
# Read at TRACE time.
_INTERPRET = False


def set_interpret(flag: bool) -> None:
    global _INTERPRET
    _INTERPRET = flag


def kernel_enabled(interpret: bool | None = None) -> bool:
    """Whether the Pallas kernel may dispatch (interpret mode, or a real
    TPU backend with the opt-in env var)."""
    return hw_kernel_enabled(
        DISPATCH_ENV, _INTERPRET if interpret is None else interpret
    )


def _softcap(x: jax.Array, cap: float) -> jax.Array:
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# pure-XLA reference (fallback + oracle)


def ragged_attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array | None,
    *,
    scale: float,
    softcap: float = 0.0,
    window: int = 0,
    is_local=False,
) -> jax.Array:
    """Masked-softmax attention over (per-document) padded buffers — the
    XLA form of the attention mathematics: the paged XLA path, the padded
    forward wherever ``models/lm._attn_core`` does not pick the fused
    kernel, and the oracle both kernels are pinned against, so those can
    never drift apart numerically.

    ``q [B, S, H, hd]`` (unscaled), ``k``/``v [B, S, KV, hd]``.
    ``lengths [B]`` adds the ragged key-side validity mask (None = the
    padded forward, no per-row mask; for valid queries causal ⊆ in-length,
    so the term is a no-op there — bit-identical outputs). ``window``:
    sliding-window width; ``is_local`` (may be traced) selects it,
    matching the padded forward's alternating-layer dispatch. Returns
    ``[B, S, H·hd]`` (pre output-projection). Rows at ``t >= lengths[b]``
    are computed but meaningless — callers discard them.
    """
    B, S, H, hd = q.shape
    KV = k.shape[2]
    # GQA: fold the group axis into the query head axis instead of
    # repeating K/V (XLA contracts over the shared kv head axis)
    g = H // KV
    pos = jnp.arange(S)
    qh = q.reshape(B, S, KV, g, hd) * scale
    logits = jnp.einsum(
        "bqkgh,bskh->bkgqs", qh, k, preferred_element_type=jnp.float32
    )
    if softcap:
        logits = _softcap(logits, softcap)
    causal = pos[:, None] >= pos[None, :]                              # [S, S]
    win = pos[:, None] - pos[None, :] < window if window else causal
    mask = jnp.where(is_local, causal & win, causal)
    if lengths is None:
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    else:
        in_len = pos[None, None, :] < lengths[:, None, None]           # [B,1,S]
        maskb = mask[None] & in_len                                    # [B,S,S]
        logits = jnp.where(maskb[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum(
        "bkgqs,bskh->bqkgh", probs, v, preferred_element_type=jnp.float32
    )
    return out.astype(v.dtype).reshape(B, S, H * hd)


# ---------------------------------------------------------------------------
# paging helpers


def paginate_kv(
    k: jax.Array, v: jax.Array, page_size: int
) -> tuple[jax.Array, jax.Array]:
    """View per-document padded K/V ``[D, S, KV, hd]`` as a page pool.

    Returns ``(kv_pages [P, 2, KV, page, hd], page_tbl [D, S//page])``
    with the dense identity table ``page_tbl[d, j] = d*(S//page) + j`` —
    the single-shot harvest's trivial allocation. A serving plane reuses
    the same kernel with a :class:`crosscoder_tpu.data.paging.PageTable`-
    built table over a long-lived pool; the kernel sees no difference.
    """
    D, S, KV, hd = k.shape
    if S % page_size:
        raise ValueError(f"seq_len {S} not divisible by page_size {page_size}")
    n_pages = S // page_size
    kp = k.reshape(D * n_pages, page_size, KV, hd).transpose(0, 2, 1, 3)
    vp = v.reshape(D * n_pages, page_size, KV, hd).transpose(0, 2, 1, 3)
    kv_pages = jnp.stack([kp, vp], axis=1)       # [P, 2, KV, page, hd]
    page_tbl = (
        jnp.arange(D, dtype=jnp.int32)[:, None] * n_pages
        + jnp.arange(n_pages, dtype=jnp.int32)[None]
    )
    return kv_pages, page_tbl


def supported(
    n_docs: int, seq_len: int, n_heads: int, n_kv_heads: int, head_dim: int,
    page_size: int,
) -> bool:
    """Shapes the kernel handles within the shared VMEM budget."""
    if page_size < 1 or page_size & (page_size - 1):
        return False
    if seq_len % page_size or n_heads % n_kv_heads:
        return False
    g = n_heads // n_kv_heads
    fp = 4  # f32 accumulation
    q_b = g * seq_len * head_dim * fp
    acc_b = g * seq_len * head_dim * fp
    ml_b = 2 * g * seq_len * fp
    page_b = 2 * page_size * head_dim * fp
    logit_b = g * seq_len * page_size * fp
    return q_b + acc_b + ml_b + page_b + logit_b <= _VMEM_BUDGET_BYTES


# ---------------------------------------------------------------------------
# Pallas kernel


def _rpa_kernel(
    page_tbl_ref,      # scalar-prefetch [D, max_pages] int32
    len_ref,           # scalar-prefetch [D] int32
    q_ref,             # [1, 1, g, S, hd] VMEM (this doc, this kv head)
    kv_ref,            # [P, 2, KV, page, hd] ANY (the page pool)
    out_ref,           # [1, 1, g, S, hd] VMEM
    k_buf,             # VMEM scratch [page, hd]
    v_buf,             # VMEM scratch [page, hd]
    sem,               # DMA semaphore
    *,
    page: int,
    scale: float,
    softcap: float,
    window: int,
):
    d = pl.program_id(0)
    kvh = pl.program_id(1)
    L = len_ref[d]
    n_pages_d = (L + page - 1) // page
    q = q_ref[0, 0].astype(jnp.float32) * scale            # [g, S, hd]
    g, S, hd = q.shape
    qp = jax.lax.broadcasted_iota(jnp.int32, (S, page), 0)

    def body(j, carry):
        m, l, acc = carry
        pid = page_tbl_ref[d, j]
        cp = pltpu.make_async_copy(kv_ref.at[pid, 0, kvh], k_buf, sem)
        cp.start()
        cp.wait()
        cp = pltpu.make_async_copy(kv_ref.at[pid, 1, kvh], v_buf, sem)
        cp.start()
        cp.wait()
        kblk = k_buf[:].astype(jnp.float32)                # [page, hd]
        logits = jax.lax.dot_general(
            q, kblk, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [g, S, page]
        if softcap:
            logits = _softcap(logits, softcap)
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (S, page), 1)
        mask = (kpos <= qp) & (kpos < L)
        if window:
            mask &= qp - kpos < window
        logits = jnp.where(mask[None], logits, NEG_INF)
        # online softmax: fold this page into the running (max, denom, acc)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        alpha = jnp.exp(m - m_new)
        # mask p explicitly: for a fully-masked page (local layers, rows
        # whose window lies in later pages) exp(NEG - NEG) would be 1
        p = jnp.where(mask[None], jnp.exp(logits - m_new[..., None]), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p, v_buf[:].astype(jnp.float32), (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [g, S, hd]
        acc = acc * alpha[..., None] + pv
        return m_new, l, acc

    m0 = jnp.full((g, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g, S), jnp.float32)
    acc0 = jnp.zeros((g, S, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_pages_d, body, (m0, l0, acc0))
    out = jnp.where(
        l[..., None] > 0, acc / jnp.maximum(l, 1e-30)[..., None], 0.0
    )
    out_ref[0, 0] = out.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "softcap", "window", "interpret"),
)
def _rpa_call(
    q5: jax.Array,            # [D, KV, g, S, hd]
    kv_pages: jax.Array,      # [P, 2, KV, page, hd]
    page_tbl: jax.Array,      # [D, max_pages] int32
    lengths: jax.Array,       # [D] int32
    page_size: int,
    scale: float,
    softcap: float,
    window: int,
    interpret: bool,
):
    D, KV, g, S, hd = q5.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(D, KV),
        in_specs=[
            pl.BlockSpec(
                # index_map also receives the scalar-prefetch refs
                (1, 1, g, S, hd), lambda d, kv, *_: (d, kv, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, S, hd), lambda d, kv, *_: (d, kv, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            # page buffers stay in the pool's dtype — the per-page DMA
            # moves input-precision bytes; the f32 upcast happens on the
            # VMEM reads inside the kernel
            pltpu.VMEM((page_size, hd), kv_pages.dtype),
            pltpu.VMEM((page_size, hd), kv_pages.dtype),
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = functools.partial(
        _rpa_kernel, page=page_size, scale=scale, softcap=softcap,
        window=window,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q5.shape, q5.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(page_tbl, lengths, q5, kv_pages)


def paged_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lengths: jax.Array,
    *,
    page_size: int,
    scale: float,
    softcap: float = 0.0,
    window: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Ragged attention through the page-table kernel when it may dispatch,
    the XLA reference otherwise. Same contract as
    :func:`ragged_attention_reference` with a STATIC ``is_local`` (the
    kernel bakes the mask; the LM's traced ``is_local`` selects between
    two instances via ``lax.cond``): ``window=0`` means global/causal.
    Returns ``[D, S, H*hd]``.
    """
    D, S, H, hd = q.shape
    KV = k.shape[2]
    inter = _INTERPRET if interpret is None else interpret
    if not (
        kernel_enabled(inter)
        and supported(D, S, H, KV, hd, page_size)
    ):
        return ragged_attention_reference(
            q, k, v, lengths, scale=scale, softcap=softcap,
            window=window, is_local=bool(window),
        )
    g = H // KV
    kv_pages, page_tbl = paginate_kv(k, v, page_size)
    q5 = q.reshape(D, S, KV, g, hd).transpose(0, 2, 3, 1, 4)
    out5 = _rpa_call(
        q5, kv_pages, page_tbl, lengths.astype(jnp.int32),
        page_size, scale, softcap, window, inter,
    )
    return out5.transpose(0, 3, 1, 2, 4).reshape(D, S, H * hd)
