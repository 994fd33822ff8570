"""The state-space mixer's three parts (Mamba-2, arXiv:2405.21060): the
causal depthwise convolution, the chunked ("state-space duality") scan and
the gated, grouped RMSNorm. :func:`crosscoder_tpu.models.lm._ssm` runs them
between the mixer's two projections; their scopes are its children
(``harvest/block/ssm/conv|scan|gate_norm``).

The recurrence the scan computes, a head ``h`` of ``H`` (``P`` wide) over a
state of ``N`` a head, ``B`` and ``C`` shared by the ``H / G`` heads of a
group (head ``h`` reads group ``h // (H / G)``: HF's ``repeat_interleave``)::

    state_t = exp(dt_t A_h) state_{t-1} + dt_t x_t ⊗ B_t      # [P, N]
    y_t     = state_t C_t + D_h x_t

Chunked, ``Q`` tokens a chunk: inside a chunk the outputs are a masked
product ``(L ∘ C Bᵀ)(x dt)`` with ``L[l, s] = exp(Σ_{s<k≤l} dt_k A)``; each
chunk's contribution to the state at its end is one product; ``S / Q``
steps of a recurrence (a ``lax.scan``) carry the state from chunk to chunk;
the state entering a chunk reaches its outputs through ``C`` and the decay
from the chunk's start. Products take the inputs' dtype (bf16 on the chip)
with float32 accumulation; decays, cumulative sums and the carried state
are float32.

Two forms of the scan, chosen by :func:`kernel_takes` when the program is
traced (in :func:`ssd_scan`, and in the mixer for the conv with the scan),
from what the code can observe (as ``ops/mhc`` and ``ops/flash_attention``
choose theirs):

- the kernel (:func:`kernel_takes`: :func:`enabled`, a :func:`supported`
  shape, no ``resets``): ``pallas:ssd_scan`` walks the chunks as its
  sequential grid axis, a group of heads a grid point, and carries each
  head's ``[P, N]`` state from chunk to chunk in a float32 VMEM scratch. A
  chunk's x, B, C and dt are read once and its y written once; no
  ``[chunks, H, P, N]`` state and no ``[Q, Q]`` decay reaches HBM (XLA's
  form writes ≈ 1 GB of them a layer-call at Falcon-H1's widths). In the
  mixer (:func:`conv_ssd_scan`) it also computes the causal conv: it reads
  x | B | C as the input projection leaves them, before the conv, and
  carries each band's previous chunk in VMEM for the taps that reach back
  across a chunk's start, so neither the conv's float32 copy of x | B | C
  nor its output reaches HBM;
- XLA's form (everything else: the CPU, a mesh, an unsupported shape, the
  paged runtime's document starts), :func:`causal_conv` then
  :func:`ssd_scan`, also the oracle the kernel is pinned against
  (tests/test_ssm_kernel.py).

The choice is counted once a trace: ``harvest/ssm_scan_kernel_traces`` /
``harvest/ssm_scan_xla_traces``, and ``harvest/ssm_conv_kernel_traces``
where the conv ran in the kernel. No environment gate, no config field.

``resets`` ([B, S] bool, True at a document's first token — the paged
runtime's packed plane) zeroes the state carried INTO that token: ``L`` is
masked across a start, a chunk's state does not pass one, and the conv's
taps do not read the previous document.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_LANES = 128
# what the kernel's blocks, its state scratch and its temporaries may take
# of VMEM (Falcon-H1's widths need ≈ 6.5 MiB, Nemotron-H's ≈ 2.0 MiB:
# ``_vmem_bytes``)
_VMEM_LIMIT_BYTES = 32 << 20

# test-only: route the kernel through the Pallas interpreter (and let it
# dispatch on the CPU backend) — same pattern as ops/mhc. Read at TRACE time.
_INTERPRET = False


def set_interpret(flag: bool) -> None:
    global _INTERPRET
    _INTERPRET = flag


def enabled() -> bool:
    """Whether the kernel may dispatch from this process: the interpreter
    (CPU tests), or a TPU backend with exactly one device (a ``pallas_call``
    is not partitioned by the SPMD partitioner)."""
    return _INTERPRET or (
        jax.default_backend() == "tpu" and jax.device_count() == 1
    )


def _segments(resets: jax.Array) -> jax.Array:
    """Each token's document number (the count of starts up to and
    including it): two tokens are in one document iff these are equal."""
    return jnp.cumsum(resets.astype(jnp.int32), axis=1)


def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                resets: jax.Array | None = None) -> jax.Array:
    """``silu(b + Σ_j w[j] · x[t − (K−1) + j])`` a channel, zeros before
    the first token: x ``[B, S, C]``, w ``[K, C]`` (tap ``K − 1`` reads the
    token itself), b ``[C]``; float32 inside, the result in x's dtype."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
    wf = w.astype(F32)
    seg = None if resets is None else jnp.pad(_segments(resets), ((0, 0), (K - 1, 0)))
    out = b.astype(F32) + wf[K - 1] * xp[:, K - 1:]
    for j in range(K - 1):
        tap = wf[j] * xp[:, j: j + S]
        if seg is not None:     # the token K-1-j back, if no start lies between
            tap = jnp.where((seg[:, j: j + S] == seg[:, K - 1:])[..., None], tap, 0.0)
        out = out + tap
    return jax.nn.silu(out).astype(x.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
             D: jax.Array, chunk: int, resets: jax.Array | None = None) -> jax.Array:
    """The recurrence above by chunks: x ``[Bt, S, H, P]``, dt ``[Bt, S, H]``
    (after the softplus), A ``[H]`` (negative), B and C ``[Bt, S, G, N]``, D
    ``[H]`` → y ``[Bt, S, H, P]`` float32. A length that is not a multiple
    of ``chunk`` is padded at the end (dt 0: no decay, no input) and cut.
    The kernel where it may run, else XLA's form (the module's docstring)."""
    from crosscoder_tpu import obs

    H, P = x.shape[2:]
    G, N = B.shape[2:]
    if kernel_takes(H, P, G, N, chunk, x.dtype, resets):
        obs.count("harvest/ssm_scan_kernel_traces")
        return _ssd_scan_kernel(x, dt, A, B, C, D, chunk)
    obs.count("harvest/ssm_scan_xla_traces")
    return _ssd_scan_xla(x, dt, A, B, C, D, chunk, resets)


def kernel_takes(H: int, P: int, G: int, N: int, chunk: int, dtype,
                 resets: jax.Array | None = None, taps: int = 0) -> bool:
    """Whether the scan (with a conv of ``taps`` taps in front, if any) runs
    as the kernel here: it may dispatch, the shape is :func:`supported`, and
    no document starts."""
    return resets is None and enabled() and supported(H, P, G, N, chunk, dtype, taps)


def conv_ssd_scan(v: jax.Array, w: jax.Array, b: jax.Array, dt: jax.Array, A: jax.Array,
                  D: jax.Array, groups: int, state: int, chunk: int) -> jax.Array:
    """:func:`causal_conv` then :func:`ssd_scan`, as the kernel alone (where
    :func:`kernel_takes`): v ``[Bt, S, ≥ 2·di + 2·G·N]`` is the mixer's input
    projection as it lies, z | x | B | C (| dt), x | B | C BEFORE the conv;
    w ``[K, di + 2·G·N]`` and b the conv's taps and bias, dt ``[Bt, S, H]``
    after the softplus, A and D ``[H]`` → y ``[Bt, S, H, P]`` float32. The
    roundings are those of XLA's form: the taps and the SiLU in float32,
    their result in v's dtype before the scan's products and ``D·x``."""
    from crosscoder_tpu import obs

    obs.count("harvest/ssm_scan_kernel_traces")
    obs.count("harvest/ssm_conv_kernel_traces")
    H = A.shape[0]
    di = w.shape[1] - 2 * groups * state
    # the conv's taps and bias a channel, channels on sublanes: [cd, K + 1]
    wb = jnp.concatenate([w.T, b[:, None]], axis=1).astype(F32)
    return _kernel_call(v, di, wb, dt, A, D, H, di // H, groups, state, chunk)


def _ssd_scan_xla(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
                  D: jax.Array, chunk: int, resets: jax.Array | None = None) -> jax.Array:
    Bt, S, H, P = x.shape
    G, N = B.shape[2:]
    R, Q = H // G, chunk
    pad = -S % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
        if resets is not None:
            resets = jnp.pad(resets, ((0, 0), (0, pad)))
    c = (S + pad) // Q
    cdt = x.dtype                                   # the products' input dtype
    xc = x.reshape(Bt, c, Q, G, R, P)
    Bc, Cc = B.reshape(Bt, c, Q, G, N), C.reshape(Bt, c, Q, G, N)
    dtc = dt.astype(F32).reshape(Bt, c, Q, G, R)
    acs = jnp.cumsum(dtc * A.astype(F32).reshape(G, R), axis=2)     # [Bt, c, Q, G, R]
    last = acs[:, :, -1:]
    xdt = xc.astype(F32) * dtc[..., None]

    causal = jnp.tril(jnp.ones((Q, Q), bool))
    into_end = into_chunk = None          # (no start: every token reaches both)
    if resets is not None:
        seg = _segments(resets).reshape(Bt, c, Q)
        same = causal & (seg[:, :, :, None] == seg[:, :, None, :])      # [Bt, c, l, s]
        before = seg[:, :, :1] - resets.reshape(Bt, c, Q)[:, :, :1]      # the document entering
        into_end = (seg[:, :, -1:] == seg)[..., None, None]             # s reaches the end
        into_chunk = (seg == before)[..., None, None]                   # the entering state reaches l
    else:
        same = causal[None, None]

    # 1. inside a chunk: (L ∘ C Bᵀ) (x dt), C Bᵀ once a group. L's exponents
    # are differences of the float32 cumulative sums (HF sums a masked copy
    # instead): at the cell's sizes a sum reaches ≈ -400, whose rounding
    # (≈ 3e-5) is far under the bf16 products' own
    cb = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc, preferred_element_type=F32)
    seg_sum = (jnp.moveaxis(acs, 2, -1)[..., :, None]
               - jnp.moveaxis(acs, 2, -1)[..., None, :])               # [Bt, c, G, R, l, s]
    L = jnp.exp(jnp.where(same[:, :, None, None], seg_sum, -jnp.inf))
    m = (L * cb[:, :, :, None]).astype(cdt)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", m, xdt.astype(cdt), preferred_element_type=F32)

    # 2. each chunk's state at its end
    to_end = jnp.exp(last - acs)
    if into_end is not None:
        to_end = jnp.where(into_end, to_end, 0.0)
    states = jnp.einsum("bcsgn,bcsgrp->bcgrpn", Bc, (xdt * to_end[..., None]).astype(cdt),
                        preferred_element_type=F32)

    # 3. chunk to chunk: the state ENTERING each chunk
    through = jnp.exp(last[:, :, 0])                                  # [Bt, c, G, R]
    if resets is not None:      # a start inside the chunk: nothing passes it
        through = jnp.where((seg[:, :, -1] == before[:, :, 0])[..., None, None], through, 0.0)

    def carry(h, inp):
        decay, s = inp
        return decay[..., None, None] * h + s, h

    _, entering = jax.lax.scan(
        carry, jnp.zeros((Bt, G, R, P, N), F32),
        (jnp.moveaxis(through, 1, 0), jnp.moveaxis(states, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                           # [Bt, c, G, R, P, N]

    # 4. the entering state's part of each output
    from_start = jnp.exp(acs)
    if into_chunk is not None:
        from_start = jnp.where(into_chunk, from_start, 0.0)
    y = y + jnp.einsum("bclgn,bcgrpn->bclgrp", Cc, entering.astype(cdt),
                       preferred_element_type=F32) * from_start[..., None]
    y = y + D.astype(F32).reshape(G, R)[:, :, None] * xc.astype(F32)
    return y.reshape(Bt, c * Q, H, P)[:, :S]


# ---------------------------------------------------------------------------
# the kernel


def _vmem_bytes(H: int, P: int, G: int, N: int, chunk: int, itemsize: int,
                taps: int = 0) -> int:
    """The kernel's VMEM at these sizes: its blocks double-buffered (x's and
    y's band of a group, B's and C's, dt), the carried states and the
    cumulative sums, and a head's temporaries (the ``[Q, Q]`` decays, the
    ``[P, Q]`` and ``[P, N]`` tiles, the sums a head a column); with a conv
    of ``taps`` taps, their blocks (a channel a row, its lanes padded), the
    taps and the bias across the lanes, the previous chunk of the group's
    bands and the conv's temporaries."""
    Q, R = chunk, H // G
    rows = -(-H // 8) * 8
    lanes = -(-H // _LANES) * _LANES
    blocks = R * P * Q * (itemsize + 4) + 2 * N * Q * itemsize + rows * Q * 4
    scratch = R * P * N * 4 + rows * Q * 4
    temps = 6 * Q * Q * 4 + 6 * P * Q * 4 + 3 * P * N * 4 + Q * lanes * 4
    if taps:
        band = R * P + 2 * N
        blocks += band * _LANES * 4
        scratch += (taps + 2) * band * Q * 4
        temps += 4 * N * Q * 4 + 4 * P * Q * 4
    return 2 * blocks + scratch + temps


def supported(H: int, P: int, G: int, N: int, chunk: int, dtype, taps: int = 0) -> bool:
    """Shapes the kernel handles: a head's band of x and y whole sublane
    tiles of the inputs' dtype (8 rows of float32, 16 of bf16: P 64 and P
    128 alike — a head is a band of rows, tokens are the lanes), states of
    whole lanes, a chunk of whole lane tiles (longer than the conv's reach
    back), B's and C's bands on whole multiples of ``N`` after x's, and the
    blocks, the state and the temporaries, those of a conv of ``taps`` taps
    included, within the VMEM limit."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)):
        return False
    sublanes = 32 // jnp.dtype(dtype).itemsize
    if P % sublanes or N % _LANES or chunk % _LANES or H % G or (H * P) % N or taps > chunk:
        return False
    return _vmem_bytes(H, P, G, N, chunk, jnp.dtype(dtype).itemsize, taps) <= _VMEM_LIMIT_BYTES


_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))          # a @ bᵀ
_TN = (((0,), (0,)), ((), ()))          # aᵀ @ b


def _cumsum(a: jax.Array) -> jax.Array:
    """Inclusive cumulative sum of ``a [K, Q]`` along its lanes, as one
    product with a triangle of ones at float32 precision: a default-precision
    product would round ``a`` to bf16, an error of order one in an exponent
    that reaches ≈ -400."""
    Q = a.shape[1]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    return jnp.dot(a, tri.astype(F32), precision=_HIGHEST, preferred_element_type=F32)


def _transpose(a: jax.Array) -> jax.Array:
    """``a [K, Q]`` → ``[Q, K]`` float32, exactly: a product with the
    identity at float32 precision (one nonzero term an entry)."""
    Q = a.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    return jax.lax.dot_general(eye.astype(F32), a, _NT, precision=_HIGHEST,
                               preferred_element_type=F32)


def _conv(raw: jax.Array, prev: jax.Array, w: list) -> jax.Array:
    """:func:`causal_conv` on a band with TOKENS ON LANES: raw ``[R, Q]``
    this chunk, prev ``[R, Q]`` the chunk before it (zeros before the
    first), w the K taps and the bias, each ``[R, Q]`` (a channel's value
    across the lanes), float32 → the SiLU of the taps' sum, float32, summed
    in :func:`causal_conv`'s order (the bias and the token's own tap, then
    taps 0 … K − 2)."""
    Q, K = raw.shape[1], len(w) - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, raw.shape, 1)
    out = w[K] + w[K - 1] * raw
    for j in range(K - 1):
        back = K - 1 - j
        # the token ``back`` lanes earlier: rotated in from this chunk, and at
        # the first ``back`` lanes from the previous chunk's last ones
        tap = pltpu.roll(jnp.where(lane >= Q - back, prev, raw), back, 1)
        out = out + w[j] * tap
    return jax.nn.silu(out)


def _scan_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, *refs, heads: int,
                 head_dim: int, conv: bool):
    """One chunk of one group's ``heads`` heads (grid ``(batch, group,
    chunk)``, the chunk axis sequential), TOKENS ON LANES: x's and y's band
    ``[R·P, Q]``, B and C ``[N, Q]``, dt ``[H, Q]``. With ``conv``, x, B and
    C arrive before the causal conv, which runs here first, each band's
    previous chunk carried in ``halo_ref`` for the taps that reach back
    across the chunk's start, its taps and bias across the lanes in
    ``wide_ref``. The intra-chunk product, the entering state's part and
    D's, then the state carried to the next chunk."""
    if conv:
        wx_ref, wb_ref, wc_ref, y_ref, state_ref, rows_ref, halo_ref, wide_ref = refs
    else:
        y_ref, state_ref, rows_ref = refs
    g, k = pl.program_id(1), pl.program_id(2)
    (RP, Q), N, P = x_ref.shape, b_ref.shape[0], head_dim
    cdt = x_ref.dtype

    @pl.when(k == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)
        if conv:        # the conv's causal zeros; its taps once a group, not once a chunk
            halo_ref[...] = jnp.zeros_like(halo_ref)
            for at, w_ref in ((0, wx_ref), (RP, wb_ref), (RP + N, wc_ref)):
                for j in range(wide_ref.shape[0]):
                    wide_ref[j, pl.ds(at, w_ref.shape[0]), :] = jnp.broadcast_to(
                        w_ref[:, j:j + 1], (w_ref.shape[0], Q))

    def convolved(raw, rows):
        """A band after the conv, in the inputs' dtype; its raw values stay
        for the next chunk's first taps."""
        raw = raw.astype(F32)
        out = _conv(raw, halo_ref[rows, :],
                    [wide_ref[j, rows, :] for j in range(wide_ref.shape[0])])
        halo_ref[rows, :] = raw
        return out.astype(cdt)

    rows_ref[...] = _cumsum(dt_ref[...] * a_ref[...])      # acs [H, Q], a head a row
    # and a head a COLUMN, the same numbers: L's exponents are exact
    # differences, 0 on its diagonal, as in XLA's form
    cols = _transpose(rows_ref[...])                        # [Q, H]
    if conv:
        Bc = convolved(b_ref[...], pl.ds(RP, N))
        Cc = convolved(c_ref[...], pl.ds(RP + N, N))
    else:
        Bc, Cc = b_ref[...], c_ref[...]                     # [N, Q]
    cb_t = jax.lax.dot_general(Bc, Cc, _TN, preferred_element_type=F32)    # (C Bᵀ)ᵀ [s, l]
    causal_t = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
                <= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1

    def head(h, carry):
        hg = g * heads + h                                  # the head among all H
        row = rows_ref[pl.ds(hg, 1), :]                     # acs [1, Q]: token l
        col = jnp.sum(jnp.where(lane == hg, cols, 0.0), axis=1, keepdims=True)  # [Q, 1]: s
        # [1, 1]: the chunk's whole decay (a reduction, whose layout broadcasts)
        last = jnp.sum(jnp.where(at_end, row, 0.0), axis=1, keepdims=True)
        band = pl.ds(pl.multiple_of(h * P, P), P)
        xb = x_ref[band, :]
        if conv:
            xb = convolved(xb, band)
        xf = xb.astype(F32)                                 # [P, Q]
        xdt = xf * dt_ref[pl.ds(hg, 1), :]
        l_t = jnp.exp(jnp.where(causal_t, row - col, -jnp.inf))           # L [l, s], transposed
        y = jnp.dot(xdt.astype(cdt), (l_t * cb_t).astype(cdt), preferred_element_type=F32)
        state = state_ref[h]                                # [P, N], entering this chunk
        y = y + jnp.dot(state.astype(cdt), Cc, preferred_element_type=F32) * jnp.exp(row)
        y_ref[band, :] = y + d_ref[hg] * xf
        into = jax.lax.dot_general((xdt * jnp.exp(last - row)).astype(cdt), Bc, _NT,
                                   preferred_element_type=F32)
        state_ref[h] = jnp.exp(last) * state + into
        return carry

    # traced once, lowered once a head: the scheduler then overlaps one head's
    # products with the next one's decays (0.37 → 0.22 ms a call at the
    # cell's shape on a v5e: PERF.md §6)
    jax.lax.fori_loop(0, heads, head, 0, unroll=True)


def _ssd_scan_kernel(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
                     D: jax.Array, chunk: int) -> jax.Array:
    """The scan alone as the kernel, on x, B and C after the conv."""
    Bt, S, H, P = x.shape
    G, N = B.shape[2:]
    # x | B | C side by side as XLA's conv leaves them (where they are
    # adjacent slices of one array, XLA folds the concatenation back into it)
    v = jnp.concatenate([x.reshape(Bt, S, H * P), B.reshape(Bt, S, G * N),
                         C.reshape(Bt, S, G * N)], axis=-1)
    return _kernel_call(v, 0, None, dt, A, D, H, P, G, N, chunk)


def _kernel_call(v: jax.Array, lead: int, wb: jax.Array | None, dt: jax.Array, A: jax.Array,
                 D: jax.Array, H: int, P: int, G: int, N: int, chunk: int) -> jax.Array:
    """``pallas:ssd_scan`` on v ``[Bt, S, W]``, x | B | C its columns from
    ``lead`` on (before the conv where ``wb``, the conv's taps and bias
    ``[di + 2·G·N, K + 1]``, is given; the blocks read no other column, so v
    is handed over whole) → y ``[Bt, S, H, P]``."""
    Bt, S, _ = v.shape
    R, Q, di = H // G, chunk, H * P
    assert lead % (R * P) == 0 and (lead + di) % N == 0, (lead, R * P, N)
    # tokens minor: the layout XLA gives the mixer's projection and its
    # elementwise parts, so that neither transpose is a copy there
    v = jnp.swapaxes(v, 1, 2)
    dt = jnp.swapaxes(dt.astype(F32), 1, 2)                 # [Bt, H, S]
    pad = -S % Q
    if pad:
        v, dt = (jnp.pad(a, ((0, 0), (0, 0), (0, pad))) for a in (v, dt))
    xs, bs = lead // (R * P), (lead + di) // N              # x's first band, B's
    in_specs = [pl.BlockSpec((None, R * P, Q), lambda b, g, k: (b, xs + g, k)),
                pl.BlockSpec((None, N, Q), lambda b, g, k: (b, bs + g, k)),
                pl.BlockSpec((None, N, Q), lambda b, g, k: (b, bs + G + g, k)),
                pl.BlockSpec((None, H, Q), lambda b, g, k: (b, 0, k)),
                pl.BlockSpec((H, 1), lambda b, g, k: (0, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM)]
    operands = [v, v, v, dt, A.astype(F32).reshape(H, 1), D.astype(F32)]
    scratch = [pltpu.VMEM((R, P, N), F32),                  # the group's carried states
               pltpu.VMEM((H, Q), F32)]                     # acs, a head a row
    if wb is not None:
        cols = wb.shape[1]                                  # the taps and the bias
        in_specs += [pl.BlockSpec((R * P, cols), lambda b, g, k: (g, 0)),
                     pl.BlockSpec((N, cols), lambda b, g, k: (di // N + g, 0)),
                     pl.BlockSpec((N, cols), lambda b, g, k: (di // N + G + g, 0))]
        operands += [wb] * 3
        scratch += [pltpu.VMEM((R * P + 2 * N, Q), F32),      # the bands' previous chunk
                    pltpu.VMEM((cols, R * P + 2 * N, Q), F32)]  # across the lanes
    y = pl.pallas_call(
        functools.partial(_scan_kernel, heads=R, head_dim=P, conv=wb is not None),
        grid=(Bt, G, (S + pad) // Q),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, R * P, Q), lambda b, g, k: (b, g, k)),
        out_shape=jax.ShapeDtypeStruct((Bt, di, S + pad), F32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="ssd_scan",
        interpret=_INTERPRET,
    )(*operands)
    return jnp.swapaxes(y[:, :, :S], 1, 2).reshape(Bt, S, H, P)


def gated_rms_norm(y: jax.Array, z: jax.Array, w: jax.Array, groups: int,
                   eps: float) -> jax.Array:
    """The gate FIRST, then an RMS over each of ``groups`` equal parts of the
    last axis, times ``w`` (HF's ``RMSNormGated``, ``norm_before_gate``
    false); float32."""
    h = y.astype(F32) * jax.nn.silu(z.astype(F32))
    h = h.reshape(h.shape[:-1] + (groups, -1))
    h = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), axis=-1, keepdims=True) + eps)
    return h.reshape(y.shape) * w.astype(F32)
