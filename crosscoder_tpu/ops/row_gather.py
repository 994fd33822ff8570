"""Sums and dots over rows fetched by DMA: ``out[t] = Σ_s w[t,s] · y[row[t,s]]``.

One kernel family, two callers. A sparse-expert layer's combine
(``ops/moe.py``: each token's gate-weighted sum of its k expert rows) and
the k-sparse TopK crosscoder step (``models/crosscoder.py``: the decode
``recon[b] = Σ_j vals[b,j] · W_dec[idx[b,j]]``, its backward
``d_vals[b,j] = ⟨g[b], W_dec[idx[b,j]]⟩``, and — grouped by latent instead
of by token — the weight gradients ``dW[h] = Σ_{(b,j): idx[b,j]=h} c[b,j] ·
rows[b]``) are the same primitive: a few rows of a large matrix, named by
an index table, fetched and reduced. XLA's row gather costs 37–46 ns a row
on a v5e; a DMA descriptor costs 15 ns whatever its bytes (PERF.md §6,
PR 30), so the rows are fetched by DMA, one copy a row.

**The row format** (:func:`pack_rows`). Rows of a 2-D bf16 array lie
interleaved in (16, 128) tiles — two rows a 32-bit word — and Mosaic refuses
a one-row slice of such a tile. A gatherable matrix is therefore kept as
32-bit words of two bf16 columns (column ``j`` low, column ``j + D/2``
high), a row's ``D/256`` lane tiles on an UNTILED axis: ``uint32 [R · D/256,
1, 128]``. A copy addresses a row as ``D/256`` consecutive entries of the
leading axis; loads go through a ``reshape`` view in whole (8, 128) tiles.

**Token-major** (:func:`weighted_sum`, :func:`dots`): a grid over tiles of
tokens; the ``[T·k]`` row table is scalar-prefetched; a tile's ``k·tt`` row
copies land in one half of a double buffer while the other half is reduced;
one wait a tile on a byte-counting semaphore. What a shape forces is derived
from the inputs, not set: the token tile is the largest whose double buffer
fits VMEM (:func:`_token_tile`), and a table larger than its share of SMEM
is cut into slices of the batch, one ``pallas_call`` a slice
(:func:`_slices`).

**Latent-major** (:func:`grouped_sums`): the pairs sorted by destination
(stable, so duplicate destinations accumulate in a fixed order); a grid over
VISITS — (chunk of sorted pairs, tile of destinations) overlaps, a static
count — fetches each chunk's rows once and forms the per-destination sums on
the MXU with a selection matrix built from the chunk's destinations and
coefficients: exact bf16 products, float32 accumulation, as the dense
product it replaces.

**What a kernel's text costs.** A kernel is traced and lowered in every
process, before the persistent cache is asked. Loops written out are
``fori_loop``s unrolled when the kernel is LOWERED: the same instructions
as Python loops give, but each body is traced once (PERF.md §6, PR 30: 17 s
of every run's set-up otherwise). Past :data:`_UNROLLED_BODIES` bodies a
group the token-major kernel keeps its loops and works in wide operations
(:func:`_wide_kernel`): written out, the TopK step's k 32 x D 4096 was
1.6 MB of text and 128 s of every run's set-up (PERF.md §6, PR 32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
GROUP = 16                  # tokens reduced together: one whole bf16 tile stored
_MAX_TOKENS = 128           # the largest token tile (PERF.md §6, PR 30: 32–256 equal)
_UNROLLED_BODIES = 128      # k·W bodies a group written out one by one; past it, wide
VMEM_LIMIT_BYTES = 64 << 20
# the most a call's prefetched row table may take of SMEM: half of a v5e's
# 1 MiB (the cell train-live-topk32k's 4096 x 32 table in one call: a call
# a slice is a kernel more to trace and lower in every process)
_SMEM_TABLE_BYTES = 512 << 10

# test-only: run the kernels in the Pallas interpreter (and let the
# crosscoder step dispatch them on the CPU backend). ops/moe.py passes its
# own flag to the calls it makes.
_INTERPRET = False


def set_interpret(flag: bool) -> None:
    global _INTERPRET
    _INTERPRET = flag


def enabled() -> bool:
    """Whether the kernels may dispatch from this process: the interpreter
    (CPU tests), or a TPU backend with exactly one device (a ``pallas_call``
    is not partitioned by the SPMD partitioner)."""
    return _INTERPRET or (
        jax.default_backend() == "tpu" and jax.device_count() == 1
    )


def pack_rows(y):
    """bf16 ``[R, D]`` → uint32 ``[R, D/2]``: column ``j`` in the low half
    of word ``j`` and column ``j + D/2`` in its high half, so that both
    halves of a row unpack to whole lane tiles. A row is then ``D/256``
    lane tiles of 32-bit words, a unit a DMA can address: rows of a
    ``[M, D]`` array lie interleaved in (8, 128) tiles (bf16: two rows a
    word), and Mosaic refuses a one-row slice of such a tile."""
    half = y.shape[-1] // 2
    return _words(y[..., :half], y[..., half:])


def _words(lo, hi):
    """Two arrays of one shape → uint32 words: ``lo`` rounded to bf16 in the
    low half, ``hi`` in the high half."""
    lo, hi = (jax.lax.bitcast_convert_type(
        a.astype(jnp.bfloat16).astype(jnp.float32), jnp.uint32) for a in (lo, hi))
    return (lo >> 16) | hi


_PACK_ROWS = 256            # rows of one tile of the pack kernel


def _pack_kernel(lo_ref, hi_ref, o_ref, *, interpret):
    rows, cols = lo_ref.shape
    W = cols // LANES
    if interpret:               # the interpreter has no rule for the pack op
        words = _words(lo_ref[...], hi_ref[...])
    else:                       # one instruction a word: rounds as astype does
        words = pltpu.pack_elementwise(
            [lo_ref[...].astype(jnp.float32), hi_ref[...].astype(jnp.float32)],
            packed_dtype=jnp.bfloat16)
    for c in range(W):
        # lane tile c of every row of the tile, to that row's c-th tile
        o_ref[pl.ds(c, rows, stride=W), 0, :] = words[:, c * LANES:(c + 1) * LANES]


def packed(lo, hi=None, *, interpret=False):
    """The gatherable form ``uint32 [R · W, 1, 128]`` of ``R`` rows of ``W``
    lane tiles of words: word ``j`` of a row holds ``lo[r, j]`` and ``hi[r,
    j]`` (rounded to bf16). With ``lo`` alone the two are the halves of its
    columns (:func:`pack_rows`' layout). XLA writes this layout at a tenth
    of the memory's bandwidth (3.5–3.9 ms for the 268 MB of a 2^15 x 4096
    ``W_dec``, PERF.md §6, PR 32), so where the rows tile evenly a kernel
    writes it, a lane tile a strided store as ``moe_down`` does."""
    if hi is None:
        cols = lo.shape[1] // 2
        args, maps = (lo, lo), (lambda i: (i, 0), lambda i: (i, 1))
    else:
        cols = lo.shape[1]
        args, maps = (lo, hi), (lambda i: (i, 0), lambda i: (i, 0))
    R, W = lo.shape[0], cols // LANES
    if R % _PACK_ROWS or cols % LANES:
        words = pack_rows(lo) if hi is None else _words(lo, hi)
        return words.reshape(-1, 1, LANES)
    return pl.pallas_call(
        functools.partial(_pack_kernel, interpret=interpret),
        grid=(R // _PACK_ROWS,),
        in_specs=[pl.BlockSpec((_PACK_ROWS, cols), m) for m in maps],
        out_specs=pl.BlockSpec((_PACK_ROWS * W, 1, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((R * W, 1, LANES), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="rows_pack",
        interpret=interpret,
    )(*args)


def packed_sources(w, *, interpret=False):
    """``[R, n, d]`` bf16 → the gatherable form of its rows ``[R, n·d]``.
    With two sources a word is ``(w[r, 0, j], w[r, 1, j])`` — which is how
    the chip lays a bf16 ``[R, 2, d]`` out anyway (tiles of 2 x 128, the two
    rows of a tile in the halves of a word), so the pack is a bitcast of
    the transposed pairs, and XLA's copy for it beats the pack kernel
    behind a relayout (2.29 against 2.90 ms with the cast, PERF.md §6,
    PR 32)."""
    R, n, d = w.shape
    if n == 2 and d % LANES == 0 and w.dtype == jnp.bfloat16:
        words = jax.lax.bitcast_convert_type(jnp.swapaxes(w, 1, 2), jnp.uint32)
        return words.reshape(-1, 1, LANES)
    return packed(w.reshape(R, n * d), interpret=interpret)


def _token_tile(top_k: int, d: int) -> int:
    """Tokens of one tile: the largest of 128, 64, 32, 16 whose double
    buffer of ``k`` rows a token, with the blocks beside it, takes at most
    half the raised VMEM limit (0: not even one group fits)."""
    tt = _MAX_TOKENS
    while tt >= GROUP and 2 * (top_k + 1) * tt * d * 2 > VMEM_LIMIT_BYTES // 2:
        tt //= 2
    return tt if tt >= GROUP else 0


def supported(n_tokens: int, top_k: int, d: int, dtype) -> bool:
    """Shapes the token-major kernels handle: bf16 rows whose halves are
    whole lanes, at least one group of tokens, a group's rows within VMEM,
    and a slice of the batch (its table within SMEM) of at least a group."""
    if jnp.dtype(dtype) != jnp.bfloat16 or d % (2 * LANES):
        return False
    if n_tokens < GROUP or not _token_tile(top_k, d):
        return False
    return GROUP * top_k * 4 <= _SMEM_TABLE_BYTES


def _slices(n_tokens: int, top_k: int) -> list[tuple[int, int]]:
    """``(first token, tokens)`` of each call: the batch whole where its
    ``[T·k]`` table fits SMEM, else slices of as many whole groups as fit
    (a remainder under one group takes a group from the slice before it)."""
    size = _SMEM_TABLE_BYTES // (4 * top_k) // GROUP * GROUP
    starts = list(range(0, n_tokens, size))
    if len(starts) > 1 and n_tokens - starts[-1] < GROUP:
        starts[-1] -= GROUP
    return [(a, b - a) for a, b in zip(starts, starts[1:] + [n_tokens])]


def _kernel(rows_ref, w_ref, y_ref, o_ref, buf, gate_buf, sem, *,
            n_tokens, dot):
    """One tile of tokens. ``dot`` false: ``o[t] = Σ_s w[t,s] · y[row[t,s]]``
    (``w_ref [tt, k]`` float32 weights, ``o_ref [tt, D]`` bf16). ``dot``
    true: ``o[t,s] = ⟨w[t], y[row[t,s]]⟩`` (``w_ref [tt, D]`` float32,
    ``o_ref [tt, k]`` float32; ``gate_buf`` then holds a group's ``w`` by
    lane tile, low halves first)."""
    tt = w_ref.shape[0]
    k = o_ref.shape[1] if dot else w_ref.shape[1]
    half = (w_ref if dot else o_ref).shape[1] // 2
    W = half // LANES                       # lane tiles of words a row
    G = GROUP
    i, n = pl.program_id(0), pl.num_programs(0)
    slot = i % 2
    # the buffer's bytes seen twice: ``buf [2, k·tt·W, 1, 128]`` keeps a row's
    # W lane tiles on an untiled axis, where a copy may address them; the
    # loads go through a view in whole (8, 128) tiles (a load of 8 rows from
    # ``buf`` itself is 8 one-row loads: 0.41 against 0.20 ms, PERF.md §6)
    tiles = buf.reshape(2 * k * tt * W, LANES)

    def fetch(tile, slot, first, count):
        """Start the copies of ``count`` tokens' k rows (slot-major in the
        buffer), from token ``first`` of tile ``tile``."""
        def token(j, carry):
            t = first + j
            # a token past the end (a last tile that is not whole) takes the
            # last token's rows again: every tile moves the same bytes
            tok = jnp.minimum(tile * tt + t, n_tokens - 1)

            def row(s, carry):
                pltpu.make_async_copy(
                    y_ref.at[pl.ds(rows_ref[tok * k + s] * W, W)],
                    buf.at[slot, pl.ds((s * tt + t) * W, W)], sem.at[slot]).start()
                return carry
            return jax.lax.fori_loop(0, k, row, carry, unroll=True)
        # a group's copies are unrolled among its sums; the first tile's
        # whole fetch, with nothing to overlap, stays a loop
        jax.lax.fori_loop(0, count, token, 0, unroll=count == G)

    @pl.when(i == 0)
    def _():
        fetch(0, 0, 0, tt)

    # one wait for the tile's k·tt copies: a DMA semaphore counts bytes, and
    # this descriptor (never started) is of the size they sum to
    pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()

    def words(s, t0, c):
        """Lane tile c of G tokens' slot-s rows (one strided load), as the
        float32 values of its low and high halves."""
        w = tiles[pl.ds(((slot * k + s) * tt + t0) * W + c, G, stride=W), :]
        return (jax.lax.bitcast_convert_type(w << 16, jnp.float32),
                jax.lax.bitcast_convert_type(w & jnp.uint32(0xFFFF0000), jnp.float32))

    def group_sum(t0):
        gates = w_ref[pl.ds(t0, G), :]
        for s in range(k):      # each slot's gate along the lanes, once a group
            gate_buf[s] = jnp.broadcast_to(gates[:, s:s + 1], (G, LANES))

        def lane_tile(c, carry):
            def add_slot(s, acc):
                lo, hi = words(s, t0, c)
                gate = gate_buf[s]
                return acc[0] + lo * gate, acc[1] + hi * gate
            zero = jnp.zeros((G, LANES), jnp.float32)
            lo, hi = jax.lax.fori_loop(0, k, add_slot, (zero, zero), unroll=True)
            col = pl.multiple_of(c * LANES, LANES)
            o_ref[pl.ds(t0, G), pl.ds(col, LANES)] = lo.astype(o_ref.dtype)
            o_ref[pl.ds(t0, G), pl.ds(half + col, LANES)] = hi.astype(o_ref.dtype)
            return carry
        return jax.lax.fori_loop(0, W, lane_tile, 0, unroll=True)

    def group_dot(t0):
        def keep(c, carry):     # the group's cotangent rows by lane tile, once
            col = pl.multiple_of(c * LANES, LANES)
            gate_buf[c] = w_ref[pl.ds(t0, G), pl.ds(col, LANES)]
            gate_buf[W + c] = w_ref[pl.ds(t0, G), pl.ds(half + col, LANES)]
            return carry
        jax.lax.fori_loop(0, W, keep, 0, unroll=True)
        lane = jax.lax.broadcasted_iota(jnp.int32, (G, k), 1)

        def slot_dot(s, out):
            def lane_tile(c, acc):
                lo, hi = words(s, t0, c)
                return acc + lo * gate_buf[c] + hi * gate_buf[W + c]
            acc = jax.lax.fori_loop(0, W, lane_tile,
                                    jnp.zeros((G, LANES), jnp.float32), unroll=True)
            return jnp.where(lane == s, jnp.sum(acc, axis=1, keepdims=True), out)
        o_ref[pl.ds(t0, G), :] = jax.lax.fori_loop(
            0, k, slot_dot, jnp.zeros((G, k), jnp.float32), unroll=True)

    def group(g, carry):
        t0 = pl.multiple_of(g * G, G)

        @pl.when(i + 1 < n)     # the next tile's copies go out between the sums
        def _():
            fetch(i + 1, 1 - slot, t0, G)

        (group_dot if dot else group_sum)(t0)
        return carry

    jax.lax.fori_loop(0, tt // G, group, 0)


def _wide_kernel(rows_ref, w_ref, y_ref, o_ref, buf, held, sem, *, n_tokens, k, dot):
    """The same tile, of ONE group of tokens, in a few wide operations: a
    lane tile of all ``k·G`` rows in one strided load, the weights as one
    column. :func:`_kernel` writes ``k·W`` small bodies a group out one by
    one for the scheduler to overlap with the copies; at k 32 and D 4096
    that is 512 bodies and as many copies, 1.6 MB of kernel text in a step,
    and lowering it took 128 s of EVERY process's set-up on the chip's host
    (PERF.md §6, PR 32). Here the text is a hundredth of that; the price is
    that a tile's copies are started before its sums, not among them.
    ``dot`` false: ``w_ref [k·G, 1]`` (slot-major), ``o_ref [G, D]``; ``dot``
    true: ``w_ref [G, D]``, ``o_ref [k·G, 1]`` (slot-major)."""
    G = GROUP
    half = (w_ref if dot else o_ref).shape[1] // 2
    W = half // LANES
    i, n = pl.program_id(0), pl.num_programs(0)
    slot = jax.lax.rem(i, 2)
    tiles = buf.reshape(2 * k * G * W, LANES)

    def fetch(tile, slot):
        def some(m, carry):
            def one(q, carry):          # row m·8 + q of the tile: token-major
                r = m * 8 + q           # (lax.div/rem: ``//`` and ``%`` lower to
                t, s = jax.lax.div(r, k), jax.lax.rem(r, k)     # ten times the text)
                tok = jnp.minimum(tile * G + t, n_tokens - 1)
                pltpu.make_async_copy(
                    y_ref.at[pl.ds(rows_ref[tok * k + s] * W, W)],
                    buf.at[slot, pl.ds((s * G + t) * W, W)], sem.at[slot]).start()
                return carry
            # eight written out an iteration — when the kernel is LOWERED: a
            # body is traced once (a copy costs 13 ms of tracing on the
            # chip's host, in every process)
            return jax.lax.fori_loop(0, 8, one, carry, unroll=True)
        jax.lax.fori_loop(0, k * G // 8, some, 0)

    @pl.when(i == 0)
    def _():
        fetch(0, 0)

    pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()

    @pl.when(i + 1 < n)
    def _():
        fetch(i + 1, 1 - slot)

    if dot:
        held[...] = jnp.zeros_like(held)
    else:
        held[...] = jnp.broadcast_to(w_ref[...], held.shape)

    def lane_tile(c, carry):
        w = tiles[pl.ds(slot * k * G * W + c, k * G, stride=W), :]      # [k·G, 128]
        lo = jax.lax.bitcast_convert_type(w << 16, jnp.float32)
        hi = jax.lax.bitcast_convert_type(w & jnp.uint32(0xFFFF0000), jnp.float32)
        col = pl.ds(pl.multiple_of(c * LANES, LANES), LANES)
        far = pl.ds(pl.multiple_of(half + c * LANES, LANES), LANES)
        if dot:
            g = (lo.reshape(k, G, LANES) * w_ref[:, col][None]
                 + hi.reshape(k, G, LANES) * w_ref[:, far][None])
            held[...] += g.reshape(k * G, LANES)
        else:
            gate = held[...]
            o_ref[:, col] = jnp.sum((lo * gate).reshape(k, G, LANES), axis=0
                                    ).astype(o_ref.dtype)
            o_ref[:, far] = jnp.sum((hi * gate).reshape(k, G, LANES), axis=0
                                    ).astype(o_ref.dtype)
        return carry
    jax.lax.fori_loop(0, W, lane_tile, 0)
    if dot:
        o_ref[...] = jnp.sum(held[...], axis=1, keepdims=True)


def _wide_call(table, w, y_packed, d, k, *, dot, name, interpret, out_dtype=None):
    """:func:`_call` through :func:`_wide_kernel`: the per-(token, slot)
    numbers cross the call as a slot-major column a group."""
    T = w.shape[0]
    G, W = GROUP, d // 2 // LANES
    n = pl.cdiv(T, G)

    def column(a):      # [T, k] -> [n·k·G, 1], a group's k·G slot-major
        a = jnp.pad(a, ((0, n * G - T), (0, 0)))
        return a.reshape(n, G, k).transpose(0, 2, 1).reshape(n * k * G, 1)

    if dot:
        args, in_block = w, (G, d)
        out_block, out = (k * G, 1), jax.ShapeDtypeStruct((n * k * G, 1), jnp.float32)
    else:
        args, in_block = column(w), (k * G, 1)
        out_block, out = (G, d), jax.ShapeDtypeStruct((T, d), out_dtype)
    res = pl.pallas_call(
        functools.partial(_wide_kernel, n_tokens=T, k=k, dot=dot),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[pl.BlockSpec(in_block, lambda i, rows: (i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(out_block, lambda i, rows: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, k * G * W, 1, LANES), jnp.uint32),
                            pltpu.VMEM((k * G, LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name=name,
        interpret=interpret,
    )(table, args, y_packed)
    if dot:             # [n·k·G, 1] slot-major -> [T, k]
        res = res.reshape(n, k, G).transpose(0, 2, 1).reshape(n * G, k)[:T]
    return res


def _call(table, w, y_packed, d, k, *, dot, name, interpret, out_dtype=None):
    """One ``pallas_call`` over the tokens of ``w`` (a whole batch or a
    slice of one), ``table [T·k]`` scalar-prefetched."""
    T = w.shape[0]
    W = d // 2 // LANES
    tt = min(_token_tile(k, d), T // GROUP * GROUP)
    if dot:
        out_block, out = (tt, k), jax.ShapeDtypeStruct((T, k), jnp.float32)
        held = (2 * W, GROUP, LANES)
    else:
        out_block, out = (tt, d), jax.ShapeDtypeStruct((T, d), out_dtype)
        held = (k, GROUP, LANES)
    return pl.pallas_call(
        functools.partial(_kernel, n_tokens=T, dot=dot),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(T, tt),),
            in_specs=[pl.BlockSpec((tt, w.shape[1]), lambda i, rows: (i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(out_block, lambda i, rows: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, k * tt * W, 1, LANES), jnp.uint32),
                            pltpu.VMEM(held, jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name=name,
        interpret=interpret,
    )(table, w, y_packed)


def _token_major(table, w, y_packed, d, k, **kw):
    # one call a slice, written out: under ``lax.map`` XLA fuses the slicing
    # into the call and runs out of VMEM for it (PR 32, on the chip)
    call = _wide_call if k * (d // 2 // LANES) > _UNROLLED_BODIES else _call
    parts = [
        call(table[t * k:(t + n) * k], w[t:t + n], y_packed, d, k, **kw)
        for t, n in _slices(w.shape[0], k)
    ]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def weighted_sum(table, weights, y_packed, d, *, name, interpret=False,
                 out_dtype=jnp.bfloat16):
    """``Σ_s weights[t, s] · y[table[t·k + s]]`` → ``[T, D]``, summed in
    float32 from the bf16 rows and rounded once to ``out_dtype``: ``table
    [T·k]`` names each slot's row of ``y_packed`` (:func:`packed`'s layout,
    left in HBM), ``weights [T, k]`` float32."""
    return _token_major(table, weights, y_packed, d, weights.shape[1], dot=False,
                        name=name, interpret=interpret, out_dtype=out_dtype)


def dots(table, g, y_packed, k, *, name, interpret=False):
    """``⟨g[t], y[table[t·k + s]]⟩`` → ``[T, k]`` float32: the same table and
    the same packed rows as :func:`weighted_sum`, ``g [T, D]`` float32."""
    return _token_major(table, g, y_packed, g.shape[1], k,
                        dot=True, name=name, interpret=interpret)


# ---------------------------------------------------------------------------
# latent-major: per-destination sums of rows, the pairs sorted by destination

PAIRS = 128                 # sorted pairs of one chunk: one MXU contraction
DESTS = 128                 # destinations of one output tile


def grouped_supported(n_out: int, n_rows: int, top_k: int, d: int, dtype) -> bool:
    """Shapes :func:`grouped_sums` handles: bf16 rows of whole lanes, whole
    tiles of destinations, and the ``[B·k]`` token table (scalar-prefetched
    whole: a chunk's rows are named by any token) within SMEM beside the
    visit tables."""
    if jnp.dtype(dtype) != jnp.bfloat16 or d % LANES or n_out % DESTS:
        return False
    pairs = -(-n_rows * top_k // PAIRS) * PAIRS
    return 4 * pairs + 8 * (pairs // PAIRS + n_out // DESTS) <= _SMEM_GROUPED_BYTES


# the visit kernel's tables: the sorted pairs' tokens and two ints a visit
_SMEM_GROUPED_BYTES = 768 << 10


def _visits(idx, cv, cd, n_out):
    """The index math of :func:`grouped_sums`, all sorts and small searches:
    the pairs ``(idx[b,j], b, cv[b,j], cd[b,j])`` sorted by destination
    (stable: duplicate destinations keep batch order), cut into chunks of
    :data:`PAIRS`, and the VISITS — every (chunk, destination tile) overlap
    in order, each tile visited at least once (a tile no pair names is
    visited by the chunk that passes it, and written as zeros). Returns
    ``(visit_chunk [V], visit_tile [V], n_valid [1], tokens [P], dst, cv,
    cd [n_chunks, 1, PAIRS])`` with ``V = n_chunks + n_tiles - 1``, the static
    bound: a tile boundary is crossed once."""
    B, k = idx.shape
    P = B * k
    C, n_tiles = PAIRS, n_out // DESTS
    tok = jnp.arange(P, dtype=jnp.int32) // k
    dst, tok, cv, cd = jax.lax.sort(
        (idx.reshape(-1).astype(jnp.int32), tok,
         cv.reshape(-1).astype(jnp.float32), cd.reshape(-1).astype(jnp.float32)),
        num_keys=1, is_stable=True)
    pad = -P % C
    if pad:     # zero pairs of the last destination: still sorted, add nothing
        dst = jnp.concatenate([dst, jnp.full((pad,), n_out - 1, jnp.int32)])
        tok, cv, cd = (jnp.concatenate([a, jnp.zeros((pad,), a.dtype)])
                       for a in (tok, cv, cd))
    n_chunks = (P + pad) // C
    first = dst[::C] // DESTS                           # each chunk's first tile
    last = dst[C - 1::C] // DESTS
    before = jnp.concatenate([jnp.full((1,), -1, jnp.int32), last[:-1]])
    # a chunk starts at its first tile if the chunk before ended there, else
    # right after that one's last (the tiles between hold no pair: zeros)
    start = jnp.where(first == before, first, before + 1)
    end = jnp.concatenate([last[:-1], jnp.full((1,), n_tiles - 1, jnp.int32)])
    counts = end - start + 1                            # visits of each chunk
    ends = jnp.cumsum(counts)
    v = jnp.arange(n_chunks + n_tiles - 1, dtype=jnp.int32)
    chunk = jnp.minimum(jnp.searchsorted(ends, v, side="right"), n_chunks - 1
                        ).astype(jnp.int32)
    tile = jnp.minimum(start[chunk] + v - (ends - counts)[chunk],
                       n_tiles - 1).astype(jnp.int32)
    shape = (n_chunks, 1, C)
    return (chunk, tile, ends[-1:].astype(jnp.int32), tok,
            dst.reshape(shape), cv.reshape(shape), cd.reshape(shape))


def _grouped_kernel(vc_ref, vt_ref, nv_ref, tok_ref, dst_ref, cv_ref, cd_ref,
                    rows_ref, od_ref, oe_ref, ob_ref, buf, acc_d, acc_e, acc_b,
                    sem, *, n_chunks):
    """One visit: chunk ``vc[v]``'s pairs against destination tile
    ``vt[v]``. A chunk's rows (``[g | x]`` packed side by side: ``g`` in the
    low halves, ``x`` in the high) are due at its first visit, when the next
    chunk's copies go out among the sums; the sums are two MXU products a
    lane tile with the selection matrices ``S[l, p] = c[p] · (dst[p] ==
    tile·DESTS + l)``. The products bind (PERF.md §6, PR 32: time goes with
    DESTS x PAIRS; a band of 32 destinations at a time, with the bands a
    chunk does not reach skipped, was slower). Eight lane tiles are written
    out an iteration and the rest is a loop: the kernel's text is lowered in
    every process, and written out whole it is 1.0 ms a step faster (8.2
    against 9.1) for a second more of every run's set-up. The accumulators keep a
    lane tile on the leading axis, where a loop may index them."""
    C, L = PAIRS, DESTS
    Wr = od_ref.shape[1] // LANES               # lane tiles of words a row
    v = pl.program_id(0)
    chunk, tile = vc_ref[v], vt_ref[v]
    prev = jnp.maximum(v - 1, 0)
    live = v < nv_ref[0]
    slot = jax.lax.rem(chunk, 2)
    tiles = buf.reshape(2 * C * Wr, LANES)
    U = next(u for u in (8, 4, 2, 1) if Wr % u == 0)
    steps = Wr // U
    # the next chunk's copies, started beside each iteration's sums (before
    # the sums where they do not split evenly over the iterations)
    per = C // steps if C % steps == 0 else 0

    def copy(c, slot, q):
        pltpu.make_async_copy(
            rows_ref.at[pl.ds(tok_ref[c * C + q] * Wr, Wr)],
            buf.at[slot, pl.ds(q * Wr, Wr)], sem.at[slot]).start()

    def fetch(c, slot):
        def pair(q, carry):
            copy(c, slot, q)
            return carry
        jax.lax.fori_loop(0, C, pair, 0)

    pl.when(v == 0)(lambda: fetch(0, 0))

    first = live & ((v == 0) | (chunk != vc_ref[prev]))
    ahead = first & (chunk + 1 < n_chunks)      # the next chunk's copies go out

    @pl.when(first)
    def _():        # the chunk's first visit: its rows are due
        # one wait for the chunk's C copies: the semaphore counts bytes
        pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()

    if not per:
        pl.when(ahead)(lambda: fetch(chunk + 1, 1 - slot))

    @pl.when(live & ((v == 0) | (tile != vt_ref[prev])))
    def _():
        acc_d[...] = jnp.zeros_like(acc_d)
        acc_e[...] = jnp.zeros_like(acc_e)
        acc_b[...] = jnp.zeros_like(acc_b)

    @pl.when(live)
    def _():
        hit = (dst_ref[0] - tile * L) == jax.lax.broadcasted_iota(jnp.int32, (L, C), 0)
        s_d = jnp.where(hit, cv_ref[0], 0.0).astype(jnp.bfloat16)      # [L, C]
        s_e = jnp.where(hit, cd_ref[0], 0.0).astype(jnp.bfloat16)
        acc_b[...] += jnp.dot(s_e, jnp.ones((C, LANES), jnp.bfloat16),
                              preferred_element_type=jnp.float32)

        def lane_tiles(j, carry):
            # the inner loops are written out when the kernel is LOWERED: the
            # instructions of Python loops, each body traced once
            @pl.when(ahead)
            def _():
                def one(q, carry):
                    copy(chunk + 1, 1 - slot, j * per + q)
                    return carry
                jax.lax.fori_loop(0, per, one, 0, unroll=True)

            def lane_tile(q, carry):
                c = j * U + q
                w = tiles[pl.ds(slot * C * Wr + c, C, stride=Wr), :]    # [C, 128]
                g = jax.lax.bitcast_convert_type(w << 16, jnp.float32)
                x = jax.lax.bitcast_convert_type(w & jnp.uint32(0xFFFF0000), jnp.float32)
                acc_d[c] += jnp.dot(s_d, g.astype(jnp.bfloat16),
                                    preferred_element_type=jnp.float32)
                acc_e[c] += jnp.dot(s_e, x.astype(jnp.bfloat16),
                                    preferred_element_type=jnp.float32)
                return carry
            return jax.lax.fori_loop(0, U, lane_tile, carry, unroll=True)
        jax.lax.fori_loop(0, steps, lane_tiles, 0)

    # the tile's last visit: round once, as the dense product's result is
    @pl.when(live & ((v + 1 == nv_ref[0]) | (vt_ref[v + 1] != tile)))
    def _():
        ob_ref[...] = acc_b[...]

        def leave(c, carry):        # the second sum leaves as [D, destinations]
            cols = pl.ds(pl.multiple_of(c * LANES, LANES), LANES)
            od_ref[:, cols] = acc_d[c].astype(od_ref.dtype)
            oe_ref[cols, :] = acc_e[c].T.astype(jnp.bfloat16).astype(oe_ref.dtype)
            return carry
        jax.lax.fori_loop(0, Wr, leave, 0)


def grouped_sums(idx, cv, cd, g, x, n_out, *, name, interpret=False):
    """Both weight gradients of a k-sparse layer in one pass over the pairs:
    ``out_d[h] = Σ cv[b,j] · g[b]`` and ``out_e[h] = Σ cd[b,j] · x[b]`` over
    the pairs with ``idx[b,j] == h`` → ``out_d [n_out, D]`` and ``out_e``
    TRANSPOSED, ``[D, n_out]`` (an encoder's orientation), both rounded to
    bf16 — ``out_e`` then written as float32, the form the optimizer reads
    (XLA's transpose and its float32 copy cost 1.2–1.3 ms each at 2^15 x
    4096, PERF.md §6, PR 32) — and ``out_b[h] = Σ cd[b,j]`` float32. ``cv``, ``cd`` are
    rounded to bf16 and the products accumulate in float32, as the dense
    products ``fᵀ·g`` and ``dhᵀ·x`` do. ``idx`` must lie in ``[0,
    n_out)``."""
    d = g.shape[1]
    chunk, tile, n_valid, tok, dst, cv, cd = _visits(idx, cv, cd, n_out)
    # one more entry: the last visit looks one ahead
    tile = jnp.concatenate([tile, tile[-1:]])
    rows = packed(g, x, interpret=interpret)                    # [B·Wr, 1, 128]
    Wr = d // LANES

    def of_chunk(v, vc, vt, *_):
        return (vc[v], 0, 0)

    def of_tile(v, vc, vt, *_):
        return (vt[v], 0)

    pairs = pl.BlockSpec((None, 1, PAIRS), of_chunk)
    out_d, out_e, out_b = pl.pallas_call(
        functools.partial(_grouped_kernel, n_chunks=dst.shape[0]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(chunk.shape[0],),
            in_specs=[pairs, pairs, pairs, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((DESTS, d), of_tile),
                       pl.BlockSpec((d, DESTS), lambda v, vc, vt, *_: (0, vt[v])),
                       pl.BlockSpec((DESTS, LANES), of_tile)],
            scratch_shapes=[pltpu.VMEM((2, PAIRS * Wr, 1, LANES), jnp.uint32),
                            pltpu.VMEM((Wr, DESTS, LANES), jnp.float32),
                            pltpu.VMEM((Wr, DESTS, LANES), jnp.float32),
                            pltpu.VMEM((DESTS, LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct((n_out, d), jnp.bfloat16),
                   jax.ShapeDtypeStruct((d, n_out), jnp.float32),
                   jax.ShapeDtypeStruct((n_out, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name=name,
        interpret=interpret,
    )(chunk, tile, n_valid, tok, dst, cv, cd, rows)
    return out_d, out_e, out_b[:, 0]


# ---------------------------------------------------------------------------
# token-major over a COMPACTED table: only the pairs that are really there
#
# (Described here and not in the module's docstring: a Pallas payload carries
# line numbers, and a line added above the kernels of the TopK step would
# compile them again on every tree's first run.) :func:`weighted_sum` where
# most of the table's slots name no row — a chip that holds a share of the
# experts: the pairs that are there lie compacted at the front of the table
# in token order, their number known to the device alone; a grid over tiles
# of tokens, each walking the chunks of pairs that name its tokens by a loop
# of dynamic length, the per-token sums formed on the MXU as the latent-major
# pass forms its sums. Nothing is fetched or summed for a pair that is not
# there.


def unpack_words(words):
    """Packed words → the float32 values of their low and high halves (a
    row's column ``j`` and ``j + D/2``)."""
    return (jax.lax.bitcast_convert_type(words << 16, jnp.float32),
            jax.lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000), jnp.float32))


def held_supported(n_tokens: int, top_k: int, d: int, dtype) -> bool:
    """Shapes :func:`held_sums` handles: :func:`supported`'s, with the
    ``[T·k]`` pair table WHOLE within SMEM — it cannot be cut into slices of
    the batch, since where a slice's pairs lie is known only on the device."""
    pairs = -(-n_tokens * top_k // PAIRS) * PAIRS
    return supported(n_tokens, top_k, d, dtype) and 4 * pairs <= _SMEM_TABLE_BYTES


def _held_kernel(starts_ref, live_ref, rows_ref, tok_ref, w_ref, y_ref, o_ref,
                 buf, acc, sem):
    """One tile of tokens against the chunks of :data:`PAIRS` sorted pairs
    that name its tokens — a dynamic count, walked by a loop: pairs past the
    live ones are neither fetched nor summed. A chunk's rows are due at its
    first visit (a chunk that straddles two tiles is fetched once), when the
    next live chunk's copies go out. The per-token sums are MXU products
    with the selection matrix ``S[l, p] = w[p] · (tok[p] == tile·dt + l)``,
    as :func:`_grouped_kernel` forms them — but the weights stay float32: each
    is split into three bf16 parts that add up to it exactly, so every
    product is exact and the sums are float32 sums of ``w · row``. The lane
    tiles are written out, up to :data:`_UNROLLED_BODIES` products a visit
    (0.48 against 0.69 ms at the laguna cell's shape; the next chunk's
    copies started among them: 0.57 — PERF.md §6, PR 34)."""
    C = PAIRS
    dt, d = o_ref.shape
    half = d // 2
    W = half // LANES                           # lane tiles of words a row
    i = pl.program_id(0)
    s0, s1 = starts_ref[i], starts_ref[i + 1]   # the tile's sorted pairs
    n_live = live_ref[0]
    shift = C.bit_length() - 1
    c0 = jax.lax.shift_right_arithmetic(s0, shift)
    seen = jax.lax.shift_right_arithmetic(s0 - 1, shift)    # the last chunk visited (-1: none)
    n = jnp.where(s1 > s0, jax.lax.shift_right_arithmetic(s1 - 1, shift) - c0 + 1, 0)
    tiles = buf.reshape(2 * C * W, LANES)

    def fetch(c, slot):
        def some(m, carry):
            def one(q, carry):
                p = m * 8 + q
                pltpu.make_async_copy(
                    y_ref.at[pl.ds(rows_ref[c * C + p] * W, W)],
                    buf.at[slot, pl.ds(p * W, W)], sem.at[slot]).start()
                return carry
            # eight written out an iteration, when the kernel is LOWERED
            return jax.lax.fori_loop(0, 8, one, carry, unroll=True)
        jax.lax.fori_loop(0, C // 8, some, 0)

    @pl.when((i == 0) & (n_live > 0))
    def _():
        fetch(0, 0)

    acc[...] = jnp.zeros_like(acc)

    def visit(j, carry):
        c = c0 + j
        slot = jax.lax.rem(c, 2)
        first = c > seen

        @pl.when(first)
        def _():    # one wait for the chunk's C copies: the semaphore counts bytes
            pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()

        @pl.when(first & ((c + 1) * C < n_live))
        def _():
            fetch(c + 1, 1 - slot)

        w = w_ref[pl.ds(c, 1), :]                               # [1, C] float32
        w1 = w.astype(jnp.bfloat16).astype(jnp.float32)
        w2 = (w - w1).astype(jnp.bfloat16).astype(jnp.float32)
        w3 = w - w1 - w2                        # what is left fits bf16: w = w1 + w2 + w3
        hit = (tok_ref[pl.ds(c, 1), :] - i * dt) == jax.lax.broadcasted_iota(
            jnp.int32, (dt, C), 0)
        sel = jnp.concatenate(
            [jnp.where(hit, part, 0.0).astype(jnp.bfloat16) for part in (w1, w2, w3)],
            axis=0)                                             # [3·dt, C]

        def lane_tile(q, carry):
            lo, hi = unpack_words(tiles[pl.ds(slot * C * W + q, C, stride=W), :])  # [C, 128]
            for k, part in ((q, lo), (W + q, hi)):
                p = jnp.dot(sel, part.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
                acc[k] += p[:dt] + p[dt:2 * dt] + p[2 * dt:]
            return carry
        return jax.lax.fori_loop(0, W, lane_tile, carry, unroll=2 * W <= _UNROLLED_BODIES)

    jax.lax.fori_loop(0, n, visit, 0)

    def leave(q, carry):        # round once
        col = pl.multiple_of(q * LANES, LANES)
        o_ref[:, pl.ds(col, LANES)] = acc[q].astype(o_ref.dtype)
        o_ref[:, pl.ds(half + col, LANES)] = acc[W + q].astype(o_ref.dtype)
        return carry
    jax.lax.fori_loop(0, W, leave, 0)


def held_sums(rows, tokens, weights, n_live, y_packed, n_tokens, d, *, name,
              interpret=False, out_dtype=jnp.bfloat16):
    """``out[t] = Σ_{p < n_live, tokens[p] = t} weights[p] · y[rows[p]]`` →
    ``[n_tokens, D]``: :func:`weighted_sum` over a table of PAIRS — ``rows
    [P]`` (a row of ``y_packed``), ``tokens [P]`` (ascending over the live
    pairs), ``weights [P]`` float32 — of which only the first ``n_live [1]``
    are there, a count the device holds. Rows are fetched and summed for the
    live pairs alone (up to the last live chunk's end, whose dead pairs fetch
    row 0 and add nothing); a token no live pair names gets exactly 0.
    Float32 weights, float32 sums of the bf16 rows, one rounding."""
    P = rows.shape[0]
    C = PAIRS
    W = d // 2 // LANES
    dt = min(_MAX_TOKENS, n_tokens // GROUP * GROUP)
    n_tiles = pl.cdiv(n_tokens, dt)
    live = jnp.arange(P, dtype=jnp.int32) < n_live[0]
    pad = -P % C
    # a dead pair names no token of any tile, and a row that is always there
    tokens = jnp.pad(jnp.where(live, tokens, 1 << 30), (0, pad), constant_values=1 << 30)
    rows = jnp.pad(jnp.where(live, rows, 0), (0, pad))
    weights = jnp.pad(weights.astype(jnp.float32), (0, pad))
    starts = jnp.searchsorted(
        tokens, jnp.arange(n_tiles + 1, dtype=jnp.int32) * dt, side="left"
    ).astype(jnp.int32)
    n_chunks = (P + pad) // C
    whole = pl.BlockSpec((n_chunks, C), lambda i, *_: (0, 0))   # fetched once
    return pl.pallas_call(
        _held_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles,),
            in_specs=[whole, whole, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((dt, d), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, C * W, 1, LANES), jnp.uint32),
                            pltpu.VMEM((2 * W, dt, LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((n_tokens, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name=name,
        interpret=interpret,
    )(starts, n_live.astype(jnp.int32), rows,
      tokens.reshape(n_chunks, C), weights.reshape(n_chunks, C), y_packed)
