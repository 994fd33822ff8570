"""One sparse-expert MLP layer: router → top-k → grouped product → combine.

``m = Σ_{e ∈ top-k(g)} p_e · (SiLU(x·Wg_e) ⊙ (x·Wu_e)) · Wd_e`` with
``g = softmax(x·Wr)`` over all experts and ``p`` the chosen gates, divided
by their sum where ``norm_topk_prob``. Every routed row is computed, none is
dropped, there is no capacity factor: the (token, slot) rows are GROUPED by
expert and each group meets only its own expert's weights.

A chip may hold a SHARE of the layer's experts (``first_expert`` and the
expert leaves' own count, fewer than the router's width): the router keeps
its whole width and its k a token, rows whose expert is absent are neither
gathered into tiles nor multiplied and count 0 in the combine, and every row
of a held expert is computed. The result is this chip's part of the sum;
nothing stands in for the absent chips. The row buffers keep their static
bound (every token may choose k held experts: no token is dropped), but in
the tile form no row MOVES for a row that is not there: the work is bounded
by two counts the device computes, the real tiles and the held (token, slot)
pairs (:func:`_held_rows`). With every expert held the layer is the whole
one, op for op.

The router's product accumulates in float32 and its softmax, top-k and gates
are float32 whatever the model's dtype: in bf16 near-ties between experts
flip, and a flipped expert is an O(1) change of that token's output.

Two forms of the grouped product, chosen in :func:`moe_mlp` — when the
program is traced — from what the code can observe (backend, device count,
shape), as ``models/lm._attn_core`` chooses its attention:

- ``tiles`` (a one-device TPU backend at a :func:`supported` shape): three
  Pallas kernels. Rows are laid out in expert-ALIGNED tiles of
  :data:`TILE_ROWS` (each expert's group padded to a whole tile), so a row
  tile meets exactly one expert and the grouped product's two kernels are
  plain matmuls whose weight block is picked by a prefetched tile → expert
  table: ``pallas:moe_gate_up`` (both products, SiLU and the gate in its
  epilogue: the ``[rows, 2·F]`` pre-activations never reach HBM) and
  ``pallas:moe_down``. Consecutive tiles of one expert keep its weights in
  VMEM; tiles past the last real one are skipped. The third,
  ``pallas:expert_combine``, forms each token's gate-weighted sum of its k
  expert rows reading ``moe_down``'s result in place: the row-gather
  kernel of ``ops/row_gather.py`` (rows fetched by DMA, one copy a row, by
  a prefetched (token, slot) → row table), which the TopK crosscoder step
  shares. For that ``moe_down`` writes each row in that module's packed
  form (32-bit words of two bf16 columns). At a shape the kernel refuses
  (``row_gather.supported``) ``moe_down`` writes plain rows and XLA gathers
  and sums them. Under a share, at a shape the row kernels take
  (``row_gather.held_supported``), the same three names do less:
  ``pallas:moe_gate_up`` fetches x's rows for its real tiles itself, by
  DMA from ``row_gather.packed(x)`` through prefetched tables (no ``[M, D]``
  copy of x, no gather of M indices), and ``pallas:expert_combine`` is
  ``row_gather.held_sums`` over the held pairs alone, compacted in token
  order with their dynamic count (an absent slot is not fetched; a token
  with no held slot gets exactly 0).
- ``ragged`` (everything else — the CPU backend, a mesh, an unsupported
  shape): rows sorted by expert, ``jax.lax.ragged_dot``. Also the oracle the
  kernels are pinned against (tests/test_moe.py).

All round at the same places (the gated hidden and each expert's output to
the model's dtype; the weighted combine in float32, rounded once). The
choices are counted in the job's telemetry plane, once per trace:
``harvest/moe_tiles_traces`` / ``harvest/moe_ragged_traces`` and, within the
tile form, ``harvest/moe_combine_kernel_traces`` /
``harvest/moe_combine_xla_traces``; under a share
``harvest/moe_held_traces``, and in the tile form which way the held rows
moved: ``harvest/moe_rows_in_kernel_traces`` with
``harvest/moe_held_combine_traces``, or ``harvest/moe_rows_in_xla_traces``
(how MUCH moved is the gauge ``harvest/moe_local_row_share``: the share of
routed rows that are held). No environment gate, no config field.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from crosscoder_tpu.ops import row_gather

_LANES = row_gather.LANES
# Rows of one expert-aligned tile. A constant of the kernel, not a knob:
# PERF.md §6 (PR 29) has the table it was chosen from.
TILE_ROWS = 128
# both kernels hold one expert's weight block double-buffered (gate and up:
# 2 x 2 x D x F x itemsize), which passes the default scoped limit
_VMEM_LIMIT_BYTES = row_gather.VMEM_LIMIT_BYTES

# test-only: route the kernels through the Pallas interpreter (and let them
# dispatch on the CPU backend) — same pattern as ops/flash_attention.
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


def supported(d_model: int, d_expert: int, dtype) -> bool:
    """Shapes the kernels handle: both widths whole lanes, and one expert's
    gate+up block double-buffered within the raised VMEM limit."""
    if d_model % _LANES or d_expert % _LANES:
        return False
    block = 2 * 2 * d_model * d_expert * jnp.dtype(dtype).itemsize
    return block <= _VMEM_LIMIT_BYTES // 2


def route(
    x: jax.Array, w_router: jax.Array, top_k: int, norm_topk_prob: bool,
    routed_scale: float = 1.0, kind: str = "softmax", bias: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``x [T, D]`` → the chosen experts ``[T, k]`` int32 (largest first;
    ties to the lowest index) and their gates ``[T, k]`` float32 (times
    ``routed_scale``, after the renormalisation). ``kind`` ``"softmax"``
    chooses and gates by the softmax over all experts; ``"sigmoid_bias"``
    scores each expert by a sigmoid, CHOOSES by score plus the per-expert
    ``bias [E]`` and GATES by the unbiased scores of the chosen."""
    logits = jnp.einsum("td,de->te", x, w_router, preferred_element_type=jnp.float32)
    if kind == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        if norm_topk_prob:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), gates * routed_scale
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    if routed_scale != 1.0:
        gates = gates * routed_scale
    return idx.astype(jnp.int32), gates


def _held(idx: jax.Array, gates: jax.Array, first_expert: int, n_held: int):
    """The routing as this chip's share sees it: expert ids relative to the
    share, an absent expert's rows under the id ``n_held`` (they sort past
    every held group, and no group counts them) with gate 0."""
    local = idx - first_expert
    held = (local >= 0) & (local < n_held)
    return jnp.where(held, local, n_held), jnp.where(held, gates, 0.0)


def _by_expert(idx: jax.Array, n_experts: int, *carried: jax.Array):
    """The (token, slot) rows sorted by expert (stable): the experts in
    sorted order ``[N]``, ``order [N]`` (sorted position → flat slot
    ``t·k + s``) and the group sizes ``[E]`` — then each of ``carried
    [T, k]`` in sorted order, taken along by the sort. Sorts and a 64-query
    search: on the chip an element-wise gather or scatter of N indices costs
    more than a sort of them (PERF.md §6, PR 29)."""
    flat = idx.reshape(-1)
    slots = jnp.arange(flat.shape[0], dtype=jnp.int32)
    experts, order, *carried = jax.lax.sort(
        (flat, slots, *(c.reshape(-1) for c in carried)), num_keys=1)
    ends = jnp.searchsorted(
        experts, jnp.arange(n_experts, dtype=jnp.int32), side="right")
    return experts, order, jnp.diff(ends, prepend=0).astype(jnp.int32), *carried


def _unsort(order: jax.Array, by_sorted_pos: jax.Array) -> jax.Array:
    """Values held by sorted position → by flat slot: ``order`` is a
    permutation, so sorting by it undoes it."""
    return jax.lax.sort((order, by_sorted_pos), num_keys=1)[1]


def _combine(y_slots: jax.Array, gates: jax.Array) -> jax.Array:
    """``[T, k, D]`` expert outputs × ``[T, k]`` gates → ``[T, D]``."""
    out = jnp.sum(y_slots.astype(jnp.float32) * gates[..., None], axis=1)
    return out.astype(y_slots.dtype)


def _experts_ragged(x, idx, gates, w_gate_up, w_down, layer, share=False):
    """The XLA form: sorted rows through ``ragged_dot``. Under a ``share``
    the absent rows lie past the last group, where ``ragged_dot`` multiplies
    nothing; what it leaves there is replaced by 0."""
    T, k = idx.shape
    w_gate_up, w_down = w_gate_up[layer], w_down[layer]
    F = w_down.shape[1]
    experts, order, sizes = _by_expert(idx, w_down.shape[0])
    inverse = _unsort(order, jnp.arange(T * k, dtype=jnp.int32))
    xs = x[order // k]                                          # [N, D]
    gu = jax.lax.ragged_dot(xs, w_gate_up, sizes, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gu[:, :F]) * gu[:, F:]).astype(x.dtype)
    y = jax.lax.ragged_dot(h, w_down, sizes, preferred_element_type=jnp.float32)
    if share:
        y = jnp.where((experts < w_down.shape[0])[:, None], y, 0.0)
    return _combine(y.astype(x.dtype)[inverse].reshape(T, k, -1), gates)


# ---------------------------------------------------------------------------
# the tile form


def _gated_product(x, wg_ref, wu_ref, o_ref):
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    o_ref[...] = (g * jax.nn.sigmoid(g) * u).astype(o_ref.dtype)


def _gate_up_kernel(te_ref, nv_ref, ly_ref, x_ref, wg_ref, wu_ref, o_ref):
    @pl.when(pl.program_id(0) < nv_ref[0])
    def _():
        _gated_product(x_ref[...], wg_ref, wu_ref, o_ref)


def _down_kernel(te_ref, nv_ref, ly_ref, h_ref, w_ref, o_ref):
    @pl.when(pl.program_id(0) < nv_ref[0])
    def _():
        y = jnp.dot(h_ref[...], w_ref[...], preferred_element_type=jnp.float32)
        if o_ref.dtype == jnp.uint32:       # packed rows, for the combine kernel
            half = y.shape[1] // 2
            W = half // _LANES              # lane tiles of words a row
            if _INTERPRET:      # the interpreter has no rule for the pack op
                words = row_gather.pack_rows(y.astype(h_ref.dtype))
            else:               # one instruction a word: rounds as astype does
                words = pltpu.pack_elementwise(
                    [y[:, :half], y[:, half:]], packed_dtype=jnp.bfloat16)
            for c in range(W):
                # lane tile c of every row of the tile, to that row's c-th tile
                o_ref[pl.ds(c, y.shape[0], stride=W), 0, :] = (
                    words[:, c * _LANES:(c + 1) * _LANES])
        else:
            o_ref[...] = y.astype(o_ref.dtype)


def _w_block(j):
    """The index map of the weight block (layer, the tile's expert), column
    block ``j``, from the prefetched tables."""
    return lambda t, te, nv, ly, *_: (ly[0], te[t], 0, j)


def _tile_call(kernel, name, prefetch, rows, weights, w_specs, n_out,
               packed=False, n_grid=None):
    """One kernel over the row tiles: ``rows [M, K]`` × the weight block of
    each tile's expert → ``[M, n_out]``, or with ``packed`` the same rows as
    :func:`row_gather.pack_rows` lays them out, ``[M · n_out / 256, 1, 128]`` uint32.
    ``prefetch`` is (tile → expert, number of real tiles, layer); a tile past
    the last real one maps to that one's blocks (nothing is fetched or
    written for it) — or, with ``n_grid`` (a count the device holds), is not
    visited at all (575 such steps cost 0.06–0.07 ms a kernel at the laguna
    cell's shape: PERF.md §6, PR 34)."""
    M, K = rows.shape
    tm = TILE_ROWS

    def row_block(t, te, nv, ly):
        return (jnp.minimum(t, nv[0] - 1), 0)

    if packed:
        words = n_out // 2 // _LANES
        out_spec = pl.BlockSpec((tm * words, 1, _LANES),
                                lambda *a: (*row_block(*a), 0))
        out_shape = jax.ShapeDtypeStruct((M * words, 1, _LANES), jnp.uint32)
    else:
        out_spec = pl.BlockSpec((tm, n_out), row_block)
        out_shape = jax.ShapeDtypeStruct((M, n_out), rows.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(M // tm if n_grid is None else n_grid,),
            in_specs=[pl.BlockSpec((tm, K), row_block), *w_specs],
            out_specs=out_spec,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name=name,
        interpret=_INTERPRET,
    )(*prefetch, rows, *weights)


def _group_tiles(sizes, n_rows, share):
    """The expert-aligned tiles of groups of ``sizes [E]``: ``shift [E]``
    (a group's tiled rows less its sorted positions), the number of real
    tiles ``[1]``, the tiles' numbers and each tile's expert ``[n_tiles]``.
    Under a ``share`` the first tile counts as real even where no row is
    held, so that tiled row 0 is always written."""
    E, tm = sizes.shape[0], TILE_ROWS
    start = jnp.cumsum(sizes) - sizes               # of each group, sorted rows
    padded = (sizes + tm - 1) // tm * tm
    p_end = jnp.cumsum(padded)
    shift = p_end - padded - start                  # tiled row - sorted position
    n_tiles = (n_rows + E * (tm - 1)) // tm         # static bound on Σ⌈size/tm⌉
    n_valid = p_end[-1:] // tm                      # [1]
    if share:
        n_valid = jnp.maximum(n_valid, 1)
    tile = jnp.arange(n_tiles, dtype=jnp.int32)
    # a tile past the end keeps the last real tile's expert: no new weights
    tile_expert = jnp.searchsorted(
        p_end, jnp.minimum(tile, n_valid[0] - 1) * tm, side="right"
    ).astype(jnp.int32)
    if share:       # (no row held at all: the first tile is some held expert's)
        tile_expert = jnp.minimum(tile_expert, E - 1)
    return shift, n_valid, tile, tile_expert


def _tiled_rows(experts, shift):
    """Sorted row → its tiled row: its expert's shift, by a one-hot sum over
    the E experts (an absent expert's row matches none and keeps its place)."""
    mine = experts[:, None] == jnp.arange(shift.shape[0], dtype=jnp.int32)[None, :]
    return jnp.arange(experts.shape[0], dtype=jnp.int32) + jnp.sum(
        jnp.where(mine, shift[None, :], 0), axis=1)


def _tile_layout(idx, n_experts, share=False):
    """The expert-aligned layout of the routed rows ``idx [T, k]``: each
    tile's expert ``[n_tiles]``, the number of real tiles ``[1]``, the token
    each tiled row holds ``[M]`` and each flat slot's tiled row ``[T·k]``.

    Under a ``share`` (ids of :func:`_held`) only the held groups get tiles.
    This is the layout XLA's gathers read, which move every row of the
    static bound: the absent rows' slots point at tiled row 0 (their gate is
    0, so the sum reads a finite row and adds nothing). Where the row kernels
    take the shape, :func:`_held_layout` is used instead."""
    T, k = idx.shape
    N, tm = T * k, TILE_ROWS
    experts, order, sizes = _by_expert(idx, n_experts)
    shift, n_valid, tile, tile_expert = _group_tiles(sizes, N, share)
    # tiled row -> the sorted row it holds: its tile's shift, taken once a
    # tile (a padding row holds some other row again; nothing reads its result)
    sorted_pos = jnp.clip(
        (tile * tm - shift[tile_expert])[:, None] + jnp.arange(tm, dtype=jnp.int32),
        0, N - 1).reshape(-1)
    dest = _tiled_rows(experts, shift)
    if share:
        dest = jnp.where(experts < n_experts, dest, 0)
    return tile_expert, n_valid, (order // k)[sorted_pos], _unsort(order, dest)


def _experts_tiles(x, idx, gates, w_gate_up, w_down, layer, share=False):
    """The kernel form: expert-aligned row tiles; the weights stay stacked
    ``[L, E, ...]`` and a block is fetched by (layer, the tile's expert).
    Where the row-gather kernel takes the shape (``row_gather.supported``)
    ``moe_down`` writes its rows packed for it and the combine is that
    kernel (``Σ_s gates[t, s] · y[rows[t·k + s]]``, as :func:`_combine` of
    the gathered rows); elsewhere the rows are gathered and summed by XLA.
    Under a ``share`` the rows go in and out by :func:`_held_rows`."""
    from crosscoder_tpu import obs

    T, k = idx.shape
    _, E, F, D = w_down.shape
    if share and row_gather.held_supported(T, k, D, x.dtype):
        return _held_rows(x, idx, gates, w_gate_up, w_down, layer)
    if share:
        obs.count("harvest/moe_rows_in_xla_traces")
    tile_expert, n_valid, token, rows = _tile_layout(idx, E, share)
    xs = x[token]                                               # [M, D]
    kernel = row_gather.supported(T, k, D, x.dtype)
    obs.count("harvest/moe_combine_kernel_traces" if kernel
              else "harvest/moe_combine_xla_traces")

    prefetch = (tile_expert, n_valid, jnp.asarray(layer, jnp.int32).reshape(1))
    h = _tile_call(
        _gate_up_kernel, "moe_gate_up", prefetch, xs, (w_gate_up, w_gate_up),
        [pl.BlockSpec((None, None, D, F), _w_block(0)),    # gate columns [0, F)
         pl.BlockSpec((None, None, D, F), _w_block(1))],   # up columns [F, 2F)
        F)
    y = _tile_call(
        _down_kernel, "moe_down", prefetch, h, (w_down,),
        [pl.BlockSpec((None, None, F, D), _w_block(0))], D, packed=kernel)
    if kernel:
        return row_gather.weighted_sum(
            rows, gates, y, D, name="expert_combine", interpret=_INTERPRET)
    return _combine(y[rows].reshape(T, k, D), gates)


# ---------------------------------------------------------------------------
# the tile form under a held share: rows move by DMA, over the rows really held


def _held_layout(idx, gates, n_experts):
    """:func:`_tile_layout` for the row kernels under a share (ids and gates
    of :func:`_held`): each tile's expert ``[n_tiles]``, the number of real
    tiles ``[1]``, each tile's first sorted position ``[n_tiles]`` and each
    sorted position's token ``[N]`` (a tile's rows are consecutive sorted
    rows: the kernel addresses them itself) — and, for the combine, the HELD
    (token, slot) pairs alone, in token order at the front of ``[N]`` tables:
    their tiled rows, their tokens, their gates and their number ``[1]``.
    The same two sorts as the other layout, each taking the gates along; no
    gather of N indices."""
    T, k = idx.shape
    N, tm = T * k, TILE_ROWS
    experts, order, sizes, gates = _by_expert(idx, n_experts, gates)
    shift, n_valid, tile, tile_expert = _group_tiles(sizes, N, True)
    # the held pairs are the first Σ sizes sorted rows; by flat slot they are
    # in token order, every absent pair behind them
    slot, rows, gates = jax.lax.sort(
        (jnp.where(experts < n_experts, order, N), _tiled_rows(experts, shift), gates),
        num_keys=1)
    return (tile_expert, n_valid, tile * tm - shift[tile_expert], order // k,
            (rows, slot // k, gates, jnp.sum(sizes).reshape(1)))


def _gate_up_rows_kernel(te_ref, nv_ref, ly_ref, first_ref, tok_ref, x_ref,
                         wg_ref, wu_ref, o_ref, buf, x_tile, sem):
    """:func:`_gate_up_kernel` that brings its own rows in: tile ``t`` holds
    the sorted rows ``first[t] + [0, 128)``, whose tokens' rows of ``x``
    (packed, left in HBM) are fetched by DMA, one copy a row, the next tile's
    while this one is multiplied; one wait a tile by byte count. The grid is
    the real tiles alone."""
    tm, D = x_tile.shape
    half = D // 2
    W = half // _LANES                      # lane tiles of words a row
    t, nv = pl.program_id(0), nv_ref[0]
    slot = jax.lax.rem(t, 2)
    last = tok_ref.shape[0] - 1
    tiles = buf.reshape(2 * tm * W, _LANES)

    def fetch(tile, slot):
        def some(m, carry):
            def one(q, carry):
                r = m * 8 + q
                # a padding row of the tile holds some other row again
                tok = tok_ref[jnp.minimum(first_ref[tile] + r, last)]
                pltpu.make_async_copy(
                    x_ref.at[pl.ds(tok * W, W)],
                    buf.at[slot, pl.ds(r * W, W)], sem.at[slot]).start()
                return carry
            # eight written out an iteration, when the kernel is LOWERED
            return jax.lax.fori_loop(0, 8, one, carry, unroll=True)
        jax.lax.fori_loop(0, tm // 8, some, 0)

    @pl.when(t == 0)
    def _():
        fetch(0, 0)

    pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()

    @pl.when(t + 1 < nv)
    def _():
        fetch(t + 1, 1 - slot)

    def unpack(c, carry):       # lane tile c of every row, to its two column halves
        lo, hi = row_gather.unpack_words(tiles[pl.ds(slot * tm * W + c, tm, stride=W), :])
        col = pl.multiple_of(c * _LANES, _LANES)
        x_tile[:, pl.ds(col, _LANES)] = lo.astype(x_tile.dtype)
        x_tile[:, pl.ds(half + col, _LANES)] = hi.astype(x_tile.dtype)
        return carry
    jax.lax.fori_loop(0, W, unpack, 0)
    _gated_product(x_tile[...], wg_ref, wu_ref, o_ref)


def _gate_up_rows(tile_expert, n_valid, layer, first, token, x_packed, w_gate_up, dtype):
    """``moe_gate_up`` over the real tiles, from ``x_packed`` (``row_gather.
    packed``'s layout) and :func:`_held_layout`'s tables → ``[M, F]``."""
    _, _, D, F = w_gate_up.shape
    F //= 2
    tm, W = TILE_ROWS, D // 2 // _LANES
    M = tile_expert.shape[0] * tm

    return pl.pallas_call(
        _gate_up_rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_valid[0],),     # the real tiles: a count the device holds
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((None, None, D, F), _w_block(0)),
                      pl.BlockSpec((None, None, D, F), _w_block(1))],
            out_specs=pl.BlockSpec((tm, F), lambda t, *_: (t, 0)),
            scratch_shapes=[pltpu.VMEM((2, tm * W, 1, _LANES), jnp.uint32),
                            pltpu.VMEM((tm, D), dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((M, F), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="moe_gate_up",
        interpret=_INTERPRET,
    )(tile_expert, n_valid, layer, first, token, x_packed, w_gate_up, w_gate_up)


def _held_rows(x, idx, gates, w_gate_up, w_down, layer):
    """:func:`_experts_tiles` under a share, at a shape the row kernels take
    (``row_gather.held_supported``): the buffers keep their static bound —
    every token may choose k held experts — and no row moves for a row that
    is not there. ``moe_gate_up`` fetches x's rows for its real tiles itself
    (no ``[M, D]`` copy of x is made), and the combine walks the held pairs
    alone (``row_gather.held_sums``): a token with no held slot gets 0."""
    from crosscoder_tpu import obs

    obs.count("harvest/moe_rows_in_kernel_traces")
    obs.count("harvest/moe_combine_kernel_traces")
    obs.count("harvest/moe_held_combine_traces")
    T, k = idx.shape
    _, E, F, D = w_down.shape
    tile_expert, n_valid, first, token, pairs = _held_layout(idx, gates, E)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    h = _gate_up_rows(tile_expert, n_valid, layer, first, token,
                      row_gather.packed(x, interpret=_INTERPRET), w_gate_up, x.dtype)
    y = _tile_call(
        _down_kernel, "moe_down", (tile_expert, n_valid, layer), h, (w_down,),
        [pl.BlockSpec((None, None, F, D), _w_block(0))], D, packed=True,
        n_grid=n_valid[0])
    return row_gather.held_sums(*pairs, y, T, D, name="expert_combine",
                                interpret=_INTERPRET)


def moe_mlp(
    x: jax.Array, w_router: jax.Array, w_gate_up: jax.Array, w_down: jax.Array,
    layer=0, *, top_k: int, norm_topk_prob: bool, routed_scale: float = 1.0,
    first_expert: int = 0, router: str = "softmax", router_bias: jax.Array | None = None,
) -> jax.Array:
    """The expert layer ``layer`` on the normed stream ``x [B, S, D]``:
    ``w_router [D, E]`` (that layer's), and the STACKED expert weights
    ``w_gate_up [L, E_held, D, 2·F]`` (gate columns first) and ``w_down
    [L, E_held, F, D]``, indexed in place by ``layer`` (traced or not): one
    layer's experts are too large to slice out for a kernel → ``[B, S, D]``
    in ``x``'s dtype. Where ``E_held < E`` the leaves are the experts
    ``[first_expert, first_expert + E_held)`` and the result is their part
    of the routed sum (the module's docstring). ``router`` is :func:`route`'s
    ``kind`` and ``router_bias [E]`` that layer's choice bias."""
    from crosscoder_tpu import obs

    B, S, D = x.shape
    x2 = x.reshape(B * S, D)
    share = w_down.shape[1] < w_router.shape[1]
    with jax.named_scope("harvest/block/moe/route"):
        if router != "softmax":
            obs.count("harvest/moe_sigmoid_traces")
        idx, gates = route(x2, w_router, top_k, norm_topk_prob, routed_scale,
                           router, router_bias)
        if share:
            obs.count("harvest/moe_held_traces")
            idx, gates = _held(idx, gates, first_expert, w_down.shape[1])
    with jax.named_scope("harvest/block/moe/experts"):
        if enabled() and supported(D, w_down.shape[2], x.dtype):
            obs.count("harvest/moe_tiles_traces")
            out = _experts_tiles(x2, idx, gates, w_gate_up, w_down, layer, share)
        else:
            obs.count("harvest/moe_ragged_traces")
            out = _experts_ragged(x2, idx, gates, w_gate_up, w_down, layer, share)
    return out.reshape(B, S, D)


def load_max_over_mean(counts: jax.Array) -> float:
    """Rows at the busiest expert over the mean, from ``[..., E]`` routed-row
    counts: 1.0 is perfectly even routing, E one expert taking all."""
    counts = np.asarray(counts, np.float64)
    return float(np.max(counts / np.mean(counts, axis=-1, keepdims=True)))


def local_row_share(counts: jax.Array, first_expert: int, n_held: int) -> float:
    """Routed rows that go to the held experts over all routed rows, from
    ``[..., E]`` counts, the mean over the leading axes: ``n_held / E`` where
    routing is even."""
    counts = np.asarray(counts, np.float64)
    held = counts[..., first_expert:first_expert + n_held].sum(-1)
    return float(np.mean(held / counts.sum(-1)))
