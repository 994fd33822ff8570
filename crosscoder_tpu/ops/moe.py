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
bound (every token may choose k held experts). With every expert held the
layer is the whole one, op for op.

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
  and sums them.
- ``ragged`` (everything else — the CPU backend, a mesh, an unsupported
  shape): rows sorted by expert, ``jax.lax.ragged_dot``. Also the oracle the
  kernels are pinned against (tests/test_moe.py).

All round at the same places (the gated hidden and each expert's output to
the model's dtype; the weighted combine in float32, rounded once). The
choices are counted in the job's telemetry plane, once per trace:
``harvest/moe_tiles_traces`` / ``harvest/moe_ragged_traces`` and, within the
tile form, ``harvest/moe_combine_kernel_traces`` /
``harvest/moe_combine_xla_traces``. No environment gate, no config field.
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
    routed_scale: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """``x [T, D]`` → the chosen experts ``[T, k]`` int32 (largest gate
    first; ties to the lowest index) and their gates ``[T, k]`` float32
    (times ``routed_scale``, after the renormalisation)."""
    logits = jnp.einsum("td,de->te", x, w_router, preferred_element_type=jnp.float32)
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


def _by_expert(idx: jax.Array, n_experts: int):
    """The (token, slot) rows sorted by expert (stable): the experts in
    sorted order ``[N]``, ``order [N]`` (sorted position → flat slot
    ``t·k + s``) and the group sizes ``[E]``. Sorts and a 64-query search:
    on the chip an element-wise gather or scatter of N indices costs more
    than a sort of them (PERF.md §6, PR 29)."""
    flat = idx.reshape(-1)
    slots = jnp.arange(flat.shape[0], dtype=jnp.int32)
    experts, order = jax.lax.sort((flat, slots), num_keys=1)
    ends = jnp.searchsorted(
        experts, jnp.arange(n_experts, dtype=jnp.int32), side="right")
    return experts, order, jnp.diff(ends, prepend=0).astype(jnp.int32)


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


def _gate_up_kernel(te_ref, nv_ref, ly_ref, x_ref, wg_ref, wu_ref, o_ref):
    @pl.when(pl.program_id(0) < nv_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        o_ref[...] = (g * jax.nn.sigmoid(g) * u).astype(o_ref.dtype)


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


def _tile_call(kernel, name, prefetch, rows, weights, w_specs, n_out,
               packed=False):
    """One kernel over the row tiles: ``rows [M, K]`` × the weight block of
    each tile's expert → ``[M, n_out]``, or with ``packed`` the same rows as
    :func:`row_gather.pack_rows` lays them out, ``[M · n_out / 256, 1, 128]`` uint32.
    ``prefetch`` is (tile → expert, number of real tiles, layer); a tile past
    the last real one maps to that one's blocks (nothing is fetched or
    written for it)."""
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
            grid=(M // tm,),
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


def _tile_layout(idx, n_experts, share=False):
    """The expert-aligned layout of the routed rows ``idx [T, k]``: each
    tile's expert ``[n_tiles]``, the number of real tiles ``[1]``, the token
    each tiled row holds ``[M]`` and each flat slot's tiled row ``[T·k]``.

    Under a ``share`` (ids of :func:`_held`) only the held groups get tiles.
    The absent rows' slots point at tiled row 0 (their gate is 0, so the
    combine reads a finite row and adds nothing), and the first tile counts
    as real even where no row is held, so that row 0 is always written."""
    T, k = idx.shape
    E, N, tm = n_experts, T * k, TILE_ROWS
    experts, order, sizes = _by_expert(idx, E)
    start = jnp.cumsum(sizes) - sizes               # of each group, sorted rows
    padded = (sizes + tm - 1) // tm * tm
    p_end = jnp.cumsum(padded)
    shift = p_end - padded - start                  # tiled row - sorted position
    n_tiles = (N + E * (tm - 1)) // tm              # static bound on Σ⌈size/tm⌉
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
    # tiled row -> the sorted row it holds: its tile's shift, taken once a
    # tile (a padding row holds some other row again; nothing reads its result)
    sorted_pos = jnp.clip(
        (tile * tm - shift[tile_expert])[:, None] + jnp.arange(tm, dtype=jnp.int32),
        0, N - 1).reshape(-1)
    # sorted row -> its tiled row (its expert's shift, by a one-hot sum over
    # the E experts), then by flat slot
    mine = experts[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :]
    dest = jnp.arange(N, dtype=jnp.int32) + jnp.sum(
        jnp.where(mine, shift[None, :], 0), axis=1)
    if share:
        dest = jnp.where(experts < E, dest, 0)
    return tile_expert, n_valid, (order // k)[sorted_pos], _unsort(order, dest)


def _experts_tiles(x, idx, gates, w_gate_up, w_down, layer, share=False):
    """The kernel form: expert-aligned row tiles; the weights stay stacked
    ``[L, E, ...]`` and a block is fetched by (layer, the tile's expert).
    Where the row-gather kernel takes the shape (``row_gather.supported``)
    ``moe_down`` writes its rows packed for it and the combine is that
    kernel (``Σ_s gates[t, s] · y[rows[t·k + s]]``, as :func:`_combine` of
    the gathered rows); elsewhere the rows are gathered and summed by XLA."""
    from crosscoder_tpu import obs

    T, k = idx.shape
    _, E, F, D = w_down.shape
    tile_expert, n_valid, token, rows = _tile_layout(idx, E, share)
    xs = x[token]                                               # [M, D]
    kernel = row_gather.supported(T, k, D, x.dtype)
    obs.count("harvest/moe_combine_kernel_traces" if kernel
              else "harvest/moe_combine_xla_traces")

    def w_block(j):
        return lambda t, te, nv, ly: (ly[0], te[t], 0, j)

    prefetch = (tile_expert, n_valid, jnp.asarray(layer, jnp.int32).reshape(1))
    h = _tile_call(
        _gate_up_kernel, "moe_gate_up", prefetch, xs, (w_gate_up, w_gate_up),
        [pl.BlockSpec((None, None, D, F), w_block(0)),     # gate columns [0, F)
         pl.BlockSpec((None, None, D, F), w_block(1))],    # up columns [F, 2F)
        F)
    y = _tile_call(
        _down_kernel, "moe_down", prefetch, h, (w_down,),
        [pl.BlockSpec((None, None, F, D), w_block(0))], D, packed=kernel)
    if kernel:
        return row_gather.weighted_sum(
            rows, gates, y, D, name="expert_combine", interpret=_INTERPRET)
    return _combine(y[rows].reshape(T, k, D), gates)


def moe_mlp(
    x: jax.Array, w_router: jax.Array, w_gate_up: jax.Array, w_down: jax.Array,
    layer=0, *, top_k: int, norm_topk_prob: bool, routed_scale: float = 1.0,
    first_expert: int = 0,
) -> jax.Array:
    """The expert layer ``layer`` on the normed stream ``x [B, S, D]``:
    ``w_router [D, E]`` (that layer's), and the STACKED expert weights
    ``w_gate_up [L, E_held, D, 2·F]`` (gate columns first) and ``w_down
    [L, E_held, F, D]``, indexed in place by ``layer`` (traced or not): one
    layer's experts are too large to slice out for a kernel → ``[B, S, D]``
    in ``x``'s dtype. Where ``E_held < E`` the leaves are the experts
    ``[first_expert, first_expert + E_held)`` and the result is their part
    of the routed sum (the module's docstring)."""
    from crosscoder_tpu import obs

    B, S, D = x.shape
    x2 = x.reshape(B * S, D)
    share = w_down.shape[1] < w_router.shape[1]
    with jax.named_scope("harvest/block/moe/route"):
        idx, gates = route(x2, w_router, top_k, norm_topk_prob, routed_scale)
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
