"""The sparse-expert layer (crosscoder_tpu/ops/moe.py) alone: both forms of
the grouped product against the plain reference's loop over experts
(benchmarks/reference/mellum_ref.py, which shares no code with it), under
even and heavily skewed routing; the combine kernel against the gathered
sum it replaces; the router's choice; the load gauge."""

import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.reference import mellum_ref   # noqa: E402
from crosscoder_tpu import obs                 # noqa: E402
from crosscoder_tpu.config import CrossCoderConfig   # noqa: E402
from crosscoder_tpu.ops import moe, row_gather   # noqa: E402

L, E, D, F, K = 2, 8, 128, 128, 3


@pytest.fixture
def interpret():
    moe.set_interpret(True)
    yield
    moe.set_interpret(False)


def _layer(seed, skew: bool):
    """Float32 weights of L stacked expert layers; ``skew`` biases the
    router so that expert 5 takes nearly every token's first slot and
    experts 0 and 1 take none."""
    ks = jax.random.split(jax.random.key(seed), 4)
    w_router = jax.random.normal(ks[0], (D, E)) * D ** -0.5
    if skew:
        w_router = w_router.at[:, 5].multiply(0.0).at[0, 5].set(12.0)
        w_router = w_router.at[:, :2].multiply(0.0).at[0, :2].set(-12.0)
    return (w_router,
            jax.random.normal(ks[1], (L, E, D, 2 * F)) * D ** -0.5,
            jax.random.normal(ks[2], (L, E, F, D)) * F ** -0.5)


def _x(seed, tokens, skew):
    x = jax.random.normal(jax.random.key(100 + seed), (1, tokens, D))
    # the skewed router reads channel 0: keep it positive for most tokens
    return x.at[:, :, 0].set(jnp.abs(x[:, :, 0]) + 0.5) if skew else x


def _reference(x, w_router, w_gate_up, w_down, layer):
    cfg = types.SimpleNamespace(d_expert=F, n_experts=E, experts_per_tok=K,
                                norm_topk_prob=True)
    with jax.default_matmul_precision("highest"):
        return mellum_ref.moe(x, {"router": w_router},
                              {"we_gate_up": w_gate_up, "we_down": w_down}, layer, cfg)


# Float32 operands on the CPU: the grouped forms and the plain loop compute
# the same products and differ by the order of float32 sums (ragged_dot and
# the kernels' dots against ``@``; the combine's k terms against the loop's E
# terms, of which E - k are exact zeros): a few roundings on outputs of
# magnitude 1-3, seen at 1.2e-6. 1e-5 leaves that room; a row sent to the
# wrong expert, a dropped row or a wrong gate moves an output by O(0.1).
ATOL = 1e-5


@pytest.mark.parametrize("tokens", [300, 64], ids=["300tok", "64tok"])
@pytest.mark.parametrize("skew", [False, True], ids=["even", "skewed"])
@pytest.mark.parametrize("form", ["ragged", "tiles"])
def test_expert_layer_matches_the_plain_loop(form, skew, tokens, request):
    if form == "tiles":
        request.getfixturevalue("interpret")
    w_router, w_gate_up, w_down = _layer(7, skew)
    x = _x(7, tokens, skew)
    idx, _ = moe.route(x[0], w_router, K, True)
    sizes = np.bincount(np.asarray(idx).reshape(-1), minlength=E)
    if skew:    # one expert takes most rows, some take none
        assert sizes[5] >= 0.9 * tokens and sizes[:2].sum() == 0 and sizes.max() > 2 * sizes.mean()
    else:
        assert sizes.min() > 0
    assert (moe.enabled() and moe.supported(D, F, x.dtype)) == (form == "tiles")
    for layer in (0, 1):
        got = moe.moe_mlp(x, w_router, w_gate_up, w_down, jnp.int32(layer),
                          top_k=K, norm_topk_prob=True)
        want = _reference(x, w_router, w_gate_up, w_down, layer)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=ATOL)


# ---------------------------------------------------------------------------
# the combine kernel (bf16 rows, D a multiple of 256)

DC = 256


def _bf16_ulp(v):
    """The spacing of bfloat16 (8 significant bits) at ``|v|`` — and, for a
    sum that cancels to nearly nothing, the float32 roundings of its k terms
    of magnitude up to 4 (2^-24 each), which are then the larger."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(v), 1e-30))) - 7) + 1e-6


def _combine_case(seed, tokens, skew):
    """A routing's own row table (``_tile_layout``) over random bf16 rows."""
    w_router, _, _ = _layer(seed, skew)
    idx, gates = moe.route(_x(seed, tokens, skew)[0], w_router, K, True)
    _, _, token, rows = moe._tile_layout(idx, E)
    y = jax.random.normal(jax.random.key(200 + seed), (token.shape[0], DC), jnp.bfloat16)
    return idx, gates, rows, y


def _kernel_combine(rows, gates, y):
    return row_gather.weighted_sum(rows, gates, row_gather.packed(y, interpret=True), DC,
                                   name="expert_combine", interpret=True)


@pytest.mark.parametrize("tokens,skew", [(256, False), (256, True), (300, False), (72, True)],
                         ids=["even", "skewed", "300tok-not-a-whole-tile", "72tok-skewed"])
def test_combine_kernel_matches_the_gathered_sum(tokens, skew, interpret):
    idx, gates, rows, y = _combine_case(11, tokens, skew)
    sizes = np.bincount(np.asarray(idx).reshape(-1), minlength=E)
    if skew:
        assert sizes[5] >= 0.9 * tokens and sizes.max() > 2 * sizes.mean()
    assert row_gather.supported(tokens, K, DC, jnp.bfloat16)
    got = _kernel_combine(rows, gates, y)
    want = moe._combine(y[rows].reshape(tokens, K, DC), gates)
    assert got.shape == want.shape and got.dtype == want.dtype == jnp.bfloat16
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # Both forms sum the same k float32 products ``gate * row`` of one token
    # and round the sum to bf16 once. They differ only in the ORDER of the
    # float32 additions (XLA's reduce over the slot axis against the
    # kernel's slot-by-slot accumulation): a few float32 roundings, which
    # can carry the one bf16 rounding across a tie — one bf16 ulp at most,
    # on a few elements in 10^5. A wrong row, gate or half moves O(1).
    assert (np.abs(got - want) <= _bf16_ulp(want)).all()
    assert (got != want).mean() < 1e-3


def test_combine_kernel_fails_on_a_swapped_table(interpret):
    """The planted fault: two tokens trade one row of the table."""
    tokens = 256
    _, gates, rows, y = _combine_case(12, tokens, False)
    want = np.asarray(moe._combine(y[rows].reshape(tokens, K, DC), gates), np.float32)
    a, b = 17 * K + 1, 201 * K + 2
    swapped = rows.at[a].set(rows[b]).at[b].set(rows[a])
    got = np.asarray(_kernel_combine(swapped, gates, y), np.float32)
    wrong = (np.abs(got - want) > _bf16_ulp(want)).any(axis=1)
    assert set(np.flatnonzero(wrong)) == {17, 201}


def test_packed_rows_hold_both_halves():
    y = jax.random.normal(jax.random.key(9), (16, DC), jnp.bfloat16)
    words = np.asarray(row_gather.pack_rows(y))
    assert words.dtype == np.uint32 and words.shape == (16, DC // 2)
    bits = np.asarray(jax.lax.bitcast_convert_type(y, jnp.uint16)).astype(np.uint32)
    np.testing.assert_array_equal(words & 0xFFFF, bits[:, :DC // 2])
    np.testing.assert_array_equal(words >> 16, bits[:, DC // 2:])


@pytest.mark.parametrize("n_tokens,top_k,d_model,dtype,want", [
    (4096, 8, 2304, jnp.bfloat16, True),      # the mellum2 cell
    (4096, 8, 2304, jnp.float32, False),      # rows are packed two bf16 a word
    (4096, 8, 2176, jnp.bfloat16, False),     # half a row is not whole lanes
    (2 ** 16, 8, 2304, jnp.bfloat16, True),   # the row table (2 MiB) passes SMEM: slices of the batch
    (4096, 8, 2 ** 15, jnp.bfloat16, True),   # a 128-token tile passes VMEM: a smaller tile
    (4096, 32, 2 ** 15, jnp.bfloat16, False),  # not even one group's double buffer fits
    (8, 8, 2304, jnp.bfloat16, False),        # less than one group of tokens
], ids=["cell", "float32", "half-lanes", "smem-sliced", "vmem-smaller-tile", "vmem", "tokens"])
def test_combine_supported(n_tokens, top_k, d_model, dtype, want):
    assert row_gather.supported(n_tokens, top_k, d_model, dtype) is want


@pytest.mark.parametrize("skew", [False, True], ids=["even", "skewed"])
def test_bf16_tile_form_with_the_combine_kernel_matches_ragged(skew, interpret, tmp_path):
    """The whole tile form at a shape the combine kernel takes (bf16, D 256)
    against ``_experts_ragged`` on the same routing."""
    tokens = 300
    ks = jax.random.split(jax.random.key(21), 3)
    w_router = _layer(21, skew)[0]
    w_gate_up = (jax.random.normal(ks[0], (L, E, DC, 2 * F)) * DC ** -0.5).astype(jnp.bfloat16)
    w_down = (jax.random.normal(ks[1], (L, E, F, DC)) * F ** -0.5).astype(jnp.bfloat16)
    x = jax.random.normal(ks[2], (tokens, DC), jnp.bfloat16)
    idx, gates = moe.route(_x(21, tokens, skew)[0], w_router, K, True)
    cfg = CrossCoderConfig(obs="on", obs_dir=str(tmp_path / "obs"), log_backend="null")
    plane = obs.acquire(cfg)
    try:
        got = moe._experts_tiles(x, idx, gates, w_gate_up, w_down, jnp.int32(1))
        assert plane.registry.get_count("harvest/moe_combine_kernel_traces") == 1
        assert plane.registry.get_count("harvest/moe_combine_xla_traces") == 0
    finally:
        plane.close()
    want = moe._experts_ragged(x, idx, gates, w_gate_up, w_down, jnp.int32(1))
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # Same roundings in both (gated hidden and each expert's row to bf16, the
    # weighted sum in float32, one rounding of it), but the products'
    # float32 sums run in another order (ragged_dot against the kernels'
    # dots), so a hidden value or a row can land one bf16 ulp apart, and k
    # such rows sum into an output: a few ulp of the LARGEST row, not of the
    # output. Outputs are O(1); rows O(1): 4 ulp at 2 = 6.3e-2. A wrong
    # expert or gate moves O(1) on every element of a token.
    assert np.abs(got - want).max() <= 6.3e-2
    assert np.median(np.abs(got - want)) <= 4e-3


def test_the_routers_choice_is_the_references_exactly():
    w_router, _, _ = _layer(3, False)
    x = _x(3, 512, False)[0]
    idx, gates = moe.route(x, w_router, K, True)
    with jax.default_matmul_precision("highest"):
        chosen, want = mellum_ref.routing(x, w_router, K, True)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)
    # ties go to the lowest index: a router of equal columns picks 0..K-1
    idx, gates = moe.route(x, jnp.ones((D, E)), K, False)
    assert (np.asarray(idx) == np.arange(K)).all()
    np.testing.assert_allclose(np.asarray(gates), 1.0 / E, rtol=1e-6)


def test_the_router_stays_float32_under_a_bf16_model():
    w_router, _, _ = _layer(4, False)
    x = _x(4, 64, False)[0].astype(jnp.bfloat16)
    idx, gates = moe.route(x, w_router.astype(jnp.bfloat16), K, True)
    assert gates.dtype == jnp.float32 and idx.dtype == jnp.int32


def test_which_form_ran_is_counted_once_per_trace(tmp_path, interpret):
    cfg = CrossCoderConfig(obs="on", obs_dir=str(tmp_path / "obs"), log_backend="null")
    plane = obs.acquire(cfg)
    try:
        w_router, w_gate_up, w_down = _layer(5, False)
        f = jax.jit(lambda x: moe.moe_mlp(x, w_router, w_gate_up, w_down, 0,
                                          top_k=K, norm_topk_prob=True))
        for _ in range(3):
            f(_x(5, 64, False))
        moe.set_interpret(False)
        moe.moe_mlp(_x(5, 64, False), w_router, w_gate_up, w_down, 0,
                    top_k=K, norm_topk_prob=True)
        assert plane.registry.get_count("harvest/moe_tiles_traces") == 1
        assert plane.registry.get_count("harvest/moe_ragged_traces") == 1
        # float32 rows: the tile form keeps XLA's gather and sum, and says so
        assert plane.registry.get_count("harvest/moe_combine_xla_traces") == 1
        assert plane.registry.get_count("harvest/moe_combine_kernel_traces") == 0
    finally:
        plane.close()


def test_load_max_over_mean():
    assert moe.load_max_over_mean(np.full((4, E), 7)) == 1.0
    one_hot = np.zeros((2, E)); one_hot[:, 3] = 10
    assert moe.load_max_over_mean(one_hot) == float(E)
    assert moe.load_max_over_mean([[1, 1, 2, 0], [1, 1, 1, 1]]) == 2.0


# ---------------------------------------------------------------------------
# a share of the experts held (PR 33): the router keeps its width, the rows
# of absent experts are neither grouped nor multiplied and count 0


def _share_reference(x, w_router, w_gate_up, w_down, layer, first, n_held, scale):
    """The plain loop over the HELD experts only, gates from the whole
    router (``mellum_ref``'s pieces; none of the program's)."""
    with jax.default_matmul_precision("highest"):
        x2 = x.reshape(-1, D)
        chosen, gates = mellum_ref.routing(x2, w_router, K, True)
        out = jnp.zeros_like(x2)
        for e in range(first, first + n_held):
            out = out + mellum_ref._expert_term(
                x2, chosen, gates * scale, w_gate_up, w_down, np.int32(layer), np.int32(e))
        return out.reshape(x.shape)


@pytest.mark.parametrize("tokens", [300, 64], ids=["300tok", "64tok"])
@pytest.mark.parametrize("skew", [False, True], ids=["even", "skewed"])
@pytest.mark.parametrize("form", ["ragged", "tiles"])
def test_a_held_share_matches_the_plain_loop_over_the_held_experts(form, skew, tokens, request):
    """Each of four ranks' two experts, in both forms: every row of a held
    expert is computed, none of an absent one; under the skewed router rank
    0 (experts 0, 1) holds NO routed row at all and rank 2 (experts 4, 5)
    nearly every one. The ranks' parts add up to the whole layer."""
    if form == "tiles":
        request.getfixturevalue("interpret")
    w_router, w_gate_up, w_down = _layer(11, skew)
    x = _x(11, tokens, skew)
    whole = moe.moe_mlp(x, w_router, w_gate_up, w_down, jnp.int32(1), top_k=K,
                        norm_topk_prob=True, routed_scale=2.5)
    np.testing.assert_allclose(
        np.asarray(whole), np.asarray(_share_reference(x, w_router, w_gate_up, w_down, 1, 0, E, 2.5)),
        rtol=0, atol=2.5 * ATOL)
    total = jnp.zeros_like(whole)
    for rank in range(4):
        held = slice(2 * rank, 2 * rank + 2)
        got = moe.moe_mlp(x, w_router, w_gate_up[:, held], w_down[:, held], jnp.int32(1),
                          top_k=K, norm_topk_prob=True, routed_scale=2.5, first_expert=2 * rank)
        want = _share_reference(x, w_router, w_gate_up, w_down, 1, 2 * rank, 2, 2.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2.5 * ATOL)
        assert bool(jnp.isfinite(got).all())
        if skew and rank == 0:
            assert not np.asarray(got).any()        # no row held: exactly nothing
        total = total + got
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=0, atol=5 * ATOL)


@pytest.mark.parametrize("skew,d_model,gated", [
    pytest.param(False, DC, True, id="even"), pytest.param(True, DC, True, id="skewed"),
    pytest.param(False, 384, True, id="even-d384-odd-lane-tiles"),
    pytest.param(True, 384, True, id="skewed-d384-odd-lane-tiles"),
    pytest.param(False, 384, False, id="even-d384-odd-lane-tiles-ungated"),
    pytest.param(True, 384, False, id="skewed-d384-odd-lane-tiles-ungated")])
def test_both_forms_agree_under_a_share_with_the_combine_kernel(skew, d_model, gated, interpret):
    """bf16 rows at a shape the row kernels take, a share of 2 of 8 experts:
    the tile form (x's rows fetched for the real tiles, the combine over the
    held pairs alone) against ``_experts_ragged`` on the same held routing;
    the tolerance is ``test_bf16_tile_form_with_the_combine_kernel_matches_ragged``'s.
    At D 384 (three lane tiles: a packed row's last high half is padding) gated
    and ungated, as Nemotron-H's 2688 is 21."""
    tokens = 300
    ks = jax.random.split(jax.random.key(23), 3)
    w_router = _layer(23, skew)[0]
    x = jax.random.normal(ks[2], (tokens, d_model), jnp.bfloat16)
    idx, gates = moe.route(_x(23, tokens, skew)[0], w_router, K, True, 2.5)
    up, width = ((L, 2, d_model, 2 * F), F) if gated else ((L, 2, FU, d_model), FU)
    for first in (0, 4):
        w_gate_up = (jax.random.normal(ks[0], up) * d_model ** -0.5).astype(jnp.bfloat16)
        w_down = (jax.random.normal(ks[1], (L, 2, width, d_model)) * width ** -0.5
                  ).astype(jnp.bfloat16)
        local, held_gates = moe._held(idx, gates, first, 2)
        assert int(local.max()) == 2 and float(held_gates[local == 2].sum()) == 0.0
        assert row_gather.held_supported(tokens, K, d_model, x.dtype)
        got = moe._experts_tiles(x, local, held_gates, w_gate_up, w_down, jnp.int32(1), True,
                                 gated)
        want = moe._experts_ragged(x, local, held_gates, w_gate_up, w_down, jnp.int32(1), True,
                                   gated)
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.isfinite(got).all()
        assert np.abs(got - want).max() <= 2.5 * 6.3e-2
        assert np.median(np.abs(got - want)) <= 2.5 * 4e-3
        # the layout: only held groups get tiles; the pair table holds the held
        # (token, slot) pairs alone, in token order, each with its own tiled row
        tile_expert, n_valid, tile_first, token, rows, toks, weights, n_live = (
            np.asarray(a) for a in jax.tree.leaves(moe._held_layout(local, held_gates, 2)))
        flat = np.asarray(local).reshape(-1)
        absent = flat == 2
        sizes = np.bincount(flat, minlength=3)[:2]
        assert int(tile_expert.max()) <= 1
        assert int(n_valid[0]) == max(int(np.ceil(sizes / moe.TILE_ROWS).sum()), 1)
        n = int(n_live[0])
        assert n == (~absent).sum()                         # no absent slot is fetched ...
        held_slots = np.flatnonzero(~absent)
        np.testing.assert_array_equal(toks[:n], held_slots // K)     # ... in token order
        np.testing.assert_array_equal(weights[:n], np.asarray(held_gates).reshape(-1)[held_slots])
        assert len(set(rows[:n])) == n                      # ... and no held row dropped
        # each pair's tiled row lies in a real tile of its own expert, and that
        # tile fetches the pair's token into that row
        assert (rows[:n] < int(n_valid[0]) * moe.TILE_ROWS).all()
        np.testing.assert_array_equal(tile_expert[rows[:n] // moe.TILE_ROWS], flat[held_slots])
        pos = tile_first[rows[:n] // moe.TILE_ROWS] + rows[:n] % moe.TILE_ROWS
        np.testing.assert_array_equal(token[pos], toks[:n])


def _plain_gate_up(x, tile_expert, n_valid, token, w_gate_up, layer):
    """``moe_gate_up`` as the whole layer runs it, on rows gathered by XLA."""
    from jax.experimental import pallas as pl

    d, f = w_gate_up.shape[2], w_gate_up.shape[3] // 2
    return moe._tile_call(
        moe._gate_up_kernel, "moe_gate_up", (tile_expert, n_valid, layer), x[token],
        (w_gate_up, w_gate_up),
        [pl.BlockSpec((None, None, d, f), moe._w_block(0)),
         pl.BlockSpec((None, None, d, f), moe._w_block(1))], f)


def _rows_in_case(tokens, skew, first, n_held=2, seed=31, d_model=DC):
    ks = jax.random.split(jax.random.key(seed), 2)
    x = jax.random.normal(ks[0], (tokens, d_model), jnp.bfloat16)
    w_gate_up = (jax.random.normal(ks[1], (L, n_held, d_model, 2 * F)) * d_model ** -0.5
                 ).astype(jnp.bfloat16)
    idx, gates = moe.route(_x(seed, tokens, skew)[0], _layer(seed, skew)[0], K, True)
    return x, w_gate_up, moe._held(idx, gates, first, n_held)


@pytest.mark.parametrize("tokens,skew,first,d_model", [
    (300, False, 0, DC), (64, False, 2, DC), (300, True, 0, DC), (300, True, 4, DC),
    (64, True, 4, DC), (300, True, 4, 384)],
    ids=["300tok-even", "64tok-even", "300tok-no-row-held", "300tok-nearly-every-row",
         "64tok-nearly-every-row", "300tok-nearly-every-row-d384-odd-lane-tiles"])
def test_the_held_tiles_fetch_their_own_rows(tokens, skew, first, d_model, interpret):
    """``moe_gate_up`` fetching x's rows by DMA for the real tiles alone
    against the plain kernel on XLA's gather of every tile's rows: the real
    tiles' results are the same bits (the same bf16 rows meet the same
    products); under the skewed router rank 0 holds no routed row (one tile
    counts as real all the same, so that tiled row 0 is written) and rank 2
    nearly every one (more real tiles than one: the double buffer turns). At
    D 384 a packed row's last high half is padding, never unpacked."""
    x, w_gate_up, (local, held_gates) = _rows_in_case(tokens, skew, first, d_model=d_model)
    layer = jnp.ones((1,), jnp.int32)
    tile_expert, n_valid, tile_first, token, _ = moe._held_layout(local, held_gates, 2)
    held = int((np.asarray(local) < 2).sum())
    if skew:
        assert held == 0 if first == 0 else held >= 0.9 * tokens
    got = moe._gate_up_rows(tile_expert, n_valid, layer, tile_first, token,
                            row_gather.packed(x, interpret=True), w_gate_up, x.dtype)
    te, nv, gathered, _ = moe._tile_layout(local, 2, True)
    np.testing.assert_array_equal(np.asarray(te), np.asarray(tile_expert))
    want = _plain_gate_up(x, te, nv, gathered, w_gate_up, layer)
    real = int(n_valid[0]) * moe.TILE_ROWS
    assert real >= moe.TILE_ROWS and (first != 4 or tokens < 128 or real > moe.TILE_ROWS)
    np.testing.assert_array_equal(np.asarray(got[:real], np.float32),
                                  np.asarray(want[:real], np.float32))


def test_the_held_tiles_row_fetch_fails_on_a_swapped_table(interpret):
    """The planted fault: two sorted positions trade their tokens."""
    x, w_gate_up, (local, held_gates) = _rows_in_case(300, False, 0)
    layer = jnp.ones((1,), jnp.int32)
    tile_expert, n_valid, tile_first, token, (rows, _, _, n_live) = moe._held_layout(
        local, held_gates, 2)
    assert int(n_valid[0]) >= 2
    a, b = int(tile_first[0]) + 5, int(tile_first[1]) + 9       # real rows of two tiles
    assert int(token[a]) != int(token[b])
    swapped = token.at[a].set(token[b]).at[b].set(token[a])
    run = lambda t: np.asarray(moe._gate_up_rows(      # noqa: E731
        tile_expert, n_valid, layer, tile_first, t,
        row_gather.packed(x, interpret=True), w_gate_up, x.dtype), np.float32)
    moved = (run(token) != run(swapped)).any(axis=1)
    # (a tile's padding rows hold some other sorted row again: only the rows
    # a held pair names are anyone's result)
    named = np.sort(np.asarray(rows)[:int(n_live[0])])
    assert set(named[moved[named]]) == {5, moe.TILE_ROWS + 9}


def _traced_ops(jaxpr, out):
    """Every equation's primitive, depth first through the sub-programs
    (``jit`` bodies, kernel bodies, loops and branches inside them)."""
    for eqn in jaxpr.eqns:
        out.append(str(eqn.primitive))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _traced_ops(sub, out)
    return out


def _whole_layer(dtype, d_model):
    w_router, _, _ = _layer(5, False)
    ks = jax.random.split(jax.random.key(5), 2)
    w_gate_up = (jax.random.normal(ks[0], (L, E, d_model, 2 * F)) * d_model ** -0.5).astype(dtype)
    w_down = (jax.random.normal(ks[1], (L, E, F, d_model)) * F ** -0.5).astype(dtype)
    w_router = jnp.zeros((d_model, E)).at[:D].set(w_router)
    x = jnp.zeros((1, 64, d_model), dtype).at[:, :, :D].set(_x(5, 64, False).astype(dtype))
    return x, lambda x: moe.moe_mlp(x, w_router, w_gate_up, w_down, 0, top_k=K,
                                    norm_topk_prob=True)


# The whole layer's traced ops in the tile form AS THE PARENT TRACED THEM
# (commit d8b8f0a, PR 33; the same script run on its checkout): the number of
# top-level equations, of equations at any depth (kernel bodies included), and
# the first 16 hex digits of the SHA-256 of their primitives joined by blanks.
# Float32 rows keep XLA's gather and sum; bf16 rows at D 256 take the combine
# kernel (``row_gather.weighted_sum`` over all k slots of every token).
PARENT_WHOLE_LAYER = {
    "float32-xla-combine": (jnp.float32, D, (86, 196, "a30b0ae31814a556")),
    "bf16-combine-kernel": (jnp.bfloat16, DC, (78, 300, "9422a609e16ae0b4")),
}


@pytest.mark.parametrize("case", list(PARENT_WHOLE_LAYER))
def test_every_expert_held_is_the_parents_tile_form_op_for_op(case, interpret, tmp_path):
    """With every expert held the tile form traces what it traced before the
    held rows moved by DMA: the layouts, the two product kernels and the
    combine are the parent's, equation for equation, inside the kernels too;
    none of the held form's counters moves."""
    import hashlib

    dtype, d_model, want = PARENT_WHOLE_LAYER[case]
    x, whole = _whole_layer(dtype, d_model)
    cfg = CrossCoderConfig(obs="on", obs_dir=str(tmp_path / "obs"), log_backend="null")
    plane = obs.acquire(cfg)
    try:
        jaxpr = jax.make_jaxpr(whole)(x).jaxpr
        get = plane.registry.get_count
        assert get("harvest/moe_tiles_traces") == 1
        assert [get(f"harvest/moe_{c}_traces") for c in (
            "held", "held_combine", "rows_in_kernel", "rows_in_xla")] == [0, 0, 0, 0]
    finally:
        plane.close()
    ops = _traced_ops(jaxpr, [])
    got = (len(jaxpr.eqns), len(ops),
           hashlib.sha256(" ".join(ops).encode()).hexdigest()[:16])
    assert got == want


def test_every_expert_held_is_the_whole_layer_op_for_op(tmp_path):
    """With as many expert leaves as the router is wide, ``moe_mlp`` traces
    what it traced before a share could be held (no ``_held``, no clamp, no
    mask, no scale) and the held form is not counted."""
    w_router, w_gate_up, w_down = _layer(5, False)
    x = _x(5, 64, False)

    def whole(x):
        return moe.moe_mlp(x, w_router, w_gate_up, w_down, 0, top_k=K, norm_topk_prob=True)

    def by_hand(x):     # the layer as PR 29 wrote it
        x2 = x.reshape(-1, D)
        idx, gates = moe.route(x2, w_router, K, True)
        return moe._experts_ragged(x2, idx, gates, w_gate_up, w_down, 0).reshape(x.shape)

    strip = lambda j: [str(e.primitive) for e in j.jaxpr.eqns]     # noqa: E731
    flat = lambda f: strip(jax.make_jaxpr(f)(x))                    # noqa: E731
    assert flat(whole) == flat(by_hand)
    cfg = CrossCoderConfig(obs="on", obs_dir=str(tmp_path / "obs"), log_backend="null")
    plane = obs.acquire(cfg)
    try:
        whole(x)
        assert plane.registry.get_count("harvest/moe_held_traces") == 0
        moe.moe_mlp(x, w_router, w_gate_up[:, :4], w_down[:, :4], 0, top_k=K,
                    norm_topk_prob=True, first_expert=4)
        assert plane.registry.get_count("harvest/moe_held_traces") == 1
    finally:
        plane.close()


@pytest.mark.parametrize("d_model", [DC, 384], ids=["d256", "d384-odd-lane-tiles"])
def test_which_way_the_held_rows_moved_is_counted_once_per_trace(d_model, tmp_path, interpret):
    """Under a share, at a shape the row kernels take — D a multiple of 256
    or an odd number of lane tiles — the rows go in and out by DMA (three
    counters, once a trace); float32 rows keep XLA's gathers over the static
    bound, and say so."""
    cfg = CrossCoderConfig(obs="on", obs_dir=str(tmp_path / "obs"), log_backend="null")
    plane = obs.acquire(cfg)
    names = ("held", "held_combine", "rows_in_kernel", "rows_in_xla",
             "combine_kernel", "combine_xla")
    counts = lambda: [plane.registry.get_count(f"harvest/moe_{c}_traces")   # noqa: E731
                      for c in names]
    try:
        x, _ = _whole_layer(jnp.bfloat16, d_model)
        w_router, _, _ = _layer(5, False)
        ks = jax.random.split(jax.random.key(6), 2)
        w_gate_up = jax.random.normal(ks[0], (L, 2, d_model, 2 * F), jnp.bfloat16)
        w_down = jax.random.normal(ks[1], (L, 2, F, d_model), jnp.bfloat16)
        router = jnp.zeros((d_model, E), jnp.bfloat16).at[:D].set(w_router.astype(jnp.bfloat16))
        f = jax.jit(lambda x: moe.moe_mlp(x, router, w_gate_up, w_down, 0, top_k=K,
                                          norm_topk_prob=True, first_expert=2))
        for _ in range(3):
            f(x)
        assert counts() == [1, 1, 1, 0, 1, 0]
        w_router, w_gate_up, w_down = _layer(5, False)
        moe.moe_mlp(_x(5, 64, False), w_router, w_gate_up[:, :2], w_down[:, :2], 0,
                    top_k=K, norm_topk_prob=True)
        assert counts() == [2, 1, 1, 1, 1, 1]
    finally:
        plane.close()


def test_local_row_share():
    counts = np.array([[4, 4, 4, 4, 0, 0, 0, 0], [1, 1, 1, 1, 1, 1, 1, 1]])
    assert moe.local_row_share(counts, 0, 4) == pytest.approx(0.75)
    assert moe.local_row_share(counts, 4, 4) == pytest.approx(0.25)
    assert moe.local_row_share(counts, 0, 8) == 1.0


# ---------------------------------------------------------------------------
# the sigmoid_bias routing kind: scores by sigmoid, choice by score + bias,
# gates from the unbiased scores of the chosen


def _bias(seed):
    return 0.1 * jax.random.normal(jax.random.key(100 + seed), (E,), jnp.float32)


def _by_hand_sigmoid(x, w_router, bias, scale):
    """One token at a time in float64 numpy: the equation, not the code."""
    logits = np.asarray(x, np.float64) @ np.asarray(w_router, np.float64)
    s = 1.0 / (1.0 + np.exp(-logits))
    idx = np.argsort(-(s + np.asarray(bias, np.float64)), axis=-1, kind="stable")[:, :K]
    g = np.take_along_axis(s, idx, axis=-1)
    return idx, g / (g.sum(-1, keepdims=True) + 1e-20) * scale


@pytest.mark.parametrize("scale", [1.0, 2.0])
def test_sigmoid_routing_is_the_equation(scale):
    w_router, _, _ = _layer(6, False)
    x, bias = _x(6, 256, False)[0], _bias(6)
    idx, gates = moe.route(x, w_router, K, True, scale, "sigmoid_bias", bias)
    want_idx, want = _by_hand_sigmoid(x, w_router, bias, scale)
    assert idx.dtype == jnp.int32 and gates.dtype == jnp.float32
    # (a float32 near-tie may order two experts otherwise than float64 does)
    assert (np.sort(np.asarray(idx), -1) == np.sort(want_idx, -1)).mean() > 0.999
    same = (np.asarray(idx) == want_idx).all(-1)
    np.testing.assert_allclose(np.asarray(gates)[same], want[same], rtol=2e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), scale, rtol=1e-5)
    # without the renormalisation the gates are the raw scores of the chosen
    _, raw = moe.route(x, w_router, K, False, 1.0, "sigmoid_bias", bias)
    logits = np.asarray(x) @ np.asarray(w_router)
    np.testing.assert_allclose(
        np.asarray(raw), np.take_along_axis(1 / (1 + np.exp(-logits)), np.asarray(idx), -1),
        rtol=2e-5)


def test_the_bias_moves_choices_and_never_gates():
    w_router, _, _ = _layer(7, False)
    x = _x(7, 256, False)[0]
    big = jnp.zeros((E,)).at[3].set(10.0)           # expert 3 is always chosen first...
    idx, gates = moe.route(x, w_router, K, False, 1.0, "sigmoid_bias", big)
    assert (np.asarray(idx)[:, 0] == 3).all()
    logits = np.asarray(x) @ np.asarray(w_router)
    np.testing.assert_allclose(np.asarray(gates)[:, 0], 1 / (1 + np.exp(-logits[:, 3])),
                               rtol=2e-5)           # ... and gated by its own score, under 1
    # ties go to the lowest index: equal scores and no bias pick 0..K-1
    idx, _ = moe.route(x, jnp.zeros((D, E)), K, True, 1.0, "sigmoid_bias", jnp.zeros((E,)))
    assert (np.asarray(idx) == np.arange(K)).all()


def test_the_softmax_kind_traces_what_it_traced(tmp_path):
    """``route`` with the default kind is the parent's op sequence (its
    equation list is held by ``test_every_expert_held_is_the_whole_layer_op_
    for_op`` through ``moe_mlp``), and only the sigmoid kind is counted."""
    w_router, w_gate_up, w_down = _layer(5, False)
    x = _x(5, 64, False)
    prims = [str(e.primitive) for e in jax.make_jaxpr(
        lambda x: moe.route(x, w_router, K, True))(x[0]).jaxpr.eqns]
    assert "logistic" not in prims and prims.count("top_k") == 1
    bias = _bias(5)
    assert prims == [str(e.primitive) for e in jax.make_jaxpr(
        lambda x: moe.route(x, w_router, K, True, 1.0, "softmax", bias))(x[0]).jaxpr.eqns]
    cfg = CrossCoderConfig(obs="on", obs_dir=str(tmp_path / "obs"), log_backend="null")
    plane = obs.acquire(cfg)
    try:
        moe.moe_mlp(x, w_router, w_gate_up, w_down, 0, top_k=K, norm_topk_prob=True)
        assert plane.registry.get_count("harvest/moe_sigmoid_traces") == 0
        f = jax.jit(lambda x: moe.moe_mlp(
            x, w_router, w_gate_up, w_down, 0, top_k=K, norm_topk_prob=True,
            routed_scale=2.0, router="sigmoid_bias", router_bias=_bias(5)))
        for _ in range(3):
            f(x)
        assert plane.registry.get_count("harvest/moe_sigmoid_traces") == 1
    finally:
        plane.close()


@pytest.mark.parametrize("form", ["ragged", "tiles"])
def test_a_sigmoid_routed_held_share_matches_the_plain_loop(form, request):
    """The held-share path, both forms of the grouped product, beneath the
    sigmoid router: experts [4, 8) of the layer against a loop over them."""
    if form == "tiles":
        request.getfixturevalue("interpret")
    w_router, w_gate_up, w_down = _layer(8, False)
    x, bias = _x(8, 128, False), _bias(8)
    got = moe.moe_mlp(x, w_router, w_gate_up[:, 4:8], w_down[:, 4:8], 0, top_k=K,
                      norm_topk_prob=True, routed_scale=2.0, first_expert=4,
                      router="sigmoid_bias", router_bias=bias)
    idx, gates = moe.route(x[0], w_router, K, True, 2.0, "sigmoid_bias", bias)
    want = jnp.zeros_like(x[0])
    F = w_down.shape[2]
    for e in range(4, 8):
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)
        h = jax.nn.silu(x[0] @ w_gate_up[0, e, :, :F]) * (x[0] @ w_gate_up[0, e, :, F:])
        want = want + g[:, None] * (h @ w_down[0, e])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the ungated relu² expert (Nemotron-H): ``relu(x·W_up)² · W_down``, its up
# block and hidden rows as wide as the expert, whole lane tiles or not

FU = 192        # 1.5 lane tiles, as Nemotron-H's 1856 is 14.5


def _ungated_layer(seed, width=FU):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (D, E)) * D ** -0.5,
            jax.random.normal(ks[1], (L, E, width, D)) * D ** -0.5,         # [out, in]
            jax.random.normal(ks[2], (L, E, width, D)) * width ** -0.5)


def _relu2_loop(x, w_router, w_up, w_down, layer, first, n_held, scale, bias):
    """Each held expert on every token, masked by its gate (the plain loop)."""
    with jax.default_matmul_precision("highest"):
        idx, gates = moe.route(x, w_router, K, True, scale, "sigmoid_bias", bias)
        out = jnp.zeros_like(x)
        for e in range(first, first + n_held):
            g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1)
            h = jnp.square(jax.nn.relu(x @ w_up[layer, e - first].T))
            out = out + g[:, None] * (h @ w_down[layer, e - first])
        return out


@pytest.mark.parametrize("held", [E, 4], ids=["every-expert", "half-held"])
@pytest.mark.parametrize("form", ["ragged", "tiles"])
def test_the_ungated_expert_at_a_width_off_the_lanes_matches_the_plain_loop(
        form, held, request, tmp_path):
    """Both forms at width 192 under the sigmoid router, every expert held or
    experts [4, 8): the tile form's ``moe_up`` and ``moe_down`` take the
    expert's whole width as one block."""
    if form == "tiles":
        request.getfixturevalue("interpret")
    w_router, w_up, w_down = _ungated_layer(5)
    first = E - held
    w_up, w_down = w_up[:, first:], w_down[:, first:]
    x, bias = _x(5, 300, False), _bias(5)
    assert moe.supported(D, FU, x.dtype, gated=False) and not moe.supported(D, FU, x.dtype)
    plane = obs.acquire(types.SimpleNamespace(
        obs="on", obs_dir=str(tmp_path / "obs"), checkpoint_dir=str(tmp_path)))
    try:
        got = moe.moe_mlp(x, w_router, w_up, w_down, jnp.int32(1), top_k=K,
                          norm_topk_prob=True, routed_scale=2.5, first_expert=first,
                          router="sigmoid_bias", router_bias=bias, gated=False)
        reg = plane.registry
        assert reg.get_count("harvest/moe_ungated_traces") == 1
        assert reg.get_count(f"harvest/moe_{form}_traces") == 1
    finally:
        plane.close()
    want = _relu2_loop(x[0], w_router, w_up, w_down, 1, first, held, 2.5, bias)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=0, atol=2.5 * ATOL)


def test_the_ungated_held_tiles_fetch_their_own_rows(interpret):
    """``moe_up`` fetching x's rows by DMA (bf16, D a multiple of 256) against
    the plain ``moe_up`` on XLA's gather: the real tiles' results are the same
    bits; a gated kernel on the same up block would not be."""
    from jax.experimental import pallas as pl

    x, _, (local, held_gates) = _rows_in_case(300, False, 0)
    w_up = (jax.random.normal(jax.random.key(3), (L, 2, FU, DC)) * DC ** -0.5).astype(jnp.bfloat16)
    layer = jnp.ones((1,), jnp.int32)
    tile_expert, n_valid, tile_first, token, _ = moe._held_layout(local, held_gates, 2)
    got = moe._gate_up_rows(tile_expert, n_valid, layer, tile_first, token,
                            row_gather.packed(x, interpret=True), w_up, x.dtype, gated=False)
    te, nv, gathered, _ = moe._tile_layout(local, 2, True)
    want = moe._tile_call(moe._up_kernel, "moe_up", (te, nv, layer), x[gathered], (w_up,),
                          [pl.BlockSpec((None, None, FU, DC), moe._w_block(0))], FU)
    real = int(n_valid[0]) * moe.TILE_ROWS
    assert got.shape == (want.shape[0], FU)
    np.testing.assert_array_equal(np.asarray(got[:real], np.float32),
                                  np.asarray(want[:real], np.float32))
    assert (np.asarray(want[:real], np.float32) >= 0).all()       # relu², not a gate


@pytest.mark.parametrize("d_model,d_expert,gated,want", [
    (2688, 1856, False, True), (2688, 1856, True, False), (2304, 896, True, True),
    (2688, 1850, False, False), (2600, 1856, False, False)],
    ids=["nemotron-ungated", "gated-off-the-lanes", "mellum2", "off-the-sublanes",
         "model-off-the-lanes"])
def test_supported_by_form(d_model, d_expert, gated, want):
    assert moe.supported(d_model, d_expert, jnp.bfloat16, gated) == want
