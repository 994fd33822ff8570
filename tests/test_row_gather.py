"""The row-gather kernels (ops/row_gather.py) against their XLA oracles, in
the Pallas interpreter on CPU: the token-major weighted sum and dot epilogue
(whole tiles, a last tile that is not whole, the table sliced over the
batch), the latent-major grouped sums (duplicate destinations, empty
destinations, a destination hit by more pairs than one chunk holds, a last
chunk that is not whole), and the whole TopK step in its row form against
the dense TopK step: losses and all four parameter gradients, the dispatch
that chooses the form, and the counters that say which was traced; the
token-major sum over a compacted table of held pairs (``held_sums``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crosscoder_tpu import obs
from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.models import crosscoder as cc
from crosscoder_tpu.ops import row_gather as rg
from crosscoder_tpu.ops import topk_pallas

F32 = jnp.float32


@pytest.fixture
def interpret():
    topk_pallas.set_interpret(True)
    rg.set_interpret(True)
    yield
    topk_pallas.set_interpret(False)
    rg.set_interpret(False)


def _bf16_ulp(v):
    """bfloat16's spacing at ``|v|``, and the float32 roundings of a sum
    that cancels to nearly nothing."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(v), 1e-30))) - 7) + 1e-6


def _token_case(T, k, D, R, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    y = jax.random.normal(ks[0], (R, D), jnp.bfloat16)
    table = jax.random.randint(ks[1], (T * k,), 0, R).astype(jnp.int32)
    w = jax.random.normal(ks[2], (T, k), F32)
    g = jax.random.normal(ks[3], (T, D), F32)
    return y, table, w, g


# (tokens, k, D, table bytes a call may hold): one tile; a last tile that is
# not whole (300 = 2 x 128 + 44); the table cut into slices of the batch,
# the last of them under one group (100 tokens -> 32, 32, 16, 20)
# ... and the kernel's loops kept loops (k·W bodies a group past 128), with
# rows as wide as a group is long (W = G: every body carries a copy), narrower
# (W < G: some copies follow the bodies) and wider (W > G: some bodies carry none)
TOKEN_CASES = [(128, 8, 256, None), (300, 4, 512, None), (100, 4, 256, 700),
               (80, 16, 4096, None), (48, 32, 2048, None), (48, 8, 8192, None)]
TOKEN_IDS = ["whole-tiles", "last-tile-not-whole", "table-sliced-over-the-batch",
             "looped-W16", "looped-W8-under-a-group", "looped-W32-over-a-group"]


@pytest.fixture
def table_bytes(monkeypatch):
    def set_(n):
        if n is not None:
            monkeypatch.setattr(rg, "_SMEM_TABLE_BYTES", n)
    return set_


@pytest.mark.parametrize("T,k,D,smem", TOKEN_CASES, ids=TOKEN_IDS)
@pytest.mark.parametrize("out_dtype", [jnp.bfloat16, F32], ids=["bf16", "f32"])
def test_weighted_sum_matches_the_gathered_sum(T, k, D, smem, out_dtype, table_bytes):
    table_bytes(smem)
    if smem:
        assert len(rg._slices(T, k)) > 1
    y, table, w, _ = _token_case(T, k, D, R=64)
    got = rg.weighted_sum(table, w, rg.packed(y), D, name="t", interpret=True,
                          out_dtype=out_dtype)
    want = jnp.sum(y[table].reshape(T, k, D).astype(F32) * w[..., None], axis=1)
    assert got.shape == (T, D) and got.dtype == out_dtype
    got, want = np.asarray(got, np.float32), np.asarray(want)
    if out_dtype == F32:    # the same k float32 products, summed in another order
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        assert (np.abs(got - want) <= _bf16_ulp(want)).all()


@pytest.mark.parametrize("T,k,D,smem", TOKEN_CASES, ids=TOKEN_IDS)
def test_dots_match_the_gathered_dots(T, k, D, smem, table_bytes):
    table_bytes(smem)
    y, table, _, g = _token_case(T, k, D, R=64, seed=1)
    got = rg.dots(table, g, rg.packed(y), k, name="t", interpret=True)
    want = jnp.einsum("td,tkd->tk", g, y[table].reshape(T, k, D).astype(F32))
    assert got.shape == (T, k) and got.dtype == F32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_a_swapped_table_entry_moves_exactly_its_tokens():
    """The planted fault: two tokens trade one row of the table."""
    T, k, D = 64, 4, 256
    y, table, w, g = _token_case(T, k, D, R=64, seed=2)
    a, b = 17 * k + 1, 41 * k + 2
    assert int(table[a]) != int(table[b])
    swapped = table.at[a].set(table[b]).at[b].set(table[a])
    for fn in (lambda t: rg.weighted_sum(t, w, rg.packed(y), D, name="t",
                                         interpret=True, out_dtype=F32),
               lambda t: rg.dots(t, g, rg.packed(y), k, name="t", interpret=True)):
        moved = np.abs(np.asarray(fn(table)) - np.asarray(fn(swapped))).max(axis=1) > 1e-3
        assert set(np.flatnonzero(moved)) == {17, 41}


def test_slices_cover_the_batch_in_whole_groups():
    for T, k, smem in [(4096, 32, 256 << 10), (4096, 8, 256 << 10), (100, 4, 700),
                       (2 ** 16, 8, 256 << 10), (4097, 32, 512 << 10)]:
        old, rg._SMEM_TABLE_BYTES = rg._SMEM_TABLE_BYTES, smem
        try:
            cuts = rg._slices(T, k)
        finally:
            rg._SMEM_TABLE_BYTES = old
        assert cuts[0][0] == 0 and sum(n for _, n in cuts) == T
        assert all(a + n == b for (a, n), (b, _) in zip(cuts, cuts[1:]))
        assert all(n >= rg.GROUP and n * k * 4 <= smem + rg.GROUP * k * 4 for _, n in cuts)
    assert rg._slices(4096, 8) == [(0, 4096)]                  # the mellum2 cell: one call
    assert rg._slices(4096, 32) == [(0, 4096)]                 # the topk32k cell: one, 512 KiB
    assert rg._slices(8192, 32) == [(0, 4096), (4096, 4096)]


# ---------------------------------------------------------------------------
# token-major over a compacted table: the held pairs alone


def _held_case(T, k, D, R, share, seed=0):
    """A ``[T, k]`` table of which a random ``share`` of the slots is held:
    the held (token, slot) pairs in token order at the front of ``[T·k]``
    tables, as ``ops/moe._held_layout`` hands them over, and the gathered
    XLA form of the same sum."""
    y, table, w, _ = _token_case(T, k, D, R, seed)
    held = jax.random.uniform(jax.random.key(50 + seed), (T * k,)) < share
    key = jnp.where(held, jnp.arange(T * k, dtype=jnp.int32), T * k)
    key, rows, weights = jax.lax.sort((key, table, w.reshape(-1)), num_keys=1)
    n_live = jnp.sum(held).astype(jnp.int32).reshape(1)
    want = jnp.sum(jnp.where(held.reshape(T, k, 1),
                             y[table].reshape(T, k, D).astype(F32) * w[..., None], 0.0), axis=1)
    return y, (rows, key // k, weights, n_live), held.reshape(T, k), want


@pytest.mark.parametrize("T,k,D,share", [
    (300, 3, 256, 0.125), (64, 3, 256, 0.5), (300, 10, 768, 0.125), (300, 3, 256, 0.0),
    (300, 3, 256, 0.97), (72, 4, 512, 1.0), (256, 8, 256, 1 / 64),
    (300, 6, 384, 0.25), (300, 6, 2688, 0.25)],
    ids=["300tok-an-eighth", "64tok-half", "k10-three-lane-tiles", "no-row-held",
         "nearly-every-row", "every-row-a-tile-count-not-whole", "tiles-without-a-pair",
         "d384-odd-lane-tiles", "d2688-the-nemotron-width"])
def test_held_sums_match_the_gathered_sum_over_the_held_slots(T, k, D, share):
    assert rg.held_supported(T, k, D, jnp.bfloat16)
    y, pairs, held, want = _held_case(T, k, D, R=64, share=share)
    n = int(pairs[3][0])
    assert n == int(held.sum()) and (share not in (0.0, 1.0) or n == int(share) * T * k)
    if D % 256:     # dead pairs behind the live ones, and tokens no live pair names
        assert 0 < n < T * k and not np.asarray(held).any(axis=1).all()
    got = jax.jit(lambda *a: rg.held_sums(*a, T, D, name="t", interpret=True))(
        *pairs, rg.packed(y))
    assert got.shape == (T, D) and got.dtype == jnp.bfloat16
    got, want = np.asarray(got, np.float32), np.asarray(want)
    # float32 weights (three exact bf16 parts on the MXU), float32 sums in
    # another order, one rounding: the oracle to a bf16 ulp
    assert (np.abs(got - want) <= _bf16_ulp(want)).all()
    assert (got != np.asarray(jnp.asarray(want).astype(jnp.bfloat16), np.float32)).mean() < 1e-3
    # a token with no held slot gets exactly nothing
    assert not got[~np.asarray(held).any(axis=1)].any()
    if share == 0.0:
        assert not got.any()


def test_held_sums_never_read_past_the_live_pairs():
    """What lies behind the live pairs is neither fetched nor summed: rows
    out of range and non-finite weights there change nothing."""
    T, k, D = 300, 3, 256
    y, (rows, tokens, weights, n_live), _, _ = _held_case(T, k, D, R=64, share=0.125)
    dead = jnp.arange(T * k) >= n_live[0]
    run = lambda r, t, w: np.asarray(rg.held_sums(      # noqa: E731
        r, t, w, n_live, rg.packed(y), T, D, name="t", interpret=True), np.float32)
    np.testing.assert_array_equal(
        run(rows, tokens, weights),
        run(jnp.where(dead, 2 ** 20, rows), jnp.where(dead, 7, tokens),
            jnp.where(dead, jnp.nan, weights)))


def test_held_sums_fail_on_a_swapped_table_entry():
    """The planted fault: two live pairs of different tokens trade rows."""
    T, k, D = 300, 3, 256
    y, (rows, tokens, weights, n_live), _, _ = _held_case(T, k, D, R=64, share=0.25, seed=2)
    a, b = 20, int(n_live[0]) - 20
    assert int(rows[a]) != int(rows[b]) and int(tokens[a]) != int(tokens[b])
    swapped = rows.at[a].set(rows[b]).at[b].set(rows[a])
    run = lambda r: np.asarray(rg.held_sums(            # noqa: E731
        r, tokens, weights, n_live, rg.packed(y), T, D, name="t", interpret=True), np.float32)
    moved = (run(rows) != run(swapped)).any(axis=1)
    assert set(np.flatnonzero(moved)) == {int(tokens[a]), int(tokens[b])}


@pytest.mark.parametrize("args,want", [
    ((8192, 10, 3072, jnp.bfloat16), True),       # the laguna cell: 320 KiB of pairs
    ((8192, 10, 3072, F32), False),
    ((16384, 10, 3072, jnp.bfloat16), False),     # the pair table passes SMEM, and is not cut
    ((8, 10, 3072, jnp.bfloat16), False),
    ((8192, 6, 2688, jnp.bfloat16), True),        # the nemotron cell: 21 lane tiles, 192 KiB
    ((8192, 6, 2600, jnp.bfloat16), False),       # a row is not whole lanes
], ids=["cell", "float32", "smem", "tokens", "nemotron-cell", "not-whole-lanes"])
def test_held_supported(args, want):
    assert rg.held_supported(*args) is want


# ---------------------------------------------------------------------------
# latent-major


def _grouped_oracle(idx, cv, cd, g, x, n_out):
    B, k = idx.shape
    flat = idx.reshape(-1)
    upd = lambda c, r: (c.astype(F32)[:, :, None] * r.astype(F32)[:, None, :]  # noqa: E731
                        ).reshape(B * k, -1)
    zero = jnp.zeros((n_out, g.shape[1]), F32)
    return (zero.at[flat].add(upd(cv, g)), zero.at[flat].add(upd(cd, x)),
            jnp.zeros((n_out,), F32).at[flat].add(cd.astype(F32).reshape(-1)))


def _grouped_case(B, k, H, D, seed, lo=0, hot=None, same=False):
    ks = jax.random.split(jax.random.key(seed), 5)
    idx = jax.random.randint(ks[0], (B, k), lo, H).astype(jnp.int32)
    if hot is not None:
        idx = idx.at[:, 0].set(hot)             # one destination, every token
    if same:
        idx = jnp.broadcast_to(idx[:1], (B, k))  # every pair a duplicate
    cv, cd = (jax.random.normal(a, (B, k), jnp.bfloat16) for a in ks[1:3])
    g, x = (jax.random.normal(a, (B, D), jnp.bfloat16) for a in ks[3:5])
    return idx, cv, cd, g, x


@pytest.mark.parametrize("B,k,H,D,kw", [
    (64, 4, 256, 256, {}),
    (64, 4, 512, 128, dict(lo=300)),            # tiles 0 and 1 hold no pair
    (160, 4, 512, 128, dict(hot=259)),          # 160 pairs on one latent: over a chunk
    (50, 3, 256, 128, {}),                      # 150 pairs: the last chunk not whole
    (32, 8, 256, 128, dict(same=True)),         # 8 latents, 32 duplicates each
], ids=["even", "empty-tiles", "one-latent-over-a-chunk", "last-chunk-not-whole",
        "every-pair-a-duplicate"])
def test_grouped_sums_match_the_scatter(B, k, H, D, kw):
    idx, cv, cd, g, x = _grouped_case(B, k, H, D, seed=3, **kw)
    assert rg.grouped_supported(H, B, k, D, jnp.bfloat16)
    got = jax.jit(lambda *a: rg.grouped_sums(*a, H, name="t", interpret=True))(
        idx, cv, cd, g, x)
    want = _grouped_oracle(idx, cv, cd, g, x, H)
    assert got[1].shape == (D, H)               # the second sum comes transposed
    assert got[0].dtype == jnp.bfloat16 and got[1].dtype == F32   # bf16 values, written wide
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(got[1].astype(jnp.bfloat16), np.float32))
    for a, b in zip((got[0], got[1].T), want[:2]):
        assert a.shape == (H, D)
        a, b = np.asarray(a, np.float32), np.asarray(b)
        # exact bf16 products summed in float32 in another order, rounded once
        assert (np.abs(a - b) <= _bf16_ulp(b)).all()
        assert (a[np.asarray(b) == 0] == 0).all()        # untouched rows are zeros
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]), rtol=1e-5, atol=1e-5)


def test_visits_cover_every_tile_once_in_order():
    for kw in ({}, dict(lo=300), dict(hot=259), dict(same=True)):
        idx, cv, cd, _, _ = _grouped_case(160, 4, 512, 128, seed=4, **kw)
        chunk, tile, n_valid, tok, dst, *_ = rg._visits(idx, cv, cd, 512)
        n = int(n_valid[0])
        chunk, tile = np.asarray(chunk), np.asarray(tile)
        n_chunks, n_tiles = dst.shape[0], 512 // rg.DESTS
        assert chunk.shape[0] == n_chunks + n_tiles - 1 >= n
        assert (np.diff(chunk[:n]) >= 0).all() and (np.diff(tile[:n]) >= 0).all()
        assert set(tile[:n]) == set(range(n_tiles)) and set(chunk[:n]) == set(range(n_chunks))
        assert (chunk[n:] == n_chunks - 1).all() and (tile[n:] == n_tiles - 1).all()
        # every pair meets the tile of its destination in one of its chunk's visits
        d = np.asarray(dst).reshape(n_chunks, -1) // rg.DESTS
        for c in range(n_chunks):
            assert set(d[c]) <= set(tile[:n][chunk[:n] == c])


@pytest.mark.parametrize("args,want", [
    ((4096, 32, 4096, jnp.bfloat16), True),       # the topk32k cell
    ((4096, 32, 4096, F32), False),               # rows are packed two bf16 a word
    ((4096, 32, 4608, jnp.bfloat16), True),       # n·d of Gemma-2-2B: 18 lane tiles of words
    ((4096, 32, 4480, jnp.bfloat16), False),      # half a row is not whole lanes
    ((8, 32, 4096, jnp.bfloat16), False),         # less than one group of tokens
    ((4096, 128, 2 ** 14, jnp.bfloat16), False),  # one group's double buffer passes VMEM
], ids=["cell", "float32", "gemma", "half-lanes", "tokens", "vmem"])
def test_supported(args, want):
    assert rg.supported(*args) is want


@pytest.mark.parametrize("args,want", [
    ((2 ** 15, 4096, 32, 4096, jnp.bfloat16), True),    # the topk32k cell
    ((2 ** 17, 4096, 32, 4608, jnp.bfloat16), True),
    ((2 ** 15, 4096, 32, 4096, F32), False),
    ((2 ** 15 + 64, 4096, 32, 4096, jnp.bfloat16), False),  # not whole tiles of latents
    ((2 ** 15, 8192, 32, 4096, jnp.bfloat16), False),   # 1 MiB of tokens: past SMEM
], ids=["cell", "2^17", "float32", "tiles", "smem"])
def test_grouped_supported(args, want):
    assert rg.grouped_supported(*args) is want


# ---------------------------------------------------------------------------
# the whole TopK step in its row form against the dense TopK step


def _cfg(**kw):
    base = dict(d_in=128, n_models=2, dict_size=512, activation="topk", topk_k=8,
                l1_coeff=0.0, batch_size=32, enc_dtype="bf16", master_dtype="fp32",
                log_backend="null")
    base.update(kw)
    return CrossCoderConfig(**base)


def _step(cfg, x, with_metrics=False):
    params = cc.init_params(jax.random.key(0), cfg, dtype=F32)

    def loss(p):
        return cc.training_loss(p, x, 0.0, cfg, with_metrics=with_metrics)

    (_, losses), grads = jax.value_and_grad(loss, has_aux=True)(params)
    return losses, grads


def _kernels_in(cfg, x):
    import re

    params = cc.init_params(jax.random.key(0), cfg, dtype=F32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: cc.training_loss(p, x, 0.0, cfg, with_metrics=False)[0]))(params))
    return sorted(set(re.findall(r"name=(topk_rows_\w+)", text)))


@pytest.mark.parametrize("batch", ["random", "identical-rows"])
def test_row_form_step_matches_the_dense_topk_step(batch, interpret):
    """All four parameter leaves at tests/test_sparse_grad.py's tolerance
    (2e-5 of the leaf's largest entry); ``identical-rows`` makes every pair a
    duplicate destination, 32 to a latent. Both steps form exact bf16
    products and sum them in float32, in another order, and round each
    gradient to bf16 once: the rounding may fall the other way on a few
    elements, by one bf16 ulp of THAT element."""
    rng = np.random.default_rng(7)
    x = rng.standard_normal((1 if batch == "identical-rows" else 32, 2, 128))
    x = jnp.asarray(np.broadcast_to(x, (32, 2, 128)), F32)
    cfg = _cfg()
    rg.set_interpret(False)
    assert not cc.rows_live(cfg, 32) and _kernels_in(cfg, x) == []
    dense, g_dense = _step(cfg, x, with_metrics=True)
    rg.set_interpret(True)
    assert _kernels_in(cfg, x) == ["topk_rows_decode", "topk_rows_dvals", "topk_rows_grads"]
    # all four products or none: with the plane switched off the step is dense
    assert _kernels_in(cfg.replace(sparse_bwd="off"), x) == []
    rows, g_rows = _step(cfg, x, with_metrics=True)
    assert float(rows.l0_loss) == float(dense.l0_loss) == cfg.topk_k
    np.testing.assert_allclose(float(rows.l2_loss), float(dense.l2_loss), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(rows.explained_variance),
                               np.asarray(dense.explained_variance), atol=1e-5)
    assert set(g_rows) == {"W_enc", "W_dec", "b_enc", "b_dec"}
    for name in g_dense:
        a = np.asarray(g_dense[name], np.float32)
        b = np.asarray(g_rows[name], np.float32)
        off = np.abs(a - b) > 2e-5 * np.abs(a).max()
        assert off.mean() < 1e-3, name
        assert (np.abs(a - b) <= _bf16_ulp(a)).all(), name


@pytest.mark.parametrize("leaf", ["W_enc", "W_dec", "b_enc", "b_dec"])
def test_row_form_gradients_fail_on_a_planted_fault(leaf, interpret, monkeypatch):
    """The comparison above can fail: the latent-major pass with its two
    coefficient lists traded (``vals`` for ``d_vals``) moves both weight
    gradients and ``b_enc``'s, and leaves ``b_dec``'s — which does not pass
    through it — where it was."""
    x = jnp.asarray(np.random.default_rng(7).standard_normal((32, 2, 128)), F32)
    cfg = _cfg()
    _, good = _step(cfg, x)
    real = rg.grouped_sums
    monkeypatch.setattr(rg, "grouped_sums",
                        lambda idx, cv, cd, *a, **kw: real(idx, cd, cv, *a, **kw))
    cc._row_ops.cache_clear()       # the step holds the entry points under jit
    try:
        _, bad = _step(cfg, x)
    finally:
        cc._row_ops.cache_clear()
    a, b = np.asarray(good[leaf], np.float32), np.asarray(bad[leaf], np.float32)
    moved = (np.abs(a - b) > 2e-5 * np.abs(a).max()).mean() > 1e-3
    assert moved == (leaf != "b_dec")


def test_auxk_step_keeps_the_form_it_had(interpret):
    """``h`` has another consumer on an AuxK step: no row kernel there."""
    cfg = _cfg(aux_k=16, aux_dead_steps=1)
    x = jax.random.normal(jax.random.key(2), (32, 2, 128), F32)
    params = cc.cast_params(cc.init_params(jax.random.key(0), cfg, dtype=F32), jnp.bfloat16)
    dead = jnp.ones((cfg.dict_size,), bool)
    text = str(jax.make_jaxpr(lambda p: cc.get_losses(p, x, cfg, dead_mask=dead).aux_loss)(params))
    assert "topk_rows" not in text
    text = str(jax.make_jaxpr(lambda p: cc.get_losses(p, x, cfg).l2_loss)(params))
    assert "topk_rows_decode" in text


def test_uncast_parameters_keep_the_dense_step(interpret):
    """float32 parameters handed to ``get_losses`` as they are: packing them
    would round what the dense path does not."""
    cfg = _cfg()
    x = jax.random.normal(jax.random.key(2), (32, 2, 128), F32)
    params = cc.init_params(jax.random.key(0), cfg, dtype=F32)
    text = str(jax.make_jaxpr(lambda p: cc.get_losses(p, x, cfg).l2_loss)(params))
    assert "topk_rows" not in text


def test_which_form_was_traced_is_counted_once_per_trace(tmp_path, interpret):
    cfg = _cfg(obs="on", obs_dir=str(tmp_path / "obs"))
    x = jax.random.normal(jax.random.key(2), (32, 2, 128), F32)
    params = cc.init_params(jax.random.key(0), cfg, dtype=F32)
    plane = obs.acquire(cfg)
    try:
        step = jax.jit(lambda p: cc.training_loss(p, x, 0.0, cfg, with_metrics=False)[0])
        step(params), step(params)
        get = plane.registry.get_count
        assert (get("perf/cc_decode_rows_traces"), get("perf/cc_bwd_rows_traces")) == (1, 1)
        assert (get("perf/cc_decode_dense_traces"), get("perf/cc_bwd_dense_traces")) == (0, 0)
        rg.set_interpret(False)
        jax.jit(lambda p: cc.training_loss(p, x, 0.0, cfg, with_metrics=True)[0])(params)
        assert (get("perf/cc_decode_dense_traces"), get("perf/cc_bwd_dense_traces")) == (1, 1)
        assert (get("perf/cc_decode_rows_traces"), get("perf/cc_bwd_rows_traces")) == (1, 1)
        relu = _cfg(activation="relu", l1_coeff=1.0, obs="on", obs_dir=str(tmp_path / "obs"))
        jax.jit(lambda p: cc.training_loss(p, x, 1.0, relu)[0])(
            cc.init_params(jax.random.key(0), relu, dtype=F32))
        assert get("perf/cc_decode_dense_traces") == 1      # TopK's counters only
    finally:
        plane.close()


@pytest.mark.parametrize("pair", [False, True], ids=["halves-of-one", "two-arrays"])
def test_the_pack_kernel_writes_the_words_xla_writes(pair):
    a = jax.random.normal(jax.random.key(5), (512, 512), F32)     # rounded to bf16
    b = jax.random.normal(jax.random.key(6), (512, 512), jnp.bfloat16)
    args = (a, b) if pair else (a,)
    got = rg.packed(*args, interpret=True)
    want = rg._words(a, b) if pair else rg.pack_rows(a)
    if not pair:    # two sources: the same words by a bitcast of the pairs
        two = a.astype(jnp.bfloat16).reshape(512, 2, 256)
        np.testing.assert_array_equal(np.asarray(rg.packed_sources(two)), np.asarray(got))
        three = jnp.concatenate([two, two[:, :1]], axis=1)[:, :, :128]     # [512, 3, 128]: no
        np.testing.assert_array_equal(
            np.asarray(rg.packed_sources(three[:, :2])).reshape(512, -1),
            np.asarray(rg.pack_rows(three[:, :2].reshape(512, 256))))
    assert got.dtype == jnp.uint32 and got.shape == (512 * want.shape[1] // 128, 1, 128)
    np.testing.assert_array_equal(np.asarray(got).reshape(want.shape), np.asarray(want))
    # rows that do not tile (R % 256) take XLA's form of the same words
    np.testing.assert_array_equal(np.asarray(rg.packed(*(x[:72] for x in args))),
                                  np.asarray(got)[:72 * want.shape[1] // 128])


@pytest.mark.parametrize("D", [256, 384, 2688], ids=["d256-whole-halves", "d384", "d2688"])
@pytest.mark.parametrize("R", [512, 72], ids=["kernel", "xla"])
def test_packed_rows_round_trip(D, R):
    """The gatherable form of bf16 rows unpacks to the same rows, bit for
    bit, whether ``D/128`` is even or odd (``⌈D/256⌉`` lane tiles of words,
    the last high half zeros); the pack kernel (rows in whole tiles) and
    XLA (rows that are not) write the same words."""
    y = jax.random.normal(jax.random.key(8), (R, D), jnp.bfloat16)
    W = rg.word_tiles(D)
    assert W == -(-D // 256) and (D % 256 or 2 * W * 128 == D)
    words = rg.packed(y, interpret=True)
    assert words.dtype == jnp.uint32 and words.shape == (R * W, 1, 128)
    words = words.reshape(R, W * 128)
    np.testing.assert_array_equal(np.asarray(words), np.asarray(rg.pack_rows(y)))
    lo, hi = rg.unpack_words(words)
    back = jnp.concatenate([lo, hi], axis=1).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(back[:, :D], np.float32), np.asarray(y, np.float32))
    assert not np.asarray(back[:, D:], np.float32).any()        # the padding is zeros
