"""Paged harvest runtime (cfg.harvest_runtime="paged"; data/paging.py +
models/lm.run_with_cache_multi_paged + data/buffer.py routing): the page
allocator, the continuous-batching packer, the padded-vs-paged CPU parity
gates (bitwise on full-length chunks, valid-position-bitwise on mixed
lengths incl. single-token and max-length documents), the replay buffer's
stream parity, the zero-cost-off guarantees, and the config validation.
All CPU, tier-1."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.data import paging
from crosscoder_tpu.data.buffer import make_buffer
from crosscoder_tpu.models import lm
from crosscoder_tpu.parallel import mesh as mesh_lib

SEQ = 16


# ---------------------------------------------------------------------------
# PageTable


def test_page_table_alloc_free_reuse():
    pt = paging.PageTable(n_pages=8, page_size=4)
    a = pt.alloc(0, 9)                       # 3 pages
    b = pt.alloc(1, 4)                       # 1 page
    assert len(a) == 3 and len(b) == 1
    assert pt.n_free == 4
    assert pt.pages_of(0) == a
    pt.free(0)
    assert pt.n_free == 7
    c = pt.alloc(2, 16)                      # 4 pages, reuses freed ids
    assert len(c) == 4 and pt.n_free == 3
    assert set(c) & set(a)                   # LIFO free-list reuse


def test_page_table_exhaustion_and_extend():
    pt = paging.PageTable(n_pages=2, page_size=4)
    assert pt.alloc(0, 12) is None           # needs 3 > 2: nothing taken
    assert pt.n_free == 2
    assert pt.alloc(0, 4) is not None
    assert pt.extend(0, 8) is not None       # grow to 2 pages (decode path)
    assert pt.extend(0, 8) == []             # already covered
    assert pt.extend(0, 12) is None          # pool exhausted
    with pytest.raises(ValueError):
        pt.alloc(0, 1)                       # double alloc
    with pytest.raises(KeyError):
        pt.extend(9, 4)


def test_page_table_table_array():
    pt = paging.PageTable(n_pages=8, page_size=4)
    pt.alloc(0, 8)
    pt.alloc(1, 4)
    tbl = pt.table([0, 1])
    assert tbl.shape == (2, 2) and tbl.dtype == np.int32
    assert list(tbl[0]) == pt.pages_of(0)
    assert tbl[1, 0] == pt.pages_of(1)[0] and tbl[1, 1] == 0


def test_page_table_rejects_bad_page_size():
    with pytest.raises(ValueError, match="power of two"):
        paging.PageTable(8, 3)


# ---------------------------------------------------------------------------
# packing


def test_pack_documents_first_fit():
    row, off, used = paging.pack_documents(np.array([8, 8, 4, 4, 8]), 16)
    # [8,8] -> row0; 4 -> row0 full? 8+8=16 full, so 4 -> row1 ...
    assert list(row) == [0, 0, 1, 1, 1]
    assert list(off) == [0, 8, 0, 4, 8]
    assert used == 2


def test_pack_documents_rejects_oversize():
    with pytest.raises(ValueError, match="exceeds seq_len"):
        paging.pack_documents(np.array([17]), 16)
    with pytest.raises(ValueError, match=">= 1"):
        paging.pack_documents(np.array([0]), 16)


def test_pack_chunk_full_length_is_identity():
    """All-full-length chunks pack to the identity layout — the property
    the production-corpus bit-parity gate rests on."""
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, 99, size=(6, SEQ), dtype=np.int32)
    chunk = paging.pack_chunk(tokens, np.full(6, SEQ))
    assert chunk.n_rows == 6
    np.testing.assert_array_equal(chunk.tokens, tokens)
    np.testing.assert_array_equal(chunk.doc_row, np.arange(6))
    np.testing.assert_array_equal(chunk.doc_off, 0)
    np.testing.assert_array_equal(
        chunk.doc_idx, np.arange(6 * SEQ).reshape(6, SEQ)
    )
    np.testing.assert_array_equal(
        chunk.plane_idx, np.arange(6 * SEQ).reshape(6, SEQ)
    )
    assert chunk.efficiency() == 1.0


def test_pack_chunk_ragged_integrity():
    """Every real token lands exactly once on the plane; maps invert."""
    rng = np.random.default_rng(1)
    lengths = np.array([1, SEQ, 7, 3, 9, 5])
    tokens = rng.integers(1, 99, size=(6, SEQ), dtype=np.int32)
    for d, ln in enumerate(lengths):
        tokens[d, ln:] = 0
    chunk = paging.pack_chunk(tokens, lengths)
    assert chunk.n_rows < 6                  # actually packed
    flat = chunk.tokens.reshape(-1)
    for d, ln in enumerate(lengths):
        np.testing.assert_array_equal(
            flat[chunk.doc_idx[d, :ln]], tokens[d, :ln], err_msg=f"doc {d}"
        )
    # per-slot ownership: plane_idx points back at the doc token there
    pos_flat = chunk.pos.reshape(-1)
    for r in range(chunk.n_rows):
        for s in range(SEQ):
            di = int(chunk.plane_idx[r, s])
            d, t = divmod(di, SEQ)
            if di != 0 and t < lengths[d]:
                assert chunk.tokens[r, s] == tokens[d, t]
                assert pos_flat[r * SEQ + s] == t
    assert chunk.efficiency() == pytest.approx(
        lengths.sum() / (chunk.n_rows * SEQ)
    )


def test_plane_rows_bucketing():
    # granularity n_docs/8, capped at the padded count
    assert paging.plane_rows(18, 32) == 20
    assert paging.plane_rows(32, 32) == 32           # identity at full
    assert paging.plane_rows(31, 32) == 32
    assert paging.plane_rows(1, 32) == 4
    assert paging.plane_rows(6, 6) == 6
    # mesh multiple wins over granularity and may exceed n_docs
    assert paging.plane_rows(5, 6, multiple=4) == 8
    # the result is ALWAYS a multiple of `multiple`, even when the n/8
    # granularity is not (the sharded device_put divisibility contract)
    assert paging.plane_rows(50, 160, multiple=16) == 64
    for needed, docs, mult in [(10, 100, 4), (7, 33, 8), (13, 23, 2)]:
        r = paging.plane_rows(needed, docs, multiple=mult)
        assert r % mult == 0 and r >= needed


def test_padding_efficiency():
    assert paging.padding_efficiency(np.array([8, 8]), 8) == 1.0
    assert paging.padding_efficiency(np.array([4, 4]), 8) == 0.5
    assert paging.padding_efficiency(np.array([]), 8) == 1.0


# ---------------------------------------------------------------------------
# continuous batching


def test_continuous_batcher_admission_and_flush():
    rng = np.random.default_rng(2)
    cb = paging.ContinuousBatcher(seq_len=8, n_rows=2)
    docs = [rng.integers(1, 99, size=n).astype(np.int32)
            for n in (5, 3, 8, 2)]
    assert cb.admit(docs[0])                 # row0: 5
    assert cb.admit(docs[1])                 # row0: 5+3=8
    assert cb.admit(docs[2])                 # row1: 8
    assert not cb.admit(docs[3])             # nothing fits: flush signal
    chunk = cb.flush()
    assert chunk.n_docs == 3 and chunk.n_rows == 2
    assert chunk.efficiency() == 1.0         # plane completely full
    flat = chunk.tokens.reshape(-1)
    for d, doc in enumerate(docs[:3]):
        np.testing.assert_array_equal(
            flat[chunk.doc_idx[d, : len(doc)]], doc
        )
    # slots retired: the rejected doc admits now
    assert cb.admit(docs[3])
    assert cb.flush().n_docs == 1
    assert cb.flush() is None


def test_continuous_batcher_with_page_table_backpressure():
    pt = paging.PageTable(n_pages=2, page_size=4)
    cb = paging.ContinuousBatcher(seq_len=8, n_rows=4, page_table=pt)
    assert cb.admit(np.array([1, 2, 3, 4, 5], np.int32))   # 2 pages
    assert pt.n_free == 0
    assert not cb.admit(np.array([1], np.int32))           # pool exhausted
    cb.flush()
    assert pt.n_free == 2                                  # pages retired
    assert cb.admit(np.array([1], np.int32))


def test_continuous_batcher_rejects_oversize():
    cb = paging.ContinuousBatcher(seq_len=4, n_rows=1)
    with pytest.raises(ValueError, match="outside"):
        cb.admit(np.arange(5))


# ---------------------------------------------------------------------------
# paged forward parity (the tentpole gates)


@pytest.fixture(scope="module")
def lm_pair():
    cfg = lm.LMConfig.tiny()
    pa = lm.init_params(jax.random.key(1), cfg)
    pb = lm.init_params(jax.random.key(2), cfg)
    return cfg, [pa, pb]


HOOKS = ("blocks.1.hook_resid_pre", "blocks.3.hook_resid_pre")


def test_paged_full_length_bit_parity(lm_pair):
    """All-full-length chunk: the paged runtime's output is BITWISE equal
    to run_with_cache_multi — identity packing + identical op sequence."""
    cfg, params = lm_pair
    rng = np.random.default_rng(3)
    tokens = rng.integers(1, cfg.vocab_size, size=(6, SEQ), dtype=np.int64)
    want = np.asarray(lm.run_with_cache_multi(
        params, jnp.asarray(tokens), cfg, HOOKS), np.float32)
    got = np.asarray(lm.run_with_cache_multi_paged(
        params, tokens, np.full(6, SEQ), cfg, HOOKS, page_size=8), np.float32)
    np.testing.assert_array_equal(got, want)


# Paged and padded harvest are two different compiled programs (packed
# token plane + per-document attention vs one padded batch), so XLA is
# free to fuse and associate their float32 reductions differently:
# agreement is to a few ulps, not bitwise (observed max |diff| 3.0e-6 on
# O(1) activations through 4 layers, jax 0.9.0 CPU). 2e-5 is ~100 float32
# ulps at that magnitude and ~200x below one bfloat16 rounding step
# (2^-8 of the value, ~4e-3), so a silent precision drop still fails.
_TWO_LOWERINGS_ATOL = 2e-5


def test_paged_mixed_length_parity(lm_pair):
    """Mixed-length chunk incl. a single-token and a max-length document:
    hook activations at valid positions equal the padded forward's to
    float32 round-off (``_TWO_LOWERINGS_ATOL``); pad positions come back
    exactly zero (the valid-length mask)."""
    cfg, params = lm_pair
    rng = np.random.default_rng(4)
    lengths = np.array([1, SEQ, 7, 3, 9, 5])
    tokens = rng.integers(1, cfg.vocab_size, size=(6, SEQ), dtype=np.int64)
    for d, ln in enumerate(lengths):
        tokens[d, ln:] = 0
    want = np.asarray(lm.run_with_cache_multi(
        params, jnp.asarray(tokens), cfg, HOOKS), np.float32)
    got = np.asarray(lm.run_with_cache_multi_paged(
        params, tokens, lengths, cfg, HOOKS, page_size=8), np.float32)
    for d, ln in enumerate(lengths):
        np.testing.assert_allclose(
            got[d, :ln], want[d, :ln], rtol=0, atol=_TWO_LOWERINGS_ATOL,
            err_msg=f"doc {d}"
        )
        assert np.all(got[d, ln:] == 0.0)


def test_paged_sublayer_hooks_parity(lm_pair):
    """attn_out/mlp_out capture sites ride the paged runtime too."""
    cfg, params = lm_pair
    hooks = ("blocks.1.hook_attn_out", "blocks.2.hook_mlp_out")
    rng = np.random.default_rng(5)
    lengths = np.array([4, SEQ, 11])
    tokens = rng.integers(1, cfg.vocab_size, size=(3, SEQ), dtype=np.int64)
    for d, ln in enumerate(lengths):
        tokens[d, ln:] = 0
    want = np.asarray(lm.run_with_cache_multi(
        params, jnp.asarray(tokens), cfg, hooks), np.float32)
    got = np.asarray(lm.run_with_cache_multi_paged(
        params, tokens, lengths, cfg, hooks, page_size=4), np.float32)
    for d, ln in enumerate(lengths):
        np.testing.assert_array_equal(
            got[d, :ln], want[d, :ln], err_msg=f"doc {d}"
        )


def test_paged_with_kernel_interpret_parity(lm_pair):
    """The full paged forward with the Pallas ragged-paged-attention
    kernel (interpret mode): allclose to the padded path (online softmax
    reassociates the attention reduction)."""
    from crosscoder_tpu.ops import paged_attention as pam

    cfg, params = lm_pair
    rng = np.random.default_rng(6)
    lengths = np.array([1, SEQ, 7, 3])
    tokens = rng.integers(1, cfg.vocab_size, size=(4, SEQ), dtype=np.int64)
    for d, ln in enumerate(lengths):
        tokens[d, ln:] = 0
    want = np.asarray(lm.run_with_cache_multi(
        params, jnp.asarray(tokens), cfg, HOOKS), np.float32)
    pam.set_interpret(True)
    try:
        got = np.asarray(lm.run_with_cache_multi_paged(
            params, tokens, lengths, cfg, HOOKS, page_size=8), np.float32)
    finally:
        pam.set_interpret(False)
    for d, ln in enumerate(lengths):
        np.testing.assert_allclose(
            got[d, :ln], want[d, :ln], rtol=2e-5, atol=2e-5,
            err_msg=f"doc {d}",
        )


# ---------------------------------------------------------------------------
# replay buffer integration


def _buf_cfg(**kw):
    base = dict(
        batch_size=32, buffer_mult=16, seq_len=17, d_in=32, n_models=2,
        model_batch_size=4, norm_calib_batches=2,
        hook_point="blocks.2.hook_resid_pre", seed=3, page_size=1,
    )
    base.update(kw)
    return CrossCoderConfig(**base)


@pytest.fixture(scope="module")
def buf_inputs():
    cfg = lm.LMConfig.tiny()
    pa = lm.init_params(jax.random.key(0), cfg)
    pb = lm.init_params(jax.random.key(1), cfg)
    rng = np.random.default_rng(7)
    tokens = rng.integers(1, 257, size=(256, 17), dtype=np.int64)
    return cfg, [pa, pb], tokens


def test_buffer_paged_stream_bit_parity(buf_inputs):
    """The CPU bit-parity gate: on the (full-length) production-shaped
    corpus the paged buffer ingests and serves EXACTLY the padded
    buffer's activation stream — store bytes and served batches equal."""
    lm_cfg, params, tokens = buf_inputs
    b_pad = make_buffer(_buf_cfg(), lm_cfg, params, tokens)
    b_pag = make_buffer(_buf_cfg(harvest_runtime="paged"), lm_cfg, params,
                        tokens)
    np.testing.assert_array_equal(
        np.asarray(b_pad._store, np.float32),
        np.asarray(b_pag._store, np.float32),
    )
    np.testing.assert_array_equal(
        b_pad.normalisation_factor, b_pag.normalisation_factor
    )
    for _ in range(3):
        np.testing.assert_array_equal(
            np.asarray(b_pad.next_raw(), np.float32),
            np.asarray(b_pag.next_raw(), np.float32),
        )
    assert b_pag.padding_efficiency() == 1.0
    assert b_pad.padding_efficiency() is None


def test_buffer_paged_ragged_corpus_serves(buf_inputs):
    """A ragged corpus (trailing pads) harvests through the paged runtime
    end-to-end: serves stay finite, NO all-zero pad row ever enters the
    replay store (pad positions wrap the document's own real rows),
    telemetry reports the real-token fraction, and refill cycles keep
    working."""
    lm_cfg, params, tokens = buf_inputs
    rng = np.random.default_rng(8)
    ragged = np.array(tokens[:128])
    lens = rng.integers(2, 18, size=128)
    for d, ln in enumerate(lens):
        ragged[d, ln:] = 0
    buf = make_buffer(_buf_cfg(harvest_runtime="paged"), lm_cfg, params,
                      ragged)
    eff = buf.padding_efficiency()
    assert eff is not None and 0.1 < eff < 1.0
    store = np.asarray(buf._store, np.float32)
    row_norms = np.abs(store).sum(axis=(1, 2))
    assert (row_norms > 0).all(), "pad rows leaked into the replay store"
    # 8 serves of 32 cross the half-buffer trigger (512//2 - 32 = 224),
    # so a full incremental refill cycle completes on the ragged corpus
    for _ in range(8):
        x = np.asarray(buf.next_raw(), np.float32)
        assert np.isfinite(x).all()
        assert (np.abs(x).sum(axis=(1, 2)) > 0).all()


def test_paged_wrap_mode_recycles_real_rows(lm_pair):
    """pad_mode='wrap' (the buffer's ingestion mode): positions past a
    document's length repeat its own post-BOS rows in cycle order;
    single-token documents fall back to the BOS row."""
    cfg, params = lm_pair
    rng = np.random.default_rng(9)
    lengths = np.array([1, 4, SEQ])
    tokens = rng.integers(1, cfg.vocab_size, size=(3, SEQ), dtype=np.int64)
    for d, ln in enumerate(lengths):
        tokens[d, ln:] = 0
    got = np.asarray(lm.run_with_cache_multi_paged(
        params, tokens, lengths, cfg, HOOKS, page_size=8, pad_mode="wrap"),
        np.float32)
    # doc 1 (len 4): t=4 -> row 1, t=5 -> row 2, t=6 -> row 3, t=7 -> row 1
    for t, src in [(4, 1), (5, 2), (6, 3), (7, 1)]:
        np.testing.assert_array_equal(got[1, t], got[1, src])
    # doc 0 (len 1): everything wraps onto the BOS row
    for t in range(1, SEQ):
        np.testing.assert_array_equal(got[0, t], got[0, 0])
    # full-length doc: untouched (identity gather)
    assert np.abs(got[2]).sum() > 0
    with pytest.raises(ValueError, match="pad_mode"):
        lm.run_with_cache_multi_paged(
            params, tokens, lengths, cfg, HOOKS, page_size=8,
            pad_mode="mask")


def test_buffer_padded_never_touches_paged_runtime(buf_inputs, monkeypatch):
    """Zero-cost off: with the default runtime the paged entry point is
    unreachable from construction through serves and refills."""
    lm_cfg, params, tokens = buf_inputs

    def boom(*a, **kw):
        raise AssertionError("paged runtime reached with harvest_runtime=padded")

    monkeypatch.setattr(lm, "run_with_cache_multi_paged", boom)
    buf = make_buffer(_buf_cfg(), lm_cfg, params, tokens)
    for _ in range(4):
        buf.next_raw()


def test_step_hlo_independent_of_harvest_runtime():
    """The compiled train step must not change when the paged knobs are
    present (harvest_runtime is a data-plane selector; page_size is inert
    without it): byte-identical HLO — the same discipline as
    --quant-buffer / sparse_bwd."""
    from crosscoder_tpu.train import schedules
    from crosscoder_tpu.train.state import init_train_state, make_optimizer
    from crosscoder_tpu.train.trainer import make_train_step

    texts = []
    for extra in ({}, dict(harvest_runtime="paged", page_size=8)):
        cfg = CrossCoderConfig(d_in=8, dict_size=32, batch_size=32,
                               enc_dtype="fp32", seq_len=16, **extra)
        mesh = mesh_lib.make_mesh(devices=jax.devices()[:1])
        tx = make_optimizer(cfg, schedules.lr_schedule(cfg))
        state = jax.eval_shape(lambda k: init_train_state(k, cfg, tx),
                               jax.random.key(0))
        shardings = mesh_lib.state_shardings(mesh, state, cfg.shard_sources)
        step = make_train_step(cfg, mesh, tx, shardings)
        state_sh = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            state, shardings,
        )
        batch = jax.ShapeDtypeStruct(
            (cfg.batch_size, cfg.n_sources, cfg.d_in), jnp.float32,
            sharding=mesh_lib.batch_sharding(mesh),
        )
        scale = jax.ShapeDtypeStruct(
            (cfg.n_sources,), jnp.float32,
            sharding=NamedSharding(mesh, P()),
        )
        texts.append(step.lower(state_sh, batch, scale).as_text())
    assert texts[0] == texts[1]


# ---------------------------------------------------------------------------
# config validation


def test_config_harvest_runtime_suggestions():
    with pytest.raises(ValueError, match="did you mean 'paged'"):
        CrossCoderConfig(harvest_runtime="pagd")
    with pytest.raises(ValueError, match="padded\\|paged"):
        CrossCoderConfig(harvest_runtime="ragged")


def test_config_page_size_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        CrossCoderConfig(page_size=48)
    with pytest.raises(ValueError, match="power of two"):
        CrossCoderConfig(page_size=0)
    CrossCoderConfig(page_size=128)          # fine when padded


def test_config_paged_seq_len_constraints():
    with pytest.raises(ValueError, match="smaller than page_size"):
        CrossCoderConfig(harvest_runtime="paged", seq_len=32, page_size=64)
    with pytest.raises(ValueError, match="must divide seq_len"):
        CrossCoderConfig(harvest_runtime="paged", seq_len=96, page_size=64)
    with pytest.raises(ValueError, match="incompatible with"):
        CrossCoderConfig(harvest_runtime="paged", seq_len=1024, page_size=64,
                         seq_shards=2)
    CrossCoderConfig(harvest_runtime="paged", seq_len=1024, page_size=64)
