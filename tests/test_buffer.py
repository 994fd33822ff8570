"""Tests for the paired-activation replay buffer (reference buffer.py:7-125
semantics), driven by the tiny fake-LM fixture — no real model downloads
(SURVEY.md §4 "fake-LM fixture")."""

import numpy as np
import pytest

import jax

from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.data.buffer import PairedActivationBuffer
from crosscoder_tpu.models import lm


SEQ = 17          # rows_per_seq = 16
HP = "blocks.2.hook_resid_pre"


@pytest.fixture(scope="module")
def lm_pair():
    cfg = lm.LMConfig.tiny()
    pa = lm.init_params(jax.random.key(0), cfg)
    pb = lm.init_params(jax.random.key(1), cfg)
    return cfg, [pa, pb]


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.default_rng(7)
    return rng.integers(0, 257, size=(256, SEQ), dtype=np.int64)


def make_cfg(**kw):
    base = dict(
        batch_size=32, buffer_mult=32, seq_len=SEQ, d_in=32, n_models=2,
        model_batch_size=4, norm_calib_batches=2, hook_point=HP, seed=3,
    )
    base.update(kw)
    return CrossCoderConfig(**base)


@pytest.fixture(scope="module")
def buf(lm_pair, tokens):
    lm_cfg, params = lm_pair
    return PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)


def test_size_accounting(buf):
    """buffer_size = batch·mult rounded down to whole (seq_len−1)-row seqs
    (reference buffer.py:15-17)."""
    assert buf.buffer_batches == 32 * 32 // 16 == 64
    assert buf.buffer_size == 64 * 16 == 1024
    assert buf._store.shape == (1024, 2, 32)


def test_first_fill_matches_direct_harvest(buf, lm_pair, tokens):
    """Store rows (harvest order) == both models' hook acts with BOS dropped,
    flattened (reference buffer.py:91-101)."""
    lm_cfg, params = lm_pair
    want = []
    for p in params:
        cache = lm.run_with_cache(p, tokens[:4], lm_cfg, [HP])
        want.append(np.asarray(cache[HP].astype(jax.numpy.bfloat16), dtype=np.float32))
    want = np.stack(want, axis=2)[:, 1:]                     # [4, S-1, 2, d]
    want = want.reshape(-1, 2, 32)
    got = buf._store[: want.shape[0]].astype(np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


def test_norm_factor_formula(buf, lm_pair, tokens):
    """factor = sqrt(d_in)/mean_token_norm per source, over the leading
    calib sequences, BOS included (reference buffer.py:44-63)."""
    lm_cfg, params = lm_pair
    n_seqs = 2 * 4
    norms = []
    for p in params:
        cache = lm.run_with_cache(p, tokens[:n_seqs], lm_cfg, [HP])
        acts = np.asarray(cache[HP].astype(jax.numpy.bfloat16), dtype=np.float32)
        norms.append(np.linalg.norm(acts, axis=-1).mean())
    want = np.sqrt(32) / np.asarray(norms)
    np.testing.assert_allclose(buf.normalisation_factor, want, rtol=2e-2)


def test_next_shape_dtype_and_scaling(lm_pair, tokens):
    lm_cfg, params = lm_pair
    b = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    idx = b._perm[: 32].copy()
    raw = b._store[idx].astype(np.float32)
    out = b.next()
    assert out.shape == (32, 2, 32) and out.dtype == np.float32
    np.testing.assert_allclose(
        out, raw * b.normalisation_factor[None, :, None], rtol=1e-6
    )


def test_refresh_cadence_and_half_refill(lm_pair, tokens):
    """The refill cycle completes at the reference's trigger point (pointer
    passes buffer//2 − batch, reference buffer.py:121) and harvests half the
    seqs per cycle (buffer.py:70-74) — but the harvest itself now runs
    INCREMENTALLY between serves (chunks land on already-served permutation
    slots), so the trigger point only drains stragglers and re-shuffles
    instead of stalling for the whole half-buffer harvest."""
    lm_cfg, params = lm_pair
    b = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    assert b.token_pointer == 64
    perm_before = b._perm.copy()
    store_before = b._store.copy()
    served = []
    for steps in range(1, 17):
        served.append(b._perm[b.pointer: b.pointer + 32].copy())
        b.next()
        if steps < 16:
            assert b.pointer == 32 * steps       # cycle not finished yet
    # trigger: after 16 serves of 32 rows the pointer passed 512 − 32
    assert b.pointer == 0
    assert b.token_pointer == 64 + 32            # half refill: 32 more seqs
    # unserved survivors (old perm tail) are byte-identical; the served
    # region was refilled with fresh rows
    survivors = perm_before[512:]
    np.testing.assert_array_equal(b._store[survivors], store_before[survivors])
    refilled = perm_before[:512]
    assert not np.array_equal(b._store[refilled], store_before[refilled])
    # no row served twice within the fill; every served position lies in
    # the refilled region
    served = np.concatenate(served)
    assert len(np.unique(served)) == len(served)
    assert set(served) <= set(refilled)


@pytest.mark.parametrize("buffer_mult", [32, 33])
def test_incremental_refill_never_corrupts_served_stream(lm_pair, tokens, buffer_mult):
    """The overlap invariant: harvest chunks written mid-cycle may only land
    on slots this fill can no longer serve, so every batch served during a
    fill is byte-identical to the store content AT fill time — the stream is
    exactly what a synchronous refresh would have served. Also probes that
    the harvest really is interleaved (token pointer advances mid-cycle,
    not in one stall at the trigger).

    buffer_mult=32 gives _cyc_tail == 0 (refill exactly covers the served
    region); 33 gives a buffer whose half-refill target exceeds the rows
    served by trigger time (_cyc_tail == 16), exercising the tail-rotation
    write path the production geometry hits (tail 3,840 at reference cfg)."""
    lm_cfg, params = lm_pair
    cfg = make_cfg(buffer_mult=buffer_mult)
    b = PairedActivationBuffer(cfg, lm_cfg, params, tokens)
    if buffer_mult == 33:
        assert b._cyc_tail > 0, "geometry no longer exercises the tail path"
    n_serve = (b.buffer_size // 2 - 32) // 32 + 1
    start_tp = b.token_pointer
    for cycle in range(2):                       # first and a survivor cycle
        snap = b._store.copy()
        perm = b._perm.copy()
        scale = b.normalisation_factor[None, :, None]
        for k in range(n_serve):
            want = snap[perm[32 * k: 32 * k + 32]].astype(np.float32) * scale
            got = b.next()
            assert np.array_equal(got, want), (cycle, k)
            if k == n_serve - 2:
                assert b.token_pointer != (start_tp + cycle * b.buffer_batches // 2) % 256, \
                    "harvest was not interleaved with serving"


def test_forced_refresh_mid_cycle_rewinds_all_dispatched_tokens(lm_pair, tokens):
    """A public refresh() mid-cycle abandons the unfinished cycle. EVERY
    sequence it dispatched — in-flight AND already drained into the store —
    is unserved (cycle rows become servable only at _finish_cycle), so the
    token stream must rewind over all of them or those sequences would be
    harvested, overwritten, and never seen (silent data gap)."""
    lm_cfg, params = lm_pair
    b = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    for _ in range(6):                           # mid-cycle; harvest underway
        b.next()
    dispatched = b._cyc_seq_done
    drained = dispatched - sum(item[1] for item in b._cyc_inflight)
    assert dispatched > 0 and drained > 0        # both kinds present mid-cycle
    tp = b.token_pointer
    b.refresh()                                  # forced half refill
    assert b.token_pointer == (tp - dispatched + 32) % 256


def test_restore_on_live_buffer_keeps_checkpoint_position(lm_pair, tokens):
    """load_state_dict() on a buffer that has been serving (Trainer.restore
    path) must resume EXACTLY at the checkpoint's stream position — the
    abandoned pre-restore cycle's chunks must not rewind the restored
    pointer. The restored live buffer must equal a fresh-buffer restore."""
    lm_cfg, params = lm_pair
    donor = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    for _ in range(20):                          # crosses one refresh
        donor.next()
    state = donor.state_dict()

    live = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    for _ in range(6):                           # live mid-cycle, chunks in flight
        live.next()
    assert live._cyc_seq_done > 0
    live.load_state_dict(state)

    fresh = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens, lazy=True)
    fresh.load_state_dict(state)
    assert live.token_pointer == fresh.token_pointer
    np.testing.assert_array_equal(live._store, fresh._store)
    for _ in range(3):
        np.testing.assert_array_equal(live.next(), fresh.next())


def test_lazy_buffer_defers_harvest(lm_pair, tokens):
    """lazy=True skips calibration+fill (the resume path must not harvest
    the buffer twice); next() before load_state_dict is an error."""
    lm_cfg, params = lm_pair
    donor = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    state = donor.state_dict()
    b = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens, lazy=True)
    assert b.token_pointer == 0 and not b._filled
    with pytest.raises(RuntimeError):
        b.next()
    b.load_state_dict(state)
    assert b.next().shape == (32, 2, 32)


def test_sharded_ragged_harvest(lm_pair, tokens):
    """model_batch_size not divisible by the mesh data axis (the default
    cfg on any 8-device TPU) must still harvest: chunks are padded to a
    fixed shard-divisible shape and results match the unsharded buffer."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    lm_cfg, params = lm_pair
    mesh = Mesh(np.array(jax.devices()).reshape(-1, 1), ("data", "model"))
    sh = NamedSharding(mesh, P("data", None))
    assert mesh.shape["data"] == 8
    b = PairedActivationBuffer(make_cfg(model_batch_size=3), lm_cfg, params,
                               tokens, batch_sharding=sh)
    assert b._chunk_seqs == 8
    ref = PairedActivationBuffer(make_cfg(model_batch_size=3), lm_cfg, params, tokens)
    np.testing.assert_allclose(
        b.normalisation_factor, ref.normalisation_factor, rtol=1e-3
    )
    np.testing.assert_allclose(
        b._store.astype(np.float32), ref._store.astype(np.float32),
        rtol=1e-2, atol=1e-2,   # batch-shape-dependent bf16 rounding only
    )


def test_no_repeat_within_fill(lm_pair, tokens):
    """Index-permutation serving = the reference's full-buffer shuffle:
    rows served between refreshes are distinct storage rows."""
    lm_cfg, params = lm_pair
    b = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    seen = []
    for _ in range(16):
        seen.append(b._perm[b.pointer: b.pointer + 32])
        b.next()
    seen = np.concatenate(seen)
    assert len(np.unique(seen)) == len(seen)


def test_multi_source_hooks(lm_pair, tokens):
    """Two hook points × two models → n_sources=4, model-major source order
    (the N4/N8 generalization of the reference's hardcoded pair)."""
    lm_cfg, params = lm_pair
    cfg = make_cfg(hook_points=("blocks.1.hook_resid_pre", "blocks.3.hook_resid_pre"))
    b = PairedActivationBuffer(cfg, lm_cfg, params, tokens)
    assert cfg.n_sources == 4
    assert b._store.shape == (1024, 4, 32)
    cache = lm.run_with_cache(params[0], tokens[:4], lm_cfg, cfg.hook_points)
    want = np.asarray(cache[cfg.hook_points[1]].astype(jax.numpy.bfloat16), np.float32)
    got = b._store[: 4 * 16, 1].astype(np.float32).reshape(4, 16, 32)
    np.testing.assert_allclose(got, want[:, 1:], rtol=1e-2, atol=1e-2)


def test_multi_source_mixed_sites(lm_pair, tokens):
    """hook_points mixing residual and sublayer sites (round-4 hook-site
    generality): a crosscoder over {resid_pre, attn_out, mlp_out} of the
    same model pair harvests each site faithfully (store slab == the
    corresponding single-site capture)."""
    lm_cfg, params = lm_pair
    cfg = make_cfg(hook_points=("blocks.1.hook_resid_pre",
                                "blocks.1.hook_attn_out",
                                "blocks.2.hook_mlp_out"))
    b = PairedActivationBuffer(cfg, lm_cfg, params, tokens)
    assert cfg.n_sources == 6                    # 2 models × 3 sites
    assert b._store.shape == (1024, 6, 32)
    for si, hp in enumerate(cfg.hook_points):
        cache = lm.run_with_cache(params[0], tokens[:4], lm_cfg, [hp])
        want = np.asarray(cache[hp].astype(jax.numpy.bfloat16), np.float32)
        got = b._store[: 4 * 16, si].astype(np.float32).reshape(4, 16, 32)
        np.testing.assert_allclose(got, want[:, 1:], rtol=1e-2, atol=1e-2,
                                   err_msg=hp)


def test_resume_roundtrip(lm_pair, tokens):
    """state_dict → fresh buffer → load_state_dict continues the token
    stream at the saved position with the saved norm factors."""
    lm_cfg, params = lm_pair
    b1 = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    for _ in range(20):                          # crosses one refresh
        b1.next()
    state = b1.state_dict()
    b2 = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    b2.load_state_dict(state)
    assert b2.token_pointer == (int(state["token_pointer"]) + 64) % 256
    np.testing.assert_array_equal(b2.normalisation_factor, b1.normalisation_factor)
    out = b2.next()
    assert out.shape == (32, 2, 32)


def test_token_wraparound(lm_pair, tokens):
    """The harvest wraps at the corpus end instead of the reference's
    IndexError past its token budget."""
    lm_cfg, params = lm_pair
    b = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens[:80])
    assert b.token_pointer == 64
    for _ in range(16):                          # one full refill cycle
        b.next()
    assert b.token_pointer == (64 + 32) % 80


def test_rejects_mismatched_models(lm_pair, tokens):
    lm_cfg, params = lm_pair
    with pytest.raises(ValueError):
        PairedActivationBuffer(make_cfg(n_models=3), lm_cfg, params, tokens)


def test_resume_rewinds_to_oldest_unserved_row(lm_pair, tokens):
    """Per-row provenance: the saved token pointer equals the OLDEST
    unserved row's source sequence, so no harvested-but-unserved token is
    skipped by save/resume (mid-fill save, survivors from the first fill
    still present)."""
    lm_cfg, params = lm_pair
    b = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    for _ in range(20):                          # crosses one refresh
        b.next()
    assert b.pointer > 0
    state = b.state_dict()
    oldest = int(b._src_global[b._perm[b.pointer:]].min())
    assert state["token_pointer"] == oldest % 256
    # survivors of the first fill are unserved ⇒ rewind reaches back into it
    assert oldest < 64


def test_save_before_first_fill_resumes_from_scratch(lm_pair, tokens):
    lm_cfg, params = lm_pair
    b = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens, lazy=True)
    state = b.state_dict()                       # crash-during-startup save
    assert state["normalisation_factor"] is None
    b2 = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens, lazy=True)
    b2.load_state_dict(state)
    assert b2._filled and b2.token_pointer == 64
    assert b2.next().shape == (32, 2, 32)


def test_next_raw_matches_next(lm_pair, tokens):
    """Raw-bf16 serving + on-host upcast·scale == the fp32 serve path, bit
    for bit — so the trainer's on-device scale path (trainer step_fn) is the
    same stream the reference serves (reference buffer.py:115-125)."""
    lm_cfg, params = lm_pair
    a = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    b = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    for _ in range(4):
        served = a.next()
        raw = b.next_raw()
        scaled = raw.astype(np.float32) * b.normalisation_factor[None, :, None]
        assert np.array_equal(served, scaled)


def test_native_and_numpy_serve_identically(lm_pair, tokens, monkeypatch):
    """The C++ gather/scatter kernels and the NumPy fallback produce the
    same buffer trajectory (fills + serves) byte-identically."""
    from crosscoder_tpu import native

    if not native.available():
        pytest.skip("native kernels unavailable")
    lm_cfg, params = lm_pair
    a = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    batches_native = [a.next() for _ in range(6)]

    # force the numpy fallback and replay
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_err", "forced-off for test")
    b = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    batches_numpy = [b.next() for _ in range(6)]
    for x, y in zip(batches_native, batches_numpy):
        assert np.array_equal(x, y)


def test_seq_parallel_harvest_matches_dense(lm_pair):
    """cfg.seq_shards routes the harvest through forward_seq_parallel (ring
    attention over the mesh data axis) — component N5 reachable from the
    production config. The harvested store, norm factors, and served stream
    must match the dense batch-sharded path."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    lm_cfg, params = lm_pair
    SEQ2 = 16                                     # divisible by the 8 shards
    rng = np.random.default_rng(5)
    toks = rng.integers(0, 257, size=(256, SEQ2), dtype=np.int64)
    mesh = Mesh(np.array(jax.devices()).reshape(-1, 1), ("data", "model"))
    sh = NamedSharding(mesh, P("data", None))

    def cfg(**kw):
        return make_cfg(seq_len=SEQ2, batch_size=30, buffer_mult=30, **kw)

    b_seq = PairedActivationBuffer(
        cfg(seq_shards=8), lm_cfg, params, toks, batch_sharding=sh
    )
    b_dense = PairedActivationBuffer(cfg(), lm_cfg, params, toks)
    np.testing.assert_allclose(
        b_seq.normalisation_factor, b_dense.normalisation_factor, rtol=1e-3
    )
    np.testing.assert_allclose(
        b_seq._store.astype(np.float32), b_dense._store.astype(np.float32),
        rtol=2e-2, atol=2e-2,   # ring-order bf16 accumulation differences only
    )
    for _ in range(3):
        a, b = b_seq.next(), b_dense.next()
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)


def test_seq_shards_validation(lm_pair, tokens):
    lm_cfg, params = lm_pair
    import pytest as _pytest

    with _pytest.raises(ValueError, match="seq_shards needs a mesh"):
        PairedActivationBuffer(
            make_cfg(seq_len=16, seq_shards=8), lm_cfg, params, tokens[:, :16]
        )
    with _pytest.raises(ValueError, match="must divide seq_len"):
        make_cfg(seq_len=17, seq_shards=8)


def test_device_buffer_matches_host_buffer(lm_pair, tokens):
    """cfg.buffer_device='hbm': the HBM-resident store serves the exact
    same stream as the host-RAM buffer — same fills, same permutations,
    same bytes — with batches coming back device-resident."""
    from crosscoder_tpu.data.buffer import DevicePairedActivationBuffer

    lm_cfg, params = lm_pair
    host = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    dev = DevicePairedActivationBuffer(make_cfg(), lm_cfg, params, tokens)
    np.testing.assert_array_equal(dev.normalisation_factor, host.normalisation_factor)
    np.testing.assert_array_equal(dev._store, host._store)
    for step in range(20):                       # crosses one refill cycle
        a = host.next()
        b = dev.next()
        assert isinstance(b, jax.Array)
        np.testing.assert_allclose(np.asarray(b), a, rtol=1e-6, atol=1e-7), step
    # raw serving parity too
    np.testing.assert_array_equal(
        np.asarray(dev.next_raw(), np.float32),
        host.next_raw().astype(np.float32),
    )


def test_device_buffer_ragged_chunk_scratch_row(lm_pair, tokens):
    """Ragged harvest chunks pad their scatter positions with the scratch
    row; served data must still exactly match the host path (which slices
    the padding off instead)."""
    from crosscoder_tpu.data.buffer import DevicePairedActivationBuffer

    lm_cfg, params = lm_pair
    # model_batch_size 3 does not divide the 4-seq first fill → ragged tail
    host = PairedActivationBuffer(make_cfg(model_batch_size=3), lm_cfg, params, tokens)
    dev = DevicePairedActivationBuffer(make_cfg(model_batch_size=3), lm_cfg, params, tokens)
    np.testing.assert_array_equal(dev._store, host._store)


def test_device_buffer_through_trainer(lm_pair, tokens):
    """End-to-end: the trainer consumes device-resident batches from the
    HBM buffer (prefetch on) and trains; loss matches the host-buffer
    trainer step for step."""
    from crosscoder_tpu.data.buffer import DevicePairedActivationBuffer
    from crosscoder_tpu.parallel import mesh as mesh_lib
    from crosscoder_tpu.train.trainer import Trainer

    lm_cfg, params = lm_pair
    cfg = make_cfg(dict_size=64, num_tokens=32 * 6, log_backend="null")
    mesh = mesh_lib.mesh_from_cfg(cfg)
    t_host = Trainer(cfg, PairedActivationBuffer(cfg, lm_cfg, params, tokens), mesh=mesh)
    t_dev = Trainer(cfg, DevicePairedActivationBuffer(cfg, lm_cfg, params, tokens), mesh=mesh)
    for _ in range(6):
        mh = t_host.step()
        md = t_dev.step()
        assert float(jax.device_get(mh["loss"])) == float(jax.device_get(md["loss"]))
    t_host.close()
    t_dev.close()


def test_refill_frac_quarter_reuses_activations(lm_pair, tokens):
    """refill_frac 0.25: each steady-state cycle serves half the buffer but
    re-harvests only a quarter — ~2 serves per harvested row, harvest FLOPs
    halved (the TPU-era freshness/throughput knob; 0.5 = reference parity).
    The serve stream must stay uncorrupted and the accounting exact."""
    lm_cfg, params = lm_pair
    b = PairedActivationBuffer(make_cfg(refill_frac=0.25), lm_cfg, params, tokens)
    assert b._refill_batches() == 16                 # vs 32 at parity
    tp0 = b.token_pointer
    # two full serve cycles; every served batch must match the store+perm
    # at fill time (the incremental-refill write-safety invariant)
    for cycle in range(2):
        snap = b._store.copy()
        perm = b._perm.copy()
        scale = b.normalisation_factor[None, :, None]
        for k in range(16):
            want = snap[perm[32 * k: 32 * k + 32]].astype(np.float32) * scale
            np.testing.assert_array_equal(b.next(), want)
    # 2 cycles x 1024/2 rows served = 1024 rows; harvested 2 x 16 seqs = 512
    assert b.token_pointer == (tp0 + 2 * 16) % 256


def test_refill_frac_validation():
    with pytest.raises(ValueError, match="refill_frac"):
        make_cfg(refill_frac=0.75)
    with pytest.raises(ValueError, match="refill_frac"):
        make_cfg(refill_frac=0.0)


# ---------------------------------------------------------------------------
# mesh-sharded HBM store (round-3; VERDICT round-2 missing #3)


def _data_mesh():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()).reshape(-1, 1), ("data", "model"))
    return mesh, NamedSharding(mesh, P("data", None))


def test_mesh_buffer_selected_and_matches_host(lm_pair, tokens):
    """On a multi-chip mesh, buffer_device='hbm' routes to the data-axis
    sharded store; the served stream must equal the host-RAM buffer's
    byte for byte, with batches coming back in the step's batch sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from crosscoder_tpu.data.buffer import (
        MeshPairedActivationBuffer, make_buffer,
    )

    lm_cfg, params = lm_pair
    mesh, sh = _data_mesh()
    host = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens,
                                  batch_sharding=sh)
    dev = make_buffer(make_cfg(buffer_device="hbm"), lm_cfg, params, tokens,
                      batch_sharding=sh)
    assert isinstance(dev, MeshPairedActivationBuffer)
    np.testing.assert_array_equal(dev.normalisation_factor,
                                  host.normalisation_factor)
    np.testing.assert_array_equal(dev._store, host._store)
    want_sh = NamedSharding(mesh, P("data", None, None))
    for step in range(20):                       # crosses one refill cycle
        a = host.next()
        b = dev.next()
        assert isinstance(b, jax.Array)
        assert b.sharding.is_equivalent_to(want_sh, b.ndim), step
        np.testing.assert_allclose(np.asarray(b), a, rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(
        np.asarray(dev.next_raw(), np.float32),
        host.next_raw().astype(np.float32),
    )


def test_mesh_buffer_padded_store_and_ragged_chunks(lm_pair, tokens):
    """buffer_size not divisible by the shard count pads the store; ragged
    harvest chunks pad their scatter positions past the PADDED store. Both
    kinds of pad rows must never reach a served batch."""
    from crosscoder_tpu.data.buffer import make_buffer

    lm_cfg, params = lm_pair
    # seq_len 13 → 12 rows/seq → buffer_size 32·32//12·12 = 1020, % 8 != 0;
    # model_batch_size 3 → ragged final chunk of the first fill
    kw = dict(seq_len=13, model_batch_size=3)
    toks = tokens[:, :13]
    mesh, sh = _data_mesh()
    host = PairedActivationBuffer(make_cfg(**kw), lm_cfg, params, toks,
                                  batch_sharding=sh)
    dev = make_buffer(make_cfg(buffer_device="hbm", **kw), lm_cfg, params,
                      toks, batch_sharding=sh)
    assert dev.buffer_size % 8 != 0 and dev._store_size % 8 == 0
    np.testing.assert_array_equal(dev._store, host._store)
    for _ in range(6):
        np.testing.assert_allclose(np.asarray(dev.next()), host.next(),
                                   rtol=1e-6, atol=1e-7)


def test_mesh_buffer_resume_matches_host(lm_pair, tokens):
    """state_dict/load_state_dict through the sharded store reproduces the
    host buffer's restored stream exactly (A4 resume determinism)."""
    from crosscoder_tpu.data.buffer import make_buffer

    lm_cfg, params = lm_pair
    mesh, sh = _data_mesh()
    host = PairedActivationBuffer(make_cfg(), lm_cfg, params, tokens,
                                  batch_sharding=sh)
    dev = make_buffer(make_cfg(buffer_device="hbm"), lm_cfg, params, tokens,
                      batch_sharding=sh)
    for _ in range(5):
        host.next(), dev.next()
    state = host.state_dict()
    assert state == dev.state_dict()
    host.load_state_dict(state)
    dev.load_state_dict(state)
    for _ in range(8):
        np.testing.assert_allclose(np.asarray(dev.next()), host.next(),
                                   rtol=1e-6, atol=1e-7)


def test_mesh_buffer_through_trainer(lm_pair, tokens):
    """The trainer consumes pre-sharded batches from the mesh store on an
    8-way data mesh; loss trajectory matches the host-buffer trainer."""
    from crosscoder_tpu.data.buffer import make_buffer
    from crosscoder_tpu.parallel import mesh as mesh_lib
    from crosscoder_tpu.train.trainer import Trainer

    lm_cfg, params = lm_pair
    cfg = make_cfg(dict_size=64, num_tokens=32 * 6, log_backend="null")
    mesh = mesh_lib.mesh_from_cfg(cfg)
    assert int(mesh.shape["data"]) == 8
    sh = mesh_lib.batch_sharding(mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    tok_sh = NamedSharding(mesh, P("data", None))
    t_host = Trainer(cfg, PairedActivationBuffer(cfg, lm_cfg, params, tokens,
                                                 batch_sharding=tok_sh),
                     mesh=mesh)
    cfg_d = cfg.replace(buffer_device="hbm")
    t_dev = Trainer(cfg_d, make_buffer(cfg_d, lm_cfg, params, tokens,
                                       batch_sharding=tok_sh), mesh=mesh)
    for _ in range(6):
        mh = t_host.step()
        md = t_dev.step()
        assert float(jax.device_get(mh["loss"])) == float(jax.device_get(md["loss"]))
    t_host.close()
    t_dev.close()


def test_mesh_buffer_serves_without_device_to_device_transfers(lm_pair, tokens):
    """LM weights handed over uncommitted on the default device (what
    ``lm.from_hf`` without shardings leaves) are committed to the mesh ONCE,
    at construction; a serve + refill cycle then makes no implicit
    device-to-device transfer — each one would be a re-replication per
    dispatch on a real multi-chip host (weights, gather indices, scalars)."""
    from crosscoder_tpu.data.buffer import make_buffer

    lm_cfg, params = lm_pair
    assert not params[0]["embed"].committed
    mesh, sh = _data_mesh()
    dev = make_buffer(make_cfg(buffer_device="hbm"), lm_cfg, params, tokens,
                      batch_sharding=sh)
    for leaf in jax.tree_util.tree_leaves(dev.model_params):
        assert leaf.committed and len(leaf.sharding.device_set) == mesh.size
    with jax.transfer_guard_device_to_device("disallow"):
        for _ in range(20):                      # crosses one refill cycle
            dev.next_raw()
