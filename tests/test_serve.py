"""The serving path (cfg.serve; serve/engine.py + serve/step.py +
serve/replica.py; docs/SERVING.md): bitwise parity of served results vs
the offline padded oracle (mixed lengths, bucket padding, the extend
path), the deadline/backpressure/shed admission semantics, the
zero-compiles-after-warmup SLO, and the replica drain hand-off. All CPU,
tier-1; the tiny serving stack comes from serve/smoke.py so the test and
the smoke drive literally the same engine."""

import numpy as np
import pytest

from crosscoder_tpu.data.paging import ContinuousBatcher
from crosscoder_tpu.serve import InferenceEngine, Shed, batch_buckets, bucket_of
from crosscoder_tpu.serve.replica import ReplicaBoard, ServeReplica
from crosscoder_tpu.serve.smoke import build_engine, oracle, serve_batch

SEQ = 16


class Clock:
    """Injected engine clock: tests advance time, nothing sleeps."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture()
def stack():
    return build_engine(serve_max_batch=8)


def _docs(rng, lm_cfg, lengths):
    return [rng.integers(1, lm_cfg.vocab_size, size=int(ln),
                         dtype=np.int32) for ln in lengths]


def _padded(docs, seq_len):
    tokens = np.zeros((len(docs), seq_len), np.int64)
    for d, doc in enumerate(docs):
        tokens[d, : doc.shape[0]] = doc
    return tokens, np.asarray([d.shape[0] for d in docs])


# ---------------------------------------------------------------------------
# the device step alone against NumPy


@pytest.mark.parametrize("B,k,n_models,n_hooks,enc_dtype", [
    (192, 8, 2, 1, "fp32"), (192, 8, 2, 1, "bf16"),     # the widest bucket of old
    (8, 8, 2, 1, "fp32"), (8, 8, 2, 1, "bf16"),         # the cells' serve_max_batch
    (1, 8, 2, 1, "bf16"),                               # one request
    (8, 32, 2, 2, "bf16"),                              # two hooks a model: pair (0, 2)
])
def test_encode_topk_diff_matches_numpy(B, k, n_models, n_hooks, enc_dtype):
    """``(vals, idx, diff)`` of ``serve.step.encode_topk_diff`` against a NumPy
    top-k of ``relu(x·W_enc + b_enc)`` at each request's last valid token.
    Small integer operands make every pre-act sum exact, and the second half
    of the dictionary copies the first (encoder columns and biases), so every
    value is tied at least twice; ReLU ties the rest at zero. Ties go to the
    lowest latent index, as ``lax.top_k`` orders them."""
    import jax
    import jax.numpy as jnp
    from ml_dtypes import bfloat16

    from crosscoder_tpu.config import CrossCoderConfig
    from crosscoder_tpu.models import crosscoder
    from crosscoder_tpu.serve.step import diff_pair, encode_topk_diff

    n, H, S = n_models * n_hooks, 1024, 8
    d = 256 // n
    hooks = tuple(f"blocks.{i}.hook_resid_pre" for i in range(n_hooks))
    cfg = CrossCoderConfig(d_in=d, dict_size=H, n_models=n_models, hook_points=hooks,
                           activation="topk", topk_k=k, l1_coeff=0.0,
                           enc_dtype=enc_dtype, log_backend="null")
    assert cfg.n_sources == n
    rng = np.random.default_rng(B + k + n)
    dt = bfloat16 if enc_dtype == "bf16" else np.float32
    W = np.tile(rng.integers(-1, 2, size=(n, d, H // 2)), 2)
    b = np.tile(rng.integers(-2, 3, size=(H // 2,)), 2)
    caps = rng.integers(-1, 2, size=(B, S, n, d))
    lengths = rng.integers(1, S + 1, size=(B,))
    norm = np.asarray([0.5, 2.0, 1.0, 4.0][:n], np.float32)
    params = dict(crosscoder.init_params(jax.random.key(2), cfg, dtype=jnp.float32),
                  W_enc=jnp.asarray(W, jnp.float32), b_enc=jnp.asarray(b, jnp.float32))
    pair = diff_pair(n, n_models)
    vals, idx, diff = encode_topk_diff(
        params, jnp.asarray(caps.astype(dt)), jnp.asarray(lengths, jnp.int32),
        jnp.asarray(norm), enc_dtype=enc_dtype, k=k, pair=pair)

    x = caps[np.arange(B), lengths - 1] * norm[:, None]          # exact: powers of two
    h = (np.einsum("bnd,ndh->bh", x, W) + b).astype(np.float32).astype(dt)
    hp = np.maximum(h.astype(np.float32), 0)
    order = np.stack([np.lexsort((np.arange(H), -row))[:k] for row in hp])
    norms = np.linalg.norm(np.asarray(params["W_dec"], np.float64), axis=-1)
    rel = norms[:, pair[1]] / (norms[:, pair[0]] + norms[:, pair[1]] + 1e-12)
    top = np.take_along_axis(hp, order, axis=1)
    assert ((np.diff(top, axis=1) == 0) & (top[:, 1:] > 0)).any()   # ties are chosen
    np.testing.assert_array_equal(np.asarray(idx), order)
    np.testing.assert_array_equal(np.asarray(vals, np.float32),
                                  np.take_along_axis(hp, order, axis=1))
    np.testing.assert_allclose(np.asarray(diff), rel[order], rtol=1e-6)


# ---------------------------------------------------------------------------
# parity vs the offline padded oracle


def test_served_bitwise_parity_mixed_lengths(stack):
    """Full bucket of mixed lengths (incl. single-token and max-length):
    served (vals, idx, diff) are BITWISE the padded-path oracle's."""
    eng, cfg, lm_cfg, lm_params, cc_params = stack
    rng = np.random.default_rng(0)
    docs = _docs(rng, lm_cfg, [1, SEQ, 7, 3, 9, 5, SEQ, 2])
    res = serve_batch(eng, docs)
    tokens, lengths = _padded(docs, SEQ)
    vals, idx, diff = oracle(eng, cfg, lm_cfg, lm_params, cc_params,
                             tokens, lengths)
    for i, r in enumerate(res):
        np.testing.assert_array_equal(r.vals, vals[i], err_msg=f"doc {i}")
        np.testing.assert_array_equal(r.idx, idx[i], err_msg=f"doc {i}")
        np.testing.assert_array_equal(r.diff, diff[i], err_msg=f"doc {i}")
        assert r.idx.dtype == np.int32 and r.vals.shape == (cfg.topk_k,)


# Bucket 4 and bucket 1 are two different compiled programs (different
# batch shapes), so the float32 matmul/reduction association may differ:
# the served activations agree to round-off, not bitwise (observed max
# |diff| 3.6e-7 on O(0.5) values, jax 0.9.0 CPU). 5e-6 is ~100 float32
# ulps there and ~400x below one bfloat16 rounding step (~2e-3), so a
# precision drop — or a pad row leaking into a real row — still fails.
# The selected latents and their diff scores stay exact.
_TWO_BUCKETS_ATOL = 5e-6


def test_bucket_padding_invisible(stack):
    """A partial batch rides a padded bucket (3 requests → bucket 4 with
    one dummy row); each request's result is what the request gets
    served alone (same latents, activations to float32 round-off) —
    pad rows never leak into real rows."""
    eng, cfg, lm_cfg, _, _ = stack
    rng = np.random.default_rng(1)
    docs = _docs(rng, lm_cfg, [5, SEQ, 2])
    together = serve_batch(eng, docs)
    assert [r.bucket for r in together] == [4, 4, 4]
    for doc, r in zip(docs, together):
        solo = serve_batch(eng, [doc])[0]
        assert solo.bucket == 1
        np.testing.assert_allclose(r.vals, solo.vals, rtol=0,
                                   atol=_TWO_BUCKETS_ATOL)
        np.testing.assert_array_equal(r.idx, solo.idx)
        np.testing.assert_array_equal(r.diff, solo.diff)


def test_extend_parity_and_page_prefix(stack):
    """The incremental path: a keep-resident request extended with
    follow-up tokens (a) keeps its prefix pages and only takes delta
    pages, (b) serves bitwise what re-prefilling the concatenation from
    scratch serves."""
    eng, cfg, lm_cfg, _, _ = stack
    rng = np.random.default_rng(2)
    full = rng.integers(1, lm_cfg.vocab_size, size=SEQ, dtype=np.int32)
    rid = eng.submit(full[: SEQ // 2], keep=True)
    pages_before = eng.pages_of(rid)
    eng.step(force=True)
    eng.extend(rid, full[SEQ // 2:])
    pages_after = eng.pages_of(rid)
    assert pages_after[: len(pages_before)] == pages_before  # prefix kept
    assert len(pages_after) > len(pages_before)              # delta granted
    ext = eng.step(force=True)[0]
    assert ext.extended and ext.request_id == rid
    eng.release(rid)
    fresh = serve_batch(eng, [full])[0]
    np.testing.assert_array_equal(ext.vals, fresh.vals)
    np.testing.assert_array_equal(ext.idx, fresh.idx)
    np.testing.assert_array_equal(ext.diff, fresh.diff)


def test_extend_requires_live_request(stack):
    eng, _, lm_cfg, _, _ = stack
    rng = np.random.default_rng(3)
    rid = eng.submit(_docs(rng, lm_cfg, [4])[0])     # keep=False
    eng.step(force=True)
    with pytest.raises(KeyError, match="not live"):
        eng.extend(rid, np.ones(2, np.int32))


# ---------------------------------------------------------------------------
# admission: deadlines, backpressure, shed


def test_bucket_helpers():
    assert batch_buckets(8) == (1, 2, 4, 8)
    assert bucket_of(1, 8) == 1 and bucket_of(3, 8) == 4
    assert bucket_of(8, 8) == 8 and bucket_of(9, 8) == 8


def test_batcher_deadline():
    cb = ContinuousBatcher(seq_len=8, n_rows=2, max_wait_s=0.05)
    assert cb.oldest_wait(1.0) == 0.0 and not cb.due(1.0)
    assert cb.admit(np.ones(3, np.int32), now=1.0)
    assert cb.oldest_wait(1.03) == pytest.approx(0.03)
    assert not cb.due(1.03)
    assert cb.due(1.06)
    cb.flush()
    assert not cb.due(99.0) and cb.oldest_wait(99.0) == 0.0


def test_step_flushes_on_deadline_not_before():
    """Deadline-aware micro-batching with an injected clock: a partial
    batch holds until the oldest request waited serve_max_wait_ms, then
    flushes without needing force or batch-full."""
    clk = Clock()
    eng, _, lm_cfg, _, _ = build_engine(serve_max_batch=8, clock=clk)
    rng = np.random.default_rng(4)
    eng.submit(_docs(rng, lm_cfg, [4])[0])
    clk.t = 0.001
    assert eng.step() == []                  # 1ms: batch open, not due
    clk.t = 0.0021
    res = eng.step()                         # past the 2ms smoke deadline
    assert len(res) == 1 and res[0].bucket == 1
    assert res[0].queue_wait_ms >= 2.0


def test_queue_overflow_sheds():
    eng, cfg, lm_cfg, _, _ = build_engine(
        serve_max_batch=1, serve_queue=2, batch_size=32)
    rng = np.random.default_rng(5)
    a, b, c = _docs(rng, lm_cfg, [3, 4, 5])
    eng.submit(a)
    eng.submit(b)
    with pytest.raises(Shed, match="queue full"):
        eng.submit(c)
    assert eng.stats()["serve/shed_total"] == 1
    assert eng.n_queued == 2                 # the admitted two survive


def test_stale_requests_evicted_with_counter():
    """cfg.serve_shed_ms: queued requests past the deadline are evicted
    (429-style) with serve/shed_total counted and was_shed() queryable;
    fresh requests are untouched."""
    clk = Clock()
    eng, _, lm_cfg, _, _ = build_engine(
        serve_max_batch=8, serve_shed_ms=50.0, clock=clk)
    rng = np.random.default_rng(6)
    stale = eng.submit(_docs(rng, lm_cfg, [4])[0])
    clk.t = 0.2                              # 200ms > 50ms deadline
    fresh = eng.submit(_docs(rng, lm_cfg, [4])[0])
    res = eng.step(force=True)
    assert [r.request_id for r in res] == [fresh]
    assert eng.was_shed(stale) and not eng.was_shed(fresh)
    assert eng.stats()["serve/shed_total"] == 1
    assert eng.stats()["serve/requests_total"] == 1


def test_page_pool_exhaustion_sheds():
    """Keep-resident sequences hold pages; when the pool can't cover a
    new request the submit sheds instead of stalling."""
    eng, cfg, lm_cfg, _, _ = build_engine(serve_max_batch=1, serve_queue=1)
    rng = np.random.default_rng(7)
    held = []
    with pytest.raises(Shed, match="page pool"):
        for _ in range(cfg.serve_queue + cfg.serve_max_batch + 1):
            held.append(eng.submit(_docs(rng, lm_cfg, [SEQ])[0], keep=True))
            eng.step(force=True)             # serve it; pages stay held
    assert eng.stats()["serve/shed_total"] == 1
    eng.release(held[0])                     # freed pages admit again
    eng.submit(_docs(rng, lm_cfg, [SEQ])[0])


def test_engine_requires_serve_on():
    from crosscoder_tpu.config import CrossCoderConfig

    cfg = CrossCoderConfig(d_in=32, dict_size=64, batch_size=8,
                           enc_dtype="fp32")
    with pytest.raises(ValueError, match="serve"):
        InferenceEngine(cfg, None, [], {})


# ---------------------------------------------------------------------------
# the zero-compile SLO


def test_zero_compiles_after_warmup():
    """warmup() builds the whole bucket ladder; arbitrary traffic after
    it (partial buckets, mixed lengths, extends) must never compile."""
    eng, cfg, lm_cfg, _, _ = build_engine(serve_max_batch=4)
    # NB not asserted > 0: the AOT memo is process-wide, so a sibling
    # test may legitimately have prewarmed every bucket already
    assert eng.warmup() == eng.compiles
    rng = np.random.default_rng(8)
    for n in (1, 3, 4, 2):
        serve_batch(eng, _docs(rng, lm_cfg, rng.integers(1, SEQ + 1, n)))
    rid = eng.submit(_docs(rng, lm_cfg, [5])[0], keep=True)
    eng.step(force=True)
    eng.extend(rid, np.ones(3, np.int32))
    eng.step(force=True)
    eng.release(rid)
    assert eng.compiles_after_warmup == 0
    assert eng.stats()["serve_compiles_after_warmup"] == 0


# ---------------------------------------------------------------------------
# replica drain hand-off


def test_replica_drain_and_adopt(tmp_path):
    """Preemption smoke: replica A spools its queued requests to the
    board; peer B's next heartbeat claims and re-submits them through its
    own admission path. Exactly-once: a second heartbeat adopts nothing."""
    board = ReplicaBoard(tmp_path / "serve_board")
    eng_a, _, lm_cfg, _, _ = build_engine(serve_max_batch=8)
    eng_b, _, _, _, _ = build_engine(serve_max_batch=8)
    rep_a = ServeReplica("a", eng_a, board)
    rep_b = ServeReplica("b", eng_b, board)
    rep_a.heartbeat()
    rep_b.heartbeat()
    assert {p["id"] for p in board.peers()} == {"a", "b"}

    rng = np.random.default_rng(9)
    docs = _docs(rng, lm_cfg, [3, SEQ, 6])
    for d in docs:
        eng_a.submit(d)
    assert rep_a.preempt() == 3              # SIGTERM body: drain + spool
    assert eng_a.n_queued == 0
    assert board.peers(exclude="b") == []    # A left the board

    assert rep_b.heartbeat() == 3            # B adopts the spool
    assert rep_b.heartbeat() == 0            # exactly once
    assert eng_b.n_queued == 3
    assert eng_b.stats()["serve/adopted_total"] == 3
    assert eng_a.stats()["serve/drained_total"] == 3
    res = eng_b.step(force=True)             # adopted requests serve
    assert len(res) == 3


def test_replica_never_adopts_own_spool(tmp_path):
    board = ReplicaBoard(tmp_path / "serve_board")
    eng, _, lm_cfg, _, _ = build_engine(serve_max_batch=8)
    rep = ServeReplica("solo", eng, board)
    rng = np.random.default_rng(10)
    eng.submit(_docs(rng, lm_cfg, [4])[0])
    rep.preempt()
    assert rep.heartbeat() == 0              # own drain record is skipped
