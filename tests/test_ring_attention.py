"""Parity tests: ring attention and the sequence-parallel Gemma forward
must match the dense single-device path exactly (the point of SURVEY
component N5 — long-context harvest without approximation)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from crosscoder_tpu.models import lm
from crosscoder_tpu.parallel.ring_attention import ring_attention


def _mesh():
    return Mesh(np.array(jax.devices()), ("data",))


def _dense_reference(q, k, v, scale, softcap, sliding_window, is_local):
    """Unsharded oracle with the same GQA/softcap/mask semantics."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    qg = q.reshape(B, S, KV, g, hd).astype(jnp.float32) * scale
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(q.dtype), k,
                        preferred_element_type=jnp.float32)
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    window = pos[:, None] - pos[None, :] < sliding_window
    mask = (causal & window) if is_local else causal
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, S, H, hd)


@pytest.mark.parametrize("is_local", [False, True])
def test_ring_attention_matches_dense(is_local):
    mesh = _mesh()
    n = 8
    B, S, H, KV, hd = 2, 64, 4, 2, 8
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    scale, softcap, window = 0.35, 50.0, 16

    ring = jax.shard_map(
        lambda q, k, v: ring_attention(
            q, k, v, axis_name="data", n_shards=n, scale=scale,
            softcap=softcap, sliding_window=window, is_local=is_local,
        ),
        mesh=mesh,
        in_specs=(P(None, "data"), P(None, "data"), P(None, "data")),
        out_specs=P(None, "data"),
        check_vma=False,
    )
    got = np.asarray(jax.jit(ring)(q, k, v))
    want = np.asarray(_dense_reference(q, k, v, scale, softcap, window, is_local))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def _lowered_text(n_shards: int) -> str:
    """StableHLO for a ring over ``n_shards`` devices with a FIXED
    per-device block shape (so any size growth is graph structure, not
    tensor constants)."""
    mesh = Mesh(np.array(jax.devices()[:n_shards]), ("data",))
    B, H, KV, hd = 1, 2, 1, 4
    S = 8 * n_shards                      # 8 positions per shard
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    ring = jax.shard_map(
        lambda q, k, v: ring_attention(
            q, k, v, axis_name="data", n_shards=n_shards, scale=0.5,
            softcap=30.0, sliding_window=8, is_local=False,
        ),
        mesh=mesh,
        in_specs=(P(None, "data"), P(None, "data"), P(None, "data")),
        out_specs=P(None, "data"),
        check_vma=False,
    )
    return jax.jit(ring).lower(q, k, v).as_text()


def test_ring_graph_size_flat_in_shard_count():
    """The lax.scan ring keeps the traced graph O(1) in n_shards (round-3
    VERDICT weak #4: the Python unroll grew it linearly — a pod-scale
    32-64-way sequence shard would have paid compile time and graph size
    for every extra device)."""
    t4, t8 = _lowered_text(4), _lowered_text(8)
    # the K/V ppermute pair appears once, inside the scan body, regardless
    # of shard count (the unrolled version had 2*(n-1) collective_permutes)
    assert t8.count("collective_permute") == t4.count("collective_permute")
    assert t8.count("collective_permute") <= 4
    # total graph size stays flat (same ops, different ring length)
    assert len(t8) < 1.25 * len(t4), (len(t4), len(t8))


def test_ring_attention_single_shard_degenerates():
    """n_shards=1 is plain blockwise attention — sanity for the accumulator."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    B, S, H, KV, hd = 1, 16, 2, 1, 4
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    ring = jax.shard_map(
        lambda q, k, v: ring_attention(
            q, k, v, axis_name="data", n_shards=1, scale=0.5),
        mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(), check_vma=False,
    )
    got = np.asarray(ring(q, k, v))
    want = np.asarray(_dense_reference(q, k, v, 0.5, 0.0, 0, False))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def tiny():
    cfg = lm.LMConfig.tiny()          # sliding_window=8 < S: both masks live
    params = lm.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(2, 64)))
    return cfg, params, tokens


def test_seq_parallel_forward_matches_dense(tiny):
    """Full Gemma-2 stack, 8-way sequence sharding: logits and captured
    residual streams equal the dense forward."""
    cfg, params, tokens = tiny
    hooks = ["blocks.1.hook_resid_pre", "blocks.3.hook_resid_pre"]
    dense_logits, dense_cache = lm.forward(params, tokens, cfg, capture=hooks)
    sp_logits, sp_cache = lm.forward_seq_parallel(
        params, tokens, cfg, _mesh(), capture=hooks, return_logits=True
    )
    np.testing.assert_allclose(
        np.asarray(sp_logits), np.asarray(dense_logits), rtol=5e-4, atol=5e-4
    )
    for hp in hooks:
        np.testing.assert_allclose(
            np.asarray(sp_cache[hp]), np.asarray(dense_cache[hp]),
            rtol=5e-4, atol=5e-4, err_msg=hp,
        )


def test_seq_parallel_sublayer_hooks_match_dense(tiny):
    """attn_out/mlp_out capture through the ring path equals the dense
    forward's (the sublayer sites ride the same capture machinery)."""
    cfg, params, tokens = tiny
    hooks = ["blocks.1.hook_attn_out", "blocks.2.hook_mlp_out"]
    _, dense = lm.forward(params, tokens, cfg, capture=hooks, return_logits=False)
    _, sp = lm.forward_seq_parallel(params, tokens, cfg, _mesh(), capture=hooks)
    for hp in hooks:
        np.testing.assert_allclose(
            np.asarray(sp[hp]), np.asarray(dense[hp]),
            rtol=5e-4, atol=5e-4, err_msg=hp,
        )


def test_seq_parallel_capture_only(tiny):
    """Harvest mode (return_logits=False) skips the unembedding and returns
    just the cache, sharded over the sequence axis."""
    cfg, params, tokens = tiny
    hp = "blocks.2.hook_resid_pre"
    logits, cache = lm.forward_seq_parallel(params, tokens, cfg, _mesh(), capture=[hp])
    assert logits is None
    assert cache[hp].shape == (2, 64, cfg.d_model)


def test_seq_parallel_rejects_indivisible(tiny):
    cfg, params, tokens = tiny
    with pytest.raises(ValueError):
        lm.forward_seq_parallel(params, tokens[:, :60], cfg, _mesh())


def test_multihost_single_process_noop():
    """initialize() must be a safe no-op off-pod; primary is process 0."""
    from crosscoder_tpu.parallel import multihost

    assert multihost.initialize() is False
    assert multihost.is_primary()
    info = multihost.process_info()
    assert info["process_count"] == 1 and info["global_devices"] == 8
