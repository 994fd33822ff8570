"""Fused causal attention (crosscoder_tpu/ops/flash_attention.py): parity
with the XLA form through the Pallas interpreter, and the selection in
``models/lm._attn_core``."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crosscoder_tpu import obs
from crosscoder_tpu.models import lm
from crosscoder_tpu.ops import flash_attention as fa
from crosscoder_tpu.ops import paged_attention as pa

HD = 128


@pytest.fixture
def interpret():
    fa.set_interpret(True)
    yield
    fa.set_interpret(False)


def _qkv(S, H, KV, seed=0, B=1):
    rng = np.random.default_rng(seed)
    mk = lambda n, s: jnp.asarray(   # noqa: E731
        rng.normal(size=(B, S, n, HD)).astype(np.float32) * s)
    # q at twice unit scale: logits of several units, so the soft-cap bends
    return mk(H, 2.0), mk(KV, 1.0), mk(KV, 1.0)


# float32 operands through the interpreter: the kernel and the XLA form
# then differ ONLY by the reassociated row reduction (per-tile running max
# and sum, normalisation after PV) — a few float32 roundings over up to
# 1024 terms, seen at 3.5e-6 on outputs of magnitude 3-4. 2e-5 leaves that
# room and no more: one bfloat16 rounding anywhere in the accumulation
# (2^-9 relative, 1e-2 absolute here) fails it five hundred times over.
ATOL = 2e-5


@pytest.mark.parametrize("S", [384, 1024], ids=["3x128", "2x512"])
@pytest.mark.parametrize("heads", [(2, 2), (4, 2)], ids=["mha", "gqa2"])
@pytest.mark.parametrize("softcap", [50.0, 0.0], ids=["cap50", "nocap"])
@pytest.mark.parametrize("window", [0, 200], ids=["causal", "window200"])
def test_fused_matches_xla_form(interpret, S, heads, softcap, window):
    """Three 128-tiles and two 512-tiles: tiles below the diagonal, the
    diagonal tile, and (window 200) tiles the window's edge crosses and
    tiles it drops."""
    H, KV = heads
    assert fa.block_for(S) in (128, 512) and S // fa.block_for(S) >= 2
    assert fa.supported(S, H, KV, HD, jnp.float32)
    q, k, v = _qkv(S, H, KV, seed=S + H)
    got = fa.flash_attention(q, k, v, scale=0.09, softcap=softcap, window=window)
    want = pa.ragged_attention_reference(
        q, k, v, None, scale=0.09, softcap=softcap, window=window,
        is_local=bool(window))
    assert got.shape == want.shape == (1, S, H * HD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=ATOL)


@pytest.mark.parametrize("shape", [
    (200, 2, 2, 128, jnp.bfloat16),     # S not a multiple of a tile
    (1024, 8, 4, 64, jnp.bfloat16),     # a head narrower than the lanes
    (1024, 3, 2, 128, jnp.bfloat16),    # a ragged GQA group
    (32768, 8, 4, 256, jnp.float32),    # whole-sequence K/V past the VMEM budget
])
def test_supported_refuses(shape):
    assert not fa.supported(*shape)


def test_supported_accepts_the_cells_and_gemma_heads():
    assert fa.supported(1024, 16, 16, 128, jnp.bfloat16)
    assert fa.supported(1024, 8, 4, 256, jnp.bfloat16)


def _cfg(H, KV, hd, window):
    return lm.LMConfig.tiny().replace(
        n_heads=H, n_kv_heads=KV, head_dim=hd, sliding_window=window,
        query_pre_attn_scalar=float(hd))


@pytest.fixture
def plane(tmp_path):
    p = obs.acquire(types.SimpleNamespace(
        obs="on", obs_dir=str(tmp_path / "obs"), checkpoint_dir=str(tmp_path)))
    yield p
    p.close()


def _counts(plane):
    return (plane.registry.get_count("harvest/attn_fused_traces"),
            plane.registry.get_count("harvest/attn_xla_traces"))


@pytest.mark.parametrize("case", ["cpu-backend", "unsupported-shape", "paged"])
def test_attn_core_falls_back_to_the_xla_form(plane, case):
    """The CPU backend, a shape the kernel refuses, and the paged runtime's
    call (``lengths`` given) all run the XLA form, bit for bit."""
    if case == "unsupported-shape":
        fa.set_interpret(True)
        S, hd = 256, 8
    else:
        fa.set_interpret(case == "paged")
        S, hd = 256, 128
    try:
        rng = np.random.default_rng(3)
        q, k, v = (jnp.asarray(rng.normal(size=(2, S, n, hd)).astype(np.float32))
                   for n in (4, 2, 2))
        cfg = _cfg(4, 2, hd, 64)
        lengths = jnp.full((2,), S, jnp.int32) if case == "paged" else None
        for is_local in (False, True):
            got = lm._attn_core(q, k, v, cfg, jnp.asarray(is_local), lengths=lengths)
            want = pa.ragged_attention_reference(
                q, k, v, lengths, scale=hd ** -0.5, softcap=cfg.attn_softcap,
                window=64, is_local=jnp.asarray(is_local))
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    finally:
        fa.set_interpret(False)
    assert _counts(plane) == (0, 2)


@pytest.mark.parametrize("window", [4096, 0, 200], ids=["inert", "none", "binds"])
def test_attn_core_takes_the_fused_path(interpret, plane, window):
    """Where the kernel may dispatch: one instance when the window cannot
    bind, ``lax.cond`` on the traced layer parity between two when it does;
    the choice is counted once per trace, not per call."""
    S = 384
    q, k, v = _qkv(S, 4, 2, seed=11, B=2)
    cfg = _cfg(4, 2, HD, window)
    f = jax.jit(lambda q, k, v, loc: lm._attn_core(q, k, v, cfg, loc))
    for is_local in (False, True, False):
        got = f(q, k, v, jnp.asarray(is_local))
        want = pa.ragged_attention_reference(
            q, k, v, None, scale=HD ** -0.5, softcap=cfg.attn_softcap,
            window=window, is_local=is_local)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=ATOL)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, loc: lm._attn_core(q, k, v, cfg, loc))(q, k, v, True)
    prims = [e.primitive.name for e in jaxpr.eqns]
    assert ("cond" in prims) == (0 < window < S)
    assert prims.count("pallas_call") == (0 if "cond" in prims else 1)
    assert _counts(plane) == (2, 0)       # the jit's trace, and make_jaxpr's


def test_counters_are_noops_without_a_plane(interpret):
    q, k, v = _qkv(256, 2, 2)
    assert not obs._PLANES
    lm._attn_core(q, k, v, _cfg(2, 2, HD, 0), jnp.asarray(False))


# ---------------------------------------------------------------------------
# the latent instance: a score head of 128 + 64 rotary dims whose key all
# heads share, a value head of 128


def _latent_parts(S, H, seed=0, B=1, dn=128, dr=64, dv=128):
    rng = np.random.default_rng(seed)
    mk = lambda n, d, s: jnp.asarray(   # noqa: E731
        rng.normal(size=(B, S, n, d)).astype(np.float32) * s)
    return mk(H, dn, 1.0), mk(H, dr, 1.0), mk(H, dn, 1.0), mk(1, dr, 1.0), mk(H, dv, 1.0)


def _expanded(parts, scale):
    """The XLA attention on the EXPANDED heads: q, k of 192 dims, the ONE
    rotary key repeated a head, v zero-padded to 192 and cut back."""
    q_nope, q_rope, k_nope, k_rope, v = parts
    B, S, H, dv = v.shape
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, q_rope.shape)], -1)
    hd = q.shape[-1]
    out = pa.ragged_attention_reference(
        q, k, jnp.pad(v, ((0, 0),) * 3 + ((0, hd - dv),)), None, scale=scale)
    return out.reshape(B, S, H, hd)[..., :dv].reshape(B, S, H * dv)


@pytest.mark.parametrize("S", [384, 1024], ids=["3x128", "2x512"])
@pytest.mark.parametrize("H", [1, 3])
def test_latent_instance_matches_xla_at_192_128_heads_with_the_shared_rotary_key(
        interpret, S, H):
    assert fa.latent_supported(S, 128, 64, 128, jnp.float32)
    parts = _latent_parts(S, H, seed=S + H)
    got = fa.flash_attention_latent(*parts, scale=0.1)
    assert got.shape == (1, S, H * 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_expanded(parts, 0.1)),
                               rtol=0, atol=ATOL)
    # the rotary key really is ONE for all heads: a key a head is another result
    q_nope, q_rope, k_nope, k_rope, v = parts
    if H > 1:
        rolled = jnp.stack([jnp.roll(q_rope[:, :, h], -h, axis=-1) for h in range(H)], 2)
        other = fa.flash_attention_latent(q_nope, rolled, k_nope, k_rope, v, scale=0.1)
        assert float(jnp.max(jnp.abs(other - got))) > 100 * ATOL


@pytest.mark.parametrize("shape", [
    (200, 128, 64, 128, jnp.bfloat16),      # S not a multiple of a tile
    (1024, 64, 64, 64, jnp.bfloat16),       # the head's own part narrower than the lanes
    (1024, 128, 64, 256, jnp.bfloat16),     # a value head of another width than the nope part
    (1024, 128, 192, 128, jnp.bfloat16),    # a rotary part past one lane tile
    (16384, 128, 64, 128, jnp.float32),     # three whole-sequence bands past the VMEM budget
])
def test_latent_supported_refuses(shape):
    assert not fa.latent_supported(*shape)


def test_latent_supported_accepts_the_cell_and_leaves_the_plain_shapes_alone():
    assert fa.latent_supported(4096, 128, 64, 128, jnp.bfloat16)
    # a third whole-sequence band in VMEM: 6.3 MB of bands, 11.0 MB with the
    # tiles and scratch, inside the 13 MiB budget at the cell's shape
    assert 3 * 2 * 4096 * 128 * 2 == 6_291_456
    assert fa._vmem_bytes(4096, 128, 512, 2, bands=3) == 11_010_048 < 13 << 20
    # every shape the plain instance accepted before it accepts now, and refuses 192
    assert fa._vmem_bytes(4096, 128, 512, 2) == (
        2 * 2 * 4096 * 128 * 2 + 2 * 2 * 512 * 128 * 2 + 2 * 512 * 128 * 4
        + 512 * 128 * 4 + 3 * 512 * 512 * 4)
    assert not fa.supported(4096, 32, 32, 192, jnp.bfloat16)


def test_latent_attention_in_the_block_takes_the_fused_path(interpret, plane):
    """``lm._latent_attend`` picks the kernel on the padded path at a supported
    shape, the expanded XLA form elsewhere; both are counted."""
    cfg = lm.LMConfig.xing4_0_29b().replace(
        n_layers=1, layer_types=(lm.FULL,), mlp_types=(lm.DENSE,), n_heads=2, n_kv_heads=2,
        dtype="fp32")
    parts = _latent_parts(256, 2, seed=5)
    kind = types.SimpleNamespace(is_local=np.bool_(False))
    got = lm._latent_attend(parts, cfg, kind, None)
    want = _expanded(parts, cfg.query_pre_attn_scalar ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=ATOL)
    assert plane.registry.get_count("harvest/attn_latent_traces") == 1
    assert _counts(plane) == (1, 0)
    fa.set_interpret(False)
    np.testing.assert_array_equal(
        np.asarray(lm._latent_attend(parts, cfg, kind, None)), np.asarray(want))
    assert plane.registry.get_count("harvest/attn_latent_traces") == 2
    assert _counts(plane) == (1, 1)
