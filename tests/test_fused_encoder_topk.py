"""Fused encoder→TopK megakernel (cfg.fused_encoder;
ops/fused_encoder_topk.py, docs/SCALING.md "Fused encoder→TopK"):
interpret-mode CPU parity against the dense oracle chain — bit-identical
(vals, idx) including threshold ties, sign-bit-set NaN patterns (the
PR 1 clamp case), duplicate-max rows, and non-tile-divisible dictionary
tails — gradient parity through the ``_fused_topk_step`` /
``_fused_batchtopk_encode`` custom VJPs, the int8 block-scaled matmul
path's quality bounds, dispatch gates, config validation, and the
zero-cost-off step-HLO identity. All CPU, tier-1; registered in
scripts/kernels.sh (the ``fused`` stanza).

Data discipline: the bit-exactness tests use integer-valued operands so
the kernel's per-tile MXU dots and the oracle's one-shot einsum are
EXACTLY equal (f32-exact sums), making "bit-identical" a deterministic
claim rather than an association-order coin flip; the float tests use
tolerances sized to f32 association noise.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.models import crosscoder as cc
from crosscoder_tpu.ops import activations as act_ops
from crosscoder_tpu.ops import fused_encoder_topk as fek
from crosscoder_tpu.ops import topk_pallas


@pytest.fixture(autouse=True)
def _interpret_kernels():
    """Route every Pallas path through the interpreter (the CPU stand-in
    for the TPU kernels, same as test_topk_pallas / test_sparse_grad)."""
    fek.set_interpret(True)
    topk_pallas.set_interpret(True)
    yield
    fek.set_interpret(False)
    topk_pallas.set_interpret(False)


def _int_operands(rng, B, nd, H, dtype, b_scale=2):
    x2 = jnp.asarray(rng.integers(-3, 4, size=(B, nd)), dtype)
    W2 = jnp.asarray(rng.integers(-2, 3, size=(nd, H)), dtype)
    b = jnp.asarray(rng.integers(-b_scale, b_scale + 1, size=(H,)),
                    jnp.float32)
    return x2, W2, b


def _oracle_chain(x2, W2, b, k):
    """The exact forward the fused kernel replaces: dense pre-acts →
    dense TopK scatter → the sparsify drain contract."""
    hf = jnp.dot(x2, W2, preferred_element_type=jnp.float32)
    h = (hf + b).astype(x2.dtype)
    f = act_ops._topk_dense(h, k)
    vals, idx = topk_pallas.sparsify(f, k)
    return h, vals, idx


def _assert_bitexact(got, want, what):
    g = np.asarray(got[0], np.float32), np.asarray(got[1])
    w = np.asarray(want[0], np.float32), np.asarray(want[1])
    np.testing.assert_array_equal(g[0], w[0], err_msg=f"{what}: vals")
    np.testing.assert_array_equal(g[1], w[1], err_msg=f"{what}: idx")


# ---------------------------------------------------------------------------
# TopK kernel vs the dense oracle chain


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,nd,H,k", [
    (48, 256, 1024, 8),       # chunk-divisible width
    (48, 256, 1000, 8),       # non-tile-divisible dictionary tail
    (33, 128, 640, 16),       # odd batch (row-block padding) + small tail
    (16, 128, 200, 32),       # width barely above k, single padded chunk
])
def test_fused_topk_bitexact(dtype, B, nd, H, k):
    rng = np.random.default_rng(0)
    x2, W2, b = _int_operands(rng, B, nd, H, dtype)
    assert fek.supported(B, nd, H, k, dtype)
    got = fek.fused_topk_encode(x2, W2, b, k)
    _, *want = _oracle_chain(x2, W2, b, k)
    _assert_bitexact(got, want, f"{dtype.__name__} [{B},{nd}]x{H} k={k}")


def test_fused_topk_threshold_ties_break_by_lowest_index():
    """Duplicate W columns manufacture exact value ties at and across the
    k-th position; selection must keep the lowest global indices, the
    lax.top_k contract the whole tier chain pins."""
    rng = np.random.default_rng(1)
    B, nd, H, k = 32, 128, 512, 8
    W = rng.integers(-2, 3, size=(nd, H)).astype(np.float32)
    for dup in (100, 200, 300, 511):          # 5-way tie incl. last column
        W[:, dup] = W[:, 7]
    x2 = jnp.asarray(rng.integers(-3, 4, size=(B, nd)), jnp.bfloat16)
    W2 = jnp.asarray(W, jnp.bfloat16)
    b = jnp.zeros((H,), jnp.float32)
    got = fek.fused_topk_encode(x2, W2, b, k)
    _, *want = _oracle_chain(x2, W2, b, k)
    _assert_bitexact(got, want, "threshold ties")


def test_fused_topk_duplicate_max_rows_and_few_positives():
    """All-equal rows (every entry ties at the max) and rows with fewer
    than k positive pre-acts (output must pad with (0.0, 0), never
    recruit zeros or pad columns)."""
    B, nd, H, k = 32, 128, 512, 8
    rng = np.random.default_rng(2)
    x2 = jnp.zeros((B, nd), jnp.bfloat16)          # h == b_enc everywhere
    W2 = jnp.asarray(rng.integers(-2, 3, size=(nd, H)), jnp.bfloat16)
    ball = jnp.full((H,), 2.0, jnp.float32)        # H-way duplicate max
    got = fek.fused_topk_encode(x2, W2, ball, k)
    _, *want = _oracle_chain(x2, W2, ball, k)
    _assert_bitexact(got, want, "duplicate-max rows")
    np.testing.assert_array_equal(np.asarray(got[1]), np.arange(k)[None, :]
                                  .repeat(B, 0))   # lowest indices win

    bfew = np.zeros((H,), np.float32)
    bfew[3], bfew[700 % H] = 5.0, 2.0              # exactly two positives
    got = fek.fused_topk_encode(x2, W2, jnp.asarray(bfew), k)
    vals, idx = np.asarray(got[0], np.float32), np.asarray(got[1])
    np.testing.assert_array_equal(idx[:, :2], [[3, 700 % H]] * B)
    np.testing.assert_array_equal(vals[:, 2:], 0.0)
    np.testing.assert_array_equal(idx[:, 2:], 0)


@pytest.mark.parametrize("payload", [0x7FFF, 0xFFFF])
def test_fused_topk_nan_patterns(payload):
    """The PR 1 composite-key clamp case: a NaN pre-act — including the
    SIGN-BIT-SET payload 0xFFFF that pre-fix silently corrupted the
    composite kernel's row — must rank as a near-max sentinel (occupying
    one top-k slot, exactly as the masked-TopK → sparsify chain gives it
    a slot then drops it at the ``> 0`` drain) and leave every other row
    bit-exact."""
    B, nd, H, k = 16, 128, 512, 8
    rng = np.random.default_rng(3)
    x2 = jnp.zeros((B, nd), jnp.bfloat16)
    W2 = jnp.asarray(rng.integers(-2, 3, size=(nd, H)), jnp.bfloat16)
    bn = np.zeros((H,), np.float32)
    bn[1:2 * k + 1] = np.arange(2 * k, 0, -1)      # 2k positives: 2k..1
    b_clean = jnp.asarray(bn)
    nan_val = jax.lax.bitcast_convert_type(
        jnp.uint16(payload), jnp.bfloat16)
    assert bool(jnp.isnan(nan_val))
    # NaN lands in column 0 of every row via the bias
    bn_nan = bn.copy()
    bn_nan[0] = np.float32(np.asarray(nan_val, np.float32))
    got_v, got_i = fek.fused_topk_encode(x2, W2, jnp.asarray(bn_nan), k)
    got_v = np.asarray(got_v, np.float32)
    got_i = np.asarray(got_i)
    # the NaN burned one slot: exactly k-1 finite survivors, and they are
    # the k-1 LARGEST finite entries (columns 1..k-1), ascending index
    np.testing.assert_array_equal(got_i[:, :k - 1],
                                  np.arange(1, k)[None, :].repeat(B, 0))
    np.testing.assert_array_equal(got_v[:, :k - 1],
                                  bn[1:k][None, :].repeat(B, 0))
    np.testing.assert_array_equal(got_v[:, k - 1:], 0.0)
    # a clean run on the same operands stays bit-exact vs the oracle
    got = fek.fused_topk_encode(x2, W2, b_clean, k)
    _, *want = _oracle_chain(x2, W2, b_clean, k)
    _assert_bitexact(got, want, "clean rows beside the NaN case")


def test_fused_topk_unsupported_shape_falls_back_to_oracle():
    """nd not lane-aligned → the dense-encode fallback, still the exact
    oracle contract (the 'dense fallback on unsupported shapes' leg)."""
    rng = np.random.default_rng(4)
    B, nd, H, k = 16, 192, 512, 8                  # 192 % 128 != 0
    x2, W2, b = _int_operands(rng, B, nd, H, jnp.float32)
    assert not fek.supported(B, nd, H, k, jnp.float32)
    got = fek.fused_topk_encode(x2, W2, b, k)
    _, *want = _oracle_chain(x2, W2, b, k)
    _assert_bitexact(got, want, "fallback")


def test_supported_gates():
    f32 = jnp.float32
    assert fek.supported(32, 256, 1024, 8, f32)
    assert fek.supported(32, 256, 1000, 8, f32)       # tails are fine
    assert not fek.supported(32, 100, 1024, 8, f32)   # contraction align
    assert not fek.supported(32, 256, 1024, 0, f32)   # k bounds
    assert not fek.supported(32, 256, 1024, 200, f32)
    assert not fek.supported(32, 256, 4, 8, f32)      # width < k
    assert not fek.supported(32, 256, 1024, 8, jnp.int8)
    # quant layout: block must be lane-aligned and divide nd
    assert fek.supported(32, 256, 1024, 8, f32, quant_block=128)
    assert not fek.supported(32, 256, 1024, 8, f32, quant_block=96)
    assert not fek.supported(32, 384, 1024, 8, f32, quant_block=256)


# ---------------------------------------------------------------------------
# int8 block-scaled in-kernel matmul (cfg.quant_encoder)


def test_fused_topk_int8_quality_bounds():
    """The --quant-encoder quality gate's test-sized stand-in: selection
    agreement and value error of the int8 block-scaled matmul vs the
    exact fused path stay inside the bench gate's bounds on
    Gaussian-activation-shaped data."""
    rng = np.random.default_rng(5)
    B, nd, H, k = 64, 512, 2048, 16
    x2 = jnp.asarray(rng.standard_normal((B, nd)), jnp.bfloat16)
    W2 = jnp.asarray(rng.standard_normal((nd, H)) * 0.05, jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal(H) * 0.01, jnp.float32)
    ev, ei = fek.fused_topk_encode(x2, W2, b, k)
    qv, qi = fek.fused_topk_encode(x2, W2, b, k, quant_block=128)
    ev, qv = np.asarray(ev, np.float32), np.asarray(qv, np.float32)
    ei, qi = np.asarray(ei), np.asarray(qi)
    overlap = np.mean([
        len(set(qi[r][qv[r] > 0]) & set(ei[r][ev[r] > 0]))
        / max((ev[r] > 0).sum(), 1)
        for r in range(B)
    ])
    assert overlap >= 0.9, f"selection agreement collapsed: {overlap}"
    rel = np.abs(qv.sum(1) - ev.sum(1)) / np.maximum(ev.sum(1), 1e-6)
    assert float(rel.mean()) < 5e-3, f"value error too large: {rel.mean()}"


# ---------------------------------------------------------------------------
# BatchTopK: fused bisection+emit vs the dense oracle


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_batchtopk_bitexact_incl_ties(dtype):
    rng = np.random.default_rng(6)
    B, nd, H, k = 48, 128, 1000, 8                 # tail width too
    W = rng.integers(-2, 3, size=(nd, H)).astype(np.float32)
    W[:, 500] = W[:, 9]                            # exact global-threshold tie
    x2 = jnp.asarray(rng.integers(-3, 4, size=(B, nd)), dtype)
    W2 = jnp.asarray(W, dtype)
    b = jnp.asarray(rng.integers(-2, 3, size=(H,)), jnp.float32)
    got = fek.fused_batchtopk_encode_raw(x2, W2, b, k)
    hf = jnp.dot(x2, W2, preferred_element_type=jnp.float32)
    h = (hf + b).astype(dtype)
    want = act_ops.batchtopk(h, k, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_fused_batchtopk_padded_rows_never_enter_the_statistic():
    """Batch padding resurrection guard: with a POSITIVE bias, zero-pad
    rows would grow positive pre-acts; the kernel must mask them out of
    the global (k·B)-th order statistic (B=33 forces row padding)."""
    rng = np.random.default_rng(7)
    B, nd, H, k = 33, 128, 512, 4
    x2, W2, _ = _int_operands(rng, B, nd, H, jnp.float32)
    b = jnp.full((H,), 3.0, jnp.float32)           # everything positive
    got = fek.fused_batchtopk_encode_raw(x2, W2, b, k)
    hf = jnp.dot(x2, W2, preferred_element_type=jnp.float32)
    h = (hf + b).astype(jnp.float32)
    want = act_ops.batchtopk(h, k, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# model tier: gradients + dispatch


def _cfg(**kw):
    base = dict(d_in=128, n_models=2, dict_size=1024, activation="topk",
                topk_k=8, l1_coeff=0.0, batch_size=32, enc_dtype="fp32",
                master_dtype="fp32", factored_decode="on", sparse_bwd="on",
                fused_encoder="on")
    base.update(kw)
    return CrossCoderConfig(**base)


def _loss_and_grads(cfg, x):
    params = cc.init_params(jax.random.key(0), cfg)

    def loss(p):
        return cc.training_loss(p, x, 0.0, cfg, with_metrics=False)[0]

    return jax.value_and_grad(loss)(params)


@pytest.mark.parametrize("activation", ["topk", "batchtopk"])
def test_grad_parity_fused_vs_dense(activation):
    """The fused tier changes how the forward is COMPUTED, not what it
    means: loss bit-equal (integer operands → exact matmuls), gradients
    within f32 association noise of the unfused tier's."""
    kw = {} if activation == "topk" else dict(
        activation="batchtopk", factored_decode="auto", sparse_bwd="auto")
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.integers(-3, 4, size=(32, 2, 128)), jnp.float32)
    l_f, g_f = _loss_and_grads(_cfg(**kw), x)
    l_d, g_d = _loss_and_grads(_cfg(fused_encoder="off", **kw), x)
    assert float(l_f) == float(l_d)
    for name in g_d:
        a = np.asarray(g_d[name], np.float32)
        b = np.asarray(g_f[name], np.float32)
        scale = max(float(np.abs(a).max()), 1e-6)
        np.testing.assert_allclose(b, a, atol=2e-5 * scale, rtol=0,
                                   err_msg=f"grad mismatch on {name}")


def test_auxk_step_keeps_the_dense_encode():
    """The h-residual escape hatch: an aux-active step needs the
    pre-acts differentiably for the AuxK ranking, so the fused tier must
    stand down there — and the step must still match the unfused AuxK
    step's loss/grads."""
    kw = dict(aux_k=16, aux_dead_steps=1)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.integers(-3, 4, size=(32, 2, 128)), jnp.float32)
    dead = jnp.ones((1024,), bool)

    def run(cfg):
        params = cc.init_params(jax.random.key(0), cfg)

        def loss(p):
            return cc.training_loss(p, x, 0.0, cfg, with_metrics=False,
                                    dead_mask=dead, aux_coeff=1.0)[0]

        return jax.value_and_grad(loss)(params)

    l_f, g_f = run(_cfg(**kw))
    l_d, g_d = run(_cfg(fused_encoder="off", **kw))
    assert float(l_f) == float(l_d)
    for name in g_d:
        a = np.asarray(g_d[name], np.float32)
        b = np.asarray(g_f[name], np.float32)
        np.testing.assert_array_equal(b, a, err_msg=name)


def test_use_fused_encoder_dispatch():
    assert cc.use_fused_encoder(_cfg(), batch=32)
    assert not cc.use_fused_encoder(_cfg(fused_encoder="off"), batch=32)
    # auto: live here because the fixture set interpret mode
    assert cc.use_fused_encoder(_cfg(fused_encoder="auto"), batch=32)
    fek.set_interpret(False)
    assert not cc.use_fused_encoder(_cfg(fused_encoder="auto"), batch=32)
    fek.set_interpret(True)
    # topk rides the sparse-backward scope: a dead plane kills the tier
    assert not cc.use_fused_encoder(
        _cfg(fused_encoder="auto", sparse_bwd="off"), batch=32)
    # auto rejects kernel-unsupported shapes (contraction misalignment)
    assert not cc.use_fused_encoder(
        _cfg(fused_encoder="auto", d_in=100), batch=32)
    # batchtopk: training mode only (a calibrated threshold is eval)
    assert cc.use_fused_encoder(
        _cfg(activation="batchtopk", factored_decode="auto",
             sparse_bwd="auto"), batch=32)
    assert not cc.use_fused_encoder(
        _cfg(activation="batchtopk", factored_decode="auto",
             sparse_bwd="auto", batchtopk_threshold=0.5), batch=32)
    # relu has nothing to fuse
    assert not cc.use_fused_encoder(
        _cfg(activation="relu", factored_decode="auto", sparse_bwd="auto",
             fused_encoder="auto"), batch=32)


def test_config_validation():
    with pytest.raises(ValueError, match="did you mean 'auto'"):
        _cfg(fused_encoder="atuo")
    with pytest.raises(ValueError, match="activation='topk' or 'batchtopk'"):
        _cfg(activation="relu", factored_decode="auto", sparse_bwd="auto")
    with pytest.raises(ValueError, match="sparse_bwd"):
        _cfg(sparse_bwd="off")
    with pytest.raises(ValueError, match="l1_coeff=0"):
        _cfg(l1_coeff=1.0, sparse_bwd="auto", factored_decode="auto")
    with pytest.raises(ValueError, match="quant_encoder requires"):
        _cfg(fused_encoder="off", quant_encoder=True)
    with pytest.raises(ValueError, match="must be a multiple of 128"):
        _cfg(quant_encoder=True, quant_block=96)
    with pytest.raises(ValueError, match="quant_encoder requires activation"):
        _cfg(activation="batchtopk", factored_decode="auto",
             sparse_bwd="auto", quant_encoder=True, quant_block=128)
    # a valid quant layout passes (nd = 256, block 128)
    assert _cfg(quant_encoder=True, quant_block=128).quant_encoder


def test_quant_encoder_step_runs_and_tracks_exact():
    """cfg.quant_encoder end-to-end through training_loss: runs, finite,
    and the loss stays near the exact fused tier's (the in-kernel int8
    matmul only perturbs selection at quantization-noise scale)."""
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.standard_normal((32, 2, 128)), jnp.float32)
    l_q, g_q = _loss_and_grads(_cfg(quant_encoder=True, quant_block=128), x)
    l_e, _ = _loss_and_grads(_cfg(), x)
    assert np.isfinite(float(l_q))
    assert abs(float(l_q) - float(l_e)) / max(abs(float(l_e)), 1e-6) < 0.05
    assert all(np.all(np.isfinite(np.asarray(g, np.float32)))
               for g in g_q.values())


# ---------------------------------------------------------------------------
# zero-cost off


# the contract engine's public step-lowering harness (the same one
# scripts/analyze.py sweeps the knob lattice with) — the local copy this
# file used to carry is retired
from crosscoder_tpu.analysis.contracts.hlo_rules import \
    lower_step_text as _lower_step_text  # noqa: E402


@pytest.mark.parametrize("activation", ["topk", "batchtopk"])
def test_step_hlo_identical_with_fused_off(activation):
    """fused_encoder="off" and a dead "auto" (no kernel — the seed's
    effective path) trace the byte-identical step: the knob's presence
    costs nothing (the acceptance criterion's step-HLO identity across
    the new knobs)."""
    fek.set_interpret(False)
    topk_pallas.set_interpret(False)
    texts = []
    for mode in ("off", "auto"):
        cfg = CrossCoderConfig(
            d_in=128, dict_size=256, batch_size=32, enc_dtype="fp32",
            activation=activation, topk_k=8, l1_coeff=0.0,
            fused_encoder=mode,
        )
        texts.append(_lower_step_text(cfg))
    assert texts[0] == texts[1]
