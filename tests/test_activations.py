"""Tests for the sparse activation family (TopK / BatchTopK / JumpReLU) —
TPU-native additions with no reference counterpart (reference has dense ReLU
only, crosscoder.py:76-77)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.models import crosscoder as cc
from crosscoder_tpu.ops import activations as act


def test_topk_keeps_k_largest():
    h = jnp.asarray(np.random.default_rng(0).normal(size=(16, 64)).astype(np.float32))
    out = act.topk(h, 8, use_pallas=False)
    n_active = np.asarray((out > 0).sum(axis=-1))
    assert (n_active <= 8).all()
    # surviving values are unchanged
    hp = np.maximum(np.asarray(h), 0)
    mask = np.asarray(out) > 0
    np.testing.assert_allclose(np.asarray(out)[mask], hp[mask])
    # each row's kept entries are its largest positives
    for r in range(16):
        kept = set(np.flatnonzero(mask[r]))
        expect = set(np.argsort(-hp[r])[: len(kept)])
        assert kept == expect


def test_topk_gradient_flows_only_through_survivors():
    h = jnp.asarray([[3.0, 1.0, 2.0, -1.0]])
    g = jax.grad(lambda x: act.topk(x, 2, use_pallas=False).sum())(h)
    np.testing.assert_allclose(np.asarray(g), [[1.0, 0.0, 1.0, 0.0]])


def test_batchtopk_global_budget():
    h = jnp.asarray(np.random.default_rng(1).normal(size=(8, 32)).astype(np.float32))
    out = act.batchtopk(h, 4)
    assert int((out > 0).sum()) <= 4 * 8


def _batchtopk_sort_oracle(h: np.ndarray, k: int) -> np.ndarray:
    """The flatten-and-sort definition batchtopk replaces: threshold = the
    (k·batch)-th largest ReLU'd value, all ties at the threshold kept."""
    hp = np.maximum(h.astype(np.float32), 0)
    kk = min(k * int(np.prod(hp.shape[:-1])), hp.size)
    thresh = np.sort(hp.reshape(-1))[::-1][kk - 1]
    return (hp * ((hp >= thresh) & (hp > 0))).astype(h.dtype)


def test_batchtopk_matches_sort_oracle():
    rng = np.random.default_rng(2)
    for dtype in (np.float32, jnp.bfloat16):
        h = rng.normal(size=(16, 96)).astype(np.float32)
        # force ties at what will be the threshold region
        h[h > 0.9] = 1.0
        h = jnp.asarray(h).astype(dtype)
        out = np.asarray(act.batchtopk(h, 3), np.float32)
        expect = np.asarray(_batchtopk_sort_oracle(np.asarray(h, np.float32), 3))
        np.testing.assert_array_equal(out, expect)


def test_batchtopk_all_zero_and_full_budget():
    z = jnp.zeros((4, 16))
    assert int((act.batchtopk(z, 2) > 0).sum()) == 0
    # budget >= total size keeps every positive entry
    h = jnp.asarray(np.random.default_rng(3).normal(size=(4, 8)).astype(np.float32))
    out = np.asarray(act.batchtopk(h, 8))
    np.testing.assert_array_equal(out, np.maximum(np.asarray(h), 0))


def test_batchtopk_production_shape():
    """VERDICT round-1 weak #5: the old flatten-and-sort became a 134M-element
    device sort at [4096, 2^15]; the bisection path must handle that shape."""
    h = jax.random.normal(jax.random.key(0), (4096, 2**15), dtype=jnp.bfloat16)
    out = jax.jit(act.batchtopk, static_argnums=1)(h, 32)
    out_np = np.asarray(out, np.float32)
    hp = np.maximum(np.asarray(h, np.float32), 0)
    n_active = int((out_np > 0).sum())
    # at least the budget is kept (bf16 ties at the threshold can exceed it —
    # the same ties-all-kept semantics the sort-based definition has)
    assert n_active >= 32 * 4096
    # exact threshold semantics: every dropped positive is strictly below
    # every kept value
    assert hp[out_np == 0].max() < out_np[out_np > 0].min()
    # grad path compiles and is masked like the forward
    g = jax.jit(jax.grad(lambda x: act.batchtopk(x, 32).astype(jnp.float32).sum()))(h)
    assert bool(((np.asarray(g, np.float32) != 0) == (out_np > 0)).all())


def test_jumprelu_forward_and_theta_grad():
    log_theta = jnp.log(jnp.asarray([0.5, 0.5, 0.5]))
    h = jnp.asarray([[0.2, 0.6, 1.5]])
    out = act.jumprelu(h, log_theta, 0.3)
    np.testing.assert_allclose(np.asarray(out), [[0.0, 0.6, 1.5]])
    # h-grad passes through active units only
    gh = jax.grad(lambda x: act.jumprelu(x, log_theta, 0.3).sum())(h)
    np.testing.assert_allclose(np.asarray(gh), [[0.0, 1.0, 1.0]])
    # theta-grad is nonzero only near the threshold (|h−θ| ≤ bandwidth/2):
    # h=0.6 with θ=0.5, bw=0.3 → inside window; others outside
    gt = jax.grad(lambda lt: act.jumprelu(h, lt, 0.3).sum(), argnums=0)(log_theta)
    assert float(gt[0]) == 0.0
    assert float(gt[1]) != 0.0
    assert float(gt[2]) == 0.0


def test_jumprelu_via_config_dispatch():
    cfg = CrossCoderConfig(d_in=8, dict_size=16, enc_dtype="fp32", activation="jumprelu")
    p = cc.init_params(jax.random.key(0), cfg)
    assert "log_theta" in p
    x = jax.random.normal(jax.random.key(1), (4, 2, 8))
    out = cc.get_losses(p, x, cfg)
    assert np.isfinite(float(out.l2_loss))


def test_topk_via_config_dispatch():
    cfg = CrossCoderConfig(d_in=8, dict_size=16, enc_dtype="fp32", activation="topk", topk_k=4)
    p = cc.init_params(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (4, 2, 8))
    f = cc.encode(p, x, cfg)
    assert int((f > 0).sum(axis=-1).max()) <= 4


def test_batchtopk_fixed_threshold_eval_mode():
    """cfg.batchtopk_threshold > 0 switches batchtopk to a FIXED global
    threshold: one example's activations no longer depend on its batch,
    and the calibrated threshold reproduces the per-batch behavior on the
    calibration distribution."""
    from crosscoder_tpu.config import CrossCoderConfig
    from crosscoder_tpu.models import crosscoder as cc

    cfg = CrossCoderConfig(d_in=16, dict_size=64, n_models=2, batch_size=32,
                           activation="batchtopk", topk_k=4, enc_dtype="fp32")
    params = cc.init_params(jax.random.key(0), cfg)
    batches = [
        np.asarray(jax.random.normal(jax.random.key(i), (32, 2, 16)))
        for i in range(4)
    ]
    thr = cc.calibrate_batchtopk_threshold(params, cfg, batches)
    assert thr > 0

    cfg_eval = cfg.replace(batchtopk_threshold=thr)
    # batch-independence: a row encoded alone == the same row in a batch
    full = cc.encode(params, jnp.asarray(batches[0]), cfg_eval)
    solo = cc.encode(params, jnp.asarray(batches[0][:1]), cfg_eval)
    # matmul tiling differs with batch size -> fp32 noise; the SUPPORT
    # must match exactly, values to reduction tolerance
    np.testing.assert_array_equal(np.asarray(full[:1]) > 0, np.asarray(solo) > 0)
    np.testing.assert_allclose(np.asarray(full[:1]), np.asarray(solo),
                               rtol=1e-5, atol=1e-6)
    # (per-batch mode would drop/keep different entries for the solo row)
    full_b = cc.encode(params, jnp.asarray(batches[0]), cfg)
    solo_b = cc.encode(params, jnp.asarray(batches[0][:1]), cfg)
    assert not np.array_equal(np.asarray(full_b[:1]), np.asarray(solo_b))

    # calibrated threshold ~ reproduces per-batch L0 on calibration data
    l0_eval = float((np.asarray(full) > 0).sum(-1).mean())
    l0_batch = float((np.asarray(full_b) > 0).sum(-1).mean())
    assert abs(l0_eval - l0_batch) / max(l0_batch, 1) < 0.5


def test_jumprelu_l0_penalty_gradient():
    """The rectangle-kernel STE: d/d log_theta of the L0 penalty is
    −(1/ε)·mean_b rect·θ per feature; h gets no gradient."""
    from crosscoder_tpu.ops.activations import jumprelu_l0

    bandwidth = 0.5
    h = jnp.asarray([[0.1, 0.9, 2.0], [0.15, 1.1, -0.3]], jnp.float32)
    log_theta = jnp.log(jnp.asarray([0.2, 1.0, 0.05], jnp.float32))

    val, grads = jax.value_and_grad(
        lambda lt, x: jumprelu_l0(x, lt, bandwidth), argnums=(0, 1)
    )(log_theta, h)
    # forward: mean over batch of counts above theta
    counts = (np.asarray(h) > np.exp(np.asarray(log_theta))).sum(-1)
    assert float(val) == counts.mean()
    # manual rectangle gradient
    theta = np.exp(np.asarray(log_theta))
    rect = (np.abs(np.asarray(h) - theta) <= bandwidth / 2).astype(np.float32)
    want_glt = -(1.0 / bandwidth) * rect.mean(0) * theta
    np.testing.assert_allclose(np.asarray(grads[0]), want_glt, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(grads[1]), np.zeros_like(h))


def test_jumprelu_l0_coeff_trains_sparsity():
    """cfg.l0_coeff > 0 drives L0 down over training where l0_coeff=0
    does not (the paper's sparsity objective, wired through
    training_loss)."""
    from crosscoder_tpu.config import CrossCoderConfig
    from crosscoder_tpu.models import crosscoder as cc
    import optax

    def run(l0_coeff):
        cfg = CrossCoderConfig(
            d_in=16, dict_size=128, n_models=2, batch_size=64,
            activation="jumprelu", jumprelu_theta=0.01,
            jumprelu_bandwidth=0.05, l1_coeff=0.0, l0_coeff=l0_coeff,
            enc_dtype="fp32",
        )
        params = cc.init_params(jax.random.key(0), cfg)
        tx = optax.adam(1e-2)
        opt = tx.init(params)
        x = jax.random.normal(jax.random.key(1), (64, 2, 16))

        @jax.jit
        def step(params, opt):
            (loss, aux), g = jax.value_and_grad(
                lambda p: cc.training_loss(p, x, 0.0, cfg), has_aux=True
            )(params)
            upd, opt = tx.update(g, opt, params)
            return optax.apply_updates(params, upd), opt, aux

        for _ in range(400):
            params, opt, aux = step(params, opt)
        return float(aux.l0_loss)

    l0_with = run(5e-2)
    l0_without = run(0.0)
    # measured: ~49 vs ~66 active latents after 400 steps
    assert l0_with < 0.85 * l0_without, (l0_with, l0_without)


@pytest.mark.parametrize("dtype", ["fp32", "bf16"])
def test_batchtopk_step_bitexact_incl_ties(dtype):
    """The BatchTopK step's activations — ``get_losses``' dense encode — to
    the bit against a NumPy restatement, with ties: integer operands make
    every pre-act sum exact in f32, the cast to bf16 rounds many of them
    together, and one latent copies another's encoder column and bias, so
    the two agree on every row (a dictionary of 1000, not whole
    lane tiles)."""
    rng = np.random.default_rng(6)
    B, d, H, k = 48, 64, 1000, 8
    cfg = CrossCoderConfig(d_in=d, dict_size=H, batch_size=B, activation="batchtopk",
                           topk_k=k, l1_coeff=0.0, enc_dtype=dtype, log_backend="null")
    jdt = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    W = rng.integers(-2, 3, size=(2, d, H))
    W[:, :, 500] = W[:, :, 9]
    x = rng.integers(-3, 4, size=(B, 2, d))
    b = rng.integers(-2, 3, size=(H,))
    b[500] = b[9]
    params = dict(cc.init_params(jax.random.key(0), cfg, dtype=jnp.float32),
                  W_enc=jnp.asarray(W, jdt), b_enc=jnp.asarray(b, jnp.float32))
    h = (np.einsum("bnd,ndh->bh", x, W) + b).astype(np.float32).astype(jdt)
    want = _batchtopk_sort_oracle(np.asarray(h, np.float32), k)
    got = np.asarray(cc.encode(params, jnp.asarray(x, jdt), cfg), np.float32)
    np.testing.assert_array_equal(got, want)
    hp = np.maximum(np.asarray(h, np.float32), 0)
    assert (hp == np.sort(hp.ravel())[::-1][k * B - 1]).sum() > 1     # a tied threshold
    np.testing.assert_array_equal(got[:, 500], got[:, 9])
    losses = cc.get_losses(params, jnp.asarray(x, jnp.float32), cfg)
    assert float(losses.l0_loss) == pytest.approx((want > 0).sum(-1).mean(), rel=1e-6)
