"""Sparse backward compute plane (cfg.sparse_bwd; ops/sparse_grad.py,
ops/row_gather.py, docs/SCALING.md "Sparse backward plane"): the XLA
scatter and the latent-major row kernel (interpret mode on CPU) against a
numpy oracle, end-to-end gradient parity of the sparse custom VJPs against
the dense factored backward — including the duplicate-index accumulation
case and a non-chunk-divisible tail width — plus the dispatch gates,
config validation, and the zero-cost guarantees (step-HLO identity with
sparse_bwd="off", no XLA scatter in the row form). All CPU, tier-1."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.models import crosscoder as cc
from crosscoder_tpu.ops import row_gather, sparse_grad, topk_pallas
from crosscoder_tpu.parallel import mesh as mesh_lib


@pytest.fixture(autouse=True)
def _interpret_kernels():
    """Every test in this file exercises the Pallas path through the
    interpreter (the CPU stand-in for the TPU kernel, same as
    test_topk_pallas / test_quant)."""
    topk_pallas.set_interpret(True)
    row_gather.set_interpret(True)
    yield
    topk_pallas.set_interpret(False)
    row_gather.set_interpret(False)


def _np_scatter_oracle(coeff, idx, rows, n_out):
    out = np.zeros((n_out, rows.shape[-1]), np.float32)
    B, k = coeff.shape
    for b in range(B):
        for j in range(k):
            d = int(idx[b, j])
            if 0 <= d < n_out:
                out[d] += float(coeff[b, j]) * rows[b].astype(np.float32)
    return out


# ---------------------------------------------------------------------------
# scatter_add_rows (XLA) and row_gather.grouped_sums (the kernel) vs oracle


def _kernel_sums(coeff, idx, rows, n_out):
    """The latent-major kernel's first sum, on bf16 coefficients and rows
    (its second sum and its count ride along on the same pairs)."""
    c, r = jnp.asarray(coeff, jnp.bfloat16), jnp.asarray(rows, jnp.bfloat16)
    out_d, out_e, out_b = row_gather.grouped_sums(
        jnp.asarray(idx), c, c, r, r, n_out, name="t", interpret=True)
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_e).T)
    return (np.asarray(out_d, np.float32), np.asarray(out_b),
            np.asarray(c, np.float32), np.asarray(r, np.float32))


def _bf16_ulp(v):
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(v), 1e-30))) - 7) + 1e-6


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_out,m,B,k", [
    (512, 128, 16, 4),
    (256, 256, 32, 8),
    (1920, 128, 8, 4),      # 1920 = 15 tiles of 128 latents
])
def test_scatter_kernel_matches_xla_and_numpy(n_out, m, B, k, dtype):
    rng = np.random.default_rng(0)
    coeff = rng.standard_normal((B, k)).astype(np.float32)
    idx = rng.integers(0, n_out, size=(B, k)).astype(np.int32)
    rows = rng.standard_normal((B, m)).astype(np.float32)
    rows_j = jnp.asarray(rows, dtype)
    assert sparse_grad.supported(n_out, m, B * k)
    got_x = sparse_grad.scatter_add_rows(
        jnp.asarray(coeff), jnp.asarray(idx), rows_j, n_out)
    oracle = _np_scatter_oracle(coeff, idx, np.asarray(rows_j, np.float32),
                                n_out)
    np.testing.assert_allclose(np.asarray(got_x), oracle, atol=1e-5, rtol=1e-5)
    if dtype == jnp.bfloat16:       # the kernel's rows are bf16
        assert row_gather.grouped_supported(n_out, B, k, m, dtype)
        got_k, _, c, r = _kernel_sums(coeff, idx, rows, n_out)
        oracle = _np_scatter_oracle(c, idx, r, n_out)
        assert (np.abs(got_k - oracle) <= _bf16_ulp(oracle)).all()


def test_scatter_duplicate_destinations_accumulate():
    """The scatter-add race case: many pairs landing on the SAME output
    row must sum them all (the kernel puts duplicates side by side in one
    chunk's selection matrix by its stable sort; determinism is its
    construction, correctness is this assert)."""
    B, k, n_out, m = 24, 8, 256, 128
    rng = np.random.default_rng(1)
    coeff = rng.standard_normal((B, k)).astype(np.float32)
    idx = np.full((B, k), 7, np.int32)          # every pair hits row 7
    idx[:, 1] = 200                              # and a second shared row
    rows = rng.standard_normal((B, m)).astype(np.float32)
    got = sparse_grad.scatter_add_rows(
        jnp.asarray(coeff), jnp.asarray(idx), jnp.asarray(rows), n_out)
    oracle = _np_scatter_oracle(coeff, idx, rows, n_out)
    np.testing.assert_allclose(np.asarray(got), oracle, atol=1e-4, rtol=1e-5)
    assert float(np.abs(oracle[7]).max()) > 0    # the row really is contested
    got_k, count, c, r = _kernel_sums(coeff, idx, rows, n_out)
    oracle = _np_scatter_oracle(c, idx, r, n_out)
    assert (np.abs(got_k - oracle) <= _bf16_ulp(oracle) + 1e-4).all()
    np.testing.assert_allclose(count[[7, 200]], [c[:, [0] + list(range(2, k))].sum(),
                                                 c[:, 1].sum()], rtol=1e-5)


def test_scatter_out_of_range_dropped_not_wrapped():
    """Negative / >= n_out destinations are dropped (scatter mode="drop"
    semantics) — numpy-style wrapping of a -1 would corrupt the last
    dictionary row's gradient."""
    B, k, n_out, m = 8, 4, 256, 128
    rng = np.random.default_rng(2)
    coeff = rng.standard_normal((B, k)).astype(np.float32)
    idx = rng.integers(0, n_out, size=(B, k)).astype(np.int32)
    idx[0, 0] = -1
    idx[1, 0] = n_out
    rows = rng.standard_normal((B, m)).astype(np.float32)
    oracle = _np_scatter_oracle(coeff, idx, rows, n_out)
    got = sparse_grad.scatter_add_rows(
        jnp.asarray(coeff), jnp.asarray(idx), jnp.asarray(rows), n_out)
    np.testing.assert_allclose(np.asarray(got), oracle, atol=1e-5, rtol=1e-5)


def test_supported_gates():
    assert sparse_grad.supported(n_out=512, m=256, n_pairs=256)
    assert not sparse_grad.supported(512, 100, 256)        # m not lane-aligned
    assert not sparse_grad.supported(512, 64, 256)         # m < 128
    assert not sparse_grad.supported(4, 256, 256)          # under one row tile
    assert not sparse_grad.supported(512, 256, 0)          # empty pair list
    assert not sparse_grad.supported(                      # the update matrix's cap
        512, 256, sparse_grad._MAX_PAIRS + 1)
    # the kernel: whole tiles of latents, bf16 rows of whole lanes
    assert row_gather.grouped_supported(1024, 32, 8, 256, jnp.bfloat16)
    assert not row_gather.grouped_supported(1024, 32, 8, 200, jnp.bfloat16)
    assert not row_gather.grouped_supported(1000, 32, 8, 256, jnp.bfloat16)
    assert not row_gather.grouped_supported(1024, 32, 8, 256, jnp.float32)


# ---------------------------------------------------------------------------
# end-to-end gradient parity: sparse VJPs vs the dense factored backward


def _cfg(**kw):
    base = dict(d_in=128, n_models=2, dict_size=1024, activation="topk",
                topk_k=8, l1_coeff=0.0, batch_size=32, enc_dtype="fp32",
                master_dtype="fp32", factored_decode="on")
    base.update(kw)
    return CrossCoderConfig(**base)


def _grads(cfg, x, dead_mask=None):
    params = cc.init_params(jax.random.key(0), cfg)

    def loss(p):
        kw = {}
        if dead_mask is not None:
            kw["dead_mask"] = dead_mask
            kw["aux_coeff"] = 1.0
        return cc.training_loss(p, x, 0.0, cfg, with_metrics=False, **kw)[0]

    return jax.value_and_grad(loss)(params)


def _assert_grad_parity(cfg_kw, x, dead_mask=None, tol=2e-5):
    l_off, g_off = _grads(_cfg(sparse_bwd="off", **cfg_kw), x, dead_mask)
    l_on, g_on = _grads(_cfg(sparse_bwd="on", **cfg_kw), x, dead_mask)
    assert float(l_off) == pytest.approx(float(l_on), rel=1e-6)
    for name in g_off:
        a = np.asarray(g_off[name], np.float32)
        b = np.asarray(g_on[name], np.float32)
        scale = max(float(np.abs(a).max()), 1e-6)
        np.testing.assert_allclose(b, a, atol=tol * scale, rtol=0,
                                   err_msg=f"grad mismatch on {name}")


@pytest.mark.parametrize("dict_size", [512, 1024, 1920])
def test_grad_parity_bare_step(dict_size):
    """The full-step sparse variant (encode+TopK+decode in one custom vjp)
    against the dense factored backward, across dict widths including the
    non-chunk-divisible 1920 (row block shrinks to 240)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((32, 2, 128)), jnp.float32)
    _assert_grad_parity(dict(dict_size=dict_size), x)


def test_grad_parity_duplicate_latent_batch():
    """Two identical examples activate the SAME k latents — every sparse
    pair is a duplicate destination, the scatter-accumulate race case."""
    rng = np.random.default_rng(4)
    row = rng.standard_normal((1, 2, 128))
    x = jnp.asarray(np.repeat(row, 32, axis=0), jnp.float32)
    _assert_grad_parity(dict(dict_size=512), x)


def test_grad_parity_auxk_step():
    """AuxK-on step: the main tier runs the (h, W_dec)-scoped sparse
    variant (h stays a residual for the aux ranking) and the aux term
    reuses the scatter plane (_sparse_aux_product) — both against the
    dense pair."""
    cfg_kw = dict(dict_size=512, aux_k=16, aux_dead_steps=1)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((32, 2, 128)), jnp.float32)
    dead = jnp.ones((512,), bool)        # everything dead: aux path fully live
    # f32 einsum-vs-scatter association differs more once the aux residual
    # couples the two losses; still well inside f32-accumulation agreement
    _assert_grad_parity(cfg_kw, x, dead_mask=dead, tol=2e-4)


def test_sparse_step_forward_matches_factored_tier():
    """sparse_bwd changes the BACKWARD only: the forward loss/recon of the
    full-step variant must match the factored tier's to f32 association
    noise."""
    cfg_off = _cfg(sparse_bwd="off")
    cfg_on = _cfg(sparse_bwd="on")
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((32, 2, 128)), jnp.float32)
    params = cc.init_params(jax.random.key(0), cfg_off)
    a = cc.get_losses(params, x, cfg_off)
    b = cc.get_losses(params, x, cfg_on)
    np.testing.assert_allclose(float(a.l2_loss), float(b.l2_loss),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(a.explained_variance),
                               np.asarray(b.explained_variance), atol=1e-5)


# ---------------------------------------------------------------------------
# dispatch gates + config validation


def test_use_sparse_bwd_dispatch():
    assert cc.use_sparse_bwd(_cfg(sparse_bwd="on"))
    assert not cc.use_sparse_bwd(_cfg(sparse_bwd="off"))
    # auto: the row kernels must be live (here: the fixture's interpret
    # mode, the CPU stand-in for a TPU backend with one device), the rows
    # bf16, and the step's batch known
    assert not cc.use_sparse_bwd(_cfg(sparse_bwd="auto"), batch=32)     # fp32 rows
    auto = _cfg(sparse_bwd="auto", enc_dtype="bf16", factored_decode="auto")
    assert cc.use_sparse_bwd(auto, batch=32)
    assert not cc.use_sparse_bwd(auto)
    row_gather.set_interpret(False)
    assert not cc.use_sparse_bwd(auto, batch=32)
    row_gather.set_interpret(True)
    # auto rejects kernel-unsupported shapes (d_in breaks lane alignment;
    # a dictionary that is not whole tiles of latents)
    assert not cc.use_sparse_bwd(auto.replace(d_in=100), batch=32)
    assert not cc.use_sparse_bwd(auto.replace(dict_size=1000), batch=32)
    # non-topk / l1 never route sparse (validated for "on", gated for auto)
    assert not cc.use_sparse_bwd(
        _cfg(sparse_bwd="auto", activation="relu", l1_coeff=2.0,
             factored_decode="auto"), batch=32)


def test_sparse_bwd_on_forces_factored_tier():
    """A forced sparse backward at a sub-crossover dict must not silently
    noop: "on" flips the factored-tier auto gate too."""
    cfg = _cfg(sparse_bwd="on", factored_decode="auto", dict_size=1024)
    assert cc.use_factored_decode(cfg)
    cfg_off = _cfg(sparse_bwd="off", factored_decode="auto", dict_size=1024)
    assert not cc.use_factored_decode(cfg_off)


def test_use_sparse_aux_gates():
    # aux reuse needs the plane active AND (in auto) the width heuristic
    assert cc.use_sparse_aux(_cfg(sparse_bwd="on", aux_k=16), batch=32)
    assert not cc.use_sparse_aux(_cfg(sparse_bwd="off", aux_k=16), batch=32)
    assert not cc.use_sparse_aux(_cfg(sparse_bwd="on", aux_k=0), batch=32)
    # auto answers for a step, and an AuxK step keeps the dense form
    assert not cc.use_sparse_aux(
        _cfg(sparse_bwd="auto", aux_k=16, dict_size=1 << 17), batch=32)
    # the pair cap is HARD, forced "on" included: B·aux_k over
    # sparse_grad._MAX_PAIRS would route the aux VJP to the XLA fallback
    # that materializes a [B·aux_k, n·d] f32 update matrix — the bench
    # recipe shape (4096·256 = 1M pairs) must fall back to the dense aux
    big = sparse_grad._MAX_PAIRS // 32 + 32      # batch 32 → pairs > cap
    assert not cc.use_sparse_aux(
        _cfg(sparse_bwd="on", aux_k=big, dict_size=1 << 17), batch=32)


def test_config_rejects_bad_sparse_bwd():
    with pytest.raises(ValueError, match="did you mean 'auto'"):
        _cfg(sparse_bwd="atuo")
    with pytest.raises(ValueError, match="sparse_bwd='on' requires"):
        _cfg(sparse_bwd="on", activation="relu", l1_coeff=0.0,
             factored_decode="auto")
    with pytest.raises(ValueError, match="l1_coeff=0"):
        _cfg(sparse_bwd="on", l1_coeff=1.0)
    with pytest.raises(ValueError, match="sparse_decode"):
        _cfg(sparse_bwd="on", sparse_decode=True)


# ---------------------------------------------------------------------------
# zero-cost guarantees


def _lower_step_text(cfg):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from crosscoder_tpu.train import schedules
    from crosscoder_tpu.train.state import init_train_state, make_optimizer
    from crosscoder_tpu.train.trainer import make_train_step

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
        (cfg.n_sources,), jnp.float32, sharding=NamedSharding(mesh, P()),
    )
    return step.lower(state_sh, batch, scale).as_text()


def test_step_hlo_identical_with_sparse_bwd_off():
    """sparse_bwd="off" (and a dead "auto" — no kernel, the seed's
    effective path) must trace the byte-identical step the pre-PR graph
    traced: the knob's presence costs nothing."""
    row_gather.set_interpret(False)      # "auto" must be DEAD for this test
    topk_pallas.set_interpret(False)
    texts = []
    for mode in ("off", "auto"):
        cfg = CrossCoderConfig(
            d_in=128, dict_size=256, batch_size=32, enc_dtype="fp32",
            activation="topk", topk_k=8, l1_coeff=0.0, sparse_bwd=mode,
        )
        texts.append(_lower_step_text(cfg))
    assert texts[0] == texts[1]


def test_sparse_on_path_has_no_xla_scatter():
    """The whole point: in the row form the bare-step gradient contains NO
    XLA scatter op — every gradient lands through the row kernels
    (interpret-lowered here), a sort or a matmul. The dense baseline's same
    lowering is scatter-free too (it's all matmuls), so also assert the
    sparse path didn't smuggle one in via its sorting/searching machinery.
    Mirrors test_quant's no-s8 assert."""
    cfg = _cfg(sparse_bwd="auto", enc_dtype="bf16", factored_decode="auto")
    assert cc.use_sparse_bwd(cfg, 32)
    params = cc.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    x = jax.ShapeDtypeStruct((32, cfg.n_sources, cfg.d_in), jnp.float32)

    def loss(p, xb):
        return cc.training_loss(p, xb, 0.0, cfg, with_metrics=False)[0]

    text = jax.jit(jax.grad(loss)).lower(params, x).as_text()
    assert "scatter" not in text
