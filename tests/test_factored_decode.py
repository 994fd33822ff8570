"""Factored TopK decode (cfg.factored_decode, the Pallas tier): the
forward through the k active rows + dense-matmul backward must reproduce
the dense TopK path's losses AND parameter gradients exactly (the
backward IS the dense backward; the forward is the same sum restricted to
its nonzero terms). Runs the kernels in Pallas interpreter mode on CPU.

No reference counterpart — the reference decode is always dense
(reference crosscoder.py:82-89); this is the TPU build's native tier.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.models import crosscoder as cc
from crosscoder_tpu.ops import row_gather, topk_pallas


@pytest.fixture(autouse=True)
def _interpret():
    topk_pallas.set_interpret(True)
    yield
    topk_pallas.set_interpret(False)
    row_gather.set_interpret(False)


def _cfgs(**kw):
    base = dict(d_in=24, dict_size=256, batch_size=64, enc_dtype="fp32",
                activation="topk", topk_k=8, l1_coeff=0.0, log_backend="null")
    base.update(kw)
    dense = CrossCoderConfig(**base, factored_decode="off")
    return dense, dense.replace(factored_decode="on")


def _data(cfg, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(cfg.batch_size, cfg.n_sources, cfg.d_in)).astype(np.float32)
    return cc.init_params(jax.random.key(1), cfg), jnp.asarray(x)


# The dispatch table of the TopK step's forms (models/crosscoder.py:
# use_factored_decode / use_sparse_bwd / rows_live), one case a row:
# (config overrides, the step's batch or None, what the process looks like)
# -> (factored tier, sparse backward plane, the row kernels). "chip": a TPU
# backend with one device; "mesh": a TPU backend with eight; "cpu": neither
# (the TopK kernel still answers through the interpreter, as in the file);
# "interpret": the CPU with the row kernels in the interpreter too, the
# stand-in for the chip where a step can run; "xla": the CPU with no
# kernel at all. The rows of the last two kinds also run the step
# (test_step_takes_the_dispatched_form).
_BF16 = dict(enc_dtype="bf16", d_in=128, dict_size=512)
_FP32 = dict(_BF16, enc_dtype="fp32")
_AUXK = dict(aux_k=16, aux_dead_steps=1)
DISPATCH = {
    "off": (dict(factored_decode="off"), 64, "cpu", (False, False, False)),
    "on": (dict(factored_decode="on"), 64, "cpu", (True, False, False)),
    # where the rows cannot be fetched by DMA, auto still asks for dict >= 2^17
    # (XLA's gather against the dense matmul: the crossover measured at PR 3)
    "auto-needs-2^17-off-the-chip": (dict(), 64, "cpu", (False, False, False)),
    "auto-2^17-off-the-chip": (dict(dict_size=2 ** 17), 64, "cpu", (True, False, False)),
    "auto-on-the-chip": (_BF16, 64, "chip", (True, True, True)),
    "auto-on-the-chip-2^17": (dict(_BF16, dict_size=2 ** 17), 64, "chip", (True, True, True)),
    "auto-on-a-mesh": (_BF16, 64, "mesh", (False, False, False)),
    "auto-on-a-mesh-2^17": (dict(_BF16, dict_size=2 ** 17), 64, "mesh", (True, False, False)),
    # an AuxK step, or a caller that knows no batch: today's behaviour stands
    "auto-no-batch": (_BF16, None, "chip", (False, False, False)),
    "auto-float32-rows": (dict(_BF16, enc_dtype="fp32"), 64, "chip", (False, False, False)),
    "auto-half-a-row-not-whole-lanes": (dict(_BF16, d_in=100), 64, "chip", (False, False, False)),
    "auto-under-one-group-of-tokens": (_BF16, 8, "chip", (False, False, False)),
    # the token-major kernels cut their table into slices of the batch; the
    # latent-major one prefetches the pairs' tokens whole (1 MiB here): all
    # four products go through the rows, or none
    "auto-pairs-past-smem": (_BF16, 32768, "chip", (False, False, False)),
    # nonzero L1 objective is unsound on this path (no grad through vals):
    # auto silently falls back rather than erroring
    "auto-with-l1": (dict(_BF16, l1_coeff=0.5), 64, "chip", (False, False, False)),
    "relu": (dict(_BF16, activation="relu", l1_coeff=1.0), 64, "chip", (False, False, False)),
    "sparse-bwd-off": (dict(_BF16, sparse_bwd="off"), 64, "chip", (False, False, False)),
    "sparse-bwd-on-on-the-chip": (dict(_BF16, sparse_bwd="on"), 64, "chip", (True, True, True)),
    "sparse-bwd-on-forces-the-tier": (dict(sparse_bwd="on"), 64, "cpu", (True, True, False)),
    # what the step can see when it is traced: the activation, an AuxK step,
    # the rows' dtype, whether the kernels run at all, and a batch the row
    # kernels take (32) or refuse (8)
    "topk-bf16-interpret": (_BF16, 32, "interpret", (True, True, True)),
    "topk-bf16-interpret-batch-refused": (_BF16, 8, "interpret", (False, False, False)),
    "topk-fp32-interpret": (_FP32, 32, "interpret", (False, False, False)),
    "topk-bf16-xla": (_BF16, 32, "xla", (False, False, False)),
    "topk-fp32-xla": (_FP32, 32, "xla", (False, False, False)),
    # the gates cannot see an AuxK step; the step keeps its dense form there
    "topk-auxk-bf16-interpret": (dict(_BF16, **_AUXK), 32, "interpret", (True, True, True)),
    "topk-auxk-fp32-interpret": (dict(_FP32, **_AUXK), 32, "interpret", (False, False, False)),
    "topk-auxk-bf16-xla": (dict(_BF16, **_AUXK), 32, "xla", (False, False, False)),
    # the forms only "on" reaches: the factored decode through XLA's take, and
    # the sparse backward through XLA's scatter (on an AuxK step: the (h,
    # W_dec)-scoped variant and the aux term's sparse VJP)
    "topk-on-fp32-interpret": (dict(_FP32, factored_decode="on"), 32, "interpret",
                               (True, False, False)),
    "topk-sparse-bwd-on-fp32-interpret": (dict(_FP32, sparse_bwd="on"), 32, "interpret",
                                          (True, True, False)),
    "topk-auxk-sparse-bwd-on-fp32-interpret": (dict(_FP32, sparse_bwd="on", **_AUXK), 32,
                                               "interpret", (True, True, False)),
    "batchtopk-bf16-interpret": (dict(_BF16, activation="batchtopk"), 32, "interpret",
                                 (False, False, False)),
    "batchtopk-fp32-interpret": (dict(_FP32, activation="batchtopk"), 32, "interpret",
                                 (False, False, False)),
    "batchtopk-bf16-xla": (dict(_BF16, activation="batchtopk"), 32, "xla", (False, False, False)),
    "batchtopk-auxk-bf16-interpret": (dict(_BF16, activation="batchtopk", **_AUXK), 32,
                                      "interpret", (False, False, False)),
}
_BASE = dict(d_in=24, dict_size=256, batch_size=64, enc_dtype="fp32", activation="topk",
             topk_k=8, l1_coeff=0.0, log_backend="null", factored_decode="auto")


def _kernels(where):
    """The interpreter latches a DISPATCH row's process has."""
    topk_pallas.set_interpret(where != "xla")
    row_gather.set_interpret(where == "interpret")


@pytest.mark.parametrize("case", list(DISPATCH))
def test_dispatch_gates(case, monkeypatch):
    from crosscoder_tpu.ops import activations as act_ops

    over, batch, where, want = DISPATCH[case]
    _kernels(where)
    if where in ("chip", "mesh"):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(jax, "device_count", lambda *a: 1 if where == "chip" else 8)
        act_ops._backend_is_tpu.cache_clear()
    try:
        cfg = CrossCoderConfig(**dict(_BASE, **over))
        assert row_gather.enabled() == (where in ("chip", "interpret"))
        factored = cc.use_factored_decode(cfg, batch)
        got = (factored, factored and cc.use_sparse_bwd(cfg, batch), cc.rows_live(cfg, batch))
        assert got == want
    finally:
        act_ops._backend_is_tpu.cache_clear()


def _loss_and_grads(params, x, cfg, dead):
    kw = {} if dead is None else dict(dead_mask=dead, aux_coeff=1.0)

    def fn(p):
        return cc.training_loss(p, x, 0.0, cfg, **kw)

    (_, losses), grads = jax.jit(jax.value_and_grad(fn, has_aux=True))(params)
    return losses, grads


def _bf16_ulp(v):
    return np.spacing(np.abs(v).astype(jnp.bfloat16)).astype(np.float32)


@pytest.mark.parametrize("case", [c for c, row in DISPATCH.items()
                                  if row[2] in ("interpret", "xla")])
def test_step_takes_the_dispatched_form(case, tmp_path):
    """The step ``get_losses`` traces for a DISPATCH row: the form it took,
    read from the ``perf/cc_{decode,bwd}_{rows,dense}_traces`` counters
    (BatchTopK counts neither), and its losses and gradients against the
    dense form's (``factored_decode="off"``, ``sparse_bwd="off"``) at this
    file's tolerances. The row form rounds each gradient to bf16 once, in
    another order than the dense step: there the two may differ on a few
    elements, by one bf16 ulp of that element (tests/test_row_gather.py)."""
    from crosscoder_tpu import obs

    over, batch, where, (_, _, rows) = DISPATCH[case]
    _kernels(where)
    cfg = CrossCoderConfig(**dict(_BASE, batch_size=batch, **over))
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((batch, cfg.n_sources, cfg.d_in)), jnp.float32)
    params = cc.init_params(jax.random.key(1), cfg, dtype=jnp.float32)
    dead = jnp.asarray(np.arange(cfg.dict_size) % 3 == 0) if cfg.aux_k else None
    plane = obs.acquire(CrossCoderConfig(obs="on", obs_dir=str(tmp_path / "obs")))
    try:
        got, g_got = _loss_and_grads(params, x, cfg, dead)
        counts = {form: (plane.registry.get_count(f"perf/cc_decode_{form}_traces"),
                         plane.registry.get_count(f"perf/cc_bwd_{form}_traces"))
                  for form in ("rows", "dense")}
    finally:
        plane.close()
    # the row kernels take the step when the gates say so, except on an AuxK
    # step: the ranking there consumes the pre-acts
    form = None if cfg.activation != "topk" else "rows" if rows and not cfg.aux_k else "dense"
    assert counts == {f: (1, 1) if f == form else (0, 0) for f in ("rows", "dense")}

    want, g_want = _loss_and_grads(
        params, x, cfg.replace(factored_decode="off", sparse_bwd="off"), dead)
    np.testing.assert_allclose(float(got.l2_loss), float(want.l2_loss), rtol=1e-5)
    np.testing.assert_allclose(float(got.l1_loss), float(want.l1_loss), rtol=1e-5)
    assert float(got.l0_loss) == float(want.l0_loss)
    np.testing.assert_allclose(np.asarray(got.explained_variance),
                               np.asarray(want.explained_variance), rtol=1e-4)
    np.testing.assert_allclose(float(got.aux_loss), float(want.aux_loss), rtol=1e-4)
    if cfg.aux_k:
        np.testing.assert_array_equal(np.asarray(got.fired), np.asarray(want.fired))
    for name in g_want:
        a, b = np.asarray(g_want[name], np.float32), np.asarray(g_got[name], np.float32)
        if form == "rows":
            assert (np.abs(a - b) > 2e-5 * np.abs(a).max()).mean() < 1e-3, name
            assert (np.abs(a - b) <= _bf16_ulp(a)).all(), name
        else:
            np.testing.assert_allclose(b, a, rtol=2e-5, atol=1e-7, err_msg=name)


def test_factored_on_with_l1_is_refused():
    # nonzero L1 objective is unsound on this path (no grad through vals)
    with pytest.raises(ValueError, match="factored_decode"):
        _cfgs()[1].replace(l1_coeff=0.5)


def test_losses_match_dense():
    dense_cfg, fact_cfg = _cfgs()
    params, x = _data(dense_cfg)
    ld = cc.get_losses(params, x, dense_cfg)
    lf = cc.get_losses(params, x, fact_cfg)
    np.testing.assert_allclose(float(ld.l2_loss), float(lf.l2_loss), rtol=1e-5)
    np.testing.assert_allclose(float(ld.l1_loss), float(lf.l1_loss), rtol=1e-5)
    assert float(ld.l0_loss) == float(lf.l0_loss)
    np.testing.assert_allclose(
        np.asarray(ld.explained_variance),
        np.asarray(lf.explained_variance), rtol=1e-4,
    )


def test_grads_match_dense_exactly():
    """The factored backward runs the SAME dense matmuls + mask as the
    dense path, so parameter gradients agree to fp tolerance (not just
    statistically)."""
    dense_cfg, fact_cfg = _cfgs()
    params, x = _data(dense_cfg, seed=3)

    def grad_of(cfg):
        def fn(p):
            loss, _ = cc.training_loss(p, x, 0.0, cfg, with_metrics=False)
            return loss
        return jax.grad(fn)(params)

    gd, gf = grad_of(dense_cfg), grad_of(fact_cfg)
    for k in gd:
        np.testing.assert_allclose(
            np.asarray(gd[k]), np.asarray(gf[k]), rtol=2e-5, atol=1e-7,
            err_msg=f"grad mismatch on {k}",
        )


def test_auxk_composes_with_factored():
    """AuxK's ranking consumes the pre-acts the factored path already
    computed; the aux loss must match the dense path's."""
    dense_cfg, fact_cfg = _cfgs(aux_k=16, aux_k_coeff=0.5)
    params, x = _data(dense_cfg, seed=5)
    dead = np.zeros(dense_cfg.dict_size, bool)
    dead[::3] = True
    dead = jnp.asarray(dead)
    ld = cc.get_losses(params, x, dense_cfg, dead_mask=dead, track_fired=True)
    lf = cc.get_losses(params, x, fact_cfg, dead_mask=dead, track_fired=True)
    np.testing.assert_allclose(float(ld.aux_loss), float(lf.aux_loss), rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(ld.fired), np.asarray(lf.fired))


def test_sparsify_matches_mask():
    h = jax.random.normal(jax.random.key(0), (96, 512), jnp.float32)
    f = np.asarray(jax.jit(lambda x: topk_pallas.topk(x, 8, True))(h))
    vals, idx = topk_pallas.sparsify(jnp.asarray(f), 8, interpret=True)
    v, i = np.asarray(vals), np.asarray(idx)
    for r in range(f.shape[0]):
        nz = np.nonzero(f[r])[0]
        assert list(i[r][v[r] != 0]) == list(nz)
        assert np.array_equal(v[r][v[r] != 0], f[r][nz])
        assert np.all(v[r][len(nz):] == 0)


def test_sparsify_wide_single_chunk_fits_vmem():
    """Width 8064 (<= 8192 but not %2048): the single-chunk leg must shrink
    its row block so the f32 scratch + input block stay inside the module's
    VMEM budget — 256 rows at 8 B/element is 16.5 MB, which Mosaic refuses
    to compile; the pre-fix geometry passed sparsify_supported and then
    died at compile time for direct callers."""
    width = 8064
    assert topk_pallas.sparsify_supported(width, 8)
    for itemsize in (4, 2):
        rows = topk_pallas._sparsify_rows(width, 4096, itemsize)
        assert rows % 32 == 0 and rows >= 32
        working_set = rows * width * (4 + itemsize)
        assert working_set <= topk_pallas._VMEM_BUDGET_BYTES, (rows, working_set)
    # and the shrunk geometry still produces correct output (interpret mode)
    h = jax.random.normal(jax.random.key(3), (64, width), jnp.float32)
    f = np.asarray(jax.jit(lambda x: topk_pallas.topk(x, 8, True))(h))
    vals, idx = topk_pallas.sparsify(jnp.asarray(f), 8, interpret=True)
    v, i = np.asarray(vals), np.asarray(idx)
    for r in range(f.shape[0]):
        nz = np.nonzero(f[r])[0]
        assert list(i[r][v[r] != 0]) == list(nz)
        assert np.array_equal(v[r][v[r] != 0], f[r][nz])
