"""The stream maps (crosscoder_tpu/ops/mhc.py): XLA's form against the
equations written out a token at a time, both kernels through the Pallas
interpreter against XLA's form, and the selection in ``read`` / ``write``."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crosscoder_tpu import obs
from crosscoder_tpu.ops import mhc

HC = mhc.HC(4, 20, 1e-6, (-30.0, 30.0), 1e-6)


@pytest.fixture
def interpret():
    mhc.set_interpret(True)
    yield
    mhc.set_interpret(False)


@pytest.fixture
def plane(tmp_path):
    p = obs.acquire(types.SimpleNamespace(
        obs="on", obs_dir=str(tmp_path / "obs"), checkpoint_dir=str(tmp_path)))
    yield p
    p.close()


def _case(T, C, dtype, seed=0, n=4):
    k = jax.random.split(jax.random.key(seed), 5)
    x = (2.0 * jax.random.normal(k[0], (T, n * C))).astype(dtype)     # streams side by side
    y = jax.random.normal(k[1], (T, C)).astype(dtype)
    phi = jax.random.normal(k[2], (n * C, n * n + 2 * n)) * (n * C) ** -0.5
    alpha = jnp.asarray([1.0, 0.7, 1.3])
    bias = (jnp.concatenate([jnp.zeros(2 * n), 2.0 * jnp.eye(n).reshape(-1)])
            + 0.1 * jax.random.normal(k[3], (n * n + 2 * n,)))
    return x, y, phi, alpha, bias


def _by_the_book(x, y, phi, alpha, bias, hc):
    """The equations of the module's docstring in float64 numpy, one token at
    a time, Python loops over the Sinkhorn iterations."""
    x, y, phi = (np.asarray(a, np.float64) for a in (x, y, phi))
    alpha, bias = np.asarray(alpha, np.float64), np.asarray(bias, np.float64)
    x = x.reshape(x.shape[0], hc.n, -1)
    T, n, C = x.shape
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))     # noqa: E731
    u, out, hp, mix = np.zeros((T, C)), np.zeros_like(x), np.zeros((T, n)), np.zeros((T, n, n))
    for t in range(T):
        v = x[t].reshape(-1)
        z = (v @ phi) / np.sqrt(np.mean(v * v) + hc.rms_eps)
        h_pre = sig(alpha[0] * z[:n] + bias[:n])
        h_post = 2.0 * sig(alpha[1] * z[n:2 * n] + bias[n:2 * n])
        A = np.clip(alpha[2] * z[2 * n:].reshape(n, n) + bias[2 * n:].reshape(n, n), *hc.clamp)
        M = np.exp(A)
        for _ in range(hc.iters):
            M = M / (M.sum(0, keepdims=True) + hc.eps)
            M = M / (M.sum(1, keepdims=True) + hc.eps)
        u[t] = h_pre @ x[t]
        out[t] = M @ x[t] + h_post[:, None] * y[t][None]
        hp[t], mix[t] = h_post, M
    return u, out.reshape(T, n * C), hp, mix


def test_xlas_form_is_the_equations():
    x, y, phi, alpha, bias = _case(24, 32, jnp.float32)
    u, maps = mhc._read_xla(x, phi, alpha, bias, HC)
    out = mhc._write_xla(x, y, maps, HC)
    want_u, want_out, want_hp, want_mix = _by_the_book(x, y, phi, alpha, bias, HC)
    np.testing.assert_allclose(np.asarray(u), want_u, atol=3e-5)
    np.testing.assert_allclose(np.asarray(maps.h_post), want_hp, atol=3e-6)
    np.testing.assert_allclose(np.asarray(maps.mix).reshape(-1, 4, 4), want_mix, atol=3e-6)
    np.testing.assert_allclose(np.asarray(out), want_out, atol=3e-5)
    # rows sum to 1 exactly (the last half-iteration), columns to Sinkhorn's remainder
    M = np.asarray(maps.mix).reshape(-1, 4, 4)
    np.testing.assert_allclose(M.sum(2), 1.0, atol=1e-5)
    assert 0 < float(mhc.col_err(maps, 4)) == pytest.approx(np.abs(M.sum(1) - 1).max(), rel=1e-3)
    assert float(mhc.col_err(maps, 4)) < 0.05
    np.testing.assert_allclose(
        np.asarray(mhc.mean_gain(maps, (24,)))[:, 0], want_hp.mean(1), atol=3e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("T,C", [(128, 128), (384, 256)])
def test_both_kernels_through_the_interpreter_against_xlas_form(interpret, dtype, T, C):
    assert mhc.supported(T, HC, C, dtype)
    x, y, phi, alpha, bias = _case(T, C, dtype, seed=T + C)
    u0, m0 = mhc._read_xla(x, phi, alpha, bias, HC)
    u1, m1 = mhc._read_kernel_call(x, phi, alpha, bias, HC)
    f = lambda a: np.asarray(a, np.float32)      # noqa: E731
    # maps are float32 in both forms; the streams' dtype rounds u and X' once
    np.testing.assert_allclose(f(m1.h_post), f(m0.h_post), atol=2e-6)
    np.testing.assert_allclose(f(m1.mix), f(m0.mix), atol=2e-6)
    one_rounding = 2e-5 if dtype == jnp.float32 else 2.0 ** -7 * 8
    np.testing.assert_allclose(f(u1), f(u0), atol=one_rounding)
    out0 = mhc._write_xla(x, y, m0, HC)
    out1 = mhc._write_kernel_call(x, y, m0, HC)
    np.testing.assert_allclose(f(out1), f(out0), atol=one_rounding)


def test_the_coefficient_matrix_is_diagonal_blocks():
    """``_diag_blocks``: coefficient j of token t at (t, start + G·j + t), 0
    elsewhere — the per-token 4 x 4 mixing of a group as ONE matrix on the
    stacked rows (stream, token)."""
    G = 32
    coefs = [jnp.full((G, 1), float(j + 1)) * jnp.arange(1, G + 1)[:, None] for j in range(4)]
    L = np.asarray(mhc._diag_blocks(coefs, G, 256, start=0))
    assert L.shape == (G, 256) and np.count_nonzero(L) == 4 * G
    for j in range(4):
        np.testing.assert_array_equal(np.diag(L[:, G * j:G * (j + 1)]), (j + 1) * np.arange(1, G + 1))
    assert not L[:, 128:].any()
    shifted = np.asarray(mhc._diag_blocks(coefs[:1], G, 256, start=128))
    np.testing.assert_array_equal(np.diag(shifted[:, 128:160]), np.arange(1, G + 1))


def test_bf16_streams_keep_phis_float32(interpret):
    """``vec(X) phi`` under bf16 streams goes through three bf16 parts of phi
    in one lane tile: the product is the float32 one, not a bf16-rounded
    phi's (which reads 2^-9 relative)."""
    x, y, phi, alpha, bias = _case(128, 128, jnp.bfloat16, seed=3)
    parts = mhc._phi_lanes(phi, jnp.bfloat16).astype(jnp.float32)
    W = phi.shape[1]
    np.testing.assert_allclose(
        np.asarray(parts[:, :W] + parts[:, W:2 * W] + parts[:, 2 * W:3 * W]),
        np.asarray(phi), rtol=2e-7, atol=1e-9)
    exact = mhc._read_xla(x.astype(jnp.float32), phi, alpha, bias, HC)[1]
    for read in (mhc._read_xla, mhc._read_kernel_call):
        got = read(x, phi, alpha, bias, HC)[1]
        np.testing.assert_allclose(np.asarray(got.h_post), np.asarray(exact.h_post), atol=5e-6)
    rounded = mhc._read_xla(x.astype(jnp.float32), phi.astype(jnp.bfloat16).astype(jnp.float32),
                            alpha, bias, HC)[1]
    assert float(jnp.max(jnp.abs(rounded.h_post - exact.h_post))) > 1e-4


@pytest.mark.parametrize("shape", [
    (100, 128, jnp.bfloat16),       # not whole token tiles
    (128, 96, jnp.bfloat16),        # a stream narrower than the lanes
    (128, 128, jnp.float16),        # a dtype the kernels do not take
    (128, 65536, jnp.float32),      # an X tile past the VMEM limit
])
def test_supported_refuses(shape):
    T, C, dtype = shape
    assert not mhc.supported(T, HC, C, dtype)
    # seven streams: 3 x 63 columns do not fit one lane tile; three: their
    # groups do not fill an MXU tile of rows
    for n in (7, 3):
        assert not mhc.supported(128, mhc.HC(n, 20, 1e-6, (-30.0, 30.0), 1e-6), 128, jnp.bfloat16)


def test_supported_accepts_the_cell():
    assert mhc.supported(8192, HC, 3584, jnp.bfloat16)


def test_read_and_write_choose_once_a_trace_and_count_it(plane):
    x, y, phi, alpha, bias = _case(128, 128, jnp.float32)
    xb = x.reshape(2, 64, 4 * 128)
    u, maps = mhc.read(xb, phi, alpha, bias, HC)                    # the CPU: XLA's form
    out = mhc.write(xb, y.reshape(2, 64, 128), maps, HC)
    assert u.shape == (2, 64, 128) and out.shape == xb.shape
    assert plane.registry.get_count("harvest/mhc_xla_traces") == 1
    assert plane.registry.get_count("harvest/mhc_kernel_traces") == 0
    mhc.set_interpret(True)
    try:
        f = jax.jit(lambda x: mhc.read(x, phi, alpha, bias, HC))
        for _ in range(3):
            u1, maps1 = f(xb)
        out1 = mhc.write(xb, y.reshape(2, 64, 128), maps1, HC)
    finally:
        mhc.set_interpret(False)
    assert plane.registry.get_count("harvest/mhc_kernel_traces") == 1
    np.testing.assert_allclose(np.asarray(u1), np.asarray(u), atol=2e-5)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out), atol=2e-5)
    prims = [e.primitive.name for e in jax.make_jaxpr(
        lambda x: mhc.read(x, phi, alpha, bias, HC))(xb).eqns]
    assert "pallas_call" not in prims


def test_the_sinkhorn_loop_is_traced_once():
    """20 iterations are ONE loop equation the lowering unrolls, not 20
    copies of the body in every process's trace."""
    x, y, phi, alpha, bias = _case(16, 32, jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x: mhc._read_xla(x, phi, alpha, bias, HC))(x)
    loops = [e for e in jaxpr.eqns if e.primitive.name in ("scan", "while")]
    assert len(loops) == 1
    assert sum(e.primitive.name == "div" for e in jaxpr.eqns) < 8


def test_head_read_is_the_learned_read():
    x, *_ = _case(8, 32, jnp.float32)
    phi = jax.random.normal(jax.random.key(9), (4 * 32, 4)) * (4 * 32) ** -0.5
    got = mhc.head_read(x, phi, jnp.asarray([1.5]), jnp.asarray([0.1, 0.0, -0.1, 0.2]), 1e-6)
    xs = np.asarray(x, np.float64).reshape(8, 4, 32)
    for t in range(8):
        v = xs[t].reshape(-1)
        z = (v @ np.asarray(phi, np.float64)) / np.sqrt(np.mean(v * v) + 1e-6)
        h = 1 / (1 + np.exp(-(1.5 * z + np.asarray([0.1, 0.0, -0.1, 0.2]))))
        np.testing.assert_allclose(np.asarray(got[t]), h @ xs[t], atol=2e-5)
