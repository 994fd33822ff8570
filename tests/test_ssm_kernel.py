"""The state-space mixer's scan kernel (crosscoder_tpu/ops/ssm.py,
``pallas:ssd_scan``) through the Pallas interpreter, at small sizes on the
CPU: against XLA's form of ``ssd_scan`` and against the float32 token-by-token
recurrence (benchmarks/reference/falcon_h1_ref.py), the choice between the two
forms and its counters, the mixer around it, and a planted fault (a cumulative
sum rounded to bf16) that the comparison must catch; the kernel with the
causal conv inside it against XLA's ``causal_conv`` then scan, and two planted
faults of the conv (a previous chunk not carried, taps in the wrong order)."""

import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.reference import falcon_h1_ref as ref   # noqa: E402
from crosscoder_tpu import obs                           # noqa: E402
from crosscoder_tpu.ops import ssm                       # noqa: E402

# float32 inputs, the same mathematics in another order (the kernel's sums
# are products with a triangle of ones; the recurrence is token by token):
# seen at 4e-7 … 1.2e-6 relative; a bf16 rounding of the products reads 1e-3,
# one of the cumulative sum's terms 1.3e-4
RTOL = 1e-5
P = N = Q = 128                     # whole lanes: the smallest shape the kernel takes


@pytest.fixture
def interpret():
    ssm.set_interpret(True)
    yield
    ssm.set_interpret(False)


@pytest.fixture
def plane(tmp_path):
    p = obs.acquire(types.SimpleNamespace(
        obs="on", obs_dir=str(tmp_path / "obs"), checkpoint_dir=str(tmp_path)))
    yield p
    p.close()


def _rel(got, want) -> float:
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _inputs(S, bt, H, G, A, dt_mean, seed=0, quiet_after=None, p=P):
    """x ``[bt, S, H, p]`` (zero from ``quiet_after`` on: what follows is the
    carried state's alone), dt ≈ ``dt_mean`` after a softplus, A as given,
    B and C ``[bt, S, G, N]``, D ``[H]``; float32."""
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)    # noqa: E731
    x = f(bt, S, H, p)
    if quiet_after is not None:
        x = x.at[:, quiet_after:].set(0.0)
    dt = dt_mean / np.log(2.0) * jax.nn.softplus(f(bt, S, H))
    return x, dt, jnp.asarray(A, jnp.float32), f(bt, S, G, N), f(bt, S, G, N), f(H)


def _recurrence(x, dt, A, B, C, D):
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref.recurrence(x[b], dt[b], A, B[b], C[b], D)
                          for b in range(x.shape[0])])


CASES = {
    # S, batch, H, G, A, mean dt
    "one-group": (256, 1, 2, 1, [-1.0, -2.0], 0.1),
    "two-groups-two-rows": (256, 2, 4, 2, [-1.0, -2.0, -3.0, -4.0], 0.1),
    "off-the-chunk": (300, 2, 4, 2, [-1.0, -2.0, -3.0, -4.0], 0.1),   # padded and cut
    "four-groups-one-chunk": (128, 1, 4, 4, [-0.5, -1.0, -2.0, -4.0], 0.1),
}


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_against_xlas_form_and_the_recurrence(interpret, case):
    S, bt, H, G, A, dt_mean = CASES[case]
    x, dt, A, B, C, D = _inputs(S, bt, H, G, A, dt_mean, seed=S + H + G)
    assert ssm.supported(H, P, G, N, Q, x.dtype)
    got = ssm._ssd_scan_kernel(x, dt, A, B, C, D, Q)
    assert got.shape == x.shape and got.dtype == jnp.float32
    assert _rel(got, ssm._ssd_scan_xla(x, dt, A, B, C, D, Q)) < RTOL
    assert _rel(got, _recurrence(x, dt, A, B, C, D)) < RTOL


def test_a_state_carried_over_chunks_and_a_decay_to_minus_400(interpret):
    """Four chunks, x zero after the first: every later output is the carried
    state's (two slow heads keep it over all four); two fast heads' chunk
    sums reach -277 and -568, so L's exponents span the float32 exp's range."""
    x, dt, A, B, C, D = _inputs(512, 1, 4, 2, [-0.01, -0.05, -3.0, -6.0], 0.6, quiet_after=128)
    sums = jnp.sum((dt * A)[0, :Q], axis=0)
    assert float(sums[2]) < -200 and float(sums[3]) < -400
    want = _recurrence(x, dt, A, B, C, D)
    assert float(jnp.linalg.norm(want[:, 384:, :2])) > 1.0       # the state reached chunk 4
    got = ssm._ssd_scan_kernel(x, dt, A, B, C, D, Q)
    assert _rel(got, want) < RTOL
    assert _rel(got[:, 128:], want[:, 128:]) < RTOL
    assert _rel(got, ssm._ssd_scan_xla(x, dt, A, B, C, D, Q)) < RTOL


def test_bf16_products_stay_by_xlas_form(interpret):
    """bf16 inputs: the two forms round the same products' inputs, so they
    agree far inside one bf16 rounding of the output (2^-9), which both are
    from the float32 recurrence."""
    x, dt, A, B, C, D = _inputs(300, 2, 4, 2, [-1.0, -2.0, -3.0, -4.0], 0.1, seed=3)
    xb, Bb, Cb = (a.astype(jnp.bfloat16) for a in (x, B, C))
    got = ssm._ssd_scan_kernel(xb, dt, A, Bb, Cb, D, Q)
    assert _rel(got, ssm._ssd_scan_xla(xb, dt, A, Bb, Cb, D, Q)) < 1e-4
    want = _recurrence(*(a.astype(jnp.float32) for a in (xb, dt, A, Bb, Cb, D)))
    assert 1e-4 < _rel(got, want) < 1e-2


@pytest.mark.parametrize("fault", ["terms", "sums"])
def test_a_bf16_cumulative_sum_fails_the_comparison(interpret, monkeypatch, fault):
    """The cumulative decay rounded to bf16 — its terms (what a product at
    default precision does to ``dt·A``) or the sums themselves — leaves the
    kernel off XLA's form by far more than the comparison's limit."""
    x, dt, A, B, C, D = _inputs(512, 1, 4, 2, [-0.01, -0.05, -3.0, -6.0], 0.6, quiet_after=128)
    want = ssm._ssd_scan_xla(x, dt, A, B, C, D, Q)
    assert _rel(ssm._ssd_scan_kernel(x, dt, A, B, C, D, Q), want) < RTOL
    real = ssm._cumsum
    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)   # noqa: E731
    monkeypatch.setattr(ssm, "_cumsum", (lambda a: real(bf(a))) if fault == "terms"
                        else (lambda a: bf(real(a))))
    assert _rel(ssm._ssd_scan_kernel(x, dt, A, B, C, D, Q), want) > 10 * RTOL


def test_resets_take_xlas_form_and_each_choice_is_counted(interpret, plane):
    x, dt, A, B, C, D = _inputs(256, 2, 4, 2, [-1.0, -2.0, -3.0, -4.0], 0.1)
    resets = jnp.zeros((2, 256), bool).at[:, 0].set(True).at[0, 77].set(True)
    prims = lambda *a: [e.primitive.name for e in jax.make_jaxpr(       # noqa: E731
        lambda *b: ssm.ssd_scan(*b, Q, *a))(x, dt, A, B, C, D).eqns]
    assert "pallas_call" not in prims(resets)
    assert plane.registry.get_count("harvest/ssm_scan_xla_traces") == 1
    assert plane.registry.get_count("harvest/ssm_scan_kernel_traces") == 0
    assert "pallas_call" in prims()
    assert plane.registry.get_count("harvest/ssm_scan_kernel_traces") == 1
    assert plane.registry.get_count("harvest/ssm_scan_xla_traces") == 1
    # a start's masks are what XLA's form exists for: with one it is not the kernel's result
    with_starts = ssm.ssd_scan(x, dt, A, B, C, D, Q, resets)
    assert _rel(with_starts, ssm._ssd_scan_xla(x, dt, A, B, C, D, Q, resets)) == 0.0
    assert _rel(ssm.ssd_scan(x, dt, A, B, C, D, Q), with_starts) > 1e-2


def test_off_the_chip_and_at_unsupported_shapes_xlas_form_runs(plane):
    x, dt, A, B, C, D = _inputs(256, 1, 2, 1, [-1.0, -2.0], 0.1)
    assert not ssm.enabled()                                    # the CPU, no interpreter
    ssm.ssd_scan(x, dt, A, B, C, D, Q)
    ssm.set_interpret(True)
    try:
        ssm.ssd_scan(x, dt, A, B, C, D, 64)                     # a chunk of half a lane tile
    finally:
        ssm.set_interpret(False)
    assert plane.registry.get_count("harvest/ssm_scan_xla_traces") == 2
    assert plane.registry.get_count("harvest/ssm_scan_kernel_traces") == 0


@pytest.mark.parametrize("shape", [
    (16, 40, 2, 128, 128, jnp.bfloat16),       # a head not a whole bf16 sublane tile
    (4, 128, 2, 96, 128, jnp.bfloat16),        # a state narrower than the lanes
    (4, 128, 2, 128, 64, jnp.bfloat16),        # a chunk of half a lane tile
    (6, 128, 4, 128, 128, jnp.bfloat16),       # heads that do not split into the groups
    (3, 128, 1, 256, 128, jnp.bfloat16),       # x's width not a whole number of B's bands
    (4, 128, 2, 128, 128, jnp.float16),        # a dtype the kernel does not take
    (256, 128, 2, 256, 128, jnp.bfloat16),     # a group's band past the VMEM limit
    (4, 128, 2, 128, 128, jnp.bfloat16, 129),  # a conv that reaches back past a chunk
    (80, 128, 2, 256, 128, jnp.bfloat16, 4),   # the scan fits, not with the conv's VMEM
])
def test_supported_refuses(shape):
    assert not ssm.supported(*shape)


def test_supported_accepts_the_cell():
    """Falcon-H1-34B's mixer (32 heads of 128 in 2 groups, a state of 256,
    chunks of 128) within the stated limit, with room."""
    assert ssm.supported(32, 128, 2, 256, 128, jnp.bfloat16)
    assert ssm._vmem_bytes(32, 128, 2, 256, 128, 2) < ssm._VMEM_LIMIT_BYTES / 2


def test_supported_accepts_both_mixer_cells_with_the_conv():
    """Falcon-H1-34B's and Nemotron-H's mixers with their four-tap conv in
    the kernel, within the stated limit, with room."""
    assert ssm.supported(32, 128, 2, 256, 128, jnp.bfloat16, 4)
    assert ssm.supported(64, 64, 8, 128, 128, jnp.bfloat16, 4)
    assert ssm._vmem_bytes(32, 128, 2, 256, 128, 2, 4) < 0.6 * ssm._VMEM_LIMIT_BYTES
    assert ssm._vmem_bytes(64, 64, 8, 128, 128, 2, 4) < ssm._VMEM_LIMIT_BYTES / 4
    # where only the scan fits, the mixer runs XLA's conv and then the scan's kernel
    assert ssm.supported(80, 128, 2, 256, 128, jnp.bfloat16)


def test_supported_accepts_nemotron_hs_heads_of_64():
    """Nemotron-H's mixer (64 heads of 64 in 8 groups, a state of 128, chunks
    of 128): a head is a band of 64 rows, four bf16 sublane tiles; P 64 and
    P 40 in float32 (five tiles of 8 rows) too."""
    assert ssm.supported(64, 64, 8, 128, 128, jnp.bfloat16)
    assert ssm.supported(16, 40, 2, 128, 128, jnp.float32)
    assert ssm._vmem_bytes(64, 64, 8, 128, 128, 2) < ssm._VMEM_LIMIT_BYTES / 8


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernel_at_head_dim_64_and_eight_heads_a_group(interpret, dtype):
    """P 64, two groups of eight heads (Nemotron-H's eight a group), a
    length off the chunk: against XLA's form (float32: to rounding; bf16
    inputs: the same products' inputs rounded alike) and the recurrence."""
    A = -np.arange(1, 17, dtype=np.float32) / 4
    x, dt, A, B, C, D = _inputs(300, 2, 16, 2, A, 0.1, seed=64, p=64)
    assert ssm.supported(16, 64, 2, N, Q, dtype)
    x, B, C = (a.astype(dtype) for a in (x, B, C))
    got = ssm._ssd_scan_kernel(x, dt, A, B, C, D, Q)
    assert got.shape == x.shape and got.dtype == jnp.float32
    want = ssm._ssd_scan_xla(x, dt, A, B, C, D, Q)
    assert _rel(got, want) < (RTOL if dtype == jnp.float32 else 1e-4)
    if dtype == jnp.float32:
        assert _rel(got, _recurrence(x, dt, A, B, C, D)) < RTOL


def _mixer_layer(arch: str, config: str, **sizes):
    """One mixer layer's leaves of a float32 model of ``arch`` at small sizes,
    its mixer at ``sizes``, and a normed input ``[2, 200, d]``."""
    import importlib

    from benchmarks import manifest
    from crosscoder_tpu.models import lm

    config = manifest.load_json(manifest.BENCH_DIR / "configs" / config)
    cfg = importlib.import_module(f"benchmarks.arch.{arch}").lm_config(
        config, dict(vocab_size=257, d_model=64, n_layers=1, n_heads=4, n_kv_heads=2,
                     head_dim=16, d_ff=128, dtype="fp32"))
    cfg = cfg.replace(**sizes)
    lp = {k: v[0] for k, v in lm.init_params(jax.random.key(0), cfg)["layers"].items()}
    return cfg, lp, jax.random.normal(jax.random.key(1), (2, 200, cfg.d_model))


def _the_mixer_in_both_forms(cfg, lp, u) -> float:
    from crosscoder_tpu.models import lm

    got = lm._ssm(u, lp, cfg)
    ssm.set_interpret(False)
    want = lm._ssm(u, lp, cfg)
    return _rel(got, want)


def test_the_mixer_with_the_kernel_is_the_mixer_with_xlas_form(interpret):
    """``lm._ssm`` on a normed input at lane-wide sizes, float32: the kernel
    reads x, B and C as bands of the projection, computes the conv and hands
    y to the gated norm, so the mixer's result is XLA's form's to float32
    rounding."""
    cfg, lp, u = _mixer_layer("falcon_h1", "falcon-h1-34b-pair-relu16k.json", ssm_heads=4,
                              ssm_head_dim=P, ssm_state=N, ssm_groups=2, ssm_chunk=Q)
    assert _the_mixer_in_both_forms(cfg, lp, u) < RTOL


def test_the_nemotron_mixer_with_the_kernel_is_the_mixer_with_xlas_form(interpret):
    """The same at Nemotron-H's head dim 64, eight heads a group."""
    cfg, lp, u = _mixer_layer("nemotron_h", "nemotron-twotower-30b-a3b-pair-relu16k.json",
                              ssm_heads=16, ssm_head_dim=64, ssm_state=N, ssm_groups=2,
                              ssm_chunk=Q)
    assert _the_mixer_in_both_forms(cfg, lp, u) < RTOL


# ---------------------------------------------------------------------------
# the causal conv inside the kernel

CONV_CASES = {
    # S, batch, H, head dim, G: Nemotron-H's 64 with eight heads a group,
    # Falcon-H1's 128; several chunks (the previous chunk's tokens reach the
    # next one's first taps) and lengths off the chunk (padded and cut)
    "nemotron-five-chunks": (640, 2, 16, 64, 2),
    "nemotron-off-the-chunk": (300, 1, 16, 64, 2),
    "falconh1-three-chunks": (384, 1, 4, 128, 2),
    "falconh1-off-the-chunk": (200, 2, 4, 128, 2),
}


def _projection(S, bt, H, p, G, dtype, seed=0):
    """The mixer's projection as it lies, z | x | B | C | dt ``[bt, S, 2·di +
    2·G·N + H]`` in ``dtype`` (x | B | C before the conv), the conv's four
    taps and bias, dt after a softplus, A and D."""
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)    # noqa: E731
    di, cd = H * p, H * p + 2 * G * N
    v = f(bt, S, di + cd + H).astype(dtype)
    dt = 0.1 / np.log(2.0) * jax.nn.softplus(f(bt, S, H))
    A = -jnp.arange(1, H + 1, dtype=jnp.float32) / 4
    return v, 0.5 * f(4, cd), 0.1 * f(cd), dt, A, f(H)


def _xlas_conv_then_scan(v, w, b, dt, A, D, G):
    bt, S = v.shape[:2]
    H, di = A.shape[0], w.shape[1] - 2 * G * N
    xbc = ssm.causal_conv(v[..., di: di + w.shape[1]], w, b)
    return ssm._ssd_scan_xla(xbc[..., :di].reshape(bt, S, H, di // H), dt, A,
                             xbc[..., di: di + G * N].reshape(bt, S, G, N),
                             xbc[..., di + G * N:].reshape(bt, S, G, N), D, Q)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", CONV_CASES)
def test_the_kernel_with_the_conv_against_xlas_conv_then_scan(interpret, case, dtype):
    """The conv's taps, SiLU and rounding in the kernel are ``causal_conv``'s
    (bf16: the same bf16 conv output, so the two forms agree as far as the
    scan alone does), over the whole length and over the first chunk, whose
    first taps read the causal zeros."""
    S, bt, H, p, G = CONV_CASES[case]
    v, w, b, dt, A, D = _projection(S, bt, H, p, G, dtype, seed=S + p)
    assert ssm.supported(H, p, G, N, Q, dtype, w.shape[0])
    got = ssm.conv_ssd_scan(v, w, b, dt, A, D, G, N, Q)
    assert got.shape == (bt, S, H, p) and got.dtype == jnp.float32
    want = _xlas_conv_then_scan(v, w, b, dt, A, D, G)
    limit = RTOL if dtype == jnp.float32 else 1e-4
    assert _rel(got, want) < limit
    assert _rel(got[:, :Q], want[:, :Q]) < limit


@pytest.mark.parametrize("fault", ["previous_chunk_dropped", "taps_reversed"])
def test_a_conv_fault_fails_the_comparison(interpret, monkeypatch, fault):
    """The previous chunk's tokens not carried (every chunk's first taps read
    zeros), or the taps applied in the reverse order, leave the kernel off
    XLA's form by far more than the comparison's limit."""
    v, w, b, dt, A, D = _projection(384, 1, 16, 64, 2, jnp.float32, seed=5)
    want = _xlas_conv_then_scan(v, w, b, dt, A, D, 2)
    assert _rel(ssm.conv_ssd_scan(v, w, b, dt, A, D, 2, N, Q), want) < RTOL
    real = ssm._conv
    if fault == "previous_chunk_dropped":
        planted = lambda raw, prev, wb: real(raw, jnp.zeros_like(prev), wb)        # noqa: E731
    else:
        planted = lambda raw, prev, wb: real(                                     # noqa: E731
            raw, prev, [*wb[-2::-1], wb[-1]])
    monkeypatch.setattr(ssm, "_conv", planted)
    assert _rel(ssm.conv_ssd_scan(v, w, b, dt, A, D, 2, N, Q), want) > 10 * RTOL


def test_the_conv_in_the_kernel_is_counted_where_it_runs(interpret, plane):
    """``harvest/ssm_conv_kernel_traces`` once a trace of the mixer on the
    kernel's path, with its scan's count; none with document starts or on
    the CPU, where XLA's conv and scan run."""
    from crosscoder_tpu.models import lm

    cfg, lp, u = _mixer_layer("falcon_h1", "falcon-h1-34b-pair-relu16k.json", ssm_heads=4,
                              ssm_head_dim=P, ssm_state=N, ssm_groups=2, ssm_chunk=Q)
    reg = plane.registry
    counts = lambda: tuple(reg.get_count(f"harvest/ssm_{c}_traces")       # noqa: E731
                           for c in ("conv_kernel", "scan_kernel", "scan_xla"))
    prims = lambda *a: {e.primitive.name for e in jax.make_jaxpr(         # noqa: E731
        lambda u: lm._ssm(u, lp, cfg, *a))(u).eqns}
    assert "pallas_call" in prims() and counts() == (1, 1, 0)
    resets = jnp.zeros(u.shape[:2], bool).at[:, 0].set(True)
    assert "pallas_call" not in prims(resets) and counts() == (1, 1, 1)
    ssm.set_interpret(False)
    assert "pallas_call" not in prims() and counts() == (1, 1, 2)
