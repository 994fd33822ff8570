"""The sparse-expert layer (crosscoder_tpu/ops/moe.py) alone: both forms of
the grouped product against the plain reference's loop over experts
(benchmarks/reference/mellum_ref.py, which shares no code with it), under
even and heavily skewed routing; the router's choice; the load gauge."""

import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.reference import mellum_ref   # noqa: E402
from crosscoder_tpu import obs                 # noqa: E402
from crosscoder_tpu.config import CrossCoderConfig   # noqa: E402
from crosscoder_tpu.ops import moe             # noqa: E402

L, E, D, F, K = 2, 8, 128, 128, 3


@pytest.fixture
def interpret():
    moe.set_interpret(True)
    yield
    moe.set_interpret(False)


def _layer(seed, skew: bool):
    """Float32 weights of L stacked expert layers; ``skew`` biases the
    router so that expert 5 takes nearly every token's first slot and
    experts 0 and 1 take none."""
    ks = jax.random.split(jax.random.key(seed), 4)
    w_router = jax.random.normal(ks[0], (D, E)) * D ** -0.5
    if skew:
        w_router = w_router.at[:, 5].multiply(0.0).at[0, 5].set(12.0)
        w_router = w_router.at[:, :2].multiply(0.0).at[0, :2].set(-12.0)
    return (w_router,
            jax.random.normal(ks[1], (L, E, D, 2 * F)) * D ** -0.5,
            jax.random.normal(ks[2], (L, E, F, D)) * F ** -0.5)


def _x(seed, tokens, skew):
    x = jax.random.normal(jax.random.key(100 + seed), (1, tokens, D))
    # the skewed router reads channel 0: keep it positive for most tokens
    return x.at[:, :, 0].set(jnp.abs(x[:, :, 0]) + 0.5) if skew else x


def _reference(x, w_router, w_gate_up, w_down, layer):
    cfg = types.SimpleNamespace(d_expert=F, n_experts=E, experts_per_tok=K,
                                norm_topk_prob=True)
    with jax.default_matmul_precision("highest"):
        return mellum_ref.moe(x, {"router": w_router},
                              {"we_gate_up": w_gate_up, "we_down": w_down}, layer, cfg)


# Float32 operands on the CPU: the grouped forms and the plain loop compute
# the same products and differ by the order of float32 sums (ragged_dot and
# the kernels' dots against ``@``; the combine's k terms against the loop's E
# terms, of which E - k are exact zeros): a few roundings on outputs of
# magnitude 1-3, seen at 1.2e-6. 1e-5 leaves that room; a row sent to the
# wrong expert, a dropped row or a wrong gate moves an output by O(0.1).
ATOL = 1e-5


@pytest.mark.parametrize("tokens", [300, 64], ids=["300tok", "64tok"])
@pytest.mark.parametrize("skew", [False, True], ids=["even", "skewed"])
@pytest.mark.parametrize("form", ["ragged", "tiles"])
def test_expert_layer_matches_the_plain_loop(form, skew, tokens, request):
    if form == "tiles":
        request.getfixturevalue("interpret")
    w_router, w_gate_up, w_down = _layer(7, skew)
    x = _x(7, tokens, skew)
    idx, _ = moe.route(x[0], w_router, K, True)
    sizes = np.bincount(np.asarray(idx).reshape(-1), minlength=E)
    if skew:    # one expert takes most rows, some take none
        assert sizes[5] >= 0.9 * tokens and sizes[:2].sum() == 0 and sizes.max() > 2 * sizes.mean()
    else:
        assert sizes.min() > 0
    assert (moe.enabled() and moe.supported(D, F, x.dtype)) == (form == "tiles")
    for layer in (0, 1):
        got = moe.moe_mlp(x, w_router, w_gate_up, w_down, jnp.int32(layer),
                          top_k=K, norm_topk_prob=True)
        want = _reference(x, w_router, w_gate_up, w_down, layer)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=ATOL)


def test_the_routers_choice_is_the_references_exactly():
    w_router, _, _ = _layer(3, False)
    x = _x(3, 512, False)[0]
    idx, gates = moe.route(x, w_router, K, True)
    with jax.default_matmul_precision("highest"):
        chosen, want = mellum_ref.routing(x, w_router, K, True)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)
    # ties go to the lowest index: a router of equal columns picks 0..K-1
    idx, gates = moe.route(x, jnp.ones((D, E)), K, False)
    assert (np.asarray(idx) == np.arange(K)).all()
    np.testing.assert_allclose(np.asarray(gates), 1.0 / E, rtol=1e-6)


def test_the_router_stays_float32_under_a_bf16_model():
    w_router, _, _ = _layer(4, False)
    x = _x(4, 64, False)[0].astype(jnp.bfloat16)
    idx, gates = moe.route(x, w_router.astype(jnp.bfloat16), K, True)
    assert gates.dtype == jnp.float32 and idx.dtype == jnp.int32


def test_which_form_ran_is_counted_once_per_trace(tmp_path, interpret):
    cfg = CrossCoderConfig(obs="on", obs_dir=str(tmp_path / "obs"), log_backend="null")
    plane = obs.acquire(cfg)
    try:
        w_router, w_gate_up, w_down = _layer(5, False)
        f = jax.jit(lambda x: moe.moe_mlp(x, w_router, w_gate_up, w_down, 0,
                                          top_k=K, norm_topk_prob=True))
        for _ in range(3):
            f(_x(5, 64, False))
        moe.set_interpret(False)
        moe.moe_mlp(_x(5, 64, False), w_router, w_gate_up, w_down, 0,
                    top_k=K, norm_topk_prob=True)
        assert plane.registry.get_count("harvest/moe_tiles_traces") == 1
        assert plane.registry.get_count("harvest/moe_ragged_traces") == 1
    finally:
        plane.close()


def test_load_max_over_mean():
    assert moe.load_max_over_mean(np.full((4, E), 7)) == 1.0
    one_hot = np.zeros((2, E)); one_hot[:, 3] = 10
    assert moe.load_max_over_mean(one_hot) == float(E)
    assert moe.load_max_over_mean([[1, 1, 2, 0], [1, 1, 1, 1]]) == 2.0
