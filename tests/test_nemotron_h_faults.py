"""Nemotron-H's planted faults (a file of their own beside
tests/test_nemotron_h.py, so that the test workers share the load): each
thing the published config fixes or leaves unsaid, planted in the PROGRAM's
configuration, tree or functions while the plain reference keeps the true
model, at a small size on the CPU in float32. A fault must fail the hooked
residual or one sublayer's own output against the reference's on the same
normed input. ``scripts/probes/_nemotron_h_faults.py`` plants the same at the
cell's widths on the chip, where the comparison that decides ``correct``
(``benchmarks/arch/nemotron_h.py`` ``HARVEST_RTOL``) holds them."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_nemotron_h import CONFIG, RTOL, TINY, _rel    # noqa: F401 — shared fixtures

from benchmarks.arch import nemotron_h as nh
from benchmarks.reference import nemotron_h_ref as ref
from crosscoder_tpu.models import lm
from crosscoder_tpu.ops import moe, ssm


def _silu_gated(real):
    """A SiLU-gated expert (``silu(x W) ⊙ (x W)``) where relu² is published."""
    def experts(x, idx, gates, w_up, w_down, layer, share=False, gated=True):
        w = jnp.swapaxes(w_up, -1, -2)                 # [out, in] -> the gated [in, gate | up]
        return real(x, idx, gates, jnp.concatenate([w, w], -1), w_down, layer, share)
    return experts


def _bias_in_the_gates(real):
    """Choose by the unbiased scores, gate by the biased ones: the published
    rule the other way round."""
    def route(x, w_router, top_k, norm_topk_prob, routed_scale=1.0, kind="softmax", bias=None):
        if kind != "sigmoid_bias":
            return real(x, w_router, top_k, norm_topk_prob, routed_scale, kind, bias)
        scores = jax.nn.sigmoid(jnp.einsum("td,de->te", x, w_router,
                                           preferred_element_type=jnp.float32))
        _, idx = jax.lax.top_k(scores, top_k)
        gates = jnp.take_along_axis(scores + bias.astype(jnp.float32), idx, axis=-1)
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), gates * routed_scale
    return route


def _gate_after_the_norm(y, z, w, groups, eps):
    h = y.astype(jnp.float32).reshape(y.shape[:-1] + (groups, -1))
    h = (h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)).reshape(y.shape)
    return h * w * jax.nn.silu(z.astype(jnp.float32))


def _ungrouped(real):
    return lambda y, z, w, groups, eps: real(y, z, w, 1, eps)


def plant(fault: str, cfg, params: dict):
    """The program's configuration and tree with one fault planted, and the
    program functions to swap while it runs (the reference keeps the true
    model). ``d_ssm_from_expand`` reads the mixer's width as ``expand ×
    hidden``: the program's tree is then another shape, drawn from the same
    key as the true one."""
    patches: list = []
    if fault == "silu_gated_experts":
        patches = [(moe, "_experts_ragged", _silu_gated(moe._experts_ragged))]
    elif fault == "routed_scale_dropped":
        cfg = cfg.replace(routed_scale=1.0)
    elif fault == "bias_in_the_gates":
        patches = [(moe, "route", _bias_in_the_gates(moe.route))]
    elif fault == "rope_applied":
        cfg = cfg.replace(rope=((lm.FULL, lm.Rope(theta=float(CONFIG["rope_theta"]))),))
    elif fault == "norm_ungrouped":
        patches = [(ssm, "gated_rms_norm", _ungrouped(ssm.gated_rms_norm))]
    elif fault == "gate_after_the_norm":
        patches = [(ssm, "gated_rms_norm", _gate_after_the_norm)]
    elif fault == "d_ssm_from_expand":
        cfg = cfg.replace(ssm_head_dim=CONFIG["expand"] * cfg.d_model // cfg.ssm_heads)
        params = lm.init_params(jax.random.key(0), cfg)
    elif fault == "float8_weights":
        params = jax.tree.map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype) if x.ndim > 2 else x, params)
    elif fault != "none":
        raise KeyError(fault)
    return cfg, params, patches


@contextlib.contextmanager
def planted(patches):
    """Swap program functions for a faulty run. Jitted forwards are keyed by
    their arguments, not by what they call: the caches go before and after."""
    was = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
    if patches:
        jax.clear_caches()
    for mod, name, new in patches:
        setattr(mod, name, new)
    try:
        yield
    finally:
        for mod, name, old in was:
            setattr(mod, name, old)
        if patches:
            jax.clear_caches()


FAULTS = ["none", "silu_gated_experts", "routed_scale_dropped", "bias_in_the_gates",
          "rope_applied", "norm_ungrouped", "gate_after_the_norm", "d_ssm_from_expand",
          "float8_weights"]


def errors(cfg, params: dict, bad_cfg, bad_params: dict, tokens) -> dict:
    """Each comparison's relative error: the hooked residual entering layer
    9, and on one stream each kind of sublayer's own output behind its norm
    (the mixer of layer 0, the expert layer of layer 1, attention of layer 5), the
    program's (``bad_*``, the functions as they stand) against the
    reference's on the true model."""
    stacks = params["layers"]
    x = jax.random.normal(jax.random.key(5), (1, tokens.shape[1], cfg.d_model))
    out = {}
    for name, c, layer in (("mixer", 0, 0), ("experts", 1, 1), ("attention", 2, 5)):
        lp = {k: (v if k in lm._HELD_LEAVES else v[0]) for k, v in bad_params["layers"][c].items()}
        kind = lm._layer_kind(bad_cfg, jnp.int32(layer), lm.layer_classes(bad_cfg)[c],
                              jnp.int32(0))
        u = lm._norm(x, lp["pre_ffw_norm" if name == "experts" else "attn_norm"], bad_cfg)
        got = (lm._ssm(u, lp, bad_cfg) if name == "mixer" else lm._mlp(u, lp, bad_cfg, 0)
               if name == "experts" else lm._attention(u, lp, bad_cfg, kind))
        with jax.default_matmul_precision("highest"):
            want = ref.sublayer(x[0], layer, stacks, cfg) - x[0]
        out[name] = _rel(got[0], want)
    hooked = lm.run_with_cache_multi([bad_params], tokens, bad_cfg, ("blocks.9.hook_resid_pre",))
    out["hooked"] = _rel(hooked[:, :, 0], ref.resid_pre(params, tokens, cfg, 9))
    return out


@pytest.fixture(scope="module")
def case():
    cfg = nh.lm_config(CONFIG, TINY)
    params = lm.init_params(jax.random.key(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, cfg.vocab_size, size=(2, 64)))
    return cfg, params, tokens


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_fails_a_comparison(fault, case):
    cfg, params, tokens = case
    bad_cfg, bad_params, patches = plant(fault, cfg, params)
    with planted(patches):
        errs = errors(cfg, params, bad_cfg, bad_params, tokens)
    if fault == "none":
        assert max(errs.values()) < RTOL < nh.HARVEST_RTOL, errs
    else:
        # here, in float32 at a tiny size, a thousand round-offs and more
        # (PERF.md §6 has the chip's readings of the hooked residual)
        assert max(errs.values()) > 1000 * RTOL, (fault, errs)


def test_the_reference_in_float8_fails_the_limit(case):
    """The limit's upper side: the reference computed in the precision below
    the stated one (weights, each sublayer's input and output, the stream)
    reads over ``HARVEST_RTOL`` against itself in float32."""
    cfg, params, tokens = case
    want = ref.resid_pre(params, tokens, cfg, 9)
    low = ref.resid_pre(params, tokens, cfg, 9, act=jnp.float8_e4m3fn)
    assert _rel(low, want) > nh.HARVEST_RTOL
