"""Xing4.0-29B-A4B's planted faults (a file of their own beside
tests/test_xing.py, so that the test workers share the load): each thing the
published config has no key for, planted in the PROGRAM's configuration, tree
or functions while the plain reference keeps the true model, at a small size
on the CPU in float32. ``scripts/probes/_xing_faults.py`` plants the same at
the cell's widths on the chip, where the comparison that decides ``correct``
(``benchmarks/arch/xing.py`` ``HARVEST_RTOL``) has to fail them."""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_xing import CONFIG, HOOK, RTOL, SEQ, TINY, _rel    # noqa: F401 — shared fixtures

from benchmarks.arch import xing
from benchmarks.reference import xing_ref
from crosscoder_tpu.models import lm
from crosscoder_tpu.ops import mhc, moe


def _without(params: dict, *leaves: str) -> dict:
    stacks = [{k: v for k, v in s.items() if k not in leaves} for s in params["layers"]]
    return {**params, "layers": tuple(stacks)}


def _with(params: dict, fn) -> dict:
    return {**params, "layers": tuple({k: fn(k, v) for k, v in s.items()}
                                      for s in params["layers"])}


def _rows_first(rows, hc, unroll=False):
    def body(_, rows):
        rows = [r / (jnp.sum(r, axis=0, keepdims=True) + hc.eps) for r in rows]
        col = sum(rows) + hc.eps
        return [r / col for r in rows]

    return list(jax.lax.fori_loop(0, hc.iters, body, list(rows)))


def _sigma_not_two_sigma(real):
    def maps_t(pre_t, hc, unroll=False):
        h_post, rows = real(pre_t, hc, unroll)
        return 0.5 * h_post, rows
    return maps_t


def _normed_before_the_read(real):
    def read(resid, lp, cfg, site):
        normed = [s.astype(jnp.float32) for s in mhc.streams_of(resid, cfg.n_streams)]
        normed = [s * jax.lax.rsqrt(jnp.mean(s * s, axis=-1, keepdims=True) + cfg.rms_eps)
                  for s in normed]
        # (the maps still see the streams; the read takes them normed one by one)
        _, maps = real(resid, lp, cfg, site)
        u, _ = real(jnp.concatenate(normed, axis=-1).astype(resid.dtype), lp, cfg, site)
        return u, maps
    return read


def _key_per_head(real):
    def attend(parts, cfg, kind, attend=None):
        q_nope, q_rope, k_nope, k_rope, v = parts
        H = q_rope.shape[2]
        # head h rotates against a key of its own (the shared one, rolled h dims)
        q_rope = jnp.stack([jnp.roll(q_rope[:, :, h], -h, axis=-1) for h in range(H)], axis=2)
        return real((q_nope, q_rope, k_nope, k_rope, v), cfg, kind, attend)
    return attend


def _no_latent_norms(real, ranks):
    def norm(x, w, cfg):
        return x if w.shape[-1] in ranks and x.shape[-1] in ranks else real(x, w, cfg)
    return norm


def _bias_in_the_gates(x, w_router, top_k, norm_topk_prob, routed_scale=1.0,
                       kind="softmax", bias=None):
    logits = jnp.einsum("td,de->te", x, w_router, preferred_element_type=jnp.float32)
    gates, idx = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, top_k)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), gates * routed_scale


def plant(fault: str, cfg: lm.LMConfig, params: dict):
    """The program's configuration and tree with one fault planted, and the
    program functions to swap while it runs (the reference keeps the true
    ones). ``scripts/probes/_xing_faults.py`` plants the same at the cell's
    widths on the chip."""
    rope = cfg.rope_of(lm.FULL)
    patches: list = []
    if fault == "float8_weights":
        params = jax.tree.map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype) if x.ndim > 2 else x, params)
    elif fault == "maps_input_dropped":
        params = _with(params, lambda k, v: jnp.zeros_like(v) if k.endswith("_phi") else v)
    elif fault == "bias_dropped":
        params = _with(params, lambda k, v: jnp.zeros_like(v) if k == "router_bias" else v)
    elif fault == "shared_expert_dropped":
        params = _without(params, "ws_gate", "ws_up", "ws_down")
    elif fault == "one_sinkhorn_iteration":
        cfg = cfg.replace(hc_sinkhorn_iters=1)
    elif fault == "m2_dropped":
        cfg = cfg.replace(query_pre_attn_scalar=float(cfg.head_dim))
    elif fault == "yarn_dropped":
        cfg = cfg.replace(rope=((lm.FULL, dataclasses.replace(rope, yarn_factor=0.0)),))
    elif fault == "softmax_for_sigmoid":
        cfg = cfg.replace(router="softmax")
    elif fault == "routed_scale_one":
        cfg = cfg.replace(routed_scale=1.0)
    elif fault == "one_expert_fewer":
        cfg = cfg.replace(experts_per_tok=cfg.experts_per_tok - 1)
    elif fault == "another_ranks_experts":
        cfg = cfg.replace(expert_rank=1)
    elif fault == "rows_before_columns":
        patches = [(mhc, "_sinkhorn", _rows_first)]
    elif fault == "two_sigma_as_sigma":
        patches = [(mhc, "_maps_t", _sigma_not_two_sigma(mhc._maps_t))]
    elif fault == "norm_before_the_read":
        patches = [(lm, "_read", _normed_before_the_read(lm._read))]
    elif fault == "streams_summed_at_the_hook":
        real_mean = lm._stream_mean
        patches = [(lm, "_stream_mean",
                    lambda r, c: (c.n_streams * real_mean(r, c)).astype(r.dtype))]
    elif fault == "rotary_key_per_head":
        patches = [(lm, "_latent_attend", _key_per_head(lm._latent_attend))]
    elif fault == "latent_norms_dropped":
        patches = [(lm, "_norm", _no_latent_norms(lm._norm, (cfg.q_lora_rank, cfg.kv_lora_rank)))]
    elif fault == "bias_in_the_gates":
        patches = [(moe, "route", _bias_in_the_gates)]
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


FAULTS = ["none", "maps_input_dropped", "one_sinkhorn_iteration", "rows_before_columns",
          "two_sigma_as_sigma", "norm_before_the_read", "streams_summed_at_the_hook",
          "rotary_key_per_head", "m2_dropped", "yarn_dropped", "latent_norms_dropped",
          "softmax_for_sigmoid", "bias_in_the_gates", "bias_dropped", "routed_scale_one",
          "one_expert_fewer", "shared_expert_dropped", "another_ranks_experts",
          "float8_weights"]
# Held by the CPU alone, in float32 where nothing else moves the number
# (PERF.md §6 has the chip's readings): after 20 iterations columns-then-rows
# and rows-then-columns differ by Sinkhorn's remainder, far under the bf16
# program's own reading — at ONE iteration the order shows, which is how the
# test below holds it; the fixture's choice bias is small (it must not
# unbalance the load), so using it in the gates as well moves them by its
# own size, a hundredth; and norming the streams one by one before the read
# reads 0.080 on the chip, between the program's 0.062 and the limit.
CPU_ONLY = {"rows_before_columns", "norm_before_the_read", "bias_in_the_gates"}


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_fails_the_chips_comparison(fault):
    """128 tokens through four tiny blocks (one dense, three sparse), YaRN's
    original context cut to 16 so that its ramp lies inside 128 positions."""
    rope = ((lm.FULL, lm.Rope(theta=100.0, yarn_factor=64.0, original_max_position=16)),)
    cfg = xing.lm_config(CONFIG, {**TINY, "n_layers": 4, "head_dim": 16})
    cfg = cfg.replace(rope=rope, rope_theta=100.0)
    params = lm.init_params(jax.random.key(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, cfg.vocab_size, size=(4, 128)))
    hook = "blocks.4.hook_resid_pre"
    want = xing_ref.resid_pre(params, tokens, cfg, 4)
    if fault == "rows_before_columns":      # at one iteration the order of the halves shows
        cfg = cfg.replace(hc_sinkhorn_iters=1)
        want = xing_ref.resid_pre(params, tokens, cfg, 4)
    bad_cfg, bad_params, patches = plant(fault, cfg, params)
    with planted(patches):
        got = lm.run_with_cache_multi([bad_params], tokens, bad_cfg, (hook,))[:, :, 0]
    err = _rel(got, want)
    if fault == "none":
        assert err < RTOL < xing.HARVEST_RTOL
    elif fault in CPU_ONLY:
        assert err > 10 * RTOL, (fault, err)
    else:
        # here, in float32 at a tiny size, a thousand round-offs and more; at
        # the cell's widths on the chip each of these reads over HARVEST_RTOL
        # (0.122 … 3.0 against 0.1: benchmarks/arch/xing.py has the table)
        assert err > 1000 * RTOL, (fault, err)


def test_the_clamp_comes_before_the_exp():
    """Planted logits over 30: unclamped, exp overflows float32 past 88 and
    the matrix is NaN; clamped to the published [-30, 30] it is finite and the
    reference's."""
    cfg = xing.lm_config(CONFIG, TINY)
    params = lm.init_params(jax.random.key(0), cfg)
    params = _with(params, lambda k, v: v.at[..., 8:].add(100.0 * jnp.eye(4).reshape(-1))
                   if k.endswith("_bias") and k.startswith("hc_") else v)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 257, size=(2, SEQ)))
    got = lm.run_with_cache_multi([params], tokens, cfg, (HOOK,))[:, :, 0]
    assert _rel(got, xing_ref.resid_pre(params, tokens, cfg, 3)) < RTOL
    wide = cfg.replace(hc_clamp=(-1e9, 1e9))
    bad = lm.run_with_cache_multi([params], tokens, wide, (HOOK,))[:, :, 0]
    assert not np.isfinite(np.asarray(bad)).all()


