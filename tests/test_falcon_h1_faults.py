"""Falcon-H1's planted faults (a file of their own beside
tests/test_falcon_h1.py, so that the test workers share the load): each
multiplier that is not 1 left out of the fold, and each part of the mixer the
published code fixes, planted in the PROGRAM's tree or functions while the
plain reference keeps the true model, at a small size on the CPU in float32.
A fault must fail at least one comparison at the limit the chip holds the
harvest to (``benchmarks/arch/falcon_h1.py`` ``HARVEST_RTOL``): the hooked
residual, or one mixer's own output against the reference's on the same
normed input. ``scripts/probes/_falcon_h1_faults.py`` plants the same at the
cell's widths on the chip."""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_falcon_h1 import CONFIG, HOOK, RTOL, TINY, _rel, checkpoint   # noqa: F401

from benchmarks.arch import falcon_h1 as fh
from benchmarks.reference import falcon_h1_ref as ref
from crosscoder_tpu.models import lm
from crosscoder_tpu.ops import ssm

# the twelve multipliers that are not 1 (attention_in is 1; the head's comes
# after every hook), as the Multipliers field and, for the five segments, its place
DROPPED = {"embedding": ("embedding", None), "key": ("key", None),
           "attention_out": ("attention_out", None), "ssm_in": ("ssm_in", None),
           **{f"ssm_{seg}": ("ssm", i) for i, seg in enumerate(("z", "x", "B", "C", "dt"))},
           "ssm_out": ("ssm_out", None), "mlp_gate": ("mlp", 0), "mlp_down": ("mlp", 1)}


def _refold(params: dict, cfg, as_cfg) -> dict:
    """The program's tree as the fold under ``as_cfg``'s multipliers would
    have made it from the same checkpoint: each leaf times its
    ``lm.multiplier`` under ``as_cfg`` over the one under ``cfg``, in
    float32, one leaf at a time (the chip's probe plants on the bf16 tree)."""
    def one(k, v):
        ratio = (np.asarray(lm.multiplier(k, as_cfg), np.float32)
                 / np.asarray(lm.multiplier(k, cfg), np.float32))
        return v if (ratio == 1).all() else (v.astype(jnp.float32) * ratio).astype(v.dtype)

    return {**params, "embed": one("embed", params["embed"]),
            "layers": {k: one(k, v) for k, v in params["layers"].items()}}


def _without_multiplier(cfg, name: str):
    field, at = DROPPED[name]
    value = 1.0
    if at is not None:
        value = tuple(1.0 if i == at else v for i, v in enumerate(getattr(cfg.mup, field)))
    return cfg.replace(mup=dataclasses.replace(cfg.mup, **{field: value}))


def _gate_after_the_norm(y, z, w, groups, eps):
    h = y.astype(jnp.float32).reshape(y.shape[:-1] + (groups, -1))
    h = (h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)).reshape(y.shape)
    return h * w * jax.nn.silu(z.astype(jnp.float32))


def _ungrouped(real):
    return lambda y, z, w, groups, eps: real(y, z, w, 1, eps)


def _not_causal(real):
    def conv(x, w, b, resets=None):        # every tap one token later: reads the next token
        return real(jnp.pad(x[:, 1:], ((0, 0), (0, 1), (0, 0))), w, b, resets)
    return conv


def _groups_tiled(real):
    """B and C of group ``h % G`` for head ``h`` (``tile``), where the model
    has ``h // (H / G)`` (``repeat_interleave``)."""
    def scan(x, dt, A, B, C, D, chunk, resets=None):
        H, G = x.shape[2], B.shape[2]
        perm = np.asarray([(h % G) * (H // G) + h // G for h in range(H)])
        order = np.argsort(perm)           # position p holds head order[p]
        y = real(x[:, :, order], dt[:, :, order], A[order], B, C, D[order], chunk, resets)
        return y[:, :, perm]
    return scan


def _float8_inputs(real):
    def scan(x, dt, A, B, C, D, chunk, resets=None):
        f8 = lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)    # noqa: E731
        return real(f8(x), f8(dt), A, f8(B), f8(C), D, chunk, resets)
    return scan


def _resets_ignored(real, n_args):
    return lambda *args: real(*args[:n_args])


def plant(fault: str, cfg, params: dict):
    """The program's configuration and tree with one fault planted, and the
    program functions to swap while it runs (the reference keeps the true
    model). ``scripts/probes/_falcon_h1_faults.py`` plants the same at the
    cell's widths on the chip."""
    patches: list = []
    if fault.startswith("dropped_"):
        params = _refold(params, cfg, _without_multiplier(cfg, fault[len("dropped_"):]))
    elif fault == "float8_weights":
        params = jax.tree.map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype) if x.ndim > 2 else x, params)
    elif fault == "gate_after_the_norm":
        patches = [(ssm, "gated_rms_norm", _gate_after_the_norm)]
    elif fault == "norm_ungrouped":
        patches = [(ssm, "gated_rms_norm", _ungrouped(ssm.gated_rms_norm))]
    elif fault in ("D_dropped", "dt_bias_dropped"):
        leaf = {"D_dropped": "ssm_D", "dt_bias_dropped": "ssm_dt_bias"}[fault]
        params = {**params, "layers": {**params["layers"],
                                       leaf: jnp.zeros_like(params["layers"][leaf])}}
    elif fault == "conv_not_causal":
        patches = [(ssm, "causal_conv", _not_causal(ssm.causal_conv))]
    elif fault == "groups_tiled":
        patches = [(ssm, "ssd_scan", _groups_tiled(ssm.ssd_scan))]
    elif fault == "float8_scan_inputs":
        patches = [(ssm, "ssd_scan", _float8_inputs(ssm.ssd_scan))]
    elif fault == "resets_ignored":
        patches = [(ssm, "ssd_scan", _resets_ignored(ssm.ssd_scan, 7)),
                   (ssm, "causal_conv", _resets_ignored(ssm.causal_conv, 3))]
    elif fault != "none":
        raise KeyError(fault)
    if any(name in ("causal_conv", "ssd_scan") for _, name, _ in patches):
        # where the scan's kernel runs it computes the conv itself: a fault
        # planted in XLA's conv or scan holds where XLA's form runs
        patches.append((ssm, "enabled", lambda: False))
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


FAULTS = ["none", *(f"dropped_{n}" for n in DROPPED), "gate_after_the_norm",
          "norm_ungrouped", "D_dropped", "conv_not_causal", "groups_tiled",
          "dt_bias_dropped", "float8_scan_inputs", "float8_weights"]


def errors(cfg, raw: dict, params: dict, tokens, n_layers: int) -> dict:
    """Each comparison's relative error: the hooked residual at ``n_layers``,
    and on layer 0's normed input the three parts of the layer, the
    program's (``params``, the functions as they stand) against the
    reference's on the checkpoint's values."""
    m, rp = cfg.mup, {k: v[0] for k, v in raw["layers"].items()}
    lp = {k: v[0] for k, v in params["layers"].items()}
    with jax.default_matmul_precision("highest"):
        h = raw["embed"][tokens[0]] * m.embedding
        u = ref.rms(h, rp["attn_norm"], cfg.rms_eps)
        want = {"attention": ref.attention(u * m.attention_in, rp, cfg) * m.attention_out,
                "mixer": ref.mamba(u, rp, cfg) * m.ssm_out, "mlp": ref.mlp(u, rp, cfg)}
        hooked = ref.resid_pre_of_checkpoint(raw, tokens, cfg, n_layers)
    un = u[None]
    got = {"attention": lm._attention(un, lp, cfg, lm._layer_kind(cfg, jnp.int32(0))),
           "mixer": lm._ssm(un, lp, cfg), "mlp": lm._mlp(un, lp, cfg, 0)}
    out = {k: _rel(got[k][0], want[k]) for k in want}
    hook = f"blocks.{n_layers}.hook_resid_pre"
    out["hooked"] = _rel(lm.run_with_cache_multi([params], tokens, cfg, (hook,))[:, :, 0], hooked)
    return out


@pytest.fixture(scope="module")
def case():
    cfg = fh.lm_config(CONFIG, {**TINY, "n_layers": 4})
    params = lm.init_params(jax.random.key(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, cfg.vocab_size, size=(2, 64)))
    return cfg, params, tokens


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_fails_a_comparison_at_the_chips_limit(fault, case):
    cfg, params, tokens = case
    bad_cfg, bad_params, patches = plant(fault, cfg, params)
    with planted(patches):
        errs = errors(bad_cfg, checkpoint(params, cfg), bad_params, tokens, 4)
    if fault == "none":
        assert max(errs.values()) < RTOL, errs
    else:
        assert max(errs.values()) > fh.HARVEST_RTOL, (fault, errs)


def test_an_ignored_start_fails_the_packed_capture(case):
    """Four documents in one row of the packed plane: with the starts
    ignored the state and the conv run from one document into the next, and
    their hooked rows leave what each gives alone."""
    cfg, params, _ = case
    S, hook = 37, "blocks.4.hook_resid_pre"
    lengths = np.asarray([S, 10, 12, 9, 5])
    tokens = np.random.default_rng(2).integers(1, 257, size=(5, S))

    def worst():
        got = lm.run_with_cache_multi_paged([params], tokens, lengths, cfg, (hook,),
                                            page_size=8)
        return max(_rel(got[d, :n, 0], ref.resid_pre(
            params, jnp.asarray(tokens[d: d + 1, :n]), cfg, 4)[0])
                   for d, n in enumerate(lengths))

    assert worst() < RTOL
    _, _, patches = plant("resets_ignored", cfg, params)
    with planted(patches):
        assert worst() > fh.HARVEST_RTOL
