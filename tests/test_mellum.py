"""Mellum2 through the program (crosscoder_tpu/models/lm.py: layer table,
pre-norm block, per-kind RoPE; crosscoder_tpu/ops/moe.py) against its plain
reference (benchmarks/reference/mellum_ref.py), at a small size on the CPU,
seeded random weights, float32. Every harvest entry point is compared; each
planted fault must fail the comparison that decides ``correct`` on the chip."""

import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import manifest                       # noqa: E402
from benchmarks.arch import mellum                    # noqa: E402
from benchmarks.reference import mellum_ref           # noqa: E402
from crosscoder_tpu import obs                        # noqa: E402
from crosscoder_tpu.config import CrossCoderConfig    # noqa: E402
from crosscoder_tpu.models import lm                  # noqa: E402
from crosscoder_tpu.ops import flash_attention as fa  # noqa: E402
from crosscoder_tpu.ops import moe                    # noqa: E402
from crosscoder_tpu.ops import paged_attention as pa  # noqa: E402

CONFIG = manifest.load_json(manifest.BENCH_DIR / "configs" / "mellum2-pair-relu16k.json")
TINY = dict(vocab_size=257, d_model=32, n_layers=4, n_heads=8, n_kv_heads=1,
            head_dim=8, d_ff=64, sliding_window=8, query_pre_attn_scalar=8.0,
            dtype="fp32")
HOOK = "blocks.4.hook_resid_pre"
SEQ = 24        # three windows: the window binds on the three window layers

# Float32 on the CPU, the same mathematics in another order (scan over
# stacked leaves, grouped experts and a folded GQA axis against Python loops,
# a loop over every expert and repeated heads): the hooked stream of four
# blocks differs by float32 round-off, seen at 4e-7 … 6e-7 relative. 5e-6
# leaves that room; one bfloat16 rounding anywhere reads 1e-3.
RTOL = 5e-6


def _rel(got, want) -> float:
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.fixture(scope="module")
def tiny():
    cfg = mellum.lm_config(CONFIG, TINY)
    assert cfg.layer_types == (lm.SLIDING,) * 3 + (lm.FULL,)
    assert cfg.sparse and 0 < cfg.experts_per_tok < cfg.n_experts
    pair = [lm.init_params(jax.random.key(s), cfg) for s in (1, 2)]
    tokens = np.random.default_rng(5).integers(1, cfg.vocab_size, size=(3, SEQ))
    want = [mellum_ref.resid_pre(p, jnp.asarray(tokens), cfg, 4) for p in pair]
    return cfg, pair, tokens, want


def test_run_with_cache_multi_and_forward_match_the_reference(tiny):
    cfg, pair, tokens, want = tiny
    got = lm.run_with_cache_multi(pair, jnp.asarray(tokens), cfg, (HOOK,))
    for m in range(2):
        assert _rel(got[:, :, m], want[m]) < RTOL
    untied = cfg.replace(tie_embeddings=False)
    params = lm.init_params(jax.random.key(1), untied)
    logits, cache = lm.forward(params, jnp.asarray(tokens), untied, capture=(HOOK,))
    assert _rel(cache[HOOK], want[0]) < RTOL       # the same seed: the same blocks
    assert logits.shape == (3, SEQ, cfg.vocab_size) and bool(jnp.isfinite(logits).all())
    assert "unembed" in params and "unembed" not in pair[0]
    assert lm.param_count(untied) == sum(x.size for x in jax.tree_util.tree_leaves(params))


def test_segmented_harvest_matches_the_reference_in_near_equal_quanta(tiny):
    cfg, pair, tokens, want = tiny
    job = lm.SegmentedHarvest(pair, jnp.asarray(tokens), cfg, (HOOK,))
    assert job.n_steps == 4 == lm.SegmentedHarvest.count(cfg, (HOOK,), 2)   # 2 + 2 a model
    got = job.result()
    for m in range(2):
        assert _rel(got[:, :, m], want[m]) < RTOL
    many = lm.SegmentedHarvest(pair, jnp.asarray(tokens), cfg, (HOOK,))
    assert many.step_many(3) == (3, True) and many.step_many(3) == (1, False)
    np.testing.assert_array_equal(np.asarray(many.result()), np.asarray(got))
    assert lm.SegmentedHarvest.quanta(14, 3) == [3, 6, 9, 12, 14]   # Ouro's, as before
    assert lm.SegmentedHarvest.quanta(4, 3) == [2, 4]
    assert lm.SegmentedHarvest.quanta(26, 3) == [3, 6, 9, 12, 15, 18, 21, 24, 26]


def test_paged_capture_matches_the_reference(tiny):
    cfg, pair, tokens, want = tiny
    full = lm.run_with_cache_multi_paged(
        pair, tokens, np.full(3, SEQ), cfg, (HOOK,), page_size=8)
    np.testing.assert_array_equal(      # identity packing: the padded program's ops
        np.asarray(full), np.asarray(lm.run_with_cache_multi(
            pair, jnp.asarray(tokens), cfg, (HOOK,))))
    lengths = np.array([SEQ, 9, 17])
    ragged = tokens.copy()
    for d, n in enumerate(lengths):
        ragged[d, n:] = 0
    got = lm.run_with_cache_multi_paged(pair, ragged, lengths, cfg, (HOOK,), page_size=8)
    for d, n in enumerate(lengths):     # a document alone, through the reference
        for m in range(2):
            alone = mellum_ref.resid_pre(pair[m], jnp.asarray(ragged[d:d + 1, :n]), cfg, 4)
            assert _rel(got[d, :n, m], alone[0]) < RTOL, (d, m)


def test_expert_choice_equals_the_references_exactly(tiny):
    cfg, pair, tokens, _ = tiny
    # the expert layer's input in block 0, by the program and by the reference
    lp = {k: v[0] for k, v in pair[0]["layers"].items() if k not in lm._HELD_LEAVES}
    embed = pair[0]["embed"][jnp.asarray(tokens)]
    resid = embed + lm._attention(lm._norm(embed, lp["attn_norm"], cfg), lp, cfg,
                                  lm._layer_kind(cfg, jnp.int32(0)))
    x = lm._norm(resid, lp["pre_ffw_norm"], cfg).reshape(-1, cfg.d_model)
    idx, gates = moe.route(x, lp["router"], cfg.experts_per_tok, cfg.norm_topk_prob)
    with jax.default_matmul_precision("highest"):
        resid = embed + mellum_ref.attention(
            mellum_ref._rms(embed, lp["attn_norm"], cfg.rms_eps), lp, cfg, cfg.layer_types[0])
        chosen, want = mellum_ref.routing(
            mellum_ref._rms(resid, lp["pre_ffw_norm"], cfg.rms_eps).reshape(-1, cfg.d_model),
            lp["router"], cfg.experts_per_tok, cfg.norm_topk_prob)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want), rtol=1e-5)
    counts = lm.expert_load(pair[0], jnp.asarray(tokens), cfg, 4)
    assert counts.shape == (4, cfg.n_experts)
    assert (np.asarray(counts).sum(-1) == 3 * SEQ * cfg.experts_per_tok).all()
    np.testing.assert_array_equal(
        np.asarray(counts[0]), np.bincount(np.asarray(idx).reshape(-1), minlength=cfg.n_experts))


def test_yarn_frequencies_and_factor_against_the_closed_form():
    """Mellum2's published numbers: theta 500000, factor 16, original 8192,
    betas 32 and 1, head size 128. cd(32) = 18.08 and cd(1) = 34.99, so pairs
    0..18 keep their frequency, pairs 35.. rotate 16 times slower, and the 16
    between are the ramp's blend."""
    cfg = mellum.lm_config(CONFIG)
    rope = cfg.rope_of(lm.FULL)
    cd = lambda r: 128 * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(500000))  # noqa: E731
    assert (math.floor(cd(32)), math.ceil(cd(1))) == (18, 35)
    i = np.arange(64)
    extra = 500000.0 ** (-2 * i / 128)
    ramp = np.clip((i - 18) / (35 - 18), 0, 1)
    want = extra / 16 * ramp + extra * (1 - ramp)
    for got in (np.asarray(lm.rope_inv_freq(rope, 128)),
                mellum_ref.yarn_inv_freq(500000.0, 128, 16.0, 8192, 32.0, 1.0)):
        np.testing.assert_allclose(got, want, rtol=1e-6)
        np.testing.assert_allclose(got[:19], extra[:19], rtol=1e-6)
        np.testing.assert_allclose(got[35:], extra[35:] / 16, rtol=1e-6)
    assert rope.attention_factor == 1.2772588722239782
    plain = cfg.rope_of(lm.SLIDING)
    assert plain == lm.Rope(theta=500000.0)
    np.testing.assert_allclose(np.asarray(lm.rope_inv_freq(plain, 128)), extra, rtol=1e-6)
    cos, sin = mellum_ref.rope_tables(cfg, lm.FULL, 4)
    np.testing.assert_allclose(cos[0], 1.2772588722239782, rtol=1e-6)    # angle 0
    np.testing.assert_allclose(mellum_ref.rope_tables(cfg, lm.SLIDING, 4)[0][0], 1.0)
    # the layer lookup selects the row: traced ids 2 (window) and 3 (full)
    kinds = jax.jit(lambda i: lm._layer_kind(cfg, i)[1:])
    local, full = kinds(2), kinds(3)
    assert bool(local[0]) and not bool(full[0])
    np.testing.assert_allclose(np.asarray(local[1]), extra, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(full[1]), want, rtol=1e-6)
    assert (float(local[2]), float(full[2])) == pytest.approx((1.0, 1.2772588722239782))


def test_published_sizes_by_name():
    cfg = lm.config_for("JetBrains/Mellum2-12B-A2.5B-Instruct")
    assert cfg == lm.config_for("JetBrains/Mellum2-12B-A2.5B-Base") == lm.LMConfig.mellum2_12b()
    published = manifest.load_json(
        Path(__file__).parent / "benchmarks" / "published" /
        "JetBrains.Mellum2-12B-A2.5B-Instruct.json")["config"]
    assert cfg.layer_types == tuple(published["layer_types"])
    assert cfg.mlp_types == tuple(published["mlp_layer_types"])
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size) == \
        (2304, 32, 4, 128, 98304)
    assert (cfg.n_experts, cfg.experts_per_tok, cfg.d_expert) == (64, 8, 896)
    assert 12.0e9 < lm.param_count(cfg) < 12.3e9            # "12B"
    # the cell's cut, by the repo's own count: ISSUE 29's arithmetic
    cut = mellum.lm_config(CONFIG)
    per_layer = 2304 * 4096 * 2 + 2 * 2304 * 512 + 2304 * 64 + 64 * 3 * 2304 * 896 + 2 * 2304
    assert lm.param_count(cut) == 4 * per_layer + 98304 * 2304 + 2304
    assert 7.5e9 < 2 * 2 * lm.param_count(cut) < 7.7e9      # the pair in bf16: 7.59 GB
    assert mellum.flops_per_token(cut, 4, 4096) == pytest.approx(645.0e6, rel=1e-3)
    assert mellum.expert_share_of_flops(cut, 4, 4096) == pytest.approx(0.6145, rel=1e-3)
    # the Gemma-2 family reads as before: the alternate table, filled in
    g = lm.LMConfig.gemma2_2b()
    assert g.layer_types == tuple(lm.SLIDING if i % 2 == 0 else lm.FULL for i in range(26))
    assert g.replace(n_layers=5).layer_types == (lm.SLIDING, lm.FULL) * 2 + (lm.SLIDING,)
    # (a mixed table builds since PR 33 — tests/test_laguna.py; one whose
    # sparse layer has no experts to route to is still refused)
    with pytest.raises(ValueError, match="sparse layers need"):
        g.replace(mlp_types=(lm.SPARSE,) + (lm.DENSE,) * 25)
    with pytest.raises(ValueError, match="layer_types"):
        lm.LMConfig.mellum2_12b().replace(n_layers=8)       # a table given by hand


def test_expert_leaves_on_a_model_axis_are_refused_by_name():
    from crosscoder_tpu.parallel import mesh as mesh_lib

    cfg = mellum.lm_config(CONFIG, TINY)
    one = mesh_lib.make_mesh(1, 1, devices=jax.devices()[:1])
    sh = lm.tp_shardings(one, cfg=cfg)
    params = lm.init_params(jax.random.key(0), cfg)
    assert jax.tree_util.tree_structure(sh) == jax.tree_util.tree_structure(params)
    if len(jax.devices()) >= 2:
        two = mesh_lib.make_mesh(1, 2, devices=jax.devices()[:2])
        with pytest.raises(NotImplementedError, match="expert parallelism"):
            lm.tp_shardings(two, cfg=cfg)
        dense = lm.tp_shardings(two)          # the Gemma-2 family: as before
        assert set(dense["layers"]) == set(lm.init_params(
            jax.random.key(0), lm.LMConfig.tiny())["layers"])


def test_state_dict_loader_maps_the_expert_and_router_leaves():
    cfg = mellum.lm_config(CONFIG, {**TINY, "n_layers": 2}).replace(tie_embeddings=False)
    params = lm.init_params(jax.random.key(3), cfg)
    lay, f = params["layers"], cfg.d_expert
    sd = {"model.embed_tokens.weight": params["embed"], "model.norm.weight": params["final_norm"],
          "lm_head.weight": params["unembed"]}
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = lay["attn_norm"][i]
        sd[p + "post_attention_layernorm.weight"] = lay["pre_ffw_norm"][i]
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
            sd[p + f"self_attn.{theirs}.weight"] = lay[ours][i].T
        sd[p + "mlp.gate.weight"] = lay["router"][i].T
        for e in range(cfg.n_experts):
            sd[p + f"mlp.experts.{e}.gate_proj.weight"] = lay["we_gate_up"][i, e, :, :f].T
            sd[p + f"mlp.experts.{e}.up_proj.weight"] = lay["we_gate_up"][i, e, :, f:].T
            sd[p + f"mlp.experts.{e}.down_proj.weight"] = lay["we_down"][i, e].T
    loaded = lm.from_torch_state_dict({k: np.asarray(v) for k, v in sd.items()}, cfg)
    assert jax.tree_util.tree_structure(loaded) == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(loaded), jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_attention_at_gqa_8_to_1_under_a_binding_window():
    """Mellum2's head grouping (8 query heads a key/value head) and a window
    shorter than the sequence, through ``_attn_core`` as the harvest calls it:
    the traced layer kind picks between the two kernel instances. Float32
    through the interpreter against the XLA form: the reassociated row
    reduction only, as tests/test_flash_attention.py (2e-5 on outputs of
    magnitude 3-4; a bfloat16 rounding reads 1e-2)."""
    S, H, KV, hd, window = 512, 8, 1, 128, 200
    assert fa.supported(4096, 32, 4, 128, jnp.bfloat16)      # the cell's shape
    rng = np.random.default_rng(9)
    q, k, v = (jnp.asarray(rng.normal(size=(1, S, n, hd)).astype(np.float32) * s)
               for n, s in ((H, 2.0), (KV, 1.0), (KV, 1.0)))
    cfg = mellum.lm_config(CONFIG, {**TINY, "head_dim": hd, "n_heads": H, "n_kv_heads": KV,
                                    "sliding_window": window, "query_pre_attn_scalar": float(hd)})
    fa.set_interpret(True)
    try:
        f = jax.jit(lambda q, k, v, loc: lm._attn_core(q, k, v, cfg, loc))
        for is_local in (True, False):
            got = f(q, k, v, jnp.asarray(is_local))
            want = pa.ragged_attention_reference(
                q, k, v, None, scale=hd ** -0.5, softcap=0.0, window=window,
                is_local=jnp.asarray(is_local))
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-5)
        local, full = f(q, k, v, jnp.asarray(True)), f(q, k, v, jnp.asarray(False))
        assert float(jnp.abs(local - full)[:, window:].max()) > 0.1    # the window binds
    finally:
        fa.set_interpret(False)


# ---------------------------------------------------------------------------
# planted faults against the chip's comparison (the relative Frobenius error
# of the hooked stream against ``arch.HARVEST_RTOL``): five fail it here as
# they do on the chip; a bf16 router fails the float32 limit of these tests


def _bf16_router(x, w_router, top_k, norm_topk_prob, routed_scale=1.0, kind="softmax",
                 bias=None):
    logits = jnp.einsum("td,de->te", x, w_router,
                        preferred_element_type=jnp.float32).astype(jnp.bfloat16)
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), gates.astype(jnp.float32)


def _fault(name: str, cfg: lm.LMConfig) -> lm.LMConfig:
    full = cfg.rope_of(lm.FULL)
    return {
        "none": cfg,
        "one_expert_fewer": cfg.replace(experts_per_tok=cfg.experts_per_tok - 1),
        "no_renormalisation": cfg.replace(norm_topk_prob=False),
        "bf16_router_softmax": cfg,
        "window_ignored": cfg.replace(sliding_window=0),
        "attention_factor_dropped": cfg.replace(
            rope=((lm.FULL, dataclasses.replace(full, attention_factor=1.0)),)),
        "window_rope_on_the_full_layer": cfg.replace(
            rope=((lm.FULL, lm.Rope(theta=full.theta,
                                    attention_factor=full.attention_factor)),)),
    }[name]


@pytest.mark.parametrize("fault", [
    "none", "one_expert_fewer", "no_renormalisation", "bf16_router_softmax",
    "window_ignored", "attention_factor_dropped", "window_rope_on_the_full_layer"])
def test_each_planted_fault_fails_the_chips_comparison(fault, monkeypatch):
    """512 tokens through four tiny blocks (a near-tie between the last
    chosen and the first unchosen expert, which a bf16 router flips, takes
    hundreds of tokens to meet), YaRN's original context cut to 16 so that
    its ramp lies inside 128 positions."""
    rope = ((lm.FULL, lm.Rope(theta=100.0, yarn_factor=16.0, original_max_position=16,
                              attention_factor=1.2772588722239782)),)
    cfg = mellum.lm_config(CONFIG, {**TINY, "rope_theta": 100.0, "rope": rope})
    params = lm.init_params(jax.random.key(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, cfg.vocab_size, size=(4, 128)))
    want = mellum.resid_pre(params, tokens, cfg, 4)
    if fault == "bf16_router_softmax":
        monkeypatch.setattr(moe, "route", _bf16_router)
    jax.clear_caches()      # the router is looked up when the program is traced
    try:
        got = lm.run_with_cache_multi([params], tokens, _fault(fault, cfg), (HOOK,))[:, :, 0]
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    err = _rel(got, want)
    if fault == "none":
        assert err < RTOL < mellum.HARVEST_RTOL
    elif fault == "bf16_router_softmax":
        # the one fault the chip's limit cannot see (0.06 here, 0.077 on the
        # chip, where the bf16 program itself reads 0.064-0.074: the fault
        # moves the logits by what the bf16 stream already does; PERF.md §6).
        # The float32 comparison of these tests sees it ten thousand times over
        assert err > 1e4 * RTOL, err
    else:
        assert err > mellum.HARVEST_RTOL, (fault, err)


def test_the_load_gauge_is_read_once_at_calibration_and_only_with_obs_on(tmp_path):
    from crosscoder_tpu.data.buffer import make_buffer

    lm_cfg = mellum.lm_config(CONFIG, {**TINY, "n_layers": 2})
    pair = [lm.init_params(jax.random.key(s), lm_cfg) for s in (1, 2)]
    tokens = np.random.default_rng(1).integers(1, lm_cfg.vocab_size, size=(64, 17))
    base = dict(d_in=32, batch_size=64, seq_len=17, buffer_mult=4, norm_calib_batches=2,
                model_batch_size=4, hook_point="blocks.2.hook_resid_pre", dict_size=64,
                log_backend="null", checkpoint_dir=str(tmp_path / "ckpt"))
    calls = []
    real = lm.expert_load
    lm.expert_load = lambda *a, **k: calls.append(1) or real(*a, **k)
    try:
        make_buffer(CrossCoderConfig(**base), lm_cfg, pair, tokens)
        assert not calls
        cfg = CrossCoderConfig(**base, obs="on", obs_dir=str(tmp_path / "obs"))
        plane = obs.acquire(cfg)
        try:
            buf = make_buffer(cfg, lm_cfg, pair, tokens)
            for _ in range(6):
                buf.next_raw()
            assert len(calls) == 1
            ratio = plane.registry.get_gauge("harvest/moe_load_max_over_mean")
            assert 1.0 <= ratio <= lm_cfg.n_experts
            assert plane.snapshot()["harvest/moe_load_max_over_mean"] == ratio
            assert plane.registry.get_count("harvest/moe_ragged_traces") >= 1
        finally:
            plane.close()
    finally:
        lm.expert_load = real
