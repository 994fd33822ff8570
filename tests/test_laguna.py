"""Laguna-S-2.1 through the program (crosscoder_tpu/models/lm.py: layer
classes, the per-head gate, partial rotary, the shared expert;
crosscoder_tpu/ops/moe.py: the held share of the experts) against its plain
reference (benchmarks/reference/laguna_ref.py), at a small size on the CPU,
seeded random weights, float32. Every harvest entry point is compared; the
ranks' shares add up to the uncut layer; each planted fault fails the
comparison that decides ``correct`` on the chip."""

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
from benchmarks.arch import laguna                    # noqa: E402
from benchmarks.reference import laguna_ref           # noqa: E402
from crosscoder_tpu import obs                        # noqa: E402
from crosscoder_tpu.config import CrossCoderConfig    # noqa: E402
from crosscoder_tpu.models import lm                  # noqa: E402
from crosscoder_tpu.ops import flash_attention as fa  # noqa: E402
from crosscoder_tpu.ops import moe                    # noqa: E402
from crosscoder_tpu.ops import paged_attention as pa  # noqa: E402

CONFIG = manifest.load_json(manifest.BENCH_DIR / "configs" / "laguna-s2.1-pair-relu16k.json")
TINY = dict(vocab_size=257, d_model=32, n_layers=5, n_heads=4, n_kv_heads=2,
            head_dim=8, d_ff=64, sliding_window=8, query_pre_attn_scalar=8.0,
            dtype="fp32")
HOOK = "blocks.5.hook_resid_pre"
SEQ = 24        # three windows: the window binds on the three window layers

# Float32 on the CPU, the same mathematics in another order (scans over
# stacked leaves, grouped experts and a folded GQA axis against Python loops
# over layers, head groups and held experts): the hooked stream of five
# blocks differs by float32 round-off, seen at 3e-7 … 6e-7 relative. 5e-6
# leaves that room; one bfloat16 rounding anywhere reads 1e-3.
RTOL = 5e-6


def _rel(got, want) -> float:
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def scans_and_conds(fn, *args) -> dict:
    """``scan`` and ``cond`` equations of a traced function, at every depth."""
    found = {"scan": 0, "cond": 0}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in found:
                found[eqn.primitive.name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.fixture(scope="module")
def tiny():
    cfg = laguna.lm_config(CONFIG, TINY)
    assert cfg.layer_types == (lm.FULL,) + (lm.SLIDING,) * 3 + (lm.FULL,)
    assert cfg.mlp_types == (lm.DENSE,) + (lm.SPARSE,) * 4
    assert cfg.heads_by_layer == (4, 6, 6, 6, 4)
    assert (cfg.n_experts, cfg.n_held, cfg.first_expert, cfg.experts_per_tok) == (16, 4, 0, 4)
    pair = [lm.init_params(jax.random.key(s), cfg) for s in (1, 2)]
    tokens = np.random.default_rng(5).integers(1, cfg.vocab_size, size=(3, SEQ))
    want = [laguna_ref.resid_pre(p, jnp.asarray(tokens), cfg, 5) for p in pair]
    return cfg, pair, tokens, want


def test_the_layers_form_three_classes_one_stack_each(tiny):
    cfg, pair, _, _ = tiny
    classes = lm.layer_classes(cfg)
    assert [(c.n_heads, c.mlp, c.layers, c.kind) for c in classes] == [
        (4, lm.DENSE, (0,), lm.FULL), (6, lm.SPARSE, (1, 2, 3), lm.SLIDING),
        (4, lm.SPARSE, (4,), lm.FULL)]
    stacks = pair[0]["layers"]
    assert isinstance(stacks, tuple) and len(stacks) == 3
    assert stacks[0]["wq"].shape == (1, 32, 32) and stacks[1]["wq"].shape == (3, 32, 48)
    assert "w_gate" in stacks[0] and "router" not in stacks[0]
    assert stacks[1]["router"].shape == (3, 32, 16)           # the model's width
    assert stacks[1]["we_down"].shape == (3, 4, 16, 32)       # the share held
    assert stacks[1]["w_attn_gate"].shape == (3, 32, 6) and stacks[2]["ws_up"].shape == (1, 32, 16)
    for c, cls in enumerate(classes):       # the reference finds a layer where the program put it
        for slot, layer in enumerate(cls.layers):
            assert laguna_ref.stack_and_slot(cfg, layer) == (c, slot)
    assert lm.param_count(cfg) == sum(x.size for x in jax.tree_util.tree_leaves(pair[0]))
    # the embedding at unit variance per element (the configuration's
    # ``assumed.weights``); every other configuration keeps d_model ** -0.5
    assert cfg.embed_std == 1.0 and lm.LMConfig.tiny().embed_std is None
    assert float(jnp.std(pair[0]["embed"])) == pytest.approx(1.0, rel=0.05)
    assert float(jnp.std(lm.init_params(jax.random.key(1), cfg.replace(embed_std=None))["embed"])) \
        == pytest.approx(32 ** -0.5, rel=0.05)


def test_run_with_cache_multi_and_forward_match_the_reference(tiny):
    cfg, pair, tokens, want = tiny
    got = lm.run_with_cache_multi(pair, jnp.asarray(tokens), cfg, (HOOK,))
    for m in range(2):
        assert _rel(got[:, :, m], want[m]) < RTOL
    untied = cfg.replace(tie_embeddings=False)
    params = lm.init_params(jax.random.key(1), untied)
    hooks = (HOOK, "blocks.1.hook_resid_pre", "blocks.4.hook_resid_pre",
             "blocks.2.hook_attn_out", "blocks.0.hook_mlp_out")
    logits, cache = lm.forward(params, jnp.asarray(tokens), untied, capture=hooks)
    assert _rel(cache[HOOK], want[0]) < RTOL       # the same seed: the same blocks
    for depth in (1, 4):        # a hook inside each run of one class
        assert _rel(cache[f"blocks.{depth}.hook_resid_pre"],
                    laguna_ref.resid_pre(params, jnp.asarray(tokens), cfg, depth)) < RTOL
    # the sublayer sites are the contributions as added to the stream
    mid = lm.run_with_cache(params, jnp.asarray(tokens), untied,
                            ("blocks.2.hook_resid_pre", "blocks.2.hook_mlp_out",
                             "blocks.3.hook_resid_pre"))
    np.testing.assert_allclose(
        np.asarray(mid["blocks.2.hook_resid_pre"] + cache["blocks.2.hook_attn_out"]
                   + mid["blocks.2.hook_mlp_out"]),
        np.asarray(mid["blocks.3.hook_resid_pre"]), rtol=1e-5, atol=1e-5)
    assert logits.shape == (3, SEQ, cfg.vocab_size) and bool(jnp.isfinite(logits).all())
    assert lm.param_count(untied) == sum(x.size for x in jax.tree_util.tree_leaves(params))


def test_an_edit_reaches_the_layer_it_names_across_classes(tiny):
    cfg, pair, tokens, _ = tiny
    tok = jnp.asarray(tokens)
    clean = lm.run_with_cache(pair[0], tok, cfg, ("blocks.3.hook_resid_pre", HOOK))
    value = clean["blocks.3.hook_resid_pre"]
    spliced = lm.forward(pair[0], tok, cfg, capture=(HOOK,), return_logits=False,
                         edits=[lm.Edit("blocks.3.hook_resid_pre", lm.replace_edit, value)])[1]
    np.testing.assert_allclose(np.asarray(spliced[HOOK]), np.asarray(clean[HOOK]),
                               rtol=1e-6, atol=1e-6)        # the identity splice
    zeroed = lm.forward(pair[0], tok, cfg, capture=(HOOK,), return_logits=False,
                        edits=[lm.Edit("blocks.4.hook_attn_out", lm.zero_edit)])[1]
    assert _rel(zeroed[HOOK], clean[HOOK]) > 1e-2


def test_segmented_harvest_matches_the_reference_in_quanta_inside_one_class(tiny):
    cfg, pair, tokens, want = tiny
    job = lm.SegmentedHarvest(pair, jnp.asarray(tokens), cfg, (HOOK,))
    assert job._bounds == [1, 4, 5]         # 1 | 3 | 1: never across two classes
    assert job.n_steps == 6 == lm.SegmentedHarvest.count(cfg, (HOOK,), 2)
    got = job.result()
    for m in range(2):
        assert _rel(got[:, :, m], want[m]) < RTOL
    np.testing.assert_array_equal(      # the same ops in the same order as the whole forward
        np.asarray(got), np.asarray(lm.run_with_cache_multi(
            pair, jnp.asarray(tokens), cfg, (HOOK,))))
    many = lm.SegmentedHarvest(pair, jnp.asarray(tokens), cfg, (HOOK,))
    # quanta fuse inside a run of one class only (here: never), so the
    # accounting is that of single steps whatever is asked
    assert many.step_many(5) == (5, True) and many.step_many(4) == (1, False)
    np.testing.assert_array_equal(np.asarray(many.result()), np.asarray(got))
    q = lm.SegmentedHarvest.quanta
    assert q(5, 3, [1, 3, 1]) == [1, 4, 5] and q(9, 3, [1, 7, 1]) == [1, 4, 6, 8, 9]
    assert q(14, 3) == q(14, 3, [14]) == [3, 6, 9, 12, 14]    # one class: as before
    inside = lm.SegmentedHarvest(pair, jnp.asarray(tokens), cfg, ("blocks.3.hook_resid_pre",))
    assert inside._bounds == [1, 3]
    assert _rel(inside.result()[:, :, 0],
                laguna_ref.resid_pre(pair[0], jnp.asarray(tokens), cfg, 3)) < RTOL


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
            alone = laguna_ref.resid_pre(pair[m], jnp.asarray(ragged[d:d + 1, :n]), cfg, 5)
            assert _rel(got[d, :n, m], alone[0]) < RTOL, (d, m)


def _block1_mlp_input(cfg, params, tokens):
    """The expert layer's input in block 1, by the program and by the
    reference, and that block's leaves (the class's stack, slot 0)."""
    tok = jnp.asarray(tokens)
    resid = lm.run_with_cache(params, tok, cfg, ("blocks.1.hook_resid_pre",))[
        "blocks.1.hook_resid_pre"]
    stack = params["layers"][1]
    lp = {k: v[0] for k, v in stack.items() if k not in lm._HELD_LEAVES}
    kind = lm._layer_kind(cfg, jnp.int32(1), lm.layer_classes(cfg)[1], jnp.int32(0))
    mine = resid + lm._attention(lm._norm(resid, lp["attn_norm"], cfg), lp, cfg, kind)
    x = lm._norm(mine, lp["pre_ffw_norm"], cfg)
    with jax.default_matmul_precision("highest"):
        ref = laguna_ref.resid_pre(params, tok, cfg, 1)
        ref = ref + laguna_ref.attention(
            laguna_ref._rms(ref, lp["attn_norm"], cfg.rms_eps), lp, cfg, lm.SLIDING, 6)
        u = laguna_ref._rms(ref, lp["pre_ffw_norm"], cfg.rms_eps)
    return x, u, lp, stack


def test_expert_choice_equals_the_references_exactly(tiny):
    cfg, pair, tokens, _ = tiny
    x, u, lp, _ = _block1_mlp_input(cfg, pair[0], tokens)
    idx, gates = moe.route(x.reshape(-1, cfg.d_model), lp["router"], cfg.experts_per_tok,
                           cfg.norm_topk_prob, cfg.routed_scale)
    with jax.default_matmul_precision("highest"):
        chosen, want = laguna_ref.routing(
            u.reshape(-1, cfg.d_model), lp["router"], cfg.experts_per_tok,
            cfg.norm_topk_prob, cfg.routed_scale)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-5)   # the routed scale
    counts = np.asarray(lm.expert_load(pair[0], jnp.asarray(tokens), cfg, 5))
    assert counts.shape == (5, 16) and not counts[0].any()      # layer 0 is dense
    assert (counts[1:].sum(-1) == 3 * SEQ * cfg.experts_per_tok).all()
    np.testing.assert_array_equal(
        counts[1], np.bincount(np.asarray(idx).reshape(-1), minlength=16))
    share = moe.local_row_share(counts[1:], cfg.first_expert, cfg.n_held)
    assert share == pytest.approx(np.mean(counts[1:, :4].sum(-1) / counts[1:].sum(-1)))
    assert 0.0 < share < 1.0
    # float32 gates under a bf16 model, at the router's whole width
    bf = moe.route(x.reshape(-1, cfg.d_model).astype(jnp.bfloat16),
                   lp["router"].astype(jnp.bfloat16), 4, True, 2.5)
    assert bf[1].dtype == jnp.float32 and int(bf[0].max()) > cfg.n_held


def test_the_ranks_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(tiny):
    """16 experts over 4 ranks: each rank's program computes its held part
    plus the shared expert; the routed parts of the four ranks plus the
    shared expert counted ONCE are the uncut reference's whole MLP layer."""
    cfg, _, tokens, _ = tiny
    whole = cfg.replace(experts_held=0)
    params = lm.init_params(jax.random.key(7), whole)
    x, u, lp, stack = _block1_mlp_input(whole, params, tokens)
    assert stack["we_down"].shape[1] == 16
    with jax.default_matmul_precision("highest"):
        uncut = laguna_ref.mlp(u, lp, stack, 0, whole)
        shared = laguna_ref.gated_mlp(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    ours_whole = lm._mlp(x, {**lp, **{k: stack[k] for k in lm._HELD_LEAVES if k in stack}}, whole, 0)
    assert _rel(ours_whole, uncut) < RTOL
    total = jnp.zeros_like(uncut)
    for rank in range(4):
        part = cfg.replace(expert_rank=rank)
        held = {k: stack[k][:, 4 * rank:4 * rank + 4] for k in lm._HELD_LEAVES if k in stack}
        ours = lm._mlp(x, {**lp, **held}, part, 0)
        with jax.default_matmul_precision("highest"):
            ref = laguna_ref.mlp(u, lp, {**stack, **held}, 0, part)
        assert _rel(ours, ref) < RTOL, rank
        assert _rel(ours - shared, uncut - shared) > 0.3        # a part, not the whole
        total = total + (ours - shared)
    assert _rel(total + shared, uncut) < RTOL


def test_partial_rotary_and_yarn_at_dim_64_against_the_closed_form():
    """Laguna's published numbers on a full layer: theta 500000, factor 128,
    original 8192, betas 32 and 1, the leading 64 of 128 dims. cd(32) = 9.04
    and cd(1) = 17.50 at dim 64, so pairs 0..9 keep their frequency, pairs
    18.. rotate 128 times slower, and the eight between are the ramp's."""
    cfg = laguna.lm_config(CONFIG)
    rope = cfg.rope_of(lm.FULL)
    assert (rope.rotary_factor, rope.attention_factor) == (0.5, 1.4852030263919618)
    cd = lambda r: 64 * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(500000))  # noqa: E731
    assert (math.floor(cd(32)), math.ceil(cd(1))) == (9, 18)
    i = np.arange(32)
    extra = 500000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 9) / (18 - 9), 0, 1)
    want = extra / 128 * ramp + extra * (1 - ramp)
    for got in (np.asarray(lm.rope_inv_freq(rope, 128)), laguna_ref.inv_freq(rope, 128)[0]):
        assert got.shape == (32,)
        np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = cfg.rope_of(lm.SLIDING)
    assert plain == lm.Rope(theta=10000.0)
    np.testing.assert_allclose(np.asarray(lm.rope_inv_freq(plain, 128)),
                               10000.0 ** (-2 * np.arange(64) / 128), rtol=1e-6)
    # the rotation itself: pairs (j, j + 32) of dims 0..63, dims 64..127 pass
    x = np.random.default_rng(0).normal(size=(1, 5, 2, 128)).astype(np.float32)
    pos = jnp.arange(5)
    got = np.asarray(lm._rope(jnp.asarray(x), pos, jnp.asarray(want, jnp.float32),
                              rope.attention_factor))
    ang = np.arange(5)[:, None] * want[None, :]
    c, s = (f(ang)[None, :, None, :] * rope.attention_factor for f in (np.cos, np.sin))
    np.testing.assert_allclose(got[..., :32], x[..., :32] * c - x[..., 32:64] * s, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got[..., 32:64], x[..., 32:64] * c + x[..., :32] * s, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    cos, sin = laguna_ref.rope_tables(cfg, lm.FULL, 5)
    np.testing.assert_allclose(np.asarray(laguna_ref.rotate(jnp.asarray(x), cos, sin)), got,
                               rtol=2e-5, atol=2e-5)
    assert cos.shape == (5, 32) and laguna_ref.rope_tables(cfg, lm.SLIDING, 5)[0].shape == (5, 64)
    # a class of one attention kind looks nothing up: the kind is static
    kind = lm._layer_kind(cfg, jnp.int32(4), lm.layer_classes(cfg)[2], jnp.int32(0))
    assert isinstance(kind.is_local, np.bool_) and not kind.is_local
    assert kind.inv_freq.shape == (32,) and kind.rope_factor == rope.attention_factor
    local = lm._layer_kind(cfg, jnp.int32(2), lm.layer_classes(cfg)[1], jnp.int32(1))
    assert local.is_local and local.inv_freq.shape == (64,) and local.rope_factor == 1.0


def test_published_sizes_by_name_and_the_cut_against_the_issues_table():
    cfg = lm.config_for("poolside/Laguna-S-2.1")
    assert cfg == lm.LMConfig.laguna_s_2_1() == lm.config_for("laguna-s-2.1-base")
    published = manifest.load_json(
        Path(__file__).parent / "benchmarks" / "published" / "poolside.Laguna-S-2.1.json")["config"]
    assert cfg.layer_types == tuple(published["layer_types"])
    assert cfg.mlp_types == tuple(published["mlp_layer_types"])
    assert cfg.heads_by_layer == tuple(published["num_attention_heads_per_layer"])
    assert (cfg.d_model, cfg.n_kv_heads, cfg.head_dim, cfg.vocab_size, cfg.d_ff) == \
        (3072, 8, 128, 100352, 12288)
    assert (cfg.n_experts, cfg.n_held, cfg.experts_per_tok, cfg.d_expert,
            cfg.d_shared_expert, cfg.routed_scale) == (256, 256, 10, 1024, 1024, 2.5)
    assert 117e9 < lm.param_count(cfg) < 118.5e9        # "~118B"
    classes = lm.layer_classes(cfg)
    assert [(c.n_heads, c.mlp, len(c.layers)) for c in classes] == [
        (48, lm.DENSE, 1), (72, lm.SPARSE, 36), (48, lm.SPARSE, 11)]
    # the published 48-layer table: layer 0, then 11 periods of (3 window, 1
    # full), then 3 window layers — one outer scan over the periods
    runs = lm._runs(cfg, 0, 48)
    assert len(runs) == 24 and lm._periodic(runs) == (1, 2, 11)
    # the cell's cut, by the repo's own count: ISSUE 33's table
    cut = laguna.lm_config(CONFIG)
    D = 3072
    expert = 3 * D * 1024
    sliding = 2 * D * 72 * 128 + 2 * D * 1024 + D * 72 + D * 256 + expert + 2 * D
    full = 2 * D * 48 * 128 + 2 * D * 1024 + D * 48 + D * 256 + expert + 2 * D
    layer0 = 2 * D * 48 * 128 + 2 * D * 1024 + D * 48 + 3 * D * 12288 + 2 * D
    assert round(sliding / 1e6, 1) == 73.4 and round(full / 1e6, 1) == 54.4
    assert round(layer0 / 1e6, 1) == 157.4 and round(expert / 1e6, 2) == 9.44
    outside = layer0 + 3 * sliding + full
    assert 431.9e6 < outside < 432.0e6
    assert lm.param_count(cut) == outside + 100352 * D + D + 4 * 32 * expert
    assert 7.78e9 < 2 * 2 * lm.param_count(cut) < 7.80e9     # the pair in bf16: 7.79 GB
    assert (cut.n_experts, cut.n_held, cut.first_expert) == (256, 32, 0)
    assert laguna.flops_per_token(cut, 5, 4096) == pytest.approx(1.112e9, rel=1e-3)
    assert laguna.expert_share_of_flops(cut, 5, 4096) == pytest.approx(0.0849, rel=1e-3)


def test_a_mixed_table_builds_and_a_table_of_one_class_keeps_todays_tree_and_program():
    g = lm.LMConfig.tiny()
    assert len(lm.layer_classes(g)) == 1 and lm.layer_classes(g)[0].kind is None
    params = lm.init_params(jax.random.key(0), g)
    assert isinstance(params["layers"], dict) and params["layers"]["wq"].shape == (4, 32, 32)
    # the table the old check refused: a leading dense layer before sparse ones
    mixed = g.replace(mlp_types=(lm.DENSE,) + (lm.SPARSE,) * 3, n_experts=4,
                      experts_per_tok=2, d_expert=16)
    assert [c.layers for c in lm.layer_classes(mixed)] == [(0,), (1, 2, 3)]
    assert lm.layer_classes(mixed)[1].kind is None      # window and full in one class
    tok = jnp.asarray(np.random.default_rng(0).integers(1, 257, size=(2, 12)))
    out = lm.run_with_cache(lm.init_params(jax.random.key(0), mixed), tok, mixed,
                            ("blocks.4.hook_resid_pre",))["blocks.4.hook_resid_pre"]
    assert bool(jnp.isfinite(out).all())
    with pytest.raises(ValueError, match="heads_by_layer"):
        g.replace(heads_by_layer=(4, 4, 4))
    with pytest.raises(ValueError, match="share of 3 experts"):
        mixed.replace(experts_held=3)

    cap = ((4, 0),)

    def whole(cfg):
        return lambda p, t: lm._scan_blocks(
            p, cfg, cap, lm._fresh_carry(p, t, cfg, 1), cfg.n_layers)[0]

    assert scans_and_conds(whole(g), params, tok) == {"scan": 1, "cond": 0}
    # a window/full table of ONE shape under the fused attention: the one
    # scan, and the one cond between the two kernel instances
    wide = g.replace(head_dim=128, n_heads=2, n_kv_heads=1, sliding_window=128,
                     query_pre_attn_scalar=128.0)
    tok2 = jnp.zeros((1, 256), jnp.int32)
    fa.set_interpret(True)
    try:
        assert scans_and_conds(whole(wide), lm.init_params(jax.random.key(0), wide), tok2) \
            == {"scan": 1, "cond": 1}
    finally:
        fa.set_interpret(False)
    # the published 48-layer table at tiny widths: layer 0, ONE outer scan
    # over the 11 periods holding one scan a run of the period (3 window
    # layers, 1 full layer), and the 3 window layers left: five layer scans
    # (and one inside each sparse run's ``searchsorted``) whatever the depth —
    # as many as at 13 layers — and the reference's stream at the end of it
    deep = laguna.lm_config(CONFIG, {**TINY, "n_layers": 48})
    dp = lm.init_params(jax.random.key(3), deep)

    def layer_scans(cfg, p, k):
        return scans_and_conds(lambda p, t: lm._scan_blocks(
            p, cfg, ((k, 0),), lm._fresh_carry(p, t, cfg, 1), k)[0], p, tok)

    shallow = laguna.lm_config(CONFIG, {**TINY, "n_layers": 16})
    assert lm._periodic(lm._runs(shallow, 0, 16)) == (1, 2, 3)
    assert layer_scans(deep, dp, 48) == {"scan": 5 + 3, "cond": 0} == layer_scans(
        shallow, lm.init_params(jax.random.key(3), shallow), 16)
    got = lm.run_with_cache(dp, tok, deep, ("blocks.48.hook_resid_pre",))
    assert _rel(got["blocks.48.hook_resid_pre"], laguna_ref.resid_pre(dp, tok, deep, 48)) < 2e-5
    counts = np.asarray(lm.expert_load(dp, tok, deep, 48))       # ``ys`` in layer order
    assert counts.shape == (48, 16) and not counts[0].any() and (counts[1:].sum(-1) == 2 * 12 * 4).all()


def test_expert_leaves_on_a_model_axis_are_refused_by_name():
    from crosscoder_tpu.parallel import mesh as mesh_lib

    cfg = laguna.lm_config(CONFIG, TINY)
    one = mesh_lib.make_mesh(1, 1, devices=jax.devices()[:1])
    sh = lm.tp_shardings(one, cfg=cfg)
    params = lm.init_params(jax.random.key(0), cfg)
    assert jax.tree_util.tree_structure(sh) == jax.tree_util.tree_structure(params)
    if len(jax.devices()) >= 2:
        two = mesh_lib.make_mesh(1, 2, devices=jax.devices()[:2])
        with pytest.raises(NotImplementedError, match="expert parallelism"):
            lm.tp_shardings(two, cfg=cfg)


def test_state_dict_loader_takes_the_whole_models_names_and_keeps_the_ranks_experts():
    cfg = laguna.lm_config(CONFIG, TINY).replace(tie_embeddings=False, expert_rank=2)
    whole = cfg.replace(experts_held=0, expert_rank=0)
    params = lm.init_params(jax.random.key(3), whole)
    f = cfg.d_expert
    sd = {"model.embed_tokens.weight": params["embed"], "model.norm.weight": params["final_norm"],
          "lm_head.weight": params["unembed"]}
    for i in range(cfg.n_layers):
        c, s = laguna_ref.stack_and_slot(cfg, i)
        lay = params["layers"][c]
        p = f"model.layers.{i}."
        sd[p + "input_layernorm.weight"] = lay["attn_norm"][s]
        sd[p + "post_attention_layernorm.weight"] = lay["pre_ffw_norm"][s]
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
                             ("wo", "o_proj"), ("w_attn_gate", "g_proj")):
            sd[p + f"self_attn.{theirs}.weight"] = lay[ours][s].T
        if i == 0:
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj")):
                sd[p + f"mlp.{theirs}.weight"] = lay[ours][s].T
            continue
        sd[p + "mlp.gate.weight"] = lay["router"][s].T
        for ours, theirs in (("ws_gate", "gate_proj"), ("ws_up", "up_proj"), ("ws_down", "down_proj")):
            sd[p + f"mlp.shared_expert.{theirs}.weight"] = lay[ours][s].T
        for e in range(16):
            sd[p + f"mlp.experts.{e}.gate_proj.weight"] = lay["we_gate_up"][s, e, :, :f].T
            sd[p + f"mlp.experts.{e}.up_proj.weight"] = lay["we_gate_up"][s, e, :, f:].T
            sd[p + f"mlp.experts.{e}.down_proj.weight"] = lay["we_down"][s, e].T
    loaded = lm.from_torch_state_dict({k: np.asarray(v) for k, v in sd.items()}, cfg)
    want = dict(params, layers=tuple(
        {k: (v[:, 8:12] if k in lm._HELD_LEAVES else v) for k, v in stack.items()}
        for stack in params["layers"]))
    assert jax.tree_util.tree_structure(loaded) == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(loaded), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert loaded["layers"][1]["we_down"].shape == (3, 4, 16, 32)


@pytest.mark.parametrize("heads", [72, 48], ids=["9to1", "6to1"])
def test_fused_attention_at_both_head_counts_under_a_binding_window(heads):
    """Laguna's two groupings (9 and 6 query heads a key/value head) and a
    window shorter than the sequence, through ``_attn_core`` as the harvest
    calls it with a STATIC layer kind (a class of one kind names its kernel
    instance; no ``cond``). Float32 through the interpreter against the XLA
    form: the reassociated row reduction only (2e-5 on outputs of magnitude
    3-4; a bfloat16 rounding reads 1e-2)."""
    S, KV, hd, window = 512, 8, 128, 200
    assert fa.supported(4096, heads, 8, 128, jnp.bfloat16)      # the cell's shape
    H = heads // 8 * 2                                          # the grouping, on 2 kv heads
    rng = np.random.default_rng(9)
    q, k, v = (jnp.asarray(rng.normal(size=(1, S, n, hd)).astype(np.float32) * s)
               for n, s in ((H, 2.0), (2, 1.0), (2, 1.0)))
    cfg = laguna.lm_config(CONFIG, {**TINY, "head_dim": hd, "n_kv_heads": 2, "n_heads": 12,
                                    "sliding_window": window, "query_pre_attn_scalar": float(hd)})
    fa.set_interpret(True)
    try:
        outs = {}
        for is_local in (True, False):
            f = jax.jit(lambda q, k, v: lm._attn_core(q, k, v, cfg, np.bool_(is_local)))
            assert scans_and_conds(f, q, k, v)["cond"] == 0
            outs[is_local] = f(q, k, v)
            want = pa.ragged_attention_reference(
                q, k, v, None, scale=hd ** -0.5, softcap=0.0, window=window,
                is_local=jnp.asarray(is_local))
            np.testing.assert_allclose(np.asarray(outs[is_local]), np.asarray(want),
                                       rtol=0, atol=2e-5)
        assert float(jnp.abs(outs[True] - outs[False])[:, window:].max()) > 0.1   # the window binds
    finally:
        fa.set_interpret(False)


# ---------------------------------------------------------------------------
# planted faults against the chip's comparison (the relative Frobenius error
# of the hooked stream against ``arch.HARVEST_RTOL``)


def _without(params, *leaves):
    return dict(params, layers=tuple(
        {k: v for k, v in stack.items() if k not in leaves} for stack in params["layers"]))


def plant(fault: str, cfg: lm.LMConfig, params: dict) -> tuple[lm.LMConfig, dict]:
    """The program's configuration and tree with one fault planted (the
    reference keeps the true ones). ``scripts/probes/_laguna_faults.py`` plants
    the same at the cell's widths on the chip."""
    full = cfg.rope_of(lm.FULL)

    def with_full(**kw):
        return cfg.replace(rope=((lm.FULL, dataclasses.replace(full, **kw)),
                                 (lm.SLIDING, cfg.rope_of(lm.SLIDING))))

    swap = {lm.FULL: lm.SLIDING, lm.SLIDING: lm.FULL}
    if fault == "float8_weights":
        params = jax.tree.map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype) if x.ndim > 2 else x, params)
    return {
        "none": (cfg, params),
        "gate_dropped": (cfg, _without(params, "w_attn_gate")),
        "full_layers_rotate_all_128": (with_full(rotary_factor=1.0), params),
        "attention_factor_dropped": (with_full(attention_factor=1.0), params),
        "head_counts_kinds_swapped": (
            cfg.replace(layer_types=tuple(swap[t] for t in cfg.layer_types)), params),
        "routed_scale_one": (cfg.replace(routed_scale=1.0), params),
        "shared_expert_dropped": (cfg, _without(params, "ws_gate", "ws_up", "ws_down")),
        "one_expert_fewer": (cfg.replace(experts_per_tok=cfg.experts_per_tok - 1), params),
        "window_ignored": (cfg.replace(sliding_window=0), params),
        "another_ranks_experts": (cfg.replace(expert_rank=1), params),
        "float8_weights": (cfg, params),
    }[fault]


FAULTS = ["none", "gate_dropped", "full_layers_rotate_all_128", "attention_factor_dropped",
          "head_counts_kinds_swapped", "routed_scale_one", "shared_expert_dropped",
          "one_expert_fewer", "window_ignored", "another_ranks_experts", "float8_weights"]


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_fails_the_chips_comparison(fault):
    """128 tokens through the five tiny blocks, YaRN's original context cut
    to 16 so that its ramp lies inside 128 positions."""
    rope = ((lm.FULL, lm.Rope(theta=100.0, yarn_factor=128.0, original_max_position=16,
                              attention_factor=1.4852030263919618, rotary_factor=0.5)),
            (lm.SLIDING, lm.Rope(theta=100.0)))
    cfg = laguna.lm_config(CONFIG, {**TINY, "rope_theta": 100.0, "rope": rope})
    params = lm.init_params(jax.random.key(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, cfg.vocab_size, size=(4, 128)))
    want = laguna.resid_pre(params, tokens, cfg, 5)
    bad_cfg, bad_params = plant(fault, cfg, params)
    got = lm.run_with_cache_multi([bad_params], tokens, bad_cfg, (HOOK,))[:, :, 0]
    err = _rel(got, want)
    if fault == "none":
        assert err < RTOL < laguna.HARVEST_RTOL
    else:       # (on the chip at the cell's widths each reads 0.061 or more: PERF.md §6)
        assert err > laguna.HARVEST_RTOL, (fault, err)


def test_the_gauges_are_read_once_at_calibration_and_only_with_obs_on(tmp_path):
    from crosscoder_tpu.data.buffer import make_buffer

    lm_cfg = laguna.lm_config(CONFIG, {**TINY, "n_layers": 2})
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
            share = plane.registry.get_gauge("harvest/moe_local_row_share")
            assert 0.0 < share < 1.0
            assert plane.snapshot()["harvest/moe_local_row_share"] == share
            assert 1.0 <= plane.registry.get_gauge("harvest/moe_load_max_over_mean") <= 16
            assert plane.registry.get_count("harvest/moe_held_traces") >= 1
            assert plane.registry.get_count("harvest/moe_ragged_traces") >= 1
        finally:
            plane.close()
    finally:
        lm.expert_load = real
