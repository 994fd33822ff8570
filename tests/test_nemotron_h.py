"""Nemotron-H's tower through the program (crosscoder_tpu/models/lm.py: layers
of one sublayer — a mixer slot and an FFN slot, either empty; the ungated
relu² MLPs; attention with no rotation; crosscoder_tpu/ops/moe.py: the
ungated experts, sigmoid routing, the held share; crosscoder_tpu/ops/ssm.py)
against its plain reference (benchmarks/reference/nemotron_h_ref.py, whose
SSM is the token-by-token recurrence), at a small size on the CPU, seeded
random weights, float32. Every harvest entry point is compared; the hook
contract of an empty slot holds; the ranks' shares add up to the uncut
layer. The planted faults are in tests/test_nemotron_h_faults.py (a file of
their own: one worker a file)."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import manifest                         # noqa: E402
from benchmarks.arch import nemotron_h as nh            # noqa: E402
from benchmarks.reference import nemotron_h_ref as ref  # noqa: E402
from crosscoder_tpu import obs                          # noqa: E402
from crosscoder_tpu.config import CrossCoderConfig      # noqa: E402
from crosscoder_tpu.models import lm                    # noqa: E402
from crosscoder_tpu.ops import moe                      # noqa: E402

CONFIG = manifest.load_json(
    manifest.BENCH_DIR / "configs" / "nemotron-twotower-30b-a3b-pair-relu16k.json")
# hidden 32, 4 query / 2 key-value heads of 8; 4 SSM heads of 8 over a state
# of 16 in 2 groups, chunks of 8; 16 experts of 16 (4 held), top 4, a shared
# one of 32 (arch/nemotron_h.py TINY_*); the cell's nine layers MEMEM*EME
TINY = dict(vocab_size=257, d_model=32, n_layers=9, n_heads=4, n_kv_heads=2,
            head_dim=8, d_ff=64, dtype="fp32")
HOOK = "blocks.9.hook_resid_pre"
SEQ = 21                            # two chunks of 8 and a part
# Float32 on the CPU, the same mathematics in another order (the chunked scan
# against the recurrence, grouped experts against each on every token, scans
# over stacked leaves against Python loops): seen at 2e-7 … 8e-7 relative.
# 5e-6 leaves that room; one bfloat16 rounding anywhere reads 1e-3.
RTOL = 5e-6
M_LAYER, E_LAYER, A_LAYER = 0, 1, 5


def _rel(got, want) -> float:
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.fixture(scope="module")
def model():
    cfg = nh.lm_config(CONFIG, TINY)
    return cfg, lm.init_params(jax.random.key(0), cfg)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(1, 257, size=(2, SEQ)))


# ---------------------------------------------------------------------------
# the published sizes


def test_the_published_sizes_by_name():
    cfg = nh.lm_config(CONFIG)
    whole = lm.LMConfig.nemotron_twotower_30b()
    assert cfg == whole.replace(
        n_layers=9, layer_types=whole.layer_types[:9], mlp_types=whole.mlp_types[:9],
        experts_held=32, tie_embeddings=True, embed_std=1.0)
    assert "".join({lm.MAMBA: "M", lm.FULL: "*", lm.NONE: "E"}[k]
                   for k in cfg.layer_types) == "MEMEM*EME"
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (2688, 32, 2, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv,
            cfg.ssm_chunk) == (64, 64, 128, 8, 4, 128)
    assert cfg.d_ssm == 4096 and cfg.ssm_proj_widths == (4096, 4096, 1024, 1024, 64)
    assert (cfg.n_experts, cfg.n_held, cfg.experts_per_tok, cfg.d_expert,
            cfg.d_shared_expert) == (128, 32, 6, 1856, 3712)
    assert cfg.routed_scale == 2.5 and cfg.router == "sigmoid_bias" and cfg.mlp_act == "relu2"
    assert cfg.rms_eps == 1e-5 and cfg.query_pre_attn_scalar == 128.0
    assert lm.rope_inv_freq(cfg.rope_of(lm.FULL), cfg.rope_dim).shape == (0,)
    assert lm.config_for("nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16") == whole
    assert whole.layer_types.count(lm.MAMBA) == 23 and whole.layer_types.count(lm.FULL) == 6
    assert whole.mlp_types.count(lm.SPARSE) == 23


def test_param_count_is_what_is_held_and_the_published_table():
    """Per layer: M 38.74 M, * 23.40 M, E 20.30 M outside its experts,
    an expert 9.98 M, the embedding 352.3 M; 1,889.1 M a model, the pair
    7.04 GiB in bf16."""
    cfg = nh.lm_config(CONFIG)
    shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg), jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == lm.param_count(cfg)
    m, e, a = (sum(v.size for v in s.values()) // len(c.layers)
               for s, c in zip(shapes["layers"], lm.layer_classes(cfg)))
    assert m == pytest.approx(38.74e6, rel=1e-3) and a == pytest.approx(23.40e6, rel=1e-3)
    expert = 2 * 2688 * 1856
    assert expert == pytest.approx(9.98e6, rel=1e-3)
    assert e - 32 * expert == pytest.approx(20.30e6, rel=1e-3)
    assert shapes["embed"].size == 131_072 * 2688
    assert lm.param_count(cfg) == pytest.approx(1889.1e6, rel=1e-4)
    assert 2 * 2 * lm.param_count(cfg) / 2**30 == pytest.approx(7.04, abs=0.01)
    small = nh.lm_config(CONFIG, TINY)
    tree = lm.init_params(jax.random.key(0), small)
    assert sum(x.size for x in jax.tree.leaves(tree)) == lm.param_count(small)


def test_the_needed_flops_and_bytes_of_the_cell():
    """The cell's split at S 4096: M 80.2 MFLOP (the scan 2.75), * 80.3
    (the core 33.6), E 70.5, 683 to the hook; the held experts 17.5 %; the
    scan's 20,608 bytes a token and layer against its 2.75 MFLOP."""
    cfg = nh.lm_config(CONFIG)
    assert nh.ssd_scan_flops_per_token(cfg, 1) == 128 * 128 * 8 + 128 * 64 * 64 + 4 * 128 * 64 * 64
    assert nh.ssd_scan_flops_per_token(cfg, 9) == 4 * 2_752_512
    assert nh.ssd_scan_bytes_per_token(cfg, 9) == 4 * 20_608
    assert nh.flops_per_token(cfg, 1, 4096) == pytest.approx(80.16e6, rel=1e-3)       # M
    assert nh.flops_per_token(cfg, 2, 4096) - nh.flops_per_token(cfg, 1, 4096) == \
        pytest.approx(70.5e6, rel=1e-3)                                                # E
    assert nh.attn_core_flops_per_token(cfg, 9, 4096) == pytest.approx(33.57e6, rel=1e-3)
    total = nh.flops_per_token(cfg, 9, 4096)
    assert total == pytest.approx(683.2e6, rel=1e-3)
    assert nh.expert_share_of_flops(cfg, 9, 4096) == pytest.approx(0.175, abs=1e-3)
    assert nh.ssd_scan_share_of_flops(cfg, 9, 4096) * total == pytest.approx(4 * 2_752_512)
    assert nh.ssd_scan_bytes_over_flops(cfg, 9, 4096) * total == pytest.approx(4 * 20_608)


# ---------------------------------------------------------------------------
# the layer table


def test_three_classes_one_stack_each_and_no_leaf_for_an_empty_slot(model):
    cfg, params = model
    classes = lm.layer_classes(cfg)
    assert [(c.kind, c.mlp, c.layers) for c in classes] == [
        (lm.MAMBA, lm.NONE, (0, 2, 4, 7)), (lm.NONE, lm.SPARSE, (1, 3, 6, 8)),
        (lm.FULL, lm.NONE, (5,))]
    mixer, experts, attention = (set(s) for s in params["layers"])
    assert "pre_ffw_norm" not in mixer | attention and "attn_norm" not in experts
    assert not mixer & {"wq", "wk", "wv", "wo"} and "ssm_in" not in attention
    assert {"we_up", "we_down", "ws_up", "ws_down"} <= experts
    assert not experts & {"we_gate_up", "ws_gate", "w_gate"}
    # nine runs of one layer: nine one-layer quanta a model
    assert lm.SegmentedHarvest.quanta(9, 3, [r.n for r in lm._runs(cfg, 0, 9)]) == \
        list(range(1, 10))


def test_the_accepted_tables_keep_their_classes():
    """A mixer slot of attention keys as before: one class for Gemma-2's two
    kinds, Laguna's three shapes, Falcon-H1's parallel pair."""
    g = lm.LMConfig.tiny()
    assert len(lm.layer_classes(g)) == 1 and lm.layer_classes(g)[0].kind is None
    f = lm.LMConfig.falcon_h1_34b()
    assert len(lm.layer_classes(f)) == 1 and f.ssm_parallel
    assert [c.layers for c in lm.layer_classes(lm.LMConfig.laguna_s_2_1())][0] == (0,)
    assert not lm.LMConfig.nemotron_twotower_30b().ssm_parallel


@pytest.mark.parametrize("bad", [
    dict(layer_types=(lm.NONE,) * 2, mlp_types=(lm.NONE, lm.DENSE)),
    dict(layer_types=(lm.MAMBA, lm.FULL), ssm_heads=0),
    dict(layer_types=(lm.NONE, lm.FULL), block_style="sandwich"),
    dict(mlp_act="swiglu")], ids=["both-empty", "mamba-no-heads", "sandwich", "act"])
def test_a_table_it_cannot_run_is_refused(bad):
    kw = dict(layer_types=(lm.MAMBA, lm.FULL), mlp_types=(lm.NONE, lm.DENSE),
              block_style="prenorm", ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2)
    with pytest.raises(ValueError):
        lm.LMConfig.tiny(n_layers=2).replace(**{**kw, **bad})


# ---------------------------------------------------------------------------
# every harvest entry point against the reference


def test_resid_pre_at_every_layer_against_the_reference(model, tokens):
    cfg, params = model
    hooks = [f"blocks.{i}.hook_resid_pre" for i in range(cfg.n_layers + 1)]
    _, cache = lm.forward(params, tokens, cfg, capture=hooks, return_logits=False)
    for i, hook in enumerate(hooks):
        assert _rel(cache[hook], ref.resid_pre(params, tokens, cfg, i)) < RTOL, hook


def test_run_with_cache_multi_and_logits_against_the_reference(model, tokens):
    cfg, params = model
    got = lm.run_with_cache_multi([params, params], tokens, cfg, (HOOK,))
    want = ref.resid_pre(params, tokens, cfg, 9)
    assert _rel(got[:, :, 0], want) < RTOL and _rel(got[:, :, 1], want) < RTOL
    cfg_h = cfg.replace(tie_embeddings=False)
    p_h = lm.init_params(jax.random.key(1), cfg_h)
    logits, _ = lm.forward(p_h, tokens, cfg_h)
    assert _rel(logits, ref.logits(p_h, tokens, cfg_h)) < RTOL


def test_segmented_harvest_in_its_quanta(model, tokens):
    cfg, params = model
    whole = lm.run_with_cache_multi([params, params], tokens, cfg, (HOOK,))
    job = lm.SegmentedHarvest([params, params], tokens, cfg, (HOOK,))
    assert job.n_steps == 18
    while job.step():
        pass
    np.testing.assert_array_equal(np.asarray(job.result()), np.asarray(whole))
    many = lm.SegmentedHarvest([params, params], tokens, cfg, (HOOK,))
    while many.step_many(3)[1]:
        pass
    np.testing.assert_array_equal(np.asarray(many.result()), np.asarray(whole))


def test_packed_capture_is_each_document_alone(model):
    """Five documents of which four share ONE row of the packed plane: the
    mixers' state and conv start afresh at each (the scan's XLA form, the
    starts being there), so every document's rows are what it gives alone."""
    from crosscoder_tpu.data import paging

    cfg, params = model
    S = 37
    lengths = np.asarray([S, 10, 12, 9, 5])
    toks = np.random.default_rng(2).integers(1, 257, size=(5, S))
    assert paging.pack_chunk(toks, lengths).n_rows == 2
    got = lm.run_with_cache_multi_paged([params], toks, lengths, cfg, (HOOK,), page_size=8)
    for d, n in enumerate(lengths):
        alone = ref.resid_pre(params, jnp.asarray(toks[d: d + 1, :n]), cfg, 9)[0]
        assert _rel(got[d, :n, 0], alone) < RTOL, d
        assert not np.asarray(got[d, n:]).any()


def test_a_dense_relu2_layer_against_the_reference(tokens):
    """The ``-`` letter (an ungated relu² MLP alone) beside the other three."""
    cfg = nh.lm_config(CONFIG, {**TINY, "n_layers": 4}).replace(
        layer_types=lm.nemotron_h_table("M-*E")[0], mlp_types=lm.nemotron_h_table("M-*E")[1])
    params = lm.init_params(jax.random.key(4), cfg)
    assert {"w_up", "w_down"} == set(params["layers"][1]) - {"pre_ffw_norm"}
    got = lm.run_with_cache(params, tokens, cfg, ("blocks.4.hook_resid_pre",))
    assert _rel(got["blocks.4.hook_resid_pre"], ref.resid_pre(params, tokens, cfg, 4)) < RTOL


def test_attention_rotates_nothing(model, tokens):
    """No rotation op is emitted, and the attention layer's output does not
    depend on where the sequence starts."""
    cfg, params = model
    text = jax.jit(lambda p, t: lm.run_with_cache(p, t, cfg, (HOOK,))).lower(
        params, tokens).as_text(debug_info=True)
    assert "harvest/block/attn/rope" not in text and "harvest/block/attn" in text
    lp = {k: v[0] for k, v in params["layers"][2].items()}
    x = jax.random.normal(jax.random.key(3), (1, SEQ, cfg.d_model))
    kind = lm._layer_kind(cfg, jnp.int32(A_LAYER), lm.layer_classes(cfg)[2], jnp.int32(0))
    a0 = lm._attention(x, lp, cfg, kind)
    a7 = lm._attention(x, lp, cfg, kind, pos=jnp.arange(SEQ) + 7)
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a7))


# ---------------------------------------------------------------------------
# the hook contract of a layer of one sublayer


def test_an_empty_slot_captures_zeros_and_the_other_what_joins_the_stream(model, tokens):
    cfg, params = model
    hooks = [f"blocks.{i}.hook_{s}" for i in (M_LAYER, E_LAYER, A_LAYER)
             for s in ("resid_pre", "resid_post", "attn_out", "mlp_out")]
    _, c = lm.forward(params, tokens, cfg, capture=hooks, return_logits=False)
    for i, full in ((M_LAYER, "attn_out"), (E_LAYER, "mlp_out"), (A_LAYER, "attn_out")):
        empty = "mlp_out" if full == "attn_out" else "attn_out"
        assert not np.asarray(c[f"blocks.{i}.hook_{empty}"]).any(), (i, empty)
        step = c[f"blocks.{i}.hook_resid_post"] - c[f"blocks.{i}.hook_resid_pre"]
        np.testing.assert_allclose(np.asarray(c[f"blocks.{i}.hook_{full}"]), np.asarray(step),
                                   rtol=1e-5, atol=1e-5)


def test_an_edit_of_an_absent_slot_is_refused_and_of_the_other_reaches_the_stream(
        model, tokens):
    cfg, params = model
    for hp in (f"blocks.{M_LAYER}.hook_mlp_out", f"blocks.{E_LAYER}.hook_attn_out",
               f"blocks.{A_LAYER}.hook_mlp_out"):
        with pytest.raises(ValueError, match="has no"):
            lm.forward(params, tokens, cfg, edits=[lm.Edit(hp, lm.zero_edit)],
                       return_logits=False)
    hp = f"blocks.{E_LAYER}.hook_mlp_out"
    _, c = lm.forward(params, tokens, cfg, capture=(hp, "blocks.2.hook_resid_pre",
                                                    "blocks.1.hook_resid_pre"),
                      edits=[lm.Edit(hp, lm.zero_edit)], return_logits=False)
    assert not np.asarray(c[hp]).any()
    np.testing.assert_allclose(np.asarray(c["blocks.2.hook_resid_pre"]),
                               np.asarray(c["blocks.1.hook_resid_pre"]), atol=1e-6)


# ---------------------------------------------------------------------------
# the expert layer


def test_the_references_expert_choice_exactly(model):
    cfg, params = model
    lp = {k: v[0] for k, v in params["layers"][1].items() if k not in lm._HELD_LEAVES}
    x = jax.random.normal(jax.random.key(2), (96, cfg.d_model))
    idx, gates = moe.route(x, lp["router"], cfg.experts_per_tok, True, cfg.routed_scale,
                           "sigmoid_bias", lp["router_bias"])
    want_idx, want_gates = ref.routing(x, lp["router"], lp["router_bias"],
                                       cfg.experts_per_tok, True, cfg.routed_scale)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want_gates), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-5)


def test_the_shares_add_up():
    """16 experts over 4 ranks: the sum over ranks of the routed part plus the
    shared expert ONCE is the uncut layer's, and each rank's whole layer is
    the reference's given that share."""
    whole_cfg = nh.lm_config(CONFIG, TINY).replace(experts_held=0)
    whole = lm.init_params(jax.random.key(3), whole_cfg)
    u = jax.random.normal(jax.random.key(4), (2, SEQ, whole_cfg.d_model))
    stack = whole["layers"][1]
    lp = {k: (v if k in lm._HELD_LEAVES else v[0]) for k, v in stack.items()}
    y_whole = lm._mlp(u, lp, whole_cfg, 0)
    shared = lm._relu2_mlp(u, lp["ws_up"], lp["ws_down"])
    total = shared
    for rank in range(4):
        cfg_r = whole_cfg.replace(experts_held=4, expert_rank=rank)
        lp_r = {**lp, **{k: stack[k][:, 4 * rank: 4 * rank + 4] for k in ("we_up", "we_down")}}
        part = lm._mlp(u, lp_r, cfg_r, 0) - shared
        with jax.default_matmul_precision("highest"):
            want = ref.experts(u.reshape(-1, whole_cfg.d_model),
                               {k: v for k, v in lp_r.items() if k not in lm._HELD_LEAVES},
                               {k: lp_r[k] for k in ("we_up", "we_down")}, 0, cfg_r)
        assert _rel(part + shared, want.reshape(u.shape)) < RTOL
        total = total + part
    assert _rel(total, y_whole) < RTOL


def _state_dict(params, cfg, rng):
    """A dict under the ASSUMED checkpoint names, made from a tree: every
    expert of the model (the ones this rank does not hold are noise)."""
    sd = {"backbone.embeddings.weight": params["embed"],
          "backbone.norm_f.weight": params["final_norm"], "lm_head.weight": params["unembed"]}
    for cls, stack in zip(lm.layer_classes(cfg), params["layers"]):
        for s, i in enumerate(cls.layers):
            g = lambda k: np.asarray(stack[k][s], np.float32)    # noqa: E731
            m = f"backbone.layers.{i}.mixer."
            sd[f"backbone.layers.{i}.norm.weight"] = g(
                "attn_norm" if cls.kind != lm.NONE else "pre_ffw_norm")
            if cls.kind == lm.MAMBA:
                sd.update({m + "in_proj.weight": g("ssm_in").T, m + "out_proj.weight": g("ssm_out").T,
                           m + "conv1d.weight": g("ssm_conv_w").T[:, None, :],
                           m + "conv1d.bias": g("ssm_conv_b"), m + "dt_bias": g("ssm_dt_bias"),
                           m + "A_log": g("ssm_A_log"), m + "D": g("ssm_D"),
                           m + "norm.weight": g("ssm_norm")})
            elif cls.kind == lm.FULL:
                sd.update({m + f"{x}_proj.weight": g(f"w{x}").T for x in "qkvo"})
            else:
                sd.update({m + "gate.weight": g("router").T,
                           m + "gate.e_score_correction_bias": g("router_bias"),
                           m + "shared_experts.up_proj.weight": g("ws_up").T,
                           m + "shared_experts.down_proj.weight": g("ws_down").T})
                F = cfg.d_expert
                for e in range(cfg.n_experts):
                    h = e - cfg.first_expert
                    if 0 <= h < cfg.n_held:
                        up, down = np.asarray(stack["we_up"][s, h]).T, np.asarray(stack["we_down"][s, h])
                    else:
                        up = rng.normal(size=(cfg.d_model, F)).astype(np.float32)
                        down = rng.normal(size=(F, cfg.d_model)).astype(np.float32)
                    sd.update({m + f"experts.{e}.up_proj.weight": up.T,
                               m + f"experts.{e}.down_proj.weight": down.T})
    return sd


@pytest.mark.parametrize("rank", [0, 2])
def test_the_loader_on_a_dict_with_the_assumed_names(rank):
    cfg = nh.lm_config(CONFIG, TINY).replace(tie_embeddings=False, expert_rank=rank)
    params = lm.init_params(jax.random.key(7), cfg)
    loaded = lm.from_torch_state_dict(_state_dict(params, cfg, np.random.default_rng(0)), cfg)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(loaded), jax.tree.leaves(params)):
        assert a.dtype == b.dtype, path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(path))


def test_the_loader_is_chosen_by_name_not_by_the_tables_shape():
    """A table with an empty slot that names no convention is refused, not
    read under Nemotron-H's names."""
    cfg = nh.lm_config(CONFIG, TINY).replace(tie_embeddings=False, state_dict_names="")
    params = lm.init_params(jax.random.key(7), cfg)
    with pytest.raises(NotImplementedError, match="state_dict_names"):
        lm.from_torch_state_dict(_state_dict(params, cfg, np.random.default_rng(0)), cfg)
    assert lm.config_for("nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16").state_dict_names \
        == CONFIG["model_type"]


def test_tp_shardings_follow_the_tree_and_refuse_a_model_axis():
    from crosscoder_tpu.parallel import mesh as mesh_lib

    cfg = nh.lm_config(CONFIG, TINY)
    sh = lm.tp_shardings(mesh_lib.make_mesh(1, 1, devices=jax.devices()[:1]), cfg=cfg)
    params = jax.eval_shape(lambda k: lm.init_params(k, cfg), jax.random.key(0))
    assert jax.tree.structure(sh) == jax.tree.structure(params)
    for s, p in zip(jax.tree.leaves(sh), jax.tree.leaves(params)):
        assert len(s.spec) == p.ndim
    with pytest.raises(NotImplementedError, match="R3"):
        lm.tp_shardings(mesh_lib.make_mesh(1, 2, devices=jax.devices()[:2]), cfg=cfg)


# ---------------------------------------------------------------------------
# the counters


def test_the_forms_are_counted_once_a_trace(tmp_path):
    from crosscoder_tpu.data.buffer import make_buffer

    lm_cfg = nh.lm_config(CONFIG, {**TINY, "n_layers": 6})
    pair = [lm.init_params(jax.random.key(s), lm_cfg) for s in (1, 2)]
    corpus = np.random.default_rng(1).integers(1, lm_cfg.vocab_size, size=(64, 17))
    cfg = CrossCoderConfig(d_in=32, batch_size=64, seq_len=17, buffer_mult=4,
                           norm_calib_batches=2, model_batch_size=4,
                           hook_point="blocks.6.hook_resid_pre", dict_size=64,
                           log_backend="null", checkpoint_dir=str(tmp_path / "ckpt"),
                           obs="on", obs_dir=str(tmp_path / "obs"))
    plane = obs.acquire(cfg)
    try:
        buf = make_buffer(cfg, lm_cfg, pair, corpus)
        for _ in range(6):
            buf.next_raw()
        reg = plane.registry
        for name in ("ssm_traces", "ssm_scan_xla_traces", "moe_ungated_traces",
                     "moe_ragged_traces", "moe_held_traces", "moe_sigmoid_traces",
                     "attn_xla_traces"):
            assert reg.get_count(f"harvest/{name}") >= 1, name
        assert reg.get_count("harvest/moe_tiles_traces") == 0
    finally:
        plane.close()
