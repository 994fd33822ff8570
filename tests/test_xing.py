"""Xing4.0-29B-A4B through the program (crosscoder_tpu/models/lm.py: a stream
of n, the latent attention, the hook on the streams' mean;
crosscoder_tpu/ops/mhc.py: the stream maps; crosscoder_tpu/ops/moe.py:
sigmoid routing, the held share) against its plain reference
(benchmarks/reference/xing_ref.py), at a small size on the CPU, seeded random
weights, float32. Every harvest entry point is compared; the mean identity
holds; the ranks' shares add up to the uncut layer. The planted faults are in
tests/test_xing_faults.py (a file of their own: one worker a file)."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import manifest                       # noqa: E402
from benchmarks.arch import xing                      # noqa: E402
from benchmarks.reference import xing_ref             # noqa: E402
from crosscoder_tpu import obs                        # noqa: E402
from crosscoder_tpu.config import CrossCoderConfig    # noqa: E402
from crosscoder_tpu.models import lm                  # noqa: E402
from crosscoder_tpu.ops import mhc, moe               # noqa: E402

CONFIG = manifest.load_json(manifest.BENCH_DIR / "configs" / "xing4.0-pair-relu16k.json")
TINY = dict(vocab_size=257, d_model=32, n_layers=3, n_heads=4, n_kv_heads=4,
            head_dim=8, d_ff=64, dtype="fp32")
HOOK = "blocks.3.hook_resid_pre"
SEQ = 24

# Float32 on the CPU, the same mathematics in another order (scans over
# stacked leaves, grouped experts, tokens-minor Sinkhorn against Python loops
# over layers, heads, held experts and iterations): seen at 4e-7 … 8e-7
# relative. 5e-6 leaves that room; one bfloat16 rounding anywhere reads 1e-3.
RTOL = 5e-6


def _rel(got, want) -> float:
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.fixture(scope="module")
def model():
    cfg = xing.lm_config(CONFIG, TINY)
    return cfg, lm.init_params(jax.random.key(0), cfg)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(1, 257, size=(3, SEQ)))


# ---------------------------------------------------------------------------
# the published sizes


def test_the_published_sizes_by_name():
    cfg = xing.lm_config(CONFIG)
    whole = lm.LMConfig.xing4_0_29b()
    for key in ("vocab_size", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
                "n_experts", "experts_per_tok", "d_expert", "d_shared_expert",
                "routed_scale", "router", "n_streams", "hc_sinkhorn_iters", "hc_eps",
                "hc_clamp", "q_lora_rank", "kv_lora_rank", "qk_rope_dim", "v_head_dim",
                "rms_eps", "rope", "query_pre_attn_scalar", "norm_topk_prob"):
        assert getattr(cfg, key) == getattr(whole, key), key
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.v_head_dim) == (3584, 32, 192, 128)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_dim) == (768, 512, 64)
    assert (cfg.n_streams, cfg.hc_sinkhorn_iters, cfg.hc_clamp) == (4, 20, (-30.0, 30.0))
    assert (cfg.n_experts, cfg.n_held, cfg.experts_per_tok, cfg.d_expert) == (64, 16, 4, 1024)
    assert cfg.mlp_types == (lm.DENSE,) * 2 + (lm.SPARSE,) * 4
    assert whole.n_layers == 40 and whole.mlp_types.count(lm.DENSE) == 2
    assert cfg.query_pre_attn_scalar ** -0.5 == pytest.approx(192 ** -0.5 * 1.41589 ** 2, rel=1e-5)
    assert lm.config_for("XingChen-AGI/Xing4.0-29B-A4B") == whole
    assert [c.layers for c in lm.layer_classes(cfg)] == [(0, 1), (2, 3, 4, 5)]


def test_param_count_is_what_is_held_and_the_issues_table():
    """ISSUE 35's table, a model: embedding 469.8 M, layers 0-1 256.4 M,
    layers 2-5 outside the routed experts 161.4 M, the held routed experts
    704.6 M; the pair in bf16 6.37 GB."""
    cfg = xing.lm_config(CONFIG)
    shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg), jax.random.key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == lm.param_count(cfg)
    dense, sparse = (sum(x.size for x in jax.tree.leaves(s)) for s in shapes["layers"])
    experts = sum(shapes["layers"][1][k].size for k in ("we_gate_up", "we_down"))
    assert round(shapes["embed"].size / 1e6, 1) == 469.8
    assert round(dense / 1e6, 1) == 256.4
    assert round((sparse - experts) / 1e6, 1) == 161.4
    assert round(experts / 1e6, 1) == 704.6
    assert 6.36e9 < 2 * 2 * lm.param_count(cfg) < 6.38e9
    assert 29.0e9 < lm.param_count(lm.LMConfig.xing4_0_29b()) < 30.0e9
    small = xing.lm_config(CONFIG, TINY)
    tree = lm.init_params(jax.random.key(0), small)
    assert sum(x.size for x in jax.tree.leaves(tree)) == lm.param_count(small)


def test_the_needed_flops_and_bytes_of_the_cell():
    cfg = xing.lm_config(CONFIG)
    total = xing.flops_per_token(cfg, 6, 4096)
    assert total == pytest.approx(1.177e9, rel=2e-3)
    assert xing.expert_share_of_flops(cfg, 6, 4096) == pytest.approx(0.075, abs=2e-3)
    assert xing.attn_core_share_of_flops(cfg, 6, 4096) == pytest.approx(0.214, abs=2e-3)
    # (n + 1) + (2n + 1) = 14 stream-widths of bf16 a sublayer
    assert xing.mhc_bytes_per_token(cfg, 6) == 6 * 2 * 14 * 3584 * 2
    assert xing.mhc_bytes_over_flops(cfg, 6, 4096) * total == xing.mhc_bytes_per_token(cfg, 6)


# ---------------------------------------------------------------------------
# every forward against the plain reference


def test_run_with_cache_multi_against_the_reference(model, tokens):
    cfg, params = model
    other = lm.init_params(jax.random.key(1), cfg)
    got = lm.run_with_cache_multi([params, other], tokens, cfg, (HOOK,))
    assert got.shape == (3, SEQ, 2, cfg.d_model)
    for m, p in enumerate((params, other)):
        assert _rel(got[:, :, m], xing_ref.resid_pre(p, tokens, cfg, 3)) < RTOL


def test_forward_with_logits_through_the_heads_read(model, tokens):
    cfg, params = model
    cfg = cfg.replace(tie_embeddings=False)
    params = lm.init_params(jax.random.key(0), cfg)
    logits, cache = lm.forward(params, tokens, cfg, capture=("blocks.1.hook_resid_pre",))
    assert _rel(logits, xing_ref.logits(params, tokens, cfg)) < RTOL
    assert _rel(cache["blocks.1.hook_resid_pre"], xing_ref.resid_pre(params, tokens, cfg, 1)) < RTOL


def test_segmented_harvest_in_its_quanta(model, tokens):
    cfg, params = model
    assert lm.SegmentedHarvest.quanta(3, 3, [1, 2]) == [1, 3]
    whole = lm.run_with_cache_multi([params, params], tokens, cfg, (HOOK,))
    job = lm.SegmentedHarvest([params, params], tokens, cfg, (HOOK,))
    assert job.n_steps == 4
    steps = 0
    while job.step():
        steps += 1
    assert steps + 1 == job.n_steps
    np.testing.assert_array_equal(np.asarray(job.result()), np.asarray(whole))
    many = lm.SegmentedHarvest([params, params], tokens, cfg, (HOOK,))
    while many.step_many(2)[1]:
        pass
    np.testing.assert_array_equal(np.asarray(many.result()), np.asarray(whole))


def test_paged_capture_carries_the_streams(model, tokens):
    cfg, params = model
    lengths = np.asarray([SEQ, SEQ - 7, 5])
    got = lm.run_with_cache_multi_paged(
        [params], np.asarray(tokens), lengths, cfg, (HOOK,), page_size=8)
    want = xing_ref.resid_pre(params, tokens, cfg, 3)
    for d, n in enumerate(lengths):
        # (a document's rows depend on its own earlier tokens only)
        assert _rel(got[d, :n, 0], want[d, :n]) < RTOL
        assert not np.asarray(got[d, n:]).any()
    full = lm.run_with_cache_multi_paged(
        [params], np.asarray(tokens), np.full(3, SEQ), cfg, (HOOK,), page_size=8)
    np.testing.assert_array_equal(
        np.asarray(full), np.asarray(lm.run_with_cache_multi([params], tokens, cfg, (HOOK,))))


def test_sequence_sharded_body_carries_the_streams(model, tokens):
    from crosscoder_tpu.parallel import mesh as mesh_lib

    cfg, params = model
    n = min(4, len(jax.devices()))
    if SEQ % n:
        pytest.skip(f"{n} devices do not divide {SEQ}")
    mesh = mesh_lib.make_mesh(n, 1, devices=jax.devices()[:n])
    got = lm.run_with_cache_multi_seq_parallel([params], tokens, cfg, (HOOK,), mesh)
    assert _rel(got[:, :, 0], xing_ref.resid_pre(params, tokens, cfg, 3)) < RTOL


# ---------------------------------------------------------------------------
# the hook: the streams' mean


def test_the_mean_identity(model, tokens):
    """Captured ``resid_pre[L+1] − resid_pre[L]`` is captured ``attn_out[L] +
    mlp_out[L]`` to Sinkhorn's remainder (``mhc_col_err`` times the streams'
    spread), and ``resid_post[L]`` IS ``resid_pre[L+1]``."""
    cfg, params = model
    hooks = [f"blocks.{layer}.hook_{site}" for layer in range(3)
             for site in ("resid_pre", "attn_out", "mlp_out", "resid_post")]
    _, c = lm.forward(params, tokens, cfg, capture=hooks, return_logits=False)
    err = np.asarray(lm.mhc_col_err(params, tokens, cfg, 3))
    assert err.shape == (3,) and 0 < err.max() < 0.05
    for layer in range(3):
        step = c[f"blocks.{layer}.hook_resid_post"] - c[f"blocks.{layer}.hook_resid_pre"]
        added = c[f"blocks.{layer}.hook_attn_out"] + c[f"blocks.{layer}.hook_mlp_out"]
        assert _rel(added, step) < 3 * float(err[layer]) + 1e-5, layer
        assert _rel(added, step) < 0.02
    np.testing.assert_array_equal(np.asarray(c["blocks.0.hook_resid_post"]),
                                  np.asarray(c["blocks.1.hook_resid_pre"]))
    # layer 0 enters with every stream the embedding: their mean is it
    np.testing.assert_allclose(np.asarray(c["blocks.0.hook_resid_pre"]),
                               np.asarray(params["embed"][tokens]), rtol=1e-6, atol=1e-6)
    # ... and 40 iterations for 20 leave a smaller remainder
    more = np.asarray(lm.mhc_col_err(params, tokens, cfg.replace(hc_sinkhorn_iters=40), 3))
    assert more.max() < err.max()


def test_a_splice_at_L_is_what_the_next_capture_reads(model, tokens):
    cfg, params = model
    value = jax.random.normal(jax.random.key(5), (3, SEQ, cfg.d_model))
    at = "blocks.2.hook_resid_pre"
    _, c = lm.forward(params, tokens, cfg, capture=(at, "blocks.1.hook_resid_post"),
                      edits=[lm.Edit(at, lm.splice_edit, value)], return_logits=False)
    _, clean = lm.forward(params, tokens, cfg, capture=(at,), return_logits=False)
    np.testing.assert_allclose(np.asarray(c[at][:, 1:]), np.asarray(value[:, 1:]), atol=2e-6)
    np.testing.assert_allclose(np.asarray(c[at][:, :1]), np.asarray(clean[at][:, :1]), atol=2e-6)
    _, z = lm.forward(params, tokens, cfg, capture=(at,),
                      edits=[lm.Edit(at, lm.zero_edit)], return_logits=False)
    assert float(jnp.max(jnp.abs(z[at]))) < 2e-6
    # the streams' deviations from their mean stay: the edit moves the loss
    # less than replacing every stream would, but it moves it
    cfg_h = cfg.replace(tie_embeddings=False)
    p_h = lm.init_params(jax.random.key(0), cfg_h)
    base = float(lm.ce_loss(p_h, tokens, cfg_h))
    cut = float(lm.ce_loss(p_h, tokens, cfg_h, [lm.Edit(at, lm.zero_edit)]))
    assert np.isfinite(cut) and abs(cut - base) > 1e-4


def test_sublayer_edits_act_on_y(model, tokens):
    cfg, params = model
    at = "blocks.1.hook_mlp_out"
    _, c = lm.forward(params, tokens, cfg, capture=(at, "blocks.1.hook_resid_post",
                                                    "blocks.1.hook_attn_out",
                                                    "blocks.1.hook_resid_pre"),
                      edits=[lm.Edit(at, lm.zero_edit)], return_logits=False)
    assert not np.asarray(c[at]).any()
    step = c["blocks.1.hook_resid_post"] - c["blocks.1.hook_resid_pre"]
    assert _rel(c["blocks.1.hook_attn_out"], step) < 0.02


def test_one_stream_is_todays_capture_edit_tree_and_jaxpr():
    """With n = 1 a read is the stream and a write an add: the tree has no
    map leaf, the carry is [B, S, D], and the traced block is the plain one."""
    cfg = lm.LMConfig.tiny()
    params = lm.init_params(jax.random.key(0), cfg)
    assert not [k for k in params["layers"] if k.startswith("hc_")]
    assert "hc_head_phi" not in params
    tok = jnp.asarray(np.random.default_rng(0).integers(1, 257, size=(2, 16)))
    resid, buf = lm._fresh_carry(params, tok, cfg, 1)
    assert resid.shape == (2, 16, cfg.d_model)
    text = str(jax.make_jaxpr(
        lambda p, t: lm.run_with_cache(p, t, cfg, ("blocks.2.hook_resid_pre",)))(params, tok))
    assert text.count("logistic") == 0 and "mhc" not in text     # no map, no Sinkhorn
    # capture and edits are the stream's own
    value = jnp.ones((2, 16, cfg.d_model))
    at = "blocks.1.hook_resid_pre"
    _, c = lm.forward(params, tok, cfg, capture=(at,),
                      edits=[lm.Edit(at, lm.replace_edit, value)], return_logits=False)
    np.testing.assert_array_equal(np.asarray(c[at]), np.asarray(value))


# ---------------------------------------------------------------------------
# routing, the share, the loader


def test_the_references_expert_choice_exactly_and_float32_gates_under_bf16(model, tokens):
    cfg, params = model
    lp = {k: v[0] for k, v in params["layers"][1].items() if k not in lm._HELD_LEAVES}
    x = jax.random.normal(jax.random.key(2), (96, cfg.d_model))
    idx, gates = moe.route(x, lp["router"], cfg.experts_per_tok, True, cfg.routed_scale,
                           "sigmoid_bias", lp["router_bias"])
    want_idx, want_gates = xing_ref.routing(
        x, lp["router"], lp["router_bias"], cfg.experts_per_tok, True, cfg.routed_scale)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want_gates), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), cfg.routed_scale, rtol=1e-5)
    # the bias changes choices, never gates: the chosen gate is the UNBIASED score
    plain_idx, _ = moe.route(x, lp["router"], cfg.experts_per_tok, True, cfg.routed_scale,
                             "sigmoid_bias", jnp.zeros_like(lp["router_bias"]))
    assert (np.asarray(plain_idx) != np.asarray(idx)).any()
    bf = moe.route(x.astype(jnp.bfloat16), lp["router"].astype(jnp.bfloat16),
                   cfg.experts_per_tok, True, cfg.routed_scale, "sigmoid_bias",
                   lp["router_bias"])
    assert bf[1].dtype == jnp.float32


def test_the_shares_add_up():
    """16 experts over 4 ranks: the sum over ranks of the routed part plus the
    shared expert ONCE is the uncut layer's y."""
    whole_cfg = xing.lm_config(CONFIG, TINY).replace(experts_held=0)
    whole = lm.init_params(jax.random.key(3), whole_cfg)
    u = jax.random.normal(jax.random.key(4), (2, SEQ, whole_cfg.d_model))
    stack = whole["layers"][1]
    lp = {k: (v if k in lm._HELD_LEAVES else v[0]) for k, v in stack.items()}
    y_whole = lm._mlp(u, lp, whole_cfg, 0)
    shared = lm._gated_mlp(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"], whole_cfg)
    total = shared
    for rank in range(4):
        cfg_r = whole_cfg.replace(experts_held=4, expert_rank=rank)
        lp_r = {**lp, **{k: stack[k][:, 4 * rank: 4 * rank + 4] for k in lm._HELD_LEAVES
                           if k in stack}}
        part = lm._mlp(u, lp_r, cfg_r, 0) - shared
        want = xing_ref.mlp(u, {k: v for k, v in lp_r.items() if k not in lm._HELD_LEAVES},
                            {k: lp_r[k] for k in lm._HELD_LEAVES if k in lp_r}, 0, cfg_r)
        assert _rel(part + shared, want) < RTOL
        total = total + part
    assert _rel(total, y_whole) < RTOL


def _state_dict(params, cfg, rng):
    """A dict under the ASSUMED checkpoint names, made from a tree: q_b and
    kv_b a head, the rotary columns INTERLEAVED, every expert of the model
    (the ones this rank does not hold are noise)."""
    H, dr, dv, rkv = cfg.n_heads, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    dn = cfg.head_dim - dr
    inter = np.empty(dr, int)
    inter[0::2], inter[1::2] = np.arange(dr // 2), np.arange(dr // 2, dr)   # split-half -> pairs
    sd = {"model.embed_tokens.weight": params["embed"], "model.norm.weight": params["final_norm"],
          "lm_head.weight": params["unembed"],
          "model.hc_head_fn.weight": params["hc_head_phi"].T,
          "model.hc_head_scale": params["hc_head_alpha"],
          "model.hc_head_base": params["hc_head_bias"]}
    for cls, stack in zip(lm.layer_classes(cfg), params["layers"]):
        for s, i in enumerate(cls.layers):
            g = lambda k: np.asarray(stack[k][s], np.float32)    # noqa: E731
            a = f"model.layers.{i}.self_attn."
            q_b = np.concatenate([g("wq_nope").reshape(-1, H, dn),
                                  g("wq_rope").reshape(-1, H, dr)[..., inter]], -1)
            kv_b = np.concatenate([g("wk_nope").reshape(rkv, H, dn),
                                   g("wv").reshape(rkv, H, dv)], -1)
            kv_a = g("wkv_a")
            sd.update({
                f"model.layers.{i}.input_layernorm.weight": g("attn_norm"),
                f"model.layers.{i}.post_attention_layernorm.weight": g("pre_ffw_norm"),
                a + "q_a_proj.weight": g("wq_a").T, a + "q_a_layernorm.weight": g("q_a_norm"),
                a + "q_b_proj.weight": q_b.reshape(-1, H * (dn + dr)).T,
                a + "kv_a_proj_with_mqa.weight": np.concatenate(
                    [kv_a[:, :rkv], kv_a[:, rkv:][:, inter]], 1).T,
                a + "kv_a_layernorm.weight": g("kv_a_norm"),
                a + "kv_b_proj.weight": kv_b.reshape(rkv, H * (dn + dv)).T,
                a + "o_proj.weight": g("wo").T})
            for site in ("attn", "ffn"):
                sd.update({f"model.layers.{i}.hc_{site}_fn.weight": g(f"hc_{site}_phi").T,
                           f"model.layers.{i}.hc_{site}_scale": g(f"hc_{site}_alpha"),
                           f"model.layers.{i}.hc_{site}_base": g(f"hc_{site}_bias")})
            m = f"model.layers.{i}.mlp."
            if cls.mlp == lm.DENSE:
                sd.update({m + "gate_proj.weight": g("w_gate").T, m + "up_proj.weight": g("w_up").T,
                           m + "down_proj.weight": g("w_down").T})
                continue
            sd.update({m + "gate.weight": g("router").T,
                       m + "gate.e_score_correction_bias": g("router_bias"),
                       m + "shared_experts.gate_proj.weight": g("ws_gate").T,
                       m + "shared_experts.up_proj.weight": g("ws_up").T,
                       m + "shared_experts.down_proj.weight": g("ws_down").T})
            F = cfg.d_expert
            for e in range(cfg.n_experts):
                h = e - cfg.first_expert
                if 0 <= h < cfg.n_held:
                    gu, dn_w = g("we_gate_up")[h], g("we_down")[h]
                else:
                    gu = rng.normal(size=(cfg.d_model, 2 * F)).astype(np.float32)
                    dn_w = rng.normal(size=(F, cfg.d_model)).astype(np.float32)
                sd.update({m + f"experts.{e}.gate_proj.weight": gu[:, :F].T,
                           m + f"experts.{e}.up_proj.weight": gu[:, F:].T,
                           m + f"experts.{e}.down_proj.weight": dn_w.T})
    return sd


@pytest.mark.parametrize("rank", [0, 2])
def test_the_loader_on_a_dict_with_the_assumed_names(rank):
    cfg = xing.lm_config(CONFIG, TINY).replace(tie_embeddings=False, expert_rank=rank)
    params = lm.init_params(jax.random.key(7), cfg)
    sd = _state_dict(params, cfg, np.random.default_rng(0))
    loaded = lm.from_torch_state_dict(sd, cfg)
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(loaded), jax.tree.leaves(params)):
        assert a.dtype == b.dtype, path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(path))
    # the rotary columns really were permuted: the interleaved dict is not the tree
    raw = np.asarray(sd["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"]).T
    assert not np.array_equal(raw[:, cfg.kv_lora_rank:],
                              np.asarray(params["layers"][0]["wkv_a"][0])[:, cfg.kv_lora_rank:])


def test_tp_shardings_follow_the_tree():
    from crosscoder_tpu.parallel import mesh as mesh_lib

    cfg = xing.lm_config(CONFIG, TINY)
    mesh = mesh_lib.make_mesh(1, 1, devices=jax.devices()[:1])
    sh = lm.tp_shardings(mesh, cfg=cfg)
    params = jax.eval_shape(lambda k: lm.init_params(k, cfg), jax.random.key(0))
    assert jax.tree.structure(sh) == jax.tree.structure(params)
    for s, p in zip(jax.tree.leaves(sh), jax.tree.leaves(params)):
        assert len(s.spec) == p.ndim


def test_the_gauges_are_read_once_at_calibration_and_only_with_obs_on(tmp_path):
    from crosscoder_tpu.data.buffer import make_buffer

    lm_cfg = xing.lm_config(CONFIG, {**TINY, "n_layers": 2})
    pair = [lm.init_params(jax.random.key(s), lm_cfg) for s in (1, 2)]
    tokens = np.random.default_rng(1).integers(1, lm_cfg.vocab_size, size=(64, 17))
    base = dict(d_in=32, batch_size=64, seq_len=17, buffer_mult=4, norm_calib_batches=2,
                model_batch_size=4, hook_point="blocks.2.hook_resid_pre", dict_size=64,
                log_backend="null", checkpoint_dir=str(tmp_path / "ckpt"))
    calls = []
    real = lm.mhc_col_err
    lm.mhc_col_err = lambda *a, **k: calls.append(1) or real(*a, **k)
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
            err = plane.registry.get_gauge("harvest/mhc_col_err")
            assert 0.0 < err < 0.1
            assert plane.snapshot()["harvest/mhc_col_err"] == err
            assert 0.0 < plane.registry.get_gauge("harvest/moe_local_row_share") < 1.0
            for counter in ("harvest/mhc_xla_traces", "harvest/attn_latent_traces",
                            "harvest/moe_sigmoid_traces", "harvest/moe_held_traces"):
                assert plane.registry.get_count(counter) >= 1, counter
            assert plane.registry.get_count("harvest/mhc_kernel_traces") == 0
        finally:
            plane.close()
    finally:
        lm.mhc_col_err = real
