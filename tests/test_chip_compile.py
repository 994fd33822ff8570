"""AOT compiles for the described chip (``on-chip-measurement`` guide §2.3).

The TPU's compiler is installed here and compiles for a v5e that is
described, not attached — so every PR can ask it, at no chip time, whether
the main-path programs and each Pallas family still compile at real
widths. Nothing runs: a compile that passes says nothing about results or
times, and is never reported as a chip run (``chip_smoke.py`` is that).

Code that asks ``jax.default_backend()`` still sees the CPU here, so the
tests steer it (and the ``ops/dispatch`` gate) by monkeypatching — in the
test, not through an option of the program.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.models import crosscoder as cc
from crosscoder_tpu.models import lm
from crosscoder_tpu.ops import activations as act_ops
from crosscoder_tpu.ops import dispatch

HBM_BYTES = 16 * 10**9          # one v5e chip
BATCH, ND, K = 4096, 4608, 32   # production batch; n_sources·d_in; topk_k
HOOK_LAYER = 14


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no libtpu / unknown topology
        pytest.skip(f"cannot describe a v5e:2x2 topology here: "
                    f"{type(e).__name__}: {str(e)[:200]}")


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to JAX's persistent cache
    but cannot be read back without the chip (the next one warns and
    recompiles) — keep the cache out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def chip(topo, monkeypatch):
    """One described chip, with backend-sniffing code steered onto its TPU
    branch (the default TopK tier dispatches on ``default_backend()``)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    act_ops._backend_is_tpu.cache_clear()
    yield SingleDeviceSharding(topo.devices[0])
    act_ops._backend_is_tpu.cache_clear()


@pytest.fixture
def gates_open(monkeypatch):
    """Every opt-in kernel family dispatches (what CROSSCODER_PALLAS=all
    does on a chip), including modules that bound the gate by value."""
    import sys

    real = dispatch.hw_kernel_enabled
    always = lambda env_var, interpret: True   # noqa: E731
    for m in list(sys.modules.values()):
        if getattr(m, "hw_kernel_enabled", None) is real:
            monkeypatch.setattr(m, "hw_kernel_enabled", always)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, sharding), tree)


def _lm_pair(sharding):
    cfg = lm.LMConfig.gemma2_2b().replace(n_layers=HOOK_LAYER)
    one = jax.eval_shape(lambda k: lm.init_params(k, cfg), jax.random.key(0))
    return cfg, (_abstract(one, sharding),) * 2


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit one chip"
    return total


# ---------------------------------------------------------------------------
# main-path programs at real width


def _compiled_train_step(cfg, mesh, with_metrics: bool):
    """The jitted train step exactly as the Trainer builds it, compiled
    for ``mesh`` from shapes alone."""
    from crosscoder_tpu.parallel import mesh as mesh_lib
    from crosscoder_tpu.train import schedules
    from crosscoder_tpu.train.state import init_train_state, make_optimizer
    from crosscoder_tpu.train.trainer import make_train_step

    tx = make_optimizer(cfg, schedules.lr_schedule(cfg))
    state = jax.eval_shape(lambda k: init_train_state(k, cfg, tx),
                           jax.random.key(0))
    step = make_train_step(cfg, mesh, tx, mesh_lib.state_shardings(mesh, state),
                           with_metrics=with_metrics)
    compiled = step.lower(
        state,
        jax.ShapeDtypeStruct((BATCH, cfg.n_sources, cfg.d_in), jnp.bfloat16),
        jax.ShapeDtypeStruct((cfg.n_sources,), jnp.float32),
    ).compile()
    _fits(compiled)
    return compiled


@pytest.mark.parametrize("leg", ["relu-2^14", "topk-2^15"])
def test_train_step_compiles(chip, topo, leg):
    """The whole train step (value_and_grad of training_loss, clip, Adam) at
    the production shape of each smoke leg. The default TopK tier must be
    the kernel, not its XLA stand-in. (This process sees eight devices, so
    the TopK step is the dense one a mesh keeps.)"""
    from crosscoder_tpu.parallel import mesh as mesh_lib

    over = (dict(dict_size=2**14) if leg.startswith("relu") else
            dict(dict_size=2**15, activation="topk", topk_k=K, l1_coeff=0.0))
    cfg = CrossCoderConfig(log_backend="null", **over)
    compiled = _compiled_train_step(
        cfg, mesh_lib.make_mesh(devices=topo.devices[:1]), with_metrics=False)
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (cfg.activation == "topk")
    assert "topk_rows" not in text


def test_topk_step_compiles_in_its_row_form(chip, topo, one_device):
    """The cell train-live-topk32k's step (2 x 2048 wide, dict 2^15, k 32,
    batch 4096) from a process that sees ONE device: every k-sparse product
    goes through rows fetched by DMA — the pack of W_dec, the decode, d_vals
    and the latent-major pass are in the compiled step, and of the dense
    [4096 x 4096 x 32768] products only the encode is left. (The variant
    with metrics holds the same kernels and is three minutes more of
    compiling: the chip's runs compile it.)"""
    from crosscoder_tpu.parallel import mesh as mesh_lib

    cfg = CrossCoderConfig(log_backend="null", d_in=2048, dict_size=2**15,
                           activation="topk", topk_k=K, l1_coeff=0.0)
    assert cc.rows_live(cfg, BATCH) and cc.use_factored_decode(cfg, BATCH)
    assert cc.use_sparse_bwd(cfg, BATCH) and not cc.use_fused_encoder(cfg, BATCH)
    text = _compiled_train_step(
        cfg, mesh_lib.make_mesh(devices=topo.devices[:1]), with_metrics=False).as_text()
    calls = [line.split(" = ")[0] for line in text.splitlines() if "tpu_custom_call" in line]
    for name in ("rows_pack", "topk_rows_decode", "topk_rows_dvals", "topk_rows_grads"):
        assert [c for c in calls if name in c], f"{name} is not in the compiled step"
    big = [line for line in text.splitlines()
           if " convolution(" in line and "4096,32768" in line.split(" = ")[0]]
    assert len(big) <= 1, big


def test_harvest_forward_compiles(chip):
    """The 2-model, 14-block capture forward at one harvest chunk."""
    cfg, pair = _lm_pair(chip)
    compiled = lm._multi_cache_impl.lower(
        pair, _sds((4, 1024), jnp.int32, chip), cfg=cfg,
        capture=(f"blocks.{HOOK_LAYER}.hook_resid_pre",),
    ).compile()
    _fits(compiled)


def test_serve_pair_compiles(chip):
    """One serve bucket: the paged prefill, then encode→TopK→diff on its
    captures (the two executables the engine builds per bucket)."""
    from crosscoder_tpu.serve import step as serve_step

    b, S = 2, 1024
    lm_cfg, pair = _lm_pair(chip)
    hook = f"blocks.{HOOK_LAYER}.hook_resid_pre"
    i32 = lambda *shape: _sds(shape, jnp.int32, chip)   # noqa: E731
    prefill = lm._paged_multi_impl.lower(
        pair, i32(b, S), i32(b, S), i32(b, S), i32(b, S), i32(b),
        cfg=lm_cfg, capture=lm._hook_layers(lm_cfg, (hook,)),
        n_scan=HOOK_LAYER, page_size=64, use_kernel=False, pad_mode="zero",
        out_dtype=None,
    ).compile()
    _fits(prefill)
    cfg = CrossCoderConfig(dict_size=2**15, activation="topk", topk_k=K,
                           l1_coeff=0.0, log_backend="null")
    params = _abstract(jax.eval_shape(
        lambda k: cc.init_params(k, cfg, jnp.float32), jax.random.key(0)), chip)
    encode = serve_step.encode_topk_diff.lower(
        params, _sds((b, S, 2, 2304), jnp.bfloat16, chip), i32(b),
        _sds((2,), jnp.float32, chip), enc_dtype="bf16", k=K,
        fused=cc.use_fused_encoder(cfg, b), pair=(0, 1),
    ).compile()
    _fits(encode)


# ---------------------------------------------------------------------------
# one compile per Pallas family (the opt-in ones with their gate steered on)


def _compiles(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the XLA reference took the kernel's place"
    return text


@pytest.mark.parametrize("width", [2**15, 2**17])
def test_topk_family_compiles(chip, width):
    from crosscoder_tpu.ops import topk_pallas

    h = _sds((BATCH, width), jnp.bfloat16, chip)
    _compiles(lambda x: topk_pallas.topk(x, K), h)
    _compiles(jax.grad(lambda x: topk_pallas.topk(x, K).astype(jnp.float32).sum()), h)
    _compiles(lambda x: topk_pallas.sparsify(x, K), h)
    _compiles(lambda x: topk_pallas.batchtopk(x, K), h)


def test_fused_encoder_family_compiles(chip):
    from crosscoder_tpu.ops import fused_encoder_topk as fek

    x = _sds((BATCH, ND), jnp.bfloat16, chip)
    W = _sds((ND, 2**15), jnp.bfloat16, chip)
    b = _sds((2**15,), jnp.bfloat16, chip)
    _compiles(lambda x, W, b: fek.fused_topk_encode(x, W, b, K), x, W, b)
    _compiles(lambda x, W, b: fek.fused_batchtopk_encode_raw(x, W, b, K), x, W, b)


def test_quant_family_compiles(chip, gates_open):
    from crosscoder_tpu.ops import quant

    _compiles(lambda x: quant.quantize_rows(x, 256),
              _sds((BATCH, ND), jnp.bfloat16, chip))


@pytest.fixture
def one_device(monkeypatch):
    """The fused attention dispatches from a process that sees ONE device
    (a pallas_call is not partitioned over a mesh); this one sees eight
    virtual CPU devices."""
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)


def _ouro_cfg() -> lm.LMConfig:
    """The Ouro cells' subject LM (``benchmarks/configs/ouro2.6b-pair-*``)."""
    return lm.LMConfig(
        vocab_size=49_152, d_model=2048, n_layers=HOOK_LAYER, n_heads=16,
        n_kv_heads=16, head_dim=128, d_ff=5632, query_pre_attn_scalar=128.0)


@pytest.mark.parametrize("heads", ["ouro-16x128", "gemma2-2b-8/4x256"])
def test_fused_attention_compiles_inside_the_harvest(chip, one_device, heads):
    """The refill's segment program (``_seg_scan_impl``, 3 blocks of a
    4-sequence chunk at seq 1024, bf16) with the fused causal attention in
    its layer scan: at the benchmark cells' heads (16 Q and KV x 128, the
    4096 window inert) and at Gemma-2-2B's (8 Q / 4 KV x 256)."""
    from crosscoder_tpu.ops import flash_attention as fa

    cfg = (_ouro_cfg() if heads.startswith("ouro")
           else lm.LMConfig.gemma2_2b().replace(n_layers=HOOK_LAYER))
    B, S = 4, 1024
    assert fa.supported(S, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, jnp.bfloat16)
    params = _abstract(jax.eval_shape(
        lambda k: lm.init_params(k, cfg), jax.random.key(0)), chip)
    capture = lm._hook_layers(cfg, (f"blocks.{HOOK_LAYER}.hook_resid_pre",))
    compiled = lm._seg_scan_impl.lower(
        params, _sds((B, S, cfg.d_model), jnp.bfloat16, chip),
        _sds((1, B, S, cfg.d_model), jnp.bfloat16, chip),
        _sds((), jnp.int32, chip), cfg=cfg, capture=capture, k=3,
    ).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the XLA form took the kernel's place"
    # the kernel reads and writes the projections' own [B, S, H*hd] layout:
    # nothing head-major is ever built
    assert f"[{B},{cfg.n_heads},{S},{cfg.head_dim}]" not in text


@pytest.mark.parametrize("table", ["one-class", "two-class"])
def test_harvest_segment_reads_its_weights_where_they_lie(chip, one_device, table):
    """A refill quantum that is a strict SUB-RANGE of its class's stack
    copies none of it: the scan's body indexes the whole stack, so the
    compiled ``_seg_scan_impl`` holds no array of the quantum's ``k`` layers
    of a weight (what a ``dynamic_slice`` in front of the ``while`` made:
    206 MB read and written a layer-call at Ouro's widths, 8.8 ms a step of
    the Ouro cells), and its temporaries stay under ONE layer's leaves plus
    the carries. At the Ouro cells' table (one class of 14, ``k`` 3) and on
    a table of two classes whose second stack (4) is deeper than its quantum
    (2) — the xing cell's shape of the problem, at small widths."""
    import math
    import re

    if table == "one-class":
        cfg, hook, (B, S), k, cls = _ouro_cfg(), HOOK_LAYER, (4, 1024), 3, None
    else:
        cfg = lm.LMConfig(
            vocab_size=4096, d_model=1024, n_layers=6, n_heads=8, n_kv_heads=4,
            head_dim=128, d_ff=4096, query_pre_attn_scalar=128.0,
            heads_by_layer=(4, 4, 8, 8, 8, 8))
        hook, (B, S), k, cls = 6, (1, 256), 2, 1
        assert [len(c.layers) for c in lm.layer_classes(cfg)] == [2, 4]
    params = _abstract(jax.eval_shape(
        lambda key: lm.init_params(key, cfg), jax.random.key(0)), chip)
    carries = (_sds((B, S, cfg.d_model), jnp.bfloat16, chip),
               _sds((1, B, S, cfg.d_model), jnp.bfloat16, chip))
    compiled = lm._seg_scan_impl.lower(
        params, *carries, _sds((), jnp.int32, chip), cfg=cfg,
        capture=lm._hook_layers(cfg, (f"blocks.{hook}.hook_resid_pre",)),
        k=k, cls=cls,
    ).compile()
    stack = lm.class_stacks(params, cfg)[cls or 0]
    assert all(v.shape[0] > k for v in stack.values())      # a strict sub-range
    results = [(line, m.group(1)) for line in compiled.as_text().splitlines()
               if (m := re.search(r" = (\w+\[[\d,]*\])", line))]
    under_leaves = [line for line, shape in results
                    if "harvest/leaves" in line and f"[{k}," in shape]
    assert not under_leaves, under_leaves[:3]
    cut = {"[" + ",".join(map(str, (k, *v.shape[1:]))) + "]"
           for v in stack.values() if v.ndim == 3}
    copies = [line for line, shape in results if shape[shape.index("["):] in cut]
    assert not copies, copies[:3]
    nbytes = lambda v: math.prod(v.shape) * v.dtype.itemsize   # noqa: E731
    one_layer = sum(nbytes(v) // v.shape[0] for v in stack.values())
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < one_layer + sum(map(nbytes, carries)), (temp, one_layer)


def test_fused_attention_window_pair_compiles(chip, one_device):
    """A window that binds (512 of 1024): ``lax.cond`` between the windowed
    and the causal instance on the traced layer parity."""
    cfg = lm.LMConfig(
        vocab_size=4096, d_model=512, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=128, d_ff=1024, sliding_window=512)
    params = _abstract(jax.eval_shape(
        lambda k: lm.init_params(k, cfg), jax.random.key(0)), chip)
    text = lm._forward_impl.lower(
        params, _sds((2, 1024), jnp.int32, chip), cfg=cfg, capture=(),
        edit_fns=(), edit_layers=(), edit_values=(), return_logits=True,
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


def test_sparse_expert_harvest_segment_compiles(chip, one_device):
    """The third cell's refill quantum at its published widths: two blocks of
    Mellum2 (``benchmarks/configs/mellum2-pair-relu16k.json``) over one
    4096-token sequence — the window (1024) and the full instance of the
    fused attention at 32 Q / 4 KV heads, and the expert layer's three
    kernels: the grouped product's two over 64 experts of 2304 x 896, whose
    stacked weights reach them whole, and the combine, which reads
    ``moe_down``'s rows in place (no gathered ``[T*k, D]`` copy is left)."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks import manifest
    from benchmarks.arch import mellum
    from crosscoder_tpu.ops import flash_attention as fa
    from crosscoder_tpu.ops import moe, row_gather

    cfg = mellum.lm_config(manifest.load_json(
        manifest.BENCH_DIR / "configs" / "mellum2-pair-relu16k.json"))
    S = 4096
    assert fa.supported(S, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, jnp.bfloat16)
    assert moe.enabled() and moe.supported(cfg.d_model, cfg.d_expert, jnp.bfloat16)
    assert row_gather.supported(S, cfg.experts_per_tok, cfg.d_model, jnp.bfloat16)
    params = _abstract(jax.eval_shape(
        lambda k: lm.init_params(k, cfg), jax.random.key(0)), chip)
    compiled = lm._seg_scan_impl.lower(
        params, _sds((1, S, cfg.d_model), jnp.bfloat16, chip),
        _sds((1, 1, S, cfg.d_model), jnp.bfloat16, chip),
        _sds((), jnp.int32, chip), cfg=cfg,
        capture=lm._hook_layers(cfg, ("blocks.4.hook_resid_pre",)), k=2,
    ).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 5      # attention x2, gate_up, down, combine
    assert "moe_gate_up" in text and "moe_down" in text
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    combine = [line for line in calls if "expert_combine" in line.split(" = ")[0]]
    assert combine, "XLA's gather and sum took the combine kernel's place"
    # ... and it reads moe_down's result itself: nothing sits between them
    down = [line.split(" = ")[0].split()[-1] for line in calls
            if "moe_down" in line.split(" = ")[0]]
    assert down and all(any(d + ")" in c or d + "," in c for d in down) for c in combine)
    # the gathered rows are gone, not renamed: no array of T*k rows of D
    gathered = f"[{S * cfg.experts_per_tok},{cfg.d_model}]"
    assert not [line for line in text.splitlines() if gathered in line]
    # no layer's experts are sliced out or copied on the way to the kernels
    stacked = f"bf16[{cfg.n_layers},{cfg.n_experts},"
    assert not [line for line in text.splitlines()
                if stacked in line.split(" = ")[-1][:60]
                and (" copy(" in line or "dynamic-slice(" in line)]


@pytest.mark.parametrize("cls,k", [(0, 1), (1, 3), (2, 1)],
                         ids=["layer0-dense-48h", "window-sparse-72h", "full-sparse-48h"])
def test_laguna_harvest_segments_compile(chip, one_device, cls, k):
    """The fourth cell's three refill quanta at its published widths, two
    4096-token sequences a forward (``benchmarks/configs/
    laguna-s2.1-pair-relu16k.json``): one program a class of layers — layer
    0 (48 heads, the full instance of the fused attention, the dense MLP of
    12,288), the three window layers (72 heads, the 512-window instance, no
    ``cond``: the class's kind is static) and the full sparse layer — the
    sparse ones with the expert layer's three kernels over the HELD 32
    experts of 3072 x 1024, whose stacked weights reach them whole."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks import manifest
    from benchmarks.arch import laguna
    from crosscoder_tpu.ops import flash_attention as fa
    from crosscoder_tpu.ops import moe, row_gather

    cfg = laguna.lm_config(manifest.load_json(
        manifest.BENCH_DIR / "configs" / "laguna-s2.1-pair-relu16k.json"))
    B, S = 2, 4096
    heads = lm.layer_classes(cfg)[cls].n_heads
    assert fa.supported(S, heads, cfg.n_kv_heads, cfg.head_dim, jnp.bfloat16)
    assert moe.enabled() and moe.supported(cfg.d_model, cfg.d_expert, jnp.bfloat16)
    assert row_gather.supported(B * S, cfg.experts_per_tok, cfg.d_model, jnp.bfloat16)
    params = _abstract(jax.eval_shape(
        lambda key: lm.init_params(key, cfg), jax.random.key(0)), chip)
    compiled = lm._seg_scan_impl.lower(
        params, _sds((B, S, cfg.d_model), jnp.bfloat16, chip),
        _sds((1, B, S, cfg.d_model), jnp.bfloat16, chip),
        _sds((), jnp.int32, chip), cfg=cfg,
        capture=lm._hook_layers(cfg, ("blocks.5.hook_resid_pre",)), k=k, cls=cls,
    ).compile()
    _fits(compiled)
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert sum("fused_causal_attention" in c.split(" = ")[0] for c in calls) == 1   # no cond
    assert " conditional(" not in text
    if cls == 0:
        assert "moe_" not in text
        return
    for name in ("moe_gate_up", "moe_down", "expert_combine"):
        assert any(name in c.split(" = ")[0] for c in calls), name
    # no layer's held experts are sliced out or copied on the way to the kernels
    stacked = f"bf16[{len(lm.layer_classes(cfg)[cls].layers)},{cfg.n_held},"
    assert not [line for line in text.splitlines()
                if stacked in line.split(" = ")[-1][:60]
                and (" copy(" in line or "dynamic-slice(" in line)]


@pytest.mark.parametrize("cls,k", [(0, 2), (1, 2)], ids=["dense-2", "sparse-2"])
def test_xing_harvest_segments_compile(chip, one_device, cls, k):
    """The fifth cell's refill quanta at its published widths, two 4096-token
    sequences a forward (``benchmarks/configs/xing4.0-pair-relu16k.json``):
    one program a class of layers — the two dense layers and two of the four
    sparse ones — each block with the stream maps' two kernels twice (read
    and write, around attention and around the MLP) on the carry ``[B, S,
    4 · 3584]`` with no relayout copy of it, the latent instance of the fused
    attention (192 / 128 heads, the shared rotary key a third band), and in
    the sparse class the expert layer's kernels over the HELD 16 experts."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks import manifest
    from benchmarks.arch import xing
    from crosscoder_tpu.ops import flash_attention as fa
    from crosscoder_tpu.ops import mhc, moe

    cfg = xing.lm_config(manifest.load_json(
        manifest.BENCH_DIR / "configs" / "xing4.0-pair-relu16k.json"))
    B, S = 2, 4096
    assert fa.latent_supported(S, 128, 64, 128, jnp.bfloat16)
    assert mhc.enabled() and mhc.supported(B * S, cfg.hc, cfg.d_model, jnp.bfloat16)
    assert moe.enabled() and moe.supported(cfg.d_model, cfg.d_expert, jnp.bfloat16)
    params = _abstract(jax.eval_shape(
        lambda key: lm.init_params(key, cfg), jax.random.key(0)), chip)
    compiled = lm._seg_scan_impl.lower(
        params, _sds((B, S, cfg.n_streams * cfg.d_model), jnp.bfloat16, chip),
        _sds((1, B, S, cfg.d_model), jnp.bfloat16, chip),
        _sds((), jnp.int32, chip), cfg=cfg,
        capture=lm._hook_layers(cfg, ("blocks.6.hook_resid_pre",)), k=k, cls=cls,
    ).compile()
    _fits(compiled)
    text = compiled.as_text()
    calls = [line.split(" = ")[0] for line in text.splitlines() if "tpu_custom_call" in line]
    assert sum("mhc_read" in c for c in calls) == 2 and sum("mhc_write" in c for c in calls) == 2
    assert sum("fused_causal_attention_latent" in c for c in calls) == 1
    assert " conditional(" not in text
    # the four streams ride the scan side by side: nothing re-tiles the carry
    carry = f"bf16[{B},{S},{cfg.n_streams * cfg.d_model}]"
    assert not [line for line in text.splitlines()
                if " copy(" in line and carry in line.split(" = ")[1][:60]]
    for name in ("moe_gate_up", "moe_down", "expert_combine"):
        assert any(name in c for c in calls) == (cls == 1), name


@pytest.mark.parametrize("window", [0, 4096])
def test_paged_attention_family_compiles(chip, gates_open, window):
    """Gemma-2-2B heads (8 Q / 4 KV × 256), global and sliding-window."""
    from crosscoder_tpu.ops import paged_attention as pa

    D, S = 4, 1024
    q = _sds((D, S, 8, 256), jnp.bfloat16, chip)
    kv = _sds((D, S, 4, 256), jnp.bfloat16, chip)
    _compiles(
        lambda q, k, v, ln: pa.paged_attention(
            q, k, v, ln, page_size=128, scale=256.0 ** -0.5, softcap=50.0,
            window=window),
        q, kv, kv, _sds((D,), jnp.int32, chip))


@pytest.mark.parametrize("use", ["pack", "decode", "dvals"])
def test_row_gather_family_compiles(chip, use):
    """The row kernels alone at the cell's shape (B 4096, k 32, H 2^15, n·d
    4096, bf16 rows): the pack, and token-major with the weighted-sum and
    the dot epilogue, the 512 KiB row table prefetched whole."""
    _row_kernel_compiles(chip, use)


def test_sparse_grad_family_compiles(chip):
    """The sparse backward plane's weight gradients at the cell's shape: the
    latent-major pass of ops/row_gather.py with its sort in front — the
    kernel that took the place of ops/sparse_grad's sorted-pair scatter,
    which Mosaic refused (dynamic scalar reads of the pair list from VMEM;
    scalar-prefetched, 1.50 M of 1.00 M SMEM; then the one-row load of a
    tiled bf16 block)."""
    _row_kernel_compiles(chip, "grads")


def _row_kernel_compiles(chip, use):
    from crosscoder_tpu.ops import row_gather as rg

    H, D = 2**15, 4096
    W = D // 256
    assert rg.supported(BATCH, K, D, jnp.bfloat16) and len(rg._slices(BATCH, K)) == 1
    assert rg.grouped_supported(H, BATCH, K, D, jnp.bfloat16)
    table = _sds((BATCH * K,), jnp.int32, chip)
    rows = _sds((H * W, 1, 128), jnp.uint32, chip)
    if use == "pack":
        _compiles(lambda w: rg.packed(w), _sds((H, D), jnp.bfloat16, chip))
    elif use == "decode":
        _compiles(lambda t, v, y: rg.weighted_sum(
            t, v, y, D, name="topk_rows_decode", out_dtype=jnp.float32),
            table, _sds((BATCH, K), jnp.float32, chip), rows)
    elif use == "dvals":
        _compiles(lambda t, g, y: rg.dots(t, g, y, K, name="topk_rows_dvals"),
                  table, _sds((BATCH, D), jnp.float32, chip), rows)
    else:
        pairs = _sds((BATCH, K), jnp.bfloat16, chip)
        wide = _sds((BATCH, D), jnp.bfloat16, chip)
        _compiles(lambda i, v, dv, g, x: rg.grouped_sums(
            i, v, dv, g, x, H, name="topk_rows_grads"),
            _sds((BATCH, K), jnp.int32, chip), pairs, pairs, wide, wide)


# ---------------------------------------------------------------------------
# four chips: the sharded step over a mesh of described devices


@pytest.mark.parametrize("shape", [(4, 1), (2, 2)])
def test_sharded_train_step_compiles(chip, topo, shape):
    """chip_smoke.py --chips 4 in rehearsal: the ReLU step over a 4x1 and a
    2x2 ('data','model') mesh — per-device bytes fit, and the compiler put
    the gradient all-reduce in."""
    from crosscoder_tpu.parallel import mesh as mesh_lib

    cfg = CrossCoderConfig(dict_size=2**14, log_backend="null",
                           data_axis_size=shape[0], model_axis_size=shape[1])
    compiled = _compiled_train_step(
        cfg, mesh_lib.make_mesh(*shape, devices=topo.devices), with_metrics=True)
    assert "all-reduce" in compiled.as_text()


# ---------------------------------------------------------------------------
# the optimizer update's layout: no whole-table relayout inside the step


def _entry_copies(text: str, dtype_shape: str) -> list[str]:
    """The plain ``copy`` ops of the ENTRY computation whose result is
    ``dtype_shape`` (as ``f32[2048,2,256]``): a relayout of a whole table
    that runs as an op of its own, every step."""
    lines = text.splitlines()
    start = next(i for i, line in enumerate(lines) if line.startswith("ENTRY "))
    end = next(i for i in range(start, len(lines)) if lines[i].startswith("}"))
    needle = f" = {dtype_shape}{{"
    return [line.strip()[:160] for line in lines[start:end]
            if needle in line and " copy(" in line]


@pytest.mark.parametrize("over,shape,variant", [
    ({}, (1, 1), "bare"), ({}, (1, 1), "full"),
    (dict(activation="topk", topk_k=8, l1_coeff=0.0), (1, 1), "bare"),
    (dict(data_axis_size=2, model_axis_size=2), (2, 2), "bare"),
], ids=["relu-bare", "relu-full", "topk-dense-bare", "relu-2x2-bare"])
def test_step_updates_its_state_in_the_layout_it_is_held_in(
        topo, over, shape, variant, monkeypatch):
    """The finding of PR 36, held without a chip at toy sizes (dict 2048,
    d_in 256, batch 512, two sources): built as the CPU's step is built,
    every variant of the step — the dense-tier TopK step and the step over
    a mesh, a shard a device, too — ends in three ``copy`` ops that put the
    new ``W_dec`` and its two Adam moments, whole, back into the layout
    they were donated in. Built as ``make_train_step`` builds it for a
    mesh of TPU devices — each gradient handed to the optimizer in the
    layout the device says it holds such a shard in — none is left, and
    the step takes and returns every leaf in the layout it had: the one
    ``mesh_lib.held_layout`` names."""
    from crosscoder_tpu.parallel import mesh as mesh_lib
    from crosscoder_tpu.train import schedules, trainer
    from crosscoder_tpu.train.state import init_train_state, make_optimizer

    cfg = CrossCoderConfig(d_in=256, dict_size=2048, batch_size=512, n_models=2,
                           enc_dtype="bf16", log_backend="null", **over)
    mesh = mesh_lib.make_mesh(*shape, devices=topo.devices[:shape[0] * shape[1]])
    tx = make_optimizer(cfg, schedules.lr_schedule(cfg))
    state = jax.eval_shape(lambda k: init_train_state(k, cfg, tx, n_data=shape[0]),
                           jax.random.key(0))
    shardings = mesh_lib.state_shardings(mesh, state)
    avals = jax.tree_util.tree_map(
        lambda a, s: _sds(a.shape, a.dtype, s), state, shardings)
    w_dec = shardings.params["W_dec"].shard_shape(state.params["W_dec"].shape)
    master = f"f32[{','.join(map(str, w_dec))}]"

    def compiled():
        return trainer.make_train_step(
            cfg, mesh, tx, shardings, with_metrics=variant == "full").lower(
            avals,
            _sds((cfg.batch_size, cfg.n_sources, cfg.d_in), jnp.bfloat16,
                 mesh_lib.batch_sharding(mesh)),
            _sds((cfg.n_sources,), jnp.float32, shardings.step),
        ).compile()

    def layouts(step):
        (state_in, *_), _ = step.input_formats
        return ([f.layout for f in jax.tree_util.tree_leaves(state_in)],
                [f.layout for f in jax.tree_util.tree_leaves(step.output_formats[0])])

    assert trainer.update_in_held_layout(mesh)
    step = compiled()
    assert _entry_copies(step.as_text(), master) == []
    monkeypatch.setattr(trainer, "update_in_held_layout", lambda mesh: False)
    plain = compiled()
    assert len(_entry_copies(plain.as_text(), master)) == 3
    assert layouts(step) == layouts(plain)
    assert [f.layout for f in step.input_formats[0][0].params.values()] == [
        mesh_lib.held_layout(avals.params[name], shardings.params[name])
        for name in step.input_formats[0][0].params]
