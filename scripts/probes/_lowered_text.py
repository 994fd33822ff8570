"""Is a cell's program the parent's? The lowered text of the accepted cells'
programs, for a described v5e (nothing is compiled, nothing runs; CPU only),
written out by one tree and compared with another tree's.

    JAX_PLATFORMS=cpu python3 scripts/probes/_lowered_text.py write <tree> <out-dir>
    JAX_PLATFORMS=cpu python3 scripts/probes/_lowered_text.py compare <out-dir-a> <out-dir-b>

``write`` lowers, from the tree given (its ``crosscoder_tpu`` and its
``benchmarks``): the mellum2 cell's harvest programs (``_seg_start_impl``,
``_seg_scan_impl`` at widths 1, 2 and 4, ``_seg_finish_impl``,
``_multi_cache_impl``), the Ouro cells' and the Laguna cell's refill quanta
(``_seg_scan_impl``, one program a class of layers) and both variants of the
TopK 2^15 and the ReLU 2^14 step at the cells' sizes. ``compare`` holds two such directories against each
other: the text outside the kernels byte for byte, and each Pallas kernel —
which travels as MLIR bytecode in its call's ``backend_config``, source
locations and absolute file names included — parsed and printed WITHOUT
locations, then with them (the tree's root replaced): a kernel that is equal
with its locations is served by the persistent cache on a tree's first run,
one that is equal only without them compiles once more.
"""
from __future__ import annotations

import base64
import os
import re
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def write(root: str, out: str) -> None:
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import manifest
    from benchmarks.arch import mellum
    from crosscoder_tpu.config import CrossCoderConfig
    from crosscoder_tpu.models import crosscoder as cc
    from crosscoder_tpu.models import lm
    from crosscoder_tpu.ops import activations as act_ops
    from crosscoder_tpu.parallel import mesh as mesh_lib
    from crosscoder_tpu.train import schedules
    from crosscoder_tpu.train.state import init_train_state, make_optimizer
    from crosscoder_tpu.train.trainer import make_train_step

    assert lm.__file__.startswith(str(Path(root).resolve())), lm.__file__
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    # the code under test asks the backend and the device count: a one-chip TPU process
    jax.default_backend = lambda: "tpu"
    jax.device_count = lambda *a: 1
    act_ops._backend_is_tpu.cache_clear()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    progs = {}
    cfg = mellum.lm_config(manifest.load_json(
        manifest.BENCH_DIR / "configs" / "mellum2-pair-relu16k.json"))
    S, D, hook = 4096, cfg.d_model, "blocks.4.hook_resid_pre"
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda k: lm.init_params(k, cfg), jax.random.key(0)))
    cap = lm._hook_layers(cfg, (hook,))
    resid, buf = sds((1, S, D), jnp.bfloat16), sds((1, 1, S, D), jnp.bfloat16)
    progs["mellum2_seg_start"] = lm._seg_start_impl.lower(
        params, sds((1, S), jnp.int32), cfg=cfg, n_cap=1)
    for k in (1, 2, 4):
        progs[f"mellum2_seg_scan_k{k}"] = lm._seg_scan_impl.lower(
            params, resid, buf, sds((), jnp.int32), cfg=cfg, capture=cap, k=k)
    progs["mellum2_seg_finish"] = lm._seg_finish_impl.lower(
        (resid,) * 2, (buf,) * 2, cfg=cfg, capture=cap, n_scan=4, out_dtype=jnp.bfloat16)
    progs["mellum2_multi_cache"] = lm._multi_cache_impl.lower(
        (params, params), sds((1, S), jnp.int32), cfg=cfg, capture=(hook,))
    # the other two harvests the benchmark has: the Gemma-style block of the two
    # Ouro cells and Laguna's three classes of layers, at their cells' shapes
    import importlib

    for stem, arch_name, quanta in (
            ("ouro2.6b-pair-relu16k", "gemma2_block", ((None, 3),)),
            ("laguna-s2.1-pair-relu16k", "laguna", ((0, 1), (1, 3), (2, 1)))):
        conf = manifest.load_json(manifest.BENCH_DIR / "configs" / f"{stem}.json")
        acfg = importlib.import_module(f"benchmarks.arch.{arch_name}").lm_config(conf)
        ccc = conf["crosscoder"]
        B2, S2 = ccc["model_batch_size"], ccc["seq_len"]
        aparams = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype),
            jax.eval_shape(lambda k: lm.init_params(k, acfg), jax.random.key(0)))
        acap = lm._hook_layers(acfg, (ccc["hook_point"],))
        for c, k in quanta:
            progs[f"{arch_name}_seg_scan_c{c}_k{k}"] = lm._seg_scan_impl.lower(
                aparams, sds((B2, S2, acfg.d_model), jnp.bfloat16),
                sds((1, B2, S2, acfg.d_model), jnp.bfloat16), sds((), jnp.int32),
                cfg=acfg, capture=acap, k=k, **({} if c is None else {"cls": c}))
    mesh = mesh_lib.make_mesh(devices=topo.devices[:1])
    for name, over in (
            ("topk32k", dict(d_in=2048, dict_size=2**15, activation="topk", topk_k=32,
                             l1_coeff=0.0)),
            ("relu16k", dict(d_in=2048, dict_size=2**14))):
        ccfg = CrossCoderConfig(log_backend="null", **over)
        assert cc.rows_live(ccfg, 4096) == (name == "topk32k")
        tx = make_optimizer(ccfg, schedules.lr_schedule(ccfg))
        state = jax.eval_shape(lambda k: init_train_state(k, ccfg, tx), jax.random.key(0))
        for metrics in (False, True):
            step = make_train_step(ccfg, mesh, tx, mesh_lib.state_shardings(mesh, state),
                                   with_metrics=metrics)
            progs[f"{name}_step_{'full' if metrics else 'bare'}"] = step.lower(
                state, jax.ShapeDtypeStruct((4096, ccfg.n_sources, ccfg.d_in), jnp.bfloat16),
                jax.ShapeDtypeStruct((ccfg.n_sources,), jnp.float32))
    os.makedirs(out, exist_ok=True)
    Path(out, "ROOT").write_text(str(Path(root).resolve()))
    for name, lowered in progs.items():
        text = lowered.as_text()
        Path(out, f"{name}.mlir").write_text(text)
        print(f"{name}: {len(text)} bytes, {len(BODY.findall(text))} kernels")


def _kernels(text: str, root: str, locations: bool) -> list[str]:
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        return [ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=locations).replace(__file__, "SCRIPT").replace(root, "ROOT")
            for body in BODY.findall(text)]


def compare(a: str, b: str) -> int:
    roots = [Path(d, "ROOT").read_text() for d in (a, b)]
    equal = True
    for path in sorted(Path(a).glob("*.mlir")):
        ta, tb = path.read_text(), Path(b, path.name).read_text()
        outside = BODY.sub("BODY", ta) == BODY.sub("BODY", tb)
        bare = _kernels(ta, roots[0], False) == _kernels(tb, roots[1], False)
        located = [x == y for x, y in zip(_kernels(ta, roots[0], True),
                                          _kernels(tb, roots[1], True))]
        equal &= outside and bare
        print(f"{path.stem}: outside the kernels {'equal' if outside else 'DIFFERENT'}; "
              f"{len(located)} kernels {'equal' if bare else 'DIFFERENT'} without locations, "
              f"{sum(located)} of them with")
    print("every program equal" if equal else "SOME PROGRAM DIFFERS")
    return 0 if equal else 1


if __name__ == "__main__":
    __file__ = str(Path(__file__).resolve())    # in every kernel's call stack, in both trees
    sys.exit(write(*sys.argv[2:4]) if sys.argv[1] == "write" else compare(*sys.argv[2:4]))
