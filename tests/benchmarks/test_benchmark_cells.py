"""Every cell's runner end to end at ``LMConfig.tiny()`` sizes on the CPU,
through ``run_cell``'s test-only ``sizes`` argument; and a configuration, a
traffic mix, a cell, a per-layer metric and an architecture added by new
files alone.

The cells run here are the ones BENCHMARK.json lists."""

import json

import accepted
import pytest

from benchmarks import manifest, run, shapes
from benchmarks.reducers import peak_share

SHIPPED = manifest.load()
TINY_LM = dict(vocab_size=257, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
               head_dim=8, d_ff=64, sliding_window=8, query_pre_attn_scalar=8.0,
               dtype="fp32")
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
TRAIN = {
    "lm": TINY_LM, "peaks": PEAKS,
    "crosscoder": dict(d_in=32, batch_size=64, seq_len=17, buffer_mult=8,
                       norm_calib_batches=2, hook_point="blocks.2.hook_resid_pre",
                       dict_size=256, topk_k=4),
    "traffic": dict(token_rows=256, schedule_steps=20000),
}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


@pytest.fixture
def interpret_kernels():
    """The default TopK tier is the Pallas kernel on a chip; here the same
    dispatch runs it through the interpreter."""
    from crosscoder_tpu.ops import activations as act_ops
    from crosscoder_tpu.ops import topk_pallas

    act_ops.set_topk_impl("pallas")
    topk_pallas.set_interpret(True)
    yield
    topk_pallas.set_interpret(False)
    act_ops.set_topk_impl("auto")


@pytest.mark.parametrize("workload", [w["name"] for w in SHIPPED["workloads"]])
def test_the_cell_runs_end_to_end_tiny(workload, root, interpret_kernels):
    cell = manifest.cell(SHIPPED, workload)
    # (long enough for ten whole cycles when six test workers share the CPUs)
    line, obs = run.run_cell(workload, 2**31 + 11, 4.0, 0, root=root, sizes=TRAIN)
    assert set(line) == LINE_KEYS and line["correct"], (obs["problems"], line)
    assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["attempted"] >= 10 and line["failed"] == 0
    # every row of the window over every second of it, by the harness's clock
    w = obs["window"]
    rows = TRAIN["crosscoder"]["batch_size"] * obs["spc"] * w["cycles"]
    assert line["metrics"]["train_rows_per_s"]["value"] == pytest.approx(rows / w["window_s"])
    units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == units
    raw = json.loads(next((root / "benchmarks" / "out" / workload).glob("*.json")).read_text())
    assert raw["phases"] and raw["series"] and raw["problems"] == []
    # last in the line: each number compared with a reference, beside its limit
    assert list(line)[-1] == "compared" and "harvest_rel_err" in line["compared"]
    assert all(set(c) == {"value", "limit"} for c in line["compared"].values())
    json.dumps(line)      # the line is plain JSON


def _problems(obs: dict) -> list:
    """Stopping the profiler can hold the tiny loop past the window's end when
    six test workers share the CPUs; then no whole cycle is left after it,
    which is not what these tests are about."""
    return [p for p in obs["problems"] if "whole cycles" not in p]


@pytest.mark.parametrize("workload", [w["name"] for w in SHIPPED["workloads"]])
def test_the_traced_run_reports_the_cells_per_layer_metrics(workload, root,
                                                            interpret_kernels):
    cell = manifest.cell(SHIPPED, workload)
    # (the traced train window pays for stopping the trace inside it)
    line, obs = run.run_cell(workload, 5, 8.0, 1, root=root, sizes=TRAIN)
    assert set(line) == LINE_KEYS | {"breakdown"} and not _problems(obs), (obs["problems"], line)
    names = {m["name"] for m in cell["per_layer"]}
    assert set(line["metrics"]) <= names
    # what only the chip can give (the kernel's own ops, device memory) may be
    # absent here; everything read from spans, counters and the trace is there
    absent = names - set(line["metrics"])
    if not obs["cycle"]["cycles"]:
        absent = {n for n in absent if not n.startswith("loop_cycle_")}
    assert all(accepted.may_be_absent_on_the_cpu(n, SHIPPED, root) for n in absent), absent
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] >= line["device"]["busy_s"]
    assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10
    assert obs["trace_reduced"]["unattributed_share"] == 0.0


def test_a_short_window_is_refused(root):
    line, _ = run.run_cell("train-live-relu16k", 1, 0.0, 0, root=root, sizes=TRAIN)
    assert line["correct"] is False and line["attempted"] < 10


# An architecture of a later PR, as the file it adds under ``benchmarks/arch/``:
# the Gemma block's configuration, a plain reference that is deliberately
# ANOTHER block's (every layer attends to the whole prefix, no soft-cap) and
# twice the true FLOP count.
PLAIN_BLOCK = '''
import dataclasses

from benchmarks.arch import gemma2_block
from benchmarks.reference import lm_ref

lm_config, HARVEST_RTOL = gemma2_block.lm_config, gemma2_block.HARVEST_RTOL


def resid_pre(params, tokens, lm_cfg, hook_layer):
    plain = dataclasses.replace(lm_cfg, sliding_window=0, attn_softcap=0.0)
    return lm_ref.resid_pre(params, tokens, plain, hook_layer)


def flops_per_token(lm_cfg, n_layers, seq_len):
    return 2 * gemma2_block.flops_per_token(lm_cfg, n_layers, seq_len)
'''


def _the_cells_own_architecture_was_reached(line, obs, root, name, cfg, mix, sizes):
    """The module's reference decided ``correct`` (the program runs the Gemma
    block, which that reference is not: the harvest check fails with the
    deviation it printed), and its FLOP count is ``harvest_peak_share``'s:
    twice what the same cell reads through the default architecture."""
    from benchmarks import arch as arch_lib
    from benchmarks.runners import train

    raw = json.loads((root / "benchmarks" / "out" / name / "seed3-trace1.json").read_text())
    err = raw["reference"]["harvest_rel_err"]
    sibling = arch_lib.of({})
    assert line["correct"] is False and err > 0.1
    assert line["compared"]["harvest_rel_err"] == {"value": err, "limit": sibling.HARVEST_RTOL}
    assert _problems(obs) == [f"hooked activations deviate by {err:.3e}"]
    cc_cfg, spc = train.crosscoder_config(cfg, mix, 0, str(root), sizes["crosscoder"], 0)
    as_sibling = shapes.train_shapes(cc_cfg, sibling.lm_config(cfg, sizes["lm"]), spc,
                                     (1, 1), sibling)
    key = "harvest_flops_per_step_per_chip"
    assert obs["shapes"][key] == 2 * as_sibling[key] > 0
    args = manifest.load_json(root / "benchmarks/metrics/harvest_peak_share.json")["args"]
    assert line["metrics"]["harvest_peak_share"]["value"] == pytest.approx(
        2 * peak_share.reduce({**obs, "shapes": as_sibling}, args))


NEW_CELLS = {
    # a smaller dictionary on one chip; a 2x2 mesh with a mesh-sharded store
    # on four; the one-chip cell again through an architecture of its own
    "train-live-relu8k": (1, dict(dict_size=2**13), dict(dict_size=128), None),
    "train-mesh-relu8k": (4, dict(dict_size=2**13, data_axis_size=2, model_axis_size=2),
                          dict(dict_size=128), None),
    "train-live-plain8k": (1, dict(dict_size=2**13), dict(dict_size=128), "plain_block"),
}


@pytest.mark.parametrize("name", sorted(NEW_CELLS))
def test_new_files_alone_add_a_config_a_mix_a_cell_and_a_metric(name, code_root):
    """What a later PR does: no file that is there is edited (BENCHMARK.json
    gains entries), and the harness runs the new cell, reads the new metric in
    it and reads the metrics that are there (harvest, device) in it too. With
    an architecture of its own, the cell's ``correct`` is decided by THAT
    module's reference and its ``harvest_peak_share`` by THAT module's count."""
    import jax

    root = code_root
    chips, published, tiny, arch = NEW_CELLS[name]
    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} devices")
    before = {f: f.read_bytes() for f in (root / "benchmarks").rglob("*") if f.is_file()}
    b = root / "benchmarks"
    cfg = json.loads((b / "configs" / "ouro2.6b-pair-relu16k.json").read_text())
    cfg["crosscoder"].update(published)
    if arch:
        cfg["arch"] = arch
        (b / "arch" / f"{arch}.py").write_text(PLAIN_BLOCK)
    (b / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "live-full.json").read_text())
    mix["token_rows"] = 128
    (b / "traffic" / "live-short-corpus.json").write_text(json.dumps(mix))
    spec = {"unit": "rows/s/chip", "better": "higher", "source": "host_clock",
            "layer": "loop (train/trainer.py)", "moves": "train_rows_per_s",
            "reducer": "field", "args": {"path": ["cycle", "rows_per_s_median_cycle"]}}
    (b / "metrics" / "loop_rows_per_s_quiet.json").write_text(json.dumps(spec))
    # a kernel's share of its roofline, which only the chip can give: its
    # file says so, and no op of this run is the kernel's
    kernel = {"unit": "%", "better": "higher", "source": "device_trace",
              "layer": "harvest (models/lm.py)", "moves": "train_rows_per_s",
              "reducer": "peak_share", "chip_only": True,
              "args": {"group": "mine", "per": ["traced_steps"],
                       "work": "harvest_flops_per_step_per_chip", "peak": "bf16_flops_per_s"}}
    (b / "metrics" / "mine_kernel_roofline.json").write_text(json.dumps(kernel))
    (b / "attribution" / "20-mine.json").write_text(json.dumps(
        {"rules": [{"group": "mine", "module": "jit_never_there"}]}))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": name, "source": cfg["source"],
                           "file": f"benchmarks/configs/{name}.json",
                           "reduced": ["num_hidden_layers", "layer_types"],
                           "why": "a smaller dictionary"})
    man["workloads"].append({"name": name, "config": name, "traffic": "live-short-corpus",
                             "chips": chips, "why": "a corpus that wraps"})
    for new, file in (("loop_rows_per_s_quiet", spec), ("mine_kernel_roofline", kernel)):
        man["per_layer"].append({k: v for k, v in file.items()
                                 if k not in ("reducer", "args", "chip_only")}
                                | {"name": new, "workloads": [name]})
    old = {"harvest_device_ms_per_step", "harvest_peak_share", "device_idle_share.train",
           "loop_cycle_median_s"}
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] == "train_rows_per_s" or m["name"] in old:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    sizes = {**TRAIN, "crosscoder": {**TRAIN["crosscoder"], **tiny}}
    line, obs = run.run_cell(name, 3, 8.0, 1, root=root, sizes=sizes)
    if arch:
        _the_cells_own_architecture_was_reached(line, obs, root, name, cfg, mix, sizes)
    else:
        assert not _problems(obs), obs["problems"]
    assert line["device"]["count"] == chips
    want = old | {"loop_rows_per_s_quiet", "setup_compile_s", "setup_cache_hit_share"}
    assert accepted.may_be_absent_on_the_cpu("mine_kernel_roofline", man, root)
    if obs["cycle"]["cycles"]:
        assert set(line["metrics"]) == want
        assert line["metrics"]["loop_rows_per_s_quiet"]["value"] == \
            obs["cycle"]["rows_per_s_median_cycle"]
    else:       # the profiler's stop ate the window: no cycle readings
        assert set(line["metrics"]) == want - {"loop_rows_per_s_quiet", "loop_cycle_median_s"}
    assert line["metrics"]["harvest_device_ms_per_step"]["value"] > 0
    assert all(f.read_bytes() == was for f, was in before.items())   # nothing edited
