"""The reduction from a trace to the per-layer metrics' inputs: on a small
trace recorded on the chip (kept in ``data/``) and on hand-made cases."""

import json
from pathlib import Path

import pytest

from benchmarks import trace_reduce
from benchmarks.reducers import device_ms_per_unit, idle_share, peak_share

DATA = Path(__file__).parent / "data"
TABLE = trace_reduce.attribution()


def _trace(ops, host=()):
    return {"devices": {"/device:TPU:0": [list(o) for o in ops]},
            "host": [list(h) for h in host]}


def test_recorded_chip_trace_reduces_to_pinned_numbers():
    """The first 400 device ops of the traced window of train-live-relu16k on
    a TPU v5 lite (PR 25), as ``trace_reduce.sample`` kept them in the run's
    raw readings."""
    trace = json.loads((DATA / "trace_relu16k_sample.json").read_text())
    want = json.loads((DATA / "trace_relu16k_sample.expected.json").read_text())
    got = trace_reduce.reduce_trace(trace, TABLE)
    assert got["n_devices"] == 1 and got["unattributed_share"] == 0.0
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["busy_s"] <= got["window_s"]
    assert set(got["groups"]) == set(want["groups"])
    for g, s in want["groups"].items():
        assert got["groups"][g] == pytest.approx(s, rel=1e-9)
    # self times: the groups add up to the busy time (nothing counted twice;
    # only ops that overlap without nesting can make the sum the larger)
    assert got["busy_s"] <= sum(got["groups"].values()) * (1 + 1e-9) <= 1.02 * got["busy_s"]
    assert [n for n, _ in got["device_ops"]] == [n for n, _ in want["device_ops"]]
    assert all(name.startswith("host:") for name, _ in got["idle_gaps"])


def test_an_op_that_contains_others_counts_only_its_own_time():
    ops = [("while.1", "jit__seg_scan_impl", 0, 100),
           ("fusion.1", "jit__seg_scan_impl", 10, 30),
           ("fusion.2", "jit__seg_scan_impl", 50, 40),
           ("fusion.3", "jit_step_fn", 100, 50)]
    r = trace_reduce.reduce_trace(_trace(ops), TABLE)
    assert r["busy_s"] == pytest.approx(150e-9) and r["window_s"] == pytest.approx(150e-9)
    assert r["groups"] == {"harvest": pytest.approx(100e-9), "cc_step": pytest.approx(50e-9)}
    by_name = dict(r["device_ops"])
    assert by_name["jit__seg_scan_impl:while.1"] == pytest.approx(30e-9)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    ops = [("a", "jit_step_fn", 0, 100), ("b", "jit_step_fn", 400, 100),
           ("c", "jit_step_fn", 520, 80)]
    host = [("step", 50, 600), ("refill_wait", 90, 200)]
    r = trace_reduce.reduce_trace(_trace(ops, host), TABLE)
    assert r["busy_s"] == pytest.approx(280e-9) and r["window_s"] == pytest.approx(600e-9)
    assert r["idle_gaps"][0] == ["host:refill_wait", pytest.approx(300e-9)]
    assert r["idle_gaps"][1] == ["host:step", pytest.approx(20e-9)]
    obs = {"trace_reduced": r}
    assert idle_share.reduce(obs, {}) == pytest.approx(100 * (1 - 280 / 600))


def test_a_later_file_adds_rules_and_spans_after_the_ones_that_are_there(tmp_path):
    """What a later PR does for its own programs: a file of its own."""
    import shutil

    from benchmarks import manifest

    shutil.copytree(manifest.BENCH_DIR / "attribution", tmp_path / "benchmarks" / "attribution")
    (tmp_path / "benchmarks" / "attribution" / "20-serve.json").write_text(json.dumps(
        {"rules": [{"group": "serve_prefill", "module": "_paged_multi_impl"},
                   {"group": "mine", "module": "step_fn"}],
         "host_spans": ["prefill"]}))
    table = trace_reduce.attribution(tmp_path)
    assert trace_reduce.group_of("jit__paged_multi_impl", "dot.1", table) == "serve_prefill"
    assert trace_reduce.group_of("jit_step_fn", "dot.1", table) == "cc_step"     # first wins
    assert table["host_spans"][-1] == "prefill" and "step" in table["host_spans"]


def test_the_first_matching_rule_names_the_group_and_a_kernel_by_its_op():
    assert trace_reduce.group_of("jit_step_fn", "fusion.3_bf16_4096_", TABLE) == "cc_step"
    assert trace_reduce.group_of("jit_step_fn", "pallas:custom-call.2_bf16_4096_32768_",
                                 TABLE) == "topk_kernel"
    assert trace_reduce.group_of("jit__seg_scan_impl", "pallas:x", TABLE) == "harvest"
    assert trace_reduce.group_of("jit_something_new", "dot.1", TABLE) is None


def test_an_unattributed_long_op_is_reported():
    ops = [("fusion.1", "jit_step_fn", 0, 10), ("big", "jit_mystery", 10, 90)]
    r = trace_reduce.reduce_trace(_trace(ops), TABLE)
    assert r["top_unattributed"] == ["jit_mystery:big"]
    assert r["unattributed_share"] == pytest.approx(0.9)


def test_tpu_op_names_are_cut_to_name_type_and_shape():
    hlo = ('%fusion.140 = bf16[4,1024,5632]{2,1,0:T(8,128)(2,1)} fusion(f32[4,1024,5632] '
           '%fusion.139), kind=kOutput, calls=%fused_computation.20')
    assert trace_reduce.short_op(hlo) == "fusion.140_bf16_4_1024_5632_"
    assert trace_reduce.short_op('%custom-call.3 = bf16[8,128]{1,0} custom-call(%x), '
                                 'custom_call_target="tpu_custom_call"').startswith("pallas:")
    assert trace_reduce.short_op("dot.3") == "dot.3"


def test_ops_take_the_module_they_start_in():
    ops = [("a", 5, 10), ("b", 105, 10), ("c", 300, 5)]
    modules = [(0, 100, "jit_step_fn"), (100, 200, "jit__dev_gather")]
    assert [o[1] for o in trace_reduce._with_modules(ops, modules)] == \
        ["jit_step_fn", "jit__dev_gather", ""]


def test_the_readers_turn_group_seconds_into_ms_and_shares():
    obs = {"trace_reduced": {"groups": {"harvest": 3.6, "cc_step": 0.6, "topk_kernel": 0.12}},
           "traced_steps": 30, "shapes": {"w": 13.0e12, "b": 269e6},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    per = ["traced_steps"]
    assert device_ms_per_unit.reduce(obs, {"group": "harvest", "per": per}) == pytest.approx(120.0)
    assert device_ms_per_unit.reduce(
        obs, {"groups": ["cc_step", "topk_kernel"], "per": per}) == pytest.approx(24.0)
    assert device_ms_per_unit.reduce(obs, {"group": "store", "per": per}) is None
    assert peak_share.reduce(obs, {"group": "harvest", "per": per, "work": "w",
                                   "peak": "bf16_flops_per_s"}) == pytest.approx(
        100 * 13.0e12 / 197e12 / 0.120)
    assert peak_share.reduce(obs, {"group": "topk_kernel", "per": per, "work": "b",
                                   "peak": "hbm_bytes_per_s"}) == pytest.approx(
        100 * 269e6 / 819e9 / 0.004)
    assert peak_share.reduce({}, {"group": "harvest", "per": per, "work": "w",
                                  "peak": "bf16_flops_per_s"}) is None
