"""The per-layer metrics PR 37 appended: device time by the program's own
scopes, each the mean of one ``perf/device/*`` gauge over the window's log
rows (reader ``row_mean``), which the program sets when the traced run's
profile window closes (``crosscoder_tpu/obs/device_scopes.py``)."""

import accepted
import pytest

from benchmarks import manifest
from benchmarks.reducers import row_mean

SHIPPED = manifest.load()
ALL = [w["name"] for w in SHIPPED["workloads"]][:5]
RELU = [w for w in ALL if w != "train-live-topk32k"]
SPARSE = ["train-live-mellum2-relu16k", "train-live-laguna-relu16k",
          "train-live-xing-relu16k"]
# metric -> (the gauge it reads, its unit, its cells)
SCOPE_METRICS = {
    "device_scoped_share": ("perf/device/scoped_share", "%", ALL),
    "harvest_attn_scope_ms_per_step": ("perf/device/harvest.block.attn_ms_per_step", "ms", ALL),
    "harvest_rope_scope_ms_per_step": ("perf/device/harvest.block.attn.rope_ms_per_step", "ms", ALL),
    "harvest_mlp_scope_ms_per_step": ("perf/device/harvest.block.mlp_ms_per_step", "ms", ALL),
    "harvest_norm_scope_ms_per_step": ("perf/device/harvest.block.norm_ms_per_step", "ms", ALL),
    "harvest_unscoped_ms_per_step": ("perf/device/harvest.unscoped_ms_per_step", "ms", ALL),
    "cc_encode_scope_ms_per_step": ("perf/device/cc.encode_ms_per_step", "ms", ALL),
    # (the TopK step's backward is a custom VJP: its dW_enc is a kernel under
    # cc/decode, and no op there is named after the forward's cc/encode)
    "cc_encode_bwd_scope_ms_per_step": ("perf/device/cc.encode_bwd_ms_per_step", "ms", RELU),
    "cc_adam_scope_ms_per_step": ("perf/device/cc.adam_ms_per_step", "ms", ALL),
    "moe_route_scope_ms_per_step": ("perf/device/harvest.block.moe.route_ms_per_step", "ms", SPARSE),
    "moe_experts_scope_ms_per_step": ("perf/device/harvest.block.moe.experts_ms_per_step", "ms", SPARSE),
    "moe_shared_scope_ms_per_step": ("perf/device/harvest.block.moe.shared_ms_per_step", "ms", SPARSE[1:]),
    "mhc_scope_ms_per_step": ("perf/device/harvest.block.mhc_ms_per_step", "ms", SPARSE[2:]),
}


@pytest.mark.parametrize("name", sorted(SCOPE_METRICS))
def test_the_metric_file_validates_and_reads_its_gauge(name):
    gauge, unit, cells = SCOPE_METRICS[name]
    entry = next(m for m in SHIPPED["per_layer"] if m["name"] == name)
    spec = manifest.load_json(manifest.metric_file(name, manifest.ROOT, SHIPPED["paths"]))
    assert entry["workloads"] == cells and entry["unit"] == spec["unit"] == unit
    assert entry["better"] == ("higher" if unit == "%" else "lower")
    assert entry["moves"] == "train_rows_per_s" and entry["source"] == "program_counter"
    assert spec["reducer"] == "row_mean" and spec["args"] == {"key": gauge}
    # only a chip's profile carries the names: the tiny CPU run may lack it
    assert accepted.may_be_absent_on_the_cpu(name, SHIPPED, manifest.ROOT)
    # the gauge appears on the log lines AFTER the window's close: the mean is
    # over the rows that carry it, and nothing is read where none does
    rows = [{"step": 15}, {"step": 30}, {"step": 45, gauge: 2.0}, {"step": 60, gauge: 2.0}]
    assert row_mean.reduce({"window_rows": rows}, spec["args"]) == 2.0
    assert row_mean.reduce({"window_rows": rows[:2]}, spec["args"]) is None


def test_the_gauges_are_the_ones_the_program_sets():
    """Every key a metric reads is a key ``device_scopes.gauges`` makes of a
    registered scope (or of a module group's ``unscoped``)."""
    from crosscoder_tpu.obs import device_scopes, scopes

    reading = {"steps": 1, "busy_s": 1.0, "scoped_share": 1.0, "by_scope": {
        "jit__seg_scan_impl": {**{s: 1.0 for s in scopes.SCOPES}, "harvest/unscoped": 1.0},
        "jit_step_fn_bare": {"cc/encode:bwd": 1.0}}}
    made = set(device_scopes.gauges(reading))
    assert {gauge for gauge, _, _ in SCOPE_METRICS.values()} <= made


def test_the_entries_come_after_every_entry_that_was_there():
    names = [m["name"] for m in SHIPPED["per_layer"]]
    first = min(names.index(n) for n in SCOPE_METRICS)
    assert names.index("mhc_col_err") == first - 1 == 34
    manifest.validate(SHIPPED)
    accepted.pr26_metrics_keep_their_places(SHIPPED)
