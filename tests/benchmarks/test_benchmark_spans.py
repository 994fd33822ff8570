"""The readers of the program's span totals, set-up spans and step-variant
modules (PR 26): on samples recorded on the chip (kept in ``data/``), on
hand-made cases, and on what a program WITHOUT the spans gives (nothing)."""

import json
from pathlib import Path

import accepted
import pytest

from benchmarks import manifest, run
from benchmarks.reducers import (interval_spans, module_device_ms,
                                 slow_interval_excess, span_total)

DATA = Path(__file__).parent / "data"
SHIPPED = manifest.load()
NEW, CELLS = accepted.PR26, accepted.CELLS


def _spec(name: str) -> dict:
    return manifest.load_json(manifest.BENCH_DIR / "metrics" / f"{name}.json")


def _row(interval_s=2.0, steps=10, **spans) -> dict:
    row = {"perf/interval_s": interval_s, "perf/interval_steps": float(steps)}
    for name, s in spans.items():
        row[f"perf/span/{name}_s"] = s
    return row


@pytest.mark.parametrize("name", NEW)
def test_the_manifest_lists_the_metric_in_both_cells_and_its_file_agrees(name):
    manifest.validate(SHIPPED)
    entry = accepted.a_pr26_metric_lists_both_accepted_cells_first(SHIPPED, name)
    for cell in CELLS:
        assert name in [m["name"] for m in manifest.cell(SHIPPED, cell)["per_layer"]]
    spec = _spec(name)
    assert "workloads" not in spec
    assert {k: spec[k] for k in entry if k != "workloads"} == \
        {k: v for k, v in entry.items() if k != "workloads"}
    if name.startswith("loop_"):
        assert entry["layer"] == "loop (train/trainer.py)"
        assert entry["source"] == "program_span"


def test_new_entries_come_after_every_entry_that_was_there():
    accepted.pr26_metrics_keep_their_places(SHIPPED)


@pytest.mark.parametrize("sample", ["spans_relu16k_sample.json", "spans_topk32k_sample.json"])
def test_recorded_chip_rows_and_spans_reduce_to_pinned_numbers(sample):
    """What one traced run of the cell on a TPU v5 lite (PR 26) logged: the
    window's rows (``perf/*`` keys) and the set-up spans of its trace.json."""
    obs = json.loads((DATA / sample).read_text())
    cell = manifest.cell(SHIPPED, obs["recorded"]["workload"])
    got = run.per_layer_values(
        {"per_layer": [m for m in cell["per_layer"] if m["name"] in obs["expected"]]}, obs)
    assert set(got) == set(obs["expected"])
    for name, want in obs["expected"].items():
        assert got[name]["value"] == pytest.approx(want, rel=1e-9, abs=1e-12), name
    # the identities the totals keep in every row after the profiler's
    rows = interval_spans.quiet_rows(obs)
    assert len(rows) == len(obs["window_rows"]) - (obs["traced_steps"] // obs["spc"] + 1)
    for r in rows:
        main = sum(r[f"perf/span/{n}_s"] for n in ("refill_wait", "step", "log_sync"))
        assert 0 < main <= r["perf/interval_s"]
        assert r["perf/refill_bubble_frac"] == pytest.approx(
            r["perf/span/refill_wait_s"] / r["perf/interval_s"], rel=1e-5)
        assert r["perf/span/produce_n"] == r["perf/interval_steps"] == obs["spc"]
        inside = sum(r.get(f"perf/span/{n}_s", 0.0)
                     for n in ("serve_gather", "harvest_dispatch", "harvest"))
        assert 0 <= inside <= r["perf/span/produce_s"]


def test_interval_spans_are_means_over_the_rows_after_the_profilers():
    quiet = [_row(2.0, 10, produce=1.5, serve_gather=0.2, harvest_dispatch=1.0, harvest=0.1,
                  refill_wait=1.4, step=0.3, log_sync=0.2),
             _row(2.2, 10, produce=1.7, serve_gather=0.2, harvest_dispatch=1.2,
                  refill_wait=1.6, step=0.3, log_sync=0.2)]
    noisy = [_row(9.0, 10, produce=8.0, refill_wait=1.0, step=0.1, log_sync=0.1)] * 3
    obs = {"window_rows": noisy + quiet, "traced_steps": 20, "spc": 10}
    value = {n: interval_spans.reduce(obs, _spec(n)["args"]) for n in NEW[:4]}
    assert value["loop_produce_self_ms_per_step"] == pytest.approx((20 + 30) / 2)
    assert value["loop_main_self_ms_per_step"] == pytest.approx(10.0)
    assert value["loop_step_dispatch_ms_per_step"] == pytest.approx(30.0)
    assert value["loop_log_sync_ms"] == pytest.approx(200.0)
    # no row after the profiler's (a run the runner refuses): the rows as they are
    assert interval_spans.reduce({"window_rows": noisy, "traced_steps": 20, "spc": 10},
                                 _spec(NEW[3])["args"]) == pytest.approx(100.0)
    # without a traced window every row counts
    assert interval_spans.reduce({"window_rows": quiet}, _spec(NEW[3])["args"]) == \
        pytest.approx(200.0)


@pytest.mark.parametrize("what, slow, want_ms", [
    # the slowest cycle is 80 ms long and the host did what it always does:
    # the wall went into waiting (refill_wait) -> the DEVICE was slow
    ("device", dict(interval_s=2.08, produce=1.58, harvest_dispatch=1.48,
                    refill_wait=1.78, step=0.2, log_sync=0.05), 0.0),
    # 80 ms more of producer host work in the slowest cycle -> the HOST was away
    ("producer", dict(interval_s=2.08, produce=1.58, harvest_dispatch=1.40,
                      refill_wait=1.78, step=0.2, log_sync=0.05), 80.0),
    # 80 ms the main thread spent outside its spans (a logger, a collector pause)
    ("main", dict(interval_s=2.08, produce=1.50, harvest_dispatch=1.40,
                  refill_wait=1.70, step=0.2, log_sync=0.05), 80.0),
])
def test_slow_cycle_excess_tells_a_slow_device_from_an_absent_host(what, slow, want_ms):
    usual = dict(interval_s=2.0, produce=1.50, harvest_dispatch=1.40,
                 refill_wait=1.70, step=0.2, log_sync=0.05)
    rows = [_row(**usual) for _ in range(4)] + [_row(**slow)] + [_row(**usual)]
    obs = {"window_rows": [_row(**usual)] * 3 + rows, "traced_steps": 20, "spc": 10}
    got = slow_interval_excess.reduce(obs, _spec("loop_slow_cycle_host_excess_ms")["args"])
    assert got == pytest.approx(want_ms, abs=1e-6)


def test_slow_cycle_excess_needs_three_rows():
    obs = {"window_rows": [_row(produce=1.0)] * 2}
    assert slow_interval_excess.reduce(
        obs, _spec("loop_slow_cycle_host_excess_ms")["args"]) is None


def test_span_total_sums_the_spans_of_one_name():
    obs = {"host_spans": [["calibrate", 10.0, 1.5], ["first_fill", 11.5, 4.0],
                          ["first_fill", 90.0, 0.5], ["step", 20.0, 0.001]]}
    assert span_total.reduce(obs, _spec("setup_calibrate_s")["args"]) == 1.5
    assert span_total.reduce(obs, _spec("setup_first_fill_s")["args"]) == 4.5
    assert span_total.reduce(obs, _spec("setup_init_state_s")["args"]) is None


def _ops(*ops):
    return {"trace": {"devices": {"/device:TPU:0": [list(o) for o in ops]}}}


def test_module_device_ms_is_self_time_over_the_variants_steps():
    obs = _ops(("fusion.1", "jit_step_fn_bare", 0, 100), ("while.1", "jit_step_fn_bare", 100, 300),
               ("fusion.2", "jit_step_fn_bare", 150, 200),          # inside the while
               ("fusion.9", "jit__seg_scan_impl", 400, 1000),
               ("fusion.1", "jit_step_fn_bare", 1400, 100), ("dot.3", "jit_step_fn_bare", 1500, 300),
               ("fusion.1", "jit_step_fn_full", 2000, 600))
    obs.update(traced_steps=3, spc=3)
    bare = module_device_ms.reduce(obs, _spec("cc_bare_device_ms_per_step")["args"])
    full = module_device_ms.reduce(obs, _spec("cc_full_device_ms_per_step")["args"])
    assert bare == pytest.approx((100 + 300 + 100 + 300) / 2 / 1e6)
    assert full == pytest.approx(600 / 1e6)
    # two devices: the mean over them
    two = {"trace": {"devices": {d: obs["trace"]["devices"]["/device:TPU:0"]
                                 for d in ("/device:TPU:0", "/device:TPU:1")}},
           "traced_steps": 3, "spc": 3}
    assert module_device_ms.reduce(two, _spec("cc_full_device_ms_per_step")["args"]) == \
        pytest.approx(600 / 1e6)


def test_recorded_chip_trace_cut_splits_the_step_by_variant():
    """The device ops from one full-metrics step to the end of the bare step
    after it (the harvest's ops between them included), train-live-relu16k on
    a TPU v5 lite (PR 26): one run of each variant."""
    obs = json.loads((DATA / "step_variants_relu16k_sample.json").read_text())
    for name, want in obs["expected"].items():
        got = module_device_ms.reduce(obs, _spec(name)["args"])
        assert got == pytest.approx(want, rel=1e-9), name
    mods = {o[1] for ops in obs["trace"]["devices"].values() for o in ops}
    assert {"jit_step_fn_bare", "jit_step_fn_full"} <= mods and len(mods) > 2


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_gives_nothing_and_does_not_raise(name):
    """The parent of PR 26 logs ``perf/refill_bubble_frac`` alone, records no
    set-up span and names both step variants ``jit_step_fn``."""
    parent = {
        "window_rows": [{"step": 15 * i, "t": 2.0 * i, "loss": 1.0,
                         "perf/refill_bubble_frac": 0.8, "perf/step_ms": 0.4,
                         "perf/step_spans": 15.0} for i in range(1, 8)],
        "host_spans": [["step", 1.0, 0.001], ["refill_wait", 1.1, 0.1], ["harvest", 1.2, 0.01]],
        "trace": {"devices": {"/device:TPU:0": [["fusion.1", "jit_step_fn", 0, 100],
                                                ["fusion.2", "jit__seg_scan_impl", 100, 100]]}},
        "traced_steps": 30, "spc": 15,
    }
    spec = _spec(name)
    cell = {"per_layer": [{**spec, "reducer": spec["reducer"], "args": spec["args"]}]}
    assert run.per_layer_values(cell, parent) == {}
    assert run.per_layer_values(cell, {}) == {}
