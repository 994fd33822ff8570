"""The manifest validator: accepts what ships, refuses what the driver would."""

import copy
import json
import subprocess
import sys

import pytest

from benchmarks import manifest

SHIPPED = manifest.load()


def test_the_shipped_manifest_is_valid_and_its_files_agree():
    manifest.validate(SHIPPED)
    for w in SHIPPED["workloads"]:
        cell = manifest.cell(SHIPPED, w["name"])
        assert cell["traffic"]["runner"] in ("train", "serve")
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]


def test_metric_files_list_no_cells():
    """Where a metric is reported is the manifest's business alone, so that a
    new cell reports it with no file edited."""
    for m in SHIPPED["per_layer"]:
        spec = manifest.load_json(manifest.BENCH_DIR / "metrics" / f"{m['name']}.json")
        assert "workloads" not in spec and spec["reducer"]


def _broken(edit):
    man = copy.deepcopy(SHIPPED)
    edit(man)
    return man


def _second_four_chip_cell(man):
    for w in man["workloads"][:2]:
        w["chips"] = 4


def _metric_on_a_cell_without_its_target(man):
    """A cell with an end-to-end metric of its own reports a per-layer metric
    that moves ``train_rows_per_s``, which it does not report."""
    man["workloads"].append({**man["workloads"][0], "name": "other", "traffic": "other"})
    man["end_to_end"].insert(0, {**man["end_to_end"][0], "name": "other_per_s",
                                 "workloads": ["other"]})
    next(p for p in man["per_layer"] if p["name"] == "harvest_peak_share")[
        "workloads"].append("other")


CASES = {
    "a bad name": lambda m: m["workloads"][0].update(name="train live"),
    "a name too long": lambda m: m["end_to_end"][0].update(name="x" * 65),
    "a bad unit": lambda m: m["end_to_end"][0].update(unit="rows per s"),
    "a greek unit": lambda m: m["per_layer"][0].update(unit="µs"),
    "a moves target a cell lacks": _metric_on_a_cell_without_its_target,
    "an unknown moves target": lambda m: m["per_layer"][0].update(moves="nothing"),
    "a second four-chip cell": _second_four_chip_cell,
    "a bound over a tenth": lambda m: m["end_to_end"][0].update(bound=0.2),
    "a bound under one percent": lambda m: m["end_to_end"][0].update(bound=0.001),
    "no setup_s": lambda m: m["end_to_end"].pop(),
    "a key too many on a metric": lambda m: m["per_layer"][0].update(why="because"),
    "a reduced width": lambda m: m["configs"][0]["reduced"].append("hidden_size"),
    "run_seconds too long": lambda m: m.update(run_seconds=52),
    "a command that leaves the repo": lambda m: m["command"].append("../x"),
    "a repeated cell": lambda m: m["workloads"].append(dict(m["workloads"][0])),
    "an unknown config": lambda m: m["workloads"][0].update(config="nope"),
    "a metric file that disagrees":
        lambda m: m["per_layer"][0].update(unit="s" if m["per_layer"][0]["unit"] != "s" else "ms"),
}


@pytest.mark.parametrize("what", sorted(CASES))
def test_the_validator_refuses(what):
    with pytest.raises(manifest.ManifestError):
        manifest.validate(_broken(CASES[what]))


def test_an_end_to_end_metric_is_host_clock_or_device_trace():
    with pytest.raises(manifest.ManifestError):
        manifest.validate(_broken(
            lambda m: m["end_to_end"][0].update(source="program_counter")))


def test_configuration_files_keep_every_number_of_the_catalog_entry():
    """Numbers of the source's config under the same key; what differs is in
    ``reduced``; no width is reduced."""
    published = {"head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
                 "max_position_embeddings": 65536, "max_window_layers": 48,
                 "num_attention_heads": 16, "num_hidden_layers": 48,
                 "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
                 "rope_theta": 1000000, "total_ut_steps": 4,
                 "early_exit_threshold": 1, "vocab_size": 49152}
    for c in SHIPPED["configs"]:
        held = manifest.load_json(manifest.ROOT / c["file"])
        differs = {k for k, v in published.items() if held.get(k) != v}
        assert differs <= set(c["reduced"]), (c["name"], differs)
        assert held["crosscoder"]["d_in"] == held["hidden_size"]
        assert c["source"] == held["source"]


def test_run_py_fails_without_a_chip_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, str(manifest.ROOT / "benchmarks" / "run.py"), "--workload",
         SHIPPED["workloads"][0]["name"], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=manifest.ROOT)
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
    assert "tpu device" in proc.stderr


def test_the_file_stays_within_the_contracts_size():
    assert len(json.dumps(SHIPPED)) < 64 * 1024
