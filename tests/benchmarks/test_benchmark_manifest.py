"""The manifest validator: accepts what ships, refuses what the driver would."""

import copy
import json
import shutil
import subprocess
import sys

import accepted
import pytest

from benchmarks import manifest

SHIPPED = manifest.load()


def test_the_shipped_manifest_is_valid_and_its_files_agree():
    manifest.validate(SHIPPED)
    accepted.traffic_files_name_runners_that_are_there(SHIPPED, manifest.ROOT)
    for w in SHIPPED["workloads"]:
        cell = manifest.cell(SHIPPED, w["name"])
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
    ``reduced``; no width is reduced. The published numbers are one file a
    model under ``published/``, found by the configuration's ``source``."""
    accepted.configurations_keep_their_published_numbers(SHIPPED, manifest.ROOT)


# The numbers of Mellum2-12B-A2.5B-Instruct's published config (the catalog
# beside the model-configs guide): what the follow-on ``model_config`` PR pins.
MELLUM2 = {
    "source": "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json",
    "config": {"head_dim": 128, "hidden_size": 2304, "intermediate_size": 7168,
               "max_position_embeddings": 131072, "max_window_layers": 0,
               "moe_intermediate_size": 896, "num_attention_heads": 32, "num_experts": 64,
               "num_experts_per_tok": 8, "num_hidden_layers": 28, "num_key_value_heads": 4,
               "rms_norm_eps": 1e-06, "sliding_window": 1024, "vocab_size": 98304},
}


def _a_later_prs_additions(root):
    """In the copy under ``root``: a configuration of another model (with its
    published numbers' file), a traffic mix naming a second runner module, a
    cell, an appended per-layer metric and the cell appended to a PR 26
    metric's list. No file that was there is edited; returns the manifest and
    the directory of published numbers."""
    b = root / "benchmarks"
    published = root / "published"
    shutil.copytree(accepted.PUBLISHED, published)
    (published / "JetBrains.Mellum2-12B-A2.5B-Instruct.json").write_text(json.dumps(MELLUM2))
    cfg = {**MELLUM2["config"], "source": MELLUM2["source"], "num_hidden_layers": 4,
           "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
           "crosscoder": {"d_in": 2304, "hook_point": "blocks.4.hook_resid_pre"}}
    (b / "configs" / "mellum2-pair-topk32k.json").write_text(json.dumps(cfg))
    (b / "runners" / "train_long.py").write_text(
        "from benchmarks.runners.train import run  # noqa: F401\n")
    mix = manifest.load_json(b / "traffic" / "live-full.json")
    (b / "traffic" / "live-long.json").write_text(json.dumps({**mix, "runner": "train_long"}))
    spec = {"unit": "%", "better": "higher", "source": "device_trace",
            "layer": "harvest (models/lm.py)", "moves": "train_rows_per_s",
            "reducer": "peak_share", "chip_only": True,
            "args": {"group": "expert_kernel", "per": ["traced_steps"],
                     "work": "expert_kernel_flops_per_step_per_chip",
                     "peak": "bf16_flops_per_s"}}
    (b / "metrics" / "expert_kernel_roofline.json").write_text(json.dumps(spec))
    man = copy.deepcopy(SHIPPED)
    man["configs"].append({"name": "mellum2-pair-topk32k", "source": MELLUM2["source"],
                           "file": "benchmarks/configs/mellum2-pair-topk32k.json",
                           "reduced": ["num_hidden_layers", "layer_types"],
                           "why": "one period of the layer pattern, every expert"})
    man["workloads"].append({"name": "train-live-mellum2", "config": "mellum2-pair-topk32k",
                             "traffic": "live-long", "chips": 1, "why": "4096-token rows"})
    man["per_layer"].append({k: v for k, v in spec.items()
                             if k not in ("reducer", "args", "chip_only")}
                            | {"name": "expert_kernel_roofline",
                               "workloads": ["train-live-mellum2"]})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("train_rows_per_s", "setup_first_fill_s"):
            m["workloads"].append("train-live-mellum2")
    return man, published


def _the_four_statements(man, root, published):
    manifest.validate(man, root)
    accepted.configurations_keep_their_published_numbers(man, root, published)
    accepted.traffic_files_name_runners_that_are_there(man, root)
    for name in accepted.PR26:
        accepted.a_pr26_metric_lists_both_accepted_cells_first(man, name)
    accepted.pr26_metrics_keep_their_places(man)


def test_a_later_prs_additions_pass_the_validator_and_the_four_statements(code_root):
    """Before ISSUE 28 the same additions failed all four (Ouro's numbers as a
    literal for every configuration, two runner names, ``workloads == CELLS``,
    the ten the LAST ten)."""
    before = {f: f.read_bytes() for f in code_root.rglob("*") if f.is_file()}
    man, published = _a_later_prs_additions(code_root)
    _the_four_statements(man, code_root, published)
    assert accepted.may_be_absent_on_the_cpu("expert_kernel_roofline", man, code_root)
    assert not accepted.may_be_absent_on_the_cpu("harvest_peak_share", man, code_root)
    assert all(f.read_bytes() == was for f, was in before.items())


def _edit_config(root, **keys):
    f = root / "benchmarks" / "configs" / "mellum2-pair-topk32k.json"
    f.write_text(json.dumps({**json.loads(f.read_text()), **keys}))


def _entry(man, name):
    return next(m for m in man["per_layer"] if m["name"] == name)


def _swap_two_of_the_ten(man, root):
    i, j = (man["per_layer"].index(_entry(man, n)) for n in accepted.PR26[2:4])
    man["per_layer"][i], man["per_layer"][j] = man["per_layer"][j], man["per_layer"][i]


# the faults the four were written for: each breaks the additions above
STILL_REFUSED = {
    "a number that differs from the published one and is not in reduced":
        lambda man, root: _edit_config(root, vocab_size=49152),
    "a published number left out of the configuration":
        lambda man, root: _edit_config(root, num_experts_per_tok=None),
    "a width in reduced":
        lambda man, root: (_edit_config(root, moe_intermediate_size=448),
                           man["configs"][-1]["reduced"].append("moe_intermediate_size")),
    "a crosscoder narrower than the model":
        lambda man, root: _edit_config(root, crosscoder={"d_in": 2048}),
    "a source with no file of published numbers":
        lambda man, root: (_edit_config(root, source="https://example.org/other"),
                           man["configs"][-1].update(source="https://example.org/other")),
    "a source that disagrees with the configuration's file":
        lambda man, root: _edit_config(
            root, source=SHIPPED["configs"][0]["source"]),
    "a runner that is not there":
        lambda man, root: (root / "benchmarks" / "runners" / "train_long.py").rename(
            root / "benchmarks" / "runners" / "elsewhere.py"),
    "a runner module with no run":
        lambda man, root: (root / "benchmarks" / "runners" / "train_long.py").write_text(
            "from benchmarks.runners.train import CycleLog  # noqa: F401\n"),
    "a PR 26 metric dropped from an accepted cell":
        lambda man, root: _entry(man, "setup_first_fill_s")["workloads"].remove(
            "train-live-topk32k"),
    "a later cell put before the accepted two":
        lambda man, root: _entry(man, "setup_first_fill_s")["workloads"].sort(
            key=lambda w: w != "train-live-mellum2"),
    "two of the ten in another order": _swap_two_of_the_ten,
    "an entry put before those that were there":
        lambda man, root: man["per_layer"].insert(0, man["per_layer"].pop()),
}


@pytest.mark.parametrize("what", sorted(STILL_REFUSED))
def test_the_four_statements_still_refuse(what, code_root):
    man, published = _a_later_prs_additions(code_root)
    STILL_REFUSED[what](man, code_root)
    with pytest.raises((AssertionError, manifest.ManifestError)):
        _the_four_statements(man, code_root, published)


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
