"""What the accepted tests state about a manifest and its data files, as
functions of the manifest and the root it is read from. The tests make each
statement of what ships, of a copy with the additions of a later PR, and of
copies broken in the way the statement was written for."""

from __future__ import annotations

import importlib
from pathlib import Path

from benchmarks import manifest

# One file a published model: its ``source`` and the numbers of its config.
# A PR that adds a configuration of another model adds that model's file.
PUBLISHED = Path(__file__).parent / "published"
# The ten metrics PR 26 appended, in its order, and the cells accepted then.
PR26 = ["loop_produce_self_ms_per_step", "loop_main_self_ms_per_step",
        "loop_step_dispatch_ms_per_step", "loop_log_sync_ms",
        "loop_slow_cycle_host_excess_ms", "setup_calibrate_s", "setup_first_fill_s",
        "setup_init_state_s", "cc_bare_device_ms_per_step", "cc_full_device_ms_per_step"]
CELLS = ["train-live-relu16k", "train-live-topk32k"]
# What only the chip can give (a kernel's own ops, device memory) may be
# absent from a traced run on the CPU: the two metrics' families that were
# there before a metric file could say so, and ``"chip_only": true``.
CHIP_ONLY_PREFIXES = ("topk_kernel_", "device_peak_hbm")


def published_numbers(directory: Path = PUBLISHED) -> dict[str, dict]:
    """``source`` -> the published numbers, over the files of ``directory``."""
    specs = [manifest.load_json(f) for f in sorted(Path(directory).glob("*.json"))]
    return {s["source"]: s["config"] for s in specs}


def configurations_keep_their_published_numbers(man: dict, root: Path,
                                                directory: Path = PUBLISHED) -> None:
    """Every number of the source's config under the same key; what differs
    is in ``reduced``; no width is reduced."""
    published = published_numbers(directory)
    for c in man["configs"]:
        assert c["source"] in published, \
            f"{c['name']}: no file under {directory} holds the numbers of {c['source']}"
        held = manifest.load_json(Path(root) / c["file"])
        differs = {k for k, v in published[c["source"]].items() if held.get(k) != v}
        assert differs <= set(c["reduced"]), (c["name"], differs - set(c["reduced"]))
        assert not [k for k in c["reduced"] if manifest._WIDTH.search(k)], c["reduced"]
        assert held["crosscoder"]["d_in"] == held["hidden_size"]
        assert c["source"] == held["source"]


def traffic_files_name_runners_that_are_there(man: dict, root: Path) -> None:
    """The ``runner`` of each cell's traffic file is a module under a
    ``runners/`` directory of ``paths``, with a ``run``."""
    homes = {(Path(r) / p / "runners").resolve()
             for r in (root, manifest.ROOT) for p in man["paths"]}
    for w in man["workloads"]:
        name = manifest.load_json(
            manifest.traffic_file(w["traffic"], root, man["paths"]))["runner"]
        try:
            mod = importlib.import_module(f"benchmarks.runners.{name}")
        except ImportError as e:
            raise AssertionError(f"{w['name']}: no runner {name!r}: {e}") from None
        assert Path(mod.__file__).resolve().parent in homes, mod.__file__
        assert callable(getattr(mod, "run", None)), f"runners/{name}.py has no run"


def a_pr26_metric_lists_both_accepted_cells_first(man: dict, name: str) -> dict:
    """A later cell appends itself; the accepted two stay, and stay first."""
    entry = next(m for m in man["per_layer"] if m["name"] == name)
    assert entry["workloads"][:2] == CELLS, (name, entry["workloads"])
    return entry


def pr26_metrics_keep_their_places(man: dict) -> None:
    """The ten in their order among themselves, after every entry that was
    there before them; entries may follow."""
    names = [m["name"] for m in man["per_layer"]]
    assert [n for n in names if n in PR26] == PR26
    assert names.index("device_peak_hbm_gib.train") == 13 < names.index(PR26[0])


def may_be_absent_on_the_cpu(name: str, man: dict, root: Path) -> bool:
    spec = manifest.load_json(manifest.metric_file(name, root, man["paths"]))
    return name.startswith(CHIP_ONLY_PREFIXES) or spec.get("chip_only") is True
