"""BENCHMARK.json and the data files it names: loading and validation.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in BENCHMARK.json:
``configs/<config>.json`` (the manifest names the file), ``traffic/<traffic>.json``
and ``metrics/<metric>.json``. A later PR adds a cell by adding files and
manifest entries; nothing here lists names.

``validate`` runs before the chip is touched and refuses what the driver
would refuse: bad names and units, a ``moves`` target that a cell reporting
the metric does not report, more than a quarter of the cells on four chips
(one always may), a cell without ``setup_s``, a metric file that disagrees
with its manifest entry.
"""

from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Any

ROOT = Path(__file__).resolve().parent.parent
BENCH_DIR = Path(__file__).resolve().parent

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_WIDTH = re.compile(r"(_dim|_rank)$|(hidden|intermediate|latent|state|proj\w*|head)_size"
                    r"|expansion|experts_per_tok")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
END_TO_END_SOURCES = ("host_clock", "device_trace")
_TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
             "end_to_end", "per_layer"}


class ManifestError(ValueError):
    """The manifest or one of its data files breaks the benchmark's contract."""


def _need(cond: bool, what: str) -> None:
    if not cond:
        raise ManifestError(what)


def _name(value: Any, what: str) -> None:
    _need(isinstance(value, str) and bool(_NAME.match(value)),
          f"{what}: bad name {value!r} (letters, digits, _ . -; at most 64)")


def _line(value: Any, what: str) -> None:
    _need(isinstance(value, str) and 1 <= len(value) <= 200
          and "\n" not in value and "\t" not in value,
          f"{what}: must be 1..200 characters on one line")


def load_json(path: Path) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise ManifestError(f"cannot read {path}: {e}") from None


def load(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def _metric_cells(metric: dict, cells: list[str]) -> list[str]:
    return list(metric.get("workloads", cells))


def validate(manifest: dict, root: Path = ROOT, files: bool = True) -> None:
    """Raise ManifestError unless ``manifest`` keeps the contract; with
    ``files`` also check each named data file against its entry."""
    root = Path(root)
    _need(set(manifest) == _TOP_KEYS,
          f"BENCHMARK.json keys {sorted(manifest)} != {sorted(_TOP_KEYS)}")
    paths = manifest["paths"]
    _need(isinstance(paths, list) and 1 <= len(paths) <= 16, "paths: 1..16")
    for p in paths:
        _need(isinstance(p, str) and re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
              and not p.startswith("/") and ".." not in p.split("/"),
              f"paths: bad directory {p!r}")
    cmd = manifest["command"]
    _need(isinstance(cmd, list) and 1 <= len(cmd) <= 32, "command: 1..32 words")
    for w in cmd:
        _line(w, "command word")
        _need(not w.startswith("/") and ".." not in w.split("/"),
              f"command word {w!r} leaves the repo")
    rs = manifest["run_seconds"]
    _need(isinstance(rs, int) and 1 <= rs <= 51, "run_seconds: 1..51")

    def under_paths(rel: str) -> bool:
        return any(rel == p or rel.startswith(p.rstrip("/") + "/") for p in paths)

    configs = manifest["configs"]
    _need(1 <= len(configs) <= 24, "configs: 1..24")
    seen_files: set[str] = set()
    for c in configs:
        _need(set(c) == {"name", "source", "file", "reduced", "why"},
              f"config entry keys {sorted(c)}")
        _name(c["name"], "config")
        _line(c["source"], f"config {c['name']} source")
        _line(c["why"], f"config {c['name']} why")
        _need(under_paths(c["file"]) and c["file"] not in seen_files,
              f"config {c['name']}: file {c['file']!r} outside paths or shared")
        seen_files.add(c["file"])
        _need(isinstance(c["reduced"], list) and len(c["reduced"]) <= 16,
              f"config {c['name']}: reduced has over 16 keys")
        for k in c["reduced"]:
            _name(k, f"config {c['name']} reduced key")
            _need(not _WIDTH.search(k),
                  f"config {c['name']}: reduced names a width ({k})")
    _need(len({c["name"] for c in configs}) == len(configs), "config names repeat")

    cells = manifest["workloads"]
    _need(1 <= len(cells) <= 24, "workloads: 1..24")
    cfg_names = {c["name"] for c in configs}
    for w in cells:
        _need(set(w) == {"name", "config", "traffic", "chips", "why"},
              f"workload entry keys {sorted(w)}")
        for k in ("name", "config", "traffic"):
            _name(w[k], f"workload {k}")
        _line(w["why"], f"workload {w['name']} why")
        _need(w["config"] in cfg_names, f"workload {w['name']}: unknown config")
        _need(w["chips"] in (1, 4), f"workload {w['name']}: chips must be 1 or 4")
    names = [w["name"] for w in cells]
    _need(len(set(names)) == len(names), "workload names repeat")
    pairs = [(w["config"], w["traffic"]) for w in cells]
    _need(len(set(pairs)) == len(pairs), "a (config, traffic) pair repeats")
    _need(cfg_names == {w["config"] for w in cells}, "a config has no cell")
    four = sum(w["chips"] == 4 for w in cells)
    _need(four <= max(1, len(cells) // 4),
          f"{four} four-chip cells of {len(cells)}: at most a quarter (one always may)")

    e2e, layer = manifest["end_to_end"], manifest["per_layer"]
    _need(1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128, "metric counts")
    all_names = [m["name"] for m in e2e + layer]
    _need(len(set(all_names)) == len(all_names), "metric names repeat")
    for m in e2e + layer:
        _name(m["name"], "metric")
        _need(isinstance(m.get("unit"), str) and bool(_UNIT.match(m["unit"])),
              f"metric {m['name']}: bad unit {m.get('unit')!r}")
        _need(m.get("better") in ("lower", "higher"), f"metric {m['name']}: better")
        for wname in m.get("workloads", []):
            _need(wname in names, f"metric {m['name']}: unknown workload {wname}")
    for m in e2e:
        _need(set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"},
              f"end_to_end {m['name']}: keys {sorted(m)}")
        _need(m["source"] in END_TO_END_SOURCES, f"end_to_end {m['name']}: source")
        _need(isinstance(m["bound"], (int, float)) and 0.01 <= m["bound"] <= 0.1,
              f"end_to_end {m['name']}: bound {m['bound']} outside 0.01..0.1")
    _need(any(m["name"] == "setup_s" and "workloads" not in m for m in e2e),
          "setup_s must be an end-to-end metric of every cell")
    e2e_cells = {m["name"]: set(_metric_cells(m, names)) for m in e2e}
    for w in names:
        _need(sum(w in c for n, c in e2e_cells.items() if n != "setup_s") >= 1,
              f"workload {w}: no end-to-end metric besides setup_s")
    for m in layer:
        _need(set(m) - {"workloads"} ==
              {"name", "unit", "better", "source", "layer", "moves"},
              f"per_layer {m['name']}: keys {sorted(m)}")
        _need(m["source"] in SOURCES, f"per_layer {m['name']}: source")
        _line(m["layer"], f"per_layer {m['name']} layer")
        _need(m["moves"] in e2e_cells, f"per_layer {m['name']}: moves unknown "
                                       f"end-to-end metric {m['moves']!r}")
        lacking = set(_metric_cells(m, names)) - e2e_cells[m["moves"]]
        _need(not lacking, f"per_layer {m['name']}: moves {m['moves']}, which "
                           f"{sorted(lacking)} do not report")
    for w in names:
        _need(any(w in _metric_cells(m, names) for m in layer),
              f"workload {w}: no per-layer metric")

    if not files:
        return
    for c in configs:
        _need((root / c["file"]).is_file(), f"config file {c['file']} missing")
    for w in cells:
        traffic_file(w["traffic"], root, paths)
    for m in layer:
        spec = load_json(metric_file(m["name"], root, paths))
        for k in ("unit", "better", "source", "layer", "moves"):
            _need(spec.get(k) == m[k], f"metrics/{m['name']}.json: {k} "
                                       f"{spec.get(k)!r} != manifest {m[k]!r}")
        # (where the metric is reported is the manifest's business alone: a
        # file that listed cells would have to be edited for every new one)
        _need("workloads" not in spec, f"metrics/{m['name']}.json: lists cells")
        _need(isinstance(spec.get("reducer"), str), f"metrics/{m['name']}.json: reducer")


def _find(kind: str, name: str, root: Path, paths: list[str]) -> Path:
    for p in paths:
        cand = Path(root) / p / kind / f"{name}.json"
        if cand.is_file():
            return cand
    raise ManifestError(f"no {kind}/{name}.json under {paths}")


def traffic_file(name: str, root: Path, paths: list[str]) -> Path:
    return _find("traffic", name, root, paths)


def metric_file(name: str, root: Path, paths: list[str]) -> Path:
    return _find("metrics", name, root, paths)


def cell(manifest: dict, workload: str, root: Path = ROOT) -> dict:
    """Everything one cell needs: its entry, configuration, traffic and the
    metrics (with their files' reducers) it reports."""
    root = Path(root)
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    _need(entry is not None, f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    names = [w["name"] for w in manifest["workloads"]]
    paths = manifest["paths"]

    def mine(ms: list[dict]) -> list[dict]:
        return [m for m in ms if workload in _metric_cells(m, names)]

    per_layer = []
    for m in mine(manifest["per_layer"]):
        spec = load_json(metric_file(m["name"], root, paths))
        per_layer.append({**m, "reducer": spec["reducer"], "args": spec.get("args", {})})
    return {
        "workload": entry,
        "config": load_json(root / cfg_entry["file"]),
        "traffic": load_json(traffic_file(entry["traffic"], root, paths)),
        "end_to_end": mine(manifest["end_to_end"]),
        "per_layer": per_layer,
        "paths": paths,
        "root": root,
    }
