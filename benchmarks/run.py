#!/usr/bin/env python3
"""One cell of the benchmark, once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Validates BENCHMARK.json and the cell's data files, fails unless JAX reports
TPU devices of a kind in ``peaks.json`` and as many as the cell asks for,
hands the cell to the runner its traffic file names, and prints as the last
line of standard output one JSON object with the keys ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (with ``--trace 1``
also ``breakdown``), and last ``compared``: each number the runner compared
with a plain reference, beside its limit (they are also the last lines of
standard error). ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics. Earlier lines (``[bench] ...``) carry the
series' summaries, the set-up phases and the host's load; the raw readings
go to ``benchmarks/out/<workload>/``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # process start, as nearly as Python can tell

import argparse     # noqa: E402
import importlib    # noqa: E402
import json         # noqa: E402
import sys          # noqa: E402
import traceback    # noqa: E402
from pathlib import Path    # noqa: E402
from typing import Any      # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import common, manifest, trace_reduce   # noqa: E402
from benchmarks.common import say                       # noqa: E402


def per_layer_values(cell: dict, obs: dict) -> dict:
    """Each of the cell's per-layer metrics through its own reader; one that
    finds nothing to read is left out."""
    out = {}
    for m in cell["per_layer"]:
        reducer = importlib.import_module(f"benchmarks.reducers.{m['reducer']}")
        value = reducer.reduce(obs, m["args"])
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: int, *,
             root: Path = ROOT, sizes: dict | None = None,
             t_start: float | None = None) -> tuple[dict, dict]:
    """Run one cell; returns (the result line as a dict, the observations).

    ``sizes`` is the tests' entry: tiny LM and crosscoder sizes and leave to
    run on whatever backend is there. ``run.py`` never passes it, so from the
    command line a cell runs at its configuration's sizes on a TPU or fails.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    man = manifest.load(root)
    manifest.validate(man, root)
    cell = manifest.cell(man, workload, root)
    chips = cell["workload"]["chips"]
    rec = common.RunRecord(root, workload, seed, trace, t_start)
    import jax

    rec.phase("python start, manifest, import jax")
    devices = jax.devices()
    rec.phase("backend start (jax.devices())")
    if sizes is None:
        if devices[0].platform != "tpu" or len(devices) != chips:
            raise RuntimeError(
                f"cell {workload} needs {chips} tpu device(s); JAX reports "
                f"{len(devices)} x {devices[0].platform}")
        peaks = common.peaks_for(devices[0].device_kind)
    else:
        peaks = sizes.get("peaks")
    from crosscoder_tpu.utils import compile_cache

    compiles = common.CompileLog().install()
    # once, before the first compile (the tests leave their process's JAX
    # configuration as it is)
    cache_dir = compile_cache.enable() if sizes is None else None
    say(f"{workload} seed {seed} seconds {seconds} trace {trace}; jax "
        f"{jax.__version__} on {len(devices)} x {devices[0].device_kind}; "
        f"XLA cache {cache_dir}")
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace)
    runner = importlib.import_module(
        f"benchmarks.runners.{cell['traffic']['runner']}")
    res = runner.run(cell, args, rec, compiles, sizes)
    obs = res["observations"]
    obs["peaks"] = peaks
    problems = list(res["problems"])
    reduced = None
    if trace and obs.get("trace"):
        rec.note("trace_sample", trace_reduce.sample(obs["trace"]))
        reduced = obs["trace_reduced"] = trace_reduce.reduce_trace(
            obs["trace"], trace_reduce.attribution(root, cell["paths"]))
        say(f"device trace {obs['trace']['file']}: busy {reduced['busy_s']:.4f} s "
            f"of {reduced['window_s']:.4f} s on {reduced['n_devices']} device(s); "
            f"unattributed share of device time "
            f"{100 * reduced.get('unattributed_share', 0):.3f}%; groups (s) "
            + ", ".join(f"{g} {s:.4f}" for g, s in sorted(reduced["groups"].items())))
        rec.note("trace_reduced", reduced)
        if reduced.get("top_unattributed"):
            problems.append("unattributed among the ten longest device ops: "
                            f"{reduced['top_unattributed']}")
        if not reduced["busy_s"] > 0:
            problems.append("no operation ran on the device in the traced window")
    elif trace:
        problems.append("the traced run left no profile")
    if trace:
        metrics = per_layer_values(cell, obs)
    else:
        metrics = {m["name"]: {"value": float(res["metrics"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell["end_to_end"] if m["name"] in res["metrics"]}
        missing = [m["name"] for m in cell["end_to_end"]
                   if m["name"] not in res["metrics"]]
        if missing:
            problems.append(f"end-to-end metrics not measured: {missing}")
    for p in problems:
        say(f"PROBLEM: {p}")
    rec.note("problems", problems)
    obs["problems"] = problems
    rec.note("metrics", metrics)
    rec.write()
    line: dict[str, Any] = {
        "correct": not problems, "attempted": int(res["attempted"]),
        "failed": int(res["failed"]), "metrics": metrics,
        "device": common.device_block(res["devices"], reduced),
    }
    if reduced:
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    # last: each number the runner compared with a reference, beside its limit
    line["compared"] = res.get("compared", {})
    return line, obs


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = ap.parse_args(argv)
    try:
        line, _ = run_cell(ns.workload, ns.seed, ns.seconds, ns.trace,
                           t_start=T_START)
    except Exception:   # noqa: BLE001 — the boundary: no result line, code 1
        traceback.print_exc(file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    for name, c in line["compared"].items():    # the last lines of standard error
        print(f"compared {name}: {c['value']:.6g} (limit {c['limit']:g})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
