#!/usr/bin/env python3
"""Back-to-back runs of one cell, each a new process: the way to look for an
odd run before a bound is set, and to take the two sets of six.

    python3 benchmarks/tools/series.py --workload <name> --runs 8 --seconds 30 \
        [--seeds 11,2147483659,...] [--trace-last] [--own-cache] [--dir <checkout>]

Never touches JAX itself (a parent that did would hold the chip). Prints each
run's earlier lines that matter and its result, then the spread of every
metric over the runs (quartile distance over the median, the driver's
measure), and copies results and raw readings under ``chiprun_out/``.
``--own-cache`` drops JAX_COMPILATION_CACHE_DIR so that the cache is the
checkout's own ``.jax_cache`` (empty in a fresh checkout: the first run
compiles). ``--dir`` runs from another checkout (an unpacked ``git archive``).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time
from pathlib import Path

KEEP = ("train_rows_per_s:", "host:", "cycle_wall_s:", "set-up phases", "PROBLEM",
        "CHECK FAILED", "first step", "harvest against", "device trace",
        "cycles after", "TopK selection")


def spread(values: list[float]) -> float:
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--trace-last", action="store_true")
    ap.add_argument("--own-cache", action="store_true")
    ap.add_argument("--dir", default=".")
    ap.add_argument("--tag", default="")
    ns = ap.parse_args()
    here = Path(ns.dir).resolve()
    seeds = [int(s) for s in ns.seeds.split(",") if s] or [
        2**31 + 104729 * (i + 1) for i in range(ns.runs)]
    env = dict(os.environ)
    if ns.own_cache:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    print(f"[series] {ns.workload} x {len(seeds)} from {here}; "
          f"JAX_COMPILATION_CACHE_DIR={env.get('JAX_COMPILATION_CACHE_DIR')!r}; "
          f"load1 {os.getloadavg()[0]:.2f}; env "
          + json.dumps({k: v for k, v in env.items() if "JAX" in k or "XLA" in k}),
          flush=True)
    out_dir = Path("chiprun_out") / "series"
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = ns.tag or ns.workload
    results = []
    for i, seed in enumerate(seeds):
        trace = int(ns.trace_last and i == len(seeds) - 1)
        cmd = [sys.executable, "benchmarks/run.py", "--workload", ns.workload,
               "--seed", str(seed), "--seconds", str(ns.seconds), "--trace", str(trace)]
        t0 = time.time()
        p = subprocess.run(cmd, cwd=here, env=env, capture_output=True, text=True)
        wall = time.time() - t0
        lines = p.stdout.strip().splitlines()
        for ln in lines[:-1]:
            if any(k in ln for k in KEEP):
                print(f"    {ln}", flush=True)
        try:
            res = json.loads(lines[-1]) if p.returncode == 0 else None
        except (json.JSONDecodeError, IndexError):
            res = None
        if res is None:
            print(f"[series] run {i} seed {seed}: rc {p.returncode}\n"
                  + p.stderr[-3000:], flush=True)
        else:
            vals = {k: v["value"] for k, v in res["metrics"].items()}
            print(f"[series] run {i} seed {seed} trace {trace} wall {wall:.1f}s "
                  f"correct {res['correct']} {json.dumps(vals)} "
                  f"peak {res['device']['memory_peak_bytes'] / 2**30:.2f} GiB", flush=True)
            if trace:
                print(f"[series] traced: {json.dumps(res)}", flush=True)
        results.append({"seed": seed, "trace": trace, "wall_s": wall,
                        "rc": p.returncode, "result": res, "stdout": lines[:-1],
                        "stderr_tail": p.stderr[-2000:]})
        with open(out_dir / f"{tag}.jsonl", "a") as f:
            f.write(json.dumps(results[-1]) + "\n")
    good = [r["result"] for r in results if r["result"] and not r["trace"]]
    names = sorted({k for r in good for k in r["metrics"]})
    for skip_first in (False, True):
        rs = good[1:] if skip_first else good
        if len(rs) < 2:
            continue
        print(f"[series] spread over {len(rs)} runs"
              + (" (first left out)" if skip_first else "") + ": " + ", ".join(
                  f"{n} median {statistics.median([r['metrics'][n]['value'] for r in rs]):.6g} "
                  f"spread {spread([r['metrics'][n]['value'] for r in rs]):.5f}"
                  for n in names), flush=True)
    raw = here / "benchmarks" / "out"
    if raw.is_dir():
        shutil.copytree(raw, Path("chiprun_out") / "raw" / tag, dirs_exist_ok=True)
    return 0 if all(r["rc"] == 0 for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
