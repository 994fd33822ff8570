"""The same-seed loss series of one cell's crosscoder step, tree against tree.

    python3 scripts/probes/_step_loss_series.py <tree> <config-stem> [--steps 46] [--tiny]
        [--ops-to <file.json>]

Builds, from the tree given (its ``crosscoder_tpu``), a ``Trainer`` at the
widths of ``benchmarks/configs/<config-stem>.json``'s ``crosscoder`` block
over a seeded raw-bf16 source (eight batches drawn once on the device, served
in turn with a norm factor a source, as the replay store serves them), and
runs ``--steps`` steps the way the loop runs them — the full variant at every
15th step, the bare one between — fetching EVERY step's loss. Prints one JSON
line: the losses as hex floats, their SHA-256, the layout each three-dimensional
leaf of the state is held in, and the wall of the steps after the
first full cycle (a WALL number: it holds the dispatch and the fetch of a
loss a step; compare tree with tree, not with a device time).

Two trees that print one digest took bit-identical steps; two that do not
differ from the first step whose loss differs (PR 36 held its step against
its parent's so: PERF.md §6 says which partial sum moved).
``--tiny`` is the CPU rehearsal (dict 512, d_in 64, batch 128).
``--ops-to`` traces steps 16 to 45 (two cycles of the loop: 28 bare steps and
2 full ones) with the profiler and writes the step's device ops, read by the
benchmark's own reader (``benchmarks/trace_reduce.py``: self time): a module
and op, its calls, and its mean self time a call in ms.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import sys
import tempfile
import time
from pathlib import Path


class SeededRawSource:
    """``next_raw`` / ``normalisation_factor`` as the replay store has them."""

    def __init__(self, cfg, n_batches: int = 8) -> None:
        import jax
        import jax.numpy as jnp
        import numpy as np

        keys = jax.random.split(jax.random.key(cfg.seed + 1), n_batches)
        draw = jax.jit(lambda k: (3.0 * jax.random.normal(
            k, (cfg.batch_size, cfg.n_sources, cfg.d_in), jnp.float32)
        ).astype(jnp.bfloat16))
        self._batches = [draw(k) for k in keys]
        self._i = 0
        self.normalisation_factor = np.linspace(0.3, 0.4, cfg.n_sources).astype(np.float32)

    def next_raw(self):
        batch = self._batches[self._i % len(self._batches)]
        self._i += 1
        return batch


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("tree")
    ap.add_argument("config")
    ap.add_argument("--steps", type=int, default=46)
    ap.add_argument("--ops-to", default="")
    ap.add_argument("--tiny", action="store_true")
    ns = ap.parse_args()
    root = Path(ns.tree).resolve()
    sys.path.insert(0, str(root))
    import jax

    import crosscoder_tpu
    from crosscoder_tpu.config import CrossCoderConfig
    from crosscoder_tpu.train.trainer import Trainer
    from crosscoder_tpu.utils import compile_cache

    assert crosscoder_tpu.__file__.startswith(str(root)), crosscoder_tpu.__file__
    compile_cache.enable()
    block = json.loads((Path(__file__).resolve().parents[2] / "benchmarks" / "configs"
                        / f"{ns.config}.json").read_text())["crosscoder"]
    keep = ("n_models", "d_in", "batch_size", "enc_dtype", "master_dtype", "dict_size",
            "activation", "topk_k", "l1_coeff", "data_axis_size", "model_axis_size")
    kw = {k: block[k] for k in keep if k in block}
    if ns.tiny:
        kw.update(d_in=64, dict_size=512, batch_size=128)
    cfg = CrossCoderConfig(**kw, seed=11, log_backend="null", log_every=15,
                           num_tokens=kw["batch_size"] * 4000)
    trainer = Trainer(cfg, SeededRawSource(cfg))
    losses, t_cycle = [], None
    profile_dir = tempfile.mkdtemp(prefix="step_ops_") if ns.ops_to else None
    last_traced = min(ns.steps, 46) - 1
    for i in range(ns.steps):
        if i == 16:
            t_cycle = time.perf_counter()
            if profile_dir:
                jax.profiler.start_trace(profile_dir)
        metrics = trainer.step(full_metrics=(i % cfg.log_every == 0))
        losses.append(float(jax.device_get(metrics["loss"])))
        if profile_dir and i == last_traced >= 16:
            jax.profiler.stop_trace()
            _write_ops(profile_dir, ns.ops_to)
    wall = (time.perf_counter() - t_cycle) / max(ns.steps - 16, 1) if t_cycle else None
    layouts = {jax.tree_util.keystr(path): str(x.format.layout.major_to_minor)
               + str(x.format.layout.tiling)
               for path, x in jax.tree_util.tree_leaves_with_path(trainer.state)
               if x.ndim >= 3}
    trainer.close()
    hexes = [float(v).hex() for v in losses]
    print(json.dumps({
        "tree": str(root), "config": ns.config, "steps": ns.steps,
        "platform": jax.devices()[0].platform, "device_kind": jax.devices()[0].device_kind,
        "sha256": hashlib.sha256(" ".join(hexes).encode()).hexdigest(),
        "first": losses[:3], "last": losses[-3:], "wall_ms_per_step": wall and 1e3 * wall,
        "layouts_of_3d_leaves": layouts, "losses_hex": hexes}))
    return 0


def _write_ops(profile_dir: str, out: str) -> None:
    """The traced steps' device ops by module and name: calls and mean self ms."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    from benchmarks import trace_reduce

    trace = trace_reduce.load_profile(Path(profile_dir))
    table: dict[tuple, list] = {}
    for ops in (trace or {"devices": {}})["devices"].values():
        for name, module, _, _, self_ns in trace_reduce._self_times(ops):
            row = table.setdefault((module, name), [0, 0])
            row[0] += 1
            row[1] += self_ns
    rows = sorted(([m, n, c, t / c / 1e6] for (m, n), (c, t) in table.items()),
                  key=lambda r: (r[0], -r[2] * r[3]))
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    Path(out).write_text(json.dumps(rows, indent=0))
    for m, n, c, ms in rows:
        if ms >= 0.05:
            print(f"  {m:24s} {n:60s} x{c:<4d} {ms:8.3f} ms", file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
