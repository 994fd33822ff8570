#!/usr/bin/env python3
"""The planted faults of tests/test_xing_faults.py at the cell's widths, on the chip:
the hooked stream mean of one seeded 4096-token sequence, the program in bf16
with a fault planted against the plain float32 reference of the true model (the
comparison that decides ``correct`` in train-live-xing-relu16k).

    chiprun -- python3 scripts/probes/_xing_faults.py [--seed N] [--tiny] [fault ...]
    chiprun -- python3 scripts/probes/_xing_faults.py --gauges SEED,SEED,...

Prints one line a fault: its relative Frobenius error beside ``HARVEST_RTOL``.
``--gauges`` instead prints, for each ``--seed`` of the cell, what its traced
run would carry as ``harvest/moe_local_row_share``,
``harvest/moe_load_max_over_mean`` and ``harvest/mhc_col_err`` (the first model
over the first calibration chunk of the ``live-full`` corpus, as
``data/buffer.py`` reads them); ``--by-layer`` the bf16 program's own reading
as it enters each block. ``--tiny`` is the CPU rehearsal (tiny widths,
float32)."""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "tests")]


def gauges(cfg, seq: int, seeds: list[int], config: dict, bias_scale: float = 1.0) -> int:
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import common, manifest
    from benchmarks.generators import uniform_rows
    from crosscoder_tpu.models import lm
    from crosscoder_tpu.ops import moe

    traffic = manifest.load_json(manifest.BENCH_DIR / "traffic" / "live-full.json")
    sparse = [i for i, kind in enumerate(cfg.mlp_types) if kind == lm.SPARSE]
    for seed in seeds:
        _, s_tok, s_a, _ = common.sub_seeds(seed)       # as runners/train.py draws them
        tokens = uniform_rows.make(traffic, seq, cfg.vocab_size, s_tok)
        chunk = jnp.asarray(tokens[: config["crosscoder"]["model_batch_size"]])
        params = common.init_lm_pair(cfg, [s_a])[0]
        if bias_scale != 1.0:       # (what another draw of the routing bias would route)
            params = {**params, "layers": tuple(
                {k: v * bias_scale if k == "router_bias" else v for k, v in s.items()}
                for s in params["layers"])}
        counts = np.asarray(lm.expert_load(params, chunk, cfg, cfg.n_layers))[sparse]
        errs = np.asarray(lm.mhc_col_err(params, chunk, cfg, cfg.n_layers))
        print(f"[gauges] seed {seed} bias x{bias_scale:g}: local_row_share "
              f"{moe.local_row_share(counts, cfg.first_expert, cfg.n_held):.4f}, "
              f"load_max_over_mean {moe.load_max_over_mean(counts):.3f}, "
              f"mhc_col_err {errs.max():.3e} (by layer {' '.join(f'{e:.1e}' for e in errs)})",
              flush=True)
        del params
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--gauges", default="")
    ap.add_argument("--by-layer", action="store_true")
    ap.add_argument("--bias-scale", type=float, default=1.0)
    ap.add_argument("faults", nargs="*")
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import test_xing_faults as t
    from benchmarks import common
    from benchmarks.arch import xing
    from crosscoder_tpu.models import lm

    cfg = xing.lm_config(t.CONFIG, t.TINY if ns.tiny else None)
    seq = 64 if ns.tiny else t.CONFIG["crosscoder"]["seq_len"]
    if ns.gauges:
        return gauges(cfg, seq, [int(x) for x in ns.gauges.split(",")], t.CONFIG, ns.bias_scale)
    s_tok, s_model = common.sub_seeds(ns.seed, 2)
    params = common.init_lm_pair(cfg, [s_model])[0]
    tokens = jnp.asarray(np.random.default_rng(s_tok).integers(1, cfg.vocab_size, size=(1, seq)))
    if ns.by_layer:     # where the bf16 program's own reading grows
        from benchmarks.reference import xing_ref

        means: list = []
        with jax.default_matmul_precision("highest"):
            means.append(jnp.mean(xing_ref.streams(params, tokens, cfg, cfg.n_layers, means), 2))
        hooks = tuple(f"blocks.{i}.hook_resid_pre" for i in range(1, cfg.n_layers + 1))
        got = lm.run_with_cache_multi([params], tokens, cfg, hooks)
        for i, want in enumerate(means[1:]):
            err = float(jnp.linalg.norm(got[:, :, i].astype(jnp.float32) - want)
                        / jnp.linalg.norm(want))
            print(f"[by-layer] seed {ns.seed}: entering block {i + 1} "
                  f"(after a {cfg.mlp_types[i]} layer): {err:.4f}", flush=True)
        return 0
    t0 = time.perf_counter()
    want = jax.block_until_ready(xing.resid_pre(params, tokens, cfg, cfg.n_layers))
    print(f"[faults] {jax.devices()[0].device_kind}; seed {ns.seed}; reference "
          f"{time.perf_counter() - t0:.1f} s; limit {xing.HARVEST_RTOL}", flush=True)
    hook = (f"blocks.{cfg.n_layers}.hook_resid_pre",)
    for fault in ns.faults or t.FAULTS:
        bad_cfg, bad_params, patches = t.plant(fault, cfg, params)
        with t.planted(patches):
            got = lm.run_with_cache_multi([bad_params], tokens, bad_cfg, hook)[:, :, 0]
            err = float(jnp.linalg.norm(got.astype(jnp.float32) - want) / jnp.linalg.norm(want))
        print(f"[faults] {fault}: {err:.4f}", flush=True)
        del bad_params, got
    return 0


if __name__ == "__main__":
    sys.exit(main())
