#!/usr/bin/env python3
"""The planted faults of tests/test_laguna.py at the cell's widths, on the chip:
the hooked stream of one seeded 4096-token sequence, the program in bf16 with a
fault planted against the plain float32 reference of the true model (the
comparison that decides ``correct`` in train-live-laguna-relu16k).

    chiprun -- python3 scripts/probes/_laguna_faults.py [--seed N] [--tiny] [fault ...]
    chiprun -- python3 scripts/probes/_laguna_faults.py --row-shares SEED,SEED,...

Prints one line a fault: its relative Frobenius error beside ``HARVEST_RTOL``.
``--row-shares`` instead prints, for each ``--seed`` of the cell, what its
traced run would carry as ``harvest/moe_local_row_share`` and
``harvest/moe_load_max_over_mean`` (the first model over the first calibration
chunk of the ``live-full`` corpus, as ``data/buffer.py`` reads them), and the
share by layer. ``--tiny`` is the CPU rehearsal (tiny widths, float32)."""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "tests")]


def row_shares(cfg, seq: int, seeds: list[int], config: dict) -> int:
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
        counts = np.asarray(lm.expert_load(params, chunk, cfg, cfg.n_layers))[sparse]
        by_layer = [moe.local_row_share(c, cfg.first_expert, cfg.n_held) for c in counts]
        print(f"[row-share] seed {seed}: local_row_share "
              f"{moe.local_row_share(counts, cfg.first_expert, cfg.n_held):.4f} "
              f"(by layer {' '.join(f'{x:.4f}' for x in by_layer)}), "
              f"load_max_over_mean {moe.load_max_over_mean(counts):.3f}", flush=True)
        del params
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--row-shares", default="")
    ap.add_argument("faults", nargs="*")
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import test_laguna as t
    from benchmarks import common
    from benchmarks.arch import laguna
    from crosscoder_tpu.models import lm

    cfg = laguna.lm_config(t.CONFIG, t.TINY if ns.tiny else None)
    seq = 64 if ns.tiny else t.CONFIG["crosscoder"]["seq_len"]
    if ns.row_shares:
        return row_shares(cfg, seq, [int(x) for x in ns.row_shares.split(",")], t.CONFIG)
    s_tok, s_model = common.sub_seeds(ns.seed, 2)
    params = common.init_lm_pair(cfg, [s_model])[0]
    tokens = jnp.asarray(np.random.default_rng(s_tok).integers(1, cfg.vocab_size, size=(1, seq)))
    t0 = time.perf_counter()
    want = jax.block_until_ready(laguna.resid_pre(params, tokens, cfg, cfg.n_layers))
    print(f"[faults] {jax.devices()[0].device_kind}; seed {ns.seed}; reference "
          f"{time.perf_counter() - t0:.1f} s; limit {laguna.HARVEST_RTOL}", flush=True)
    hook = (f"blocks.{cfg.n_layers}.hook_resid_pre",)
    for fault in ns.faults or t.FAULTS:
        bad_cfg, bad_params = t.plant(fault, cfg, params)
        got = lm.run_with_cache_multi([bad_params], tokens, bad_cfg, hook)[:, :, 0]
        err = float(jnp.linalg.norm(got.astype(jnp.float32) - want) / jnp.linalg.norm(want))
        print(f"[faults] {fault}: {err:.4f}", flush=True)
        del bad_params, got
    return 0


if __name__ == "__main__":
    sys.exit(main())
