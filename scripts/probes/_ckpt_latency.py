"""Checkpoint save latency at production shape (round-3 VERDICT weak #3 /
next-round #4): measure (a) the legacy synchronous save, (b) the
background save's blocking portion (device→host fetch only), and (c) the
background write's drain time, on a dict-2^16 fp32-master TrainState.

Run on the chip (the interesting number is the real device→host fetch +
the real disk write):

    python _ckpt_latency.py --out artifacts/CKPT_LATENCY_r04.json
    python _ckpt_latency.py --platform cpu ...   # air-gapped sanity

The "blocking" number is what training stalls per periodic save; sync-vs-
blocking is the overlap win; the SIGTERM preemption window shrinks from
(fetch+write) to (fetch) + joined-write-at-exit.
"""
import _bootstrap  # noqa: F401  (repo-root sys.path + cwd shim)

import argparse
import json
import time
from pathlib import Path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dict-size", type=int, default=2**16)
    ap.add_argument("--d-in", type=int, default=2304)
    ap.add_argument("--batch-size", type=int, default=4096)
    ap.add_argument("--steps-between", type=int, default=6,
                    help="train steps issued while the background write runs")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", type=str, default="artifacts/CKPT_LATENCY_r04.json")
    ap.add_argument("--ckpt-dir", type=str, default="/tmp/ckpt_latency")
    ap.add_argument("--platform", type=str, default=None, choices=("cpu", "tpu"))
    args = ap.parse_args(argv)

    import jax
    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from crosscoder_tpu.checkpoint.ckpt import Checkpointer
    from crosscoder_tpu.config import CrossCoderConfig
    from crosscoder_tpu.train.trainer import Trainer

    cfg = CrossCoderConfig(
        d_in=args.d_in, dict_size=args.dict_size, batch_size=args.batch_size,
        num_tokens=args.batch_size * 10_000, enc_dtype="bf16",
        master_dtype="fp32", log_backend="null", checkpoint_dir=args.ckpt_dir,
        data_source="synthetic", prefetch=False,
    )
    # state bytes: params + 2 Adam moments, all fp32 (+ the weights artifact copy)
    per_leaf = cfg.dict_size * (2 * cfg.n_sources * cfg.d_in + 1) + cfg.n_sources * cfg.d_in
    state_gb = per_leaf * 3 * 4 / 1e9

    tr = Trainer(cfg, checkpointer=Checkpointer(cfg=cfg))
    # warm the step compile + one batch
    m = tr.step()
    float(jax.device_get(m["loss"]))

    results = {"shape": {"dict_size": cfg.dict_size, "d_in": cfg.d_in,
                         "n_sources": cfg.n_sources, "master_dtype": "fp32",
                         "approx_state_GB": round(state_gb, 2)},
               "platform": jax.default_backend(), "runs": []}

    for r in range(args.repeats):
        # (a) legacy synchronous save: fetch + write, loop fully stalled
        t0 = time.perf_counter()
        tr.save(background=False)
        sync_s = time.perf_counter() - t0

        # a step between the two saves: the donated update produces FRESH
        # device arrays, so the background save's fetch cannot hit
        # jax.Array's cached host copy from the save above (which would
        # understate the blocking portion)
        m = tr.step()
        float(jax.device_get(m["loss"]))

        # (b) background save: blocking portion is the fetch
        t0 = time.perf_counter()
        tr.save(background=True)
        blocking_s = time.perf_counter() - t0
        # (c) steps proceed during the write; drain = residual write time
        t0 = time.perf_counter()
        for _ in range(args.steps_between):
            m = tr.step()
        float(jax.device_get(m["loss"]))
        steps_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        tr.checkpointer.wait()
        drain_s = time.perf_counter() - t0
        results["runs"].append({
            "sync_save_s": round(sync_s, 3),
            "background_blocking_s": round(blocking_s, 3),
            "steps_during_write_s": round(steps_s, 3),
            "writer_drain_s": round(drain_s, 3),
        })
        print(json.dumps(results["runs"][-1]))

    runs = results["runs"][1:] or results["runs"]   # drop cold-cache run

    def med(k):
        vals = sorted(r[k] for r in runs)
        n = len(vals)
        # true median: even counts average the middle two (picking
        # vals[n//2] alone would report the MAX of two kept runs)
        m = vals[n // 2] if n % 2 else (vals[n // 2 - 1] + vals[n // 2]) / 2
        return round(m, 3)

    results["median"] = {k: med(k) for k in runs[0]}
    results["overlap_win"] = round(
        results["median"]["sync_save_s"]
        - results["median"]["background_blocking_s"], 3
    )
    tr.close()

    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(results, indent=2))
    print(json.dumps({"median": results["median"],
                      "overlap_win_s": results["overlap_win"]}))
    print(f"wrote {out}")
    return results


if __name__ == "__main__":
    main()
