"""Serve smoke: parity + latency SLO on CPU-tiny shapes, one command.

``python -m crosscoder_tpu.serve.smoke`` (or ``scripts/serve_smoke.sh``)
drives a synthetic client against a tiny-LM :class:`InferenceEngine` and
checks every property the serving path promises, exiting nonzero when any
fails:

- **parity**: served ``(vals, idx, diff)`` at mixed lengths are BITWISE
  equal to the offline oracle (padded :func:`lm.run_with_cache_multi`
  captures through the same encode step);
- **extend parity**: an incremental request (prefix served, follow-up via
  :meth:`InferenceEngine.extend`) serves bitwise what re-prefilling the
  concatenation from scratch serves;
- **SLO gate**: per-request latency p99 ≤ 3 × p50 at batch 8 (the bench
  serve leg's gate, at smoke depth);
- **zero compiles after warmup**: the whole traffic run builds no
  executable the warmup didn't.

Prints one JSON line to stdout (progress to stderr), mirroring the
drill/bench reporting contract.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(f"[serve_smoke] {msg}", file=sys.stderr, flush=True)


def build_engine(serve_max_batch: int = 8, seq_len: int = 16,
                 clock=time.monotonic, **cfg_overrides):
    """Tiny-LM serving stack: 2 fake models, 2 hooked layers, a topk
    crosscoder — the fake-LM pattern every harvest parity gate uses.
    ``cfg_overrides`` land on the CrossCoderConfig (tests pin queue
    depths and shed deadlines through them)."""
    import jax

    from crosscoder_tpu.config import CrossCoderConfig
    from crosscoder_tpu.models import crosscoder, lm
    from crosscoder_tpu.serve import InferenceEngine

    lm_cfg = lm.LMConfig.tiny()
    params = [lm.init_params(jax.random.key(1), lm_cfg),
              lm.init_params(jax.random.key(2), lm_cfg)]
    hooks = ("blocks.1.hook_resid_pre", "blocks.3.hook_resid_pre")
    kw = dict(
        d_in=lm_cfg.d_model, dict_size=64, batch_size=serve_max_batch,
        enc_dtype="fp32", activation="topk", topk_k=4, n_models=2,
        hook_points=hooks, seq_len=seq_len, page_size=8,
        serve="on", serve_max_batch=serve_max_batch, serve_max_wait_ms=2.0,
        serve_queue=4 * serve_max_batch, log_backend="null", seed=7,
    )
    kw.update(cfg_overrides)
    cfg = CrossCoderConfig(**kw)
    cc_params = crosscoder.init_params(jax.random.key(3), cfg)
    eng = InferenceEngine(cfg, lm_cfg, params, cc_params, clock=clock)
    return eng, cfg, lm_cfg, params, cc_params


def oracle(eng, cfg, lm_cfg, lm_params, cc_params, tokens, lengths):
    """Offline padded-path reference for a request batch: the exact
    answer the serving path must reproduce bit-for-bit."""
    import jax.numpy as jnp

    from crosscoder_tpu.models import lm
    from crosscoder_tpu.serve import step as serve_step

    caps = lm.run_with_cache_multi(
        lm_params, jnp.asarray(tokens), lm_cfg, eng._hooks)
    vals, idx, diff = serve_step.encode_topk_diff(
        cc_params, caps, jnp.asarray(lengths, jnp.int32),
        jnp.asarray(eng._norm), enc_dtype=cfg.enc_dtype, k=cfg.topk_k,
        pair=eng._pair)
    return np.asarray(vals), np.asarray(idx), np.asarray(diff)


def serve_batch(eng, docs, *, keep: bool = False):
    rids = [eng.submit(d, keep=keep) for d in docs]
    results = eng.step(force=True)
    got = {r.request_id: r for r in results}
    return [got[r] for r in rids]


def check_parity(eng, cfg, lm_cfg, lm_params, cc_params) -> bool:
    S = cfg.seq_len
    rng = np.random.default_rng(11)
    lengths = np.array([1, S, 7, 3, 9, 5, S, 2])[: cfg.serve_max_batch]
    tokens = rng.integers(1, lm_cfg.vocab_size,
                          size=(lengths.size, S), dtype=np.int64)
    for d, ln in enumerate(lengths):
        tokens[d, ln:] = 0
    res = serve_batch(eng, [tokens[d, :ln].astype(np.int32)
                            for d, ln in enumerate(lengths)])
    want = oracle(eng, cfg, lm_cfg, lm_params, cc_params, tokens, lengths)
    ok = all(
        np.array_equal(r.vals, want[0][i]) and
        np.array_equal(r.idx, want[1][i]) and
        np.array_equal(r.diff, want[2][i])
        for i, r in enumerate(res)
    )
    log(f"mixed-length parity vs padded oracle: {'OK' if ok else 'FAIL'}")
    return ok


def check_extend(eng, cfg, lm_cfg, lm_params, cc_params) -> bool:
    rng = np.random.default_rng(13)
    full = rng.integers(1, lm_cfg.vocab_size, size=cfg.seq_len - 2,
                        dtype=np.int32)
    cut = full.shape[0] // 2
    rid = eng.submit(full[:cut], keep=True)
    eng.step(force=True)                       # serve the prefix
    eng.extend(rid, full[cut:])
    ext = eng.step(force=True)[0]
    eng.release(rid)
    fresh = serve_batch(eng, [full])[0]        # re-prefill from scratch
    ok = (ext.extended
          and np.array_equal(ext.vals, fresh.vals)
          and np.array_equal(ext.idx, fresh.idx)
          and np.array_equal(ext.diff, fresh.diff))
    log(f"extend-path parity vs re-prefill: {'OK' if ok else 'FAIL'}")
    return ok


def latency_leg(eng, cfg, lm_cfg, batch: int, reps: int) -> dict:
    """Drive `reps` full micro-batches of size `batch`; per-request
    latency = queue_wait + prefill + encode (the request's wall clock as
    the engine accounts it)."""
    rng = np.random.default_rng(17 + batch)
    lat = []
    t0 = time.perf_counter()
    for _ in range(reps):
        docs = [rng.integers(1, lm_cfg.vocab_size,
                             size=int(rng.integers(1, cfg.seq_len + 1)),
                             dtype=np.int32)
                for _ in range(batch)]
        for r in serve_batch(eng, docs):
            lat.append(r.queue_wait_ms + r.prefill_ms + r.encode_ms)
    wall = time.perf_counter() - t0
    lat = np.asarray(lat)
    return {
        "batch": batch,
        "p50_ms": round(float(np.percentile(lat, 50)), 3),
        "p99_ms": round(float(np.percentile(lat, 99)), 3),
        "max_ms": round(float(lat.max()), 3),
        "req_s": round(len(lat) / wall, 1),
    }


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reps", type=int, default=25,
                    help="micro-batches per latency leg")
    ns = ap.parse_args(argv)

    t0 = time.perf_counter()
    eng, cfg, lm_cfg, lm_params, cc_params = build_engine()
    log(f"warming {len(eng.buckets)} buckets {eng.buckets} ...")
    n_warm = eng.warmup()
    log(f"warmup built {n_warm} executables in "
        f"{time.perf_counter() - t0:.1f}s")

    parity_ok = check_parity(eng, cfg, lm_cfg, lm_params, cc_params)
    extend_ok = check_extend(eng, cfg, lm_cfg, lm_params, cc_params)

    legs = [latency_leg(eng, cfg, lm_cfg, b, ns.reps) for b in (1, 8)]
    at8 = legs[-1]
    gate_ok = at8["p99_ms"] <= 3.0 * at8["p50_ms"]
    zero_compiles_ok = eng.compiles_after_warmup == 0
    log(f"batch-8 p50={at8['p50_ms']}ms p99={at8['p99_ms']}ms "
        f"(gate p99<=3*p50: {'OK' if gate_ok else 'FAIL'}); "
        f"compiles after warmup: {eng.compiles_after_warmup}")

    ok = parity_ok and extend_ok and gate_ok and zero_compiles_ok
    print(  # contracts: allow(lint-no-stdout-print) — one-line report
        json.dumps({"serve_smoke": {
        "ok": ok, "parity_ok": parity_ok, "extend_ok": extend_ok,
        "gate_ok": gate_ok, "zero_compiles_ok": zero_compiles_ok,
        "warmup_compiles": n_warm, "legs": legs,
        "shed_total": eng.stats().get("serve/shed_total", 0),
    }}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
