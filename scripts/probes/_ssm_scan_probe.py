#!/usr/bin/env python3
"""The state-space mixer's chunked scan alone at the falconh1 cell's shape, in
both forms, and the whole mixer around it, on a one-chip TPU host:

    python3 scripts/probes/_ssm_scan_probe.py [--tiny] [--reps N]

Prints milliseconds a call (``reps`` calls back to back after one that
compiles, the median of three such) and each form's share of the scan's
roofline (the needed FLOPs and bytes of ``benchmarks/arch/falcon_h1.py``); the
relative difference of the kernel from XLA's form on the same bf16 inputs, of
each from XLA's form in float32 on the same values, and of a kernel whose
cumulative sum is rounded to bf16 (the planted fault, which must stand far
off). The inputs are handed over tokens minor, as the mixer's conv leaves them
in the harvest's program. ``--tiny`` is the CPU rehearsal (the kernel through
the interpreter)."""

from __future__ import annotations

import argparse
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--reps", type=int, default=10)
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmarks import manifest
    from benchmarks.arch import falcon_h1 as fh
    from crosscoder_tpu.models import lm
    from crosscoder_tpu.ops import ssm

    config = manifest.load_json(manifest.BENCH_DIR / "configs" / "falcon-h1-34b-pair-relu16k.json")
    if ns.tiny:
        ssm.set_interpret(True)
        cfg = fh.lm_config(config, dict(vocab_size=257, d_model=128, n_layers=1, n_heads=2,
                                        n_kv_heads=1, head_dim=128, d_ff=256, dtype="bf16"))
        cfg = cfg.replace(ssm_heads=4, ssm_head_dim=128, ssm_state=128, ssm_groups=2,
                          ssm_chunk=128)
        S = 256
    else:
        cfg = fh.lm_config(config).replace(n_layers=1, layer_types=(lm.FULL,))
        S = config["crosscoder"]["seq_len"]
    H, P, G, N, Q = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_chunk
    di, gn = H * P, G * N
    flops, bytes_ = S * fh.ssd_scan_flops_per_token(cfg, 1), S * fh.ssd_scan_bytes_per_token(cfg, 1)
    least = max(flops / 197e12, bytes_ / 819e9)
    print(f"[probe] {jax.devices()[0].device_kind}; S {S}, H {H}, P {P}, G {G}, N {N}, Q {Q}; "
          f"kernel supported {ssm.supported(H, P, G, N, Q, jnp.bfloat16)}, enabled {ssm.enabled()}; "
          f"least time {1e3 * least:.4f} ms", flush=True)

    def timed(name, fn, *args, roof=True):
        # ``reps`` calls back to back, one sync at the end: the device's time
        # a call, not the host's launch latency; the median of three such
        f = jax.jit(fn)
        out = jax.block_until_ready(f(*args))
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(ns.reps):
                last = f(*args)
            jax.block_until_ready(last)
            ts.append((time.perf_counter() - t0) / ns.reps)
        ms = 1e3 * statistics.median(ts)
        share = f", {100 * least / (ms / 1e3):.1f}% of the scan's roofline" if roof else ""
        print(f"[probe] {name}: {ms:.4f} ms (min {1e3 * min(ts):.4f}){share}", flush=True)
        return out

    k = jax.random.split(jax.random.key(0), 4)
    # x | B | C as the conv leaves them (after its SiLU), tokens minor; dt as
    # the cell's weights give it: softplus around a log-uniform [1e-3, 1e-1]
    xbc_t = (0.5 * jax.random.normal(k[0], (1, di + 2 * gn, S))).astype(jnp.bfloat16)
    step = jnp.exp(jax.random.uniform(k[1], (H,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    dt_t = jax.nn.softplus(0.5 * jax.random.normal(k[2], (1, H, S))
                           + (step + jnp.log(-jnp.expm1(-step)))[:, None])
    A = -jnp.arange(1, H + 1, dtype=jnp.float32)
    D = jnp.ones((H,), jnp.float32)

    def scan(form, dtype=jnp.bfloat16):
        def f(xbc_t, dt_t):
            xbc = jnp.swapaxes(xbc_t, 1, 2).astype(dtype)
            y = form(xbc[..., :di].reshape(1, S, H, P), jnp.swapaxes(dt_t, 1, 2), A,
                     xbc[..., di:di + gn].reshape(1, S, G, N),
                     xbc[..., di + gn:].reshape(1, S, G, N), D, Q)
            return jnp.swapaxes(y.reshape(1, S, di), 1, 2)          # tokens minor, as it is read
        return f

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    y_xla = timed("scan, XLA's form", scan(ssm._ssd_scan_xla), xbc_t, dt_t)
    y_kernel = timed("scan, kernel    ", scan(ssm._ssd_scan_kernel), xbc_t, dt_t)
    with jax.default_matmul_precision("highest"):
        y_f32 = jax.jit(scan(ssm._ssd_scan_xla, jnp.float32))(xbc_t, dt_t)
    real = ssm._cumsum
    ssm._cumsum = lambda a: real(a.astype(jnp.bfloat16).astype(jnp.float32))
    try:
        y_fault = jax.jit(scan(ssm._ssd_scan_kernel))(xbc_t, dt_t)
    finally:
        ssm._cumsum = real
    print(f"[probe] rel. difference: kernel from XLA's form {rel(y_kernel, y_xla):.3e}; from "
          f"float32: kernel {rel(y_kernel, y_f32):.3e}, XLA's form {rel(y_xla, y_f32):.3e}; "
          f"bf16-rounded cumulative sum {rel(y_fault, y_xla):.3e}", flush=True)

    # the whole mixer on a normed input, the scan in each form
    params = jax.jit(lm.init_params, static_argnums=1)(jax.random.key(1), cfg)
    lp = {key: v[0] for key, v in params["layers"].items()}
    u = jax.random.normal(k[3], (1, S, cfg.d_model)).astype(lm.dtype_of(cfg.dtype))
    mixer = lambda u: lm._ssm(u, lp, cfg)                        # noqa: E731
    out_kernel = timed("mixer, kernel    ", mixer, u, roof=False)
    was = ssm.enabled
    ssm.enabled = lambda: False
    try:
        out_xla = timed("mixer, XLA's form", lambda u: lm._ssm(u, lp, cfg), u, roof=False)
    finally:
        ssm.enabled = was
    print(f"[probe] mixer rel. difference {rel(out_kernel, out_xla):.3e}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
