#!/usr/bin/env python3
"""The state-space mixer's causal conv and chunked scan alone at the falconh1
and nemotron cells' shapes, in each form, and the whole mixer around them, on
a one-chip TPU host:

    python3 scripts/probes/_ssm_scan_probe.py [--tiny] [--reps N] [--cells falconh1,nemotron]

A cell at a time, milliseconds a call (``reps`` calls back to back after one
that compiles, the median of three such):

- the scan alone on x | B | C after the conv, XLA's form against the kernel,
  each with its share of the scan's roofline (the needed FLOPs and bytes of
  ``benchmarks/arch/<arch>.py``);
- the conv and the scan from the mixer's projection as it lies (bf16, tokens
  minor, z | x | B | C | dt): XLA's conv then the kernel (two XLA ops in front
  of it: the conv's float32 copy of x | B | C and its taps fused into the
  transpose) against the kernel that computes the conv itself;
- the whole mixer with the kernel against XLA's form.

Then the relative differences on the same bf16 inputs: the kernel from XLA's
form and each from XLA's form in float32 (the scan alone, and the conv with
the scan), and three planted faults that must stand far off — the cumulative
sum rounded to bf16, the previous chunk's tokens not carried into a chunk's
first taps, and the taps in the reverse order. ``--tiny`` is the CPU
rehearsal (the kernel through the interpreter)."""

from __future__ import annotations

import argparse
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

CELLS = {"falconh1": ("falcon_h1", "falcon-h1-34b-pair-relu16k.json"),
         "nemotron": ("nemotron_h", "nemotron-twotower-30b-a3b-pair-relu16k.json")}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--cells", default="falconh1,nemotron")
    ns = ap.parse_args()

    import jax

    from crosscoder_tpu.ops import ssm

    if ns.tiny:
        ssm.set_interpret(True)
    print(f"[probe] {jax.devices()[0].device_kind}; kernel enabled {ssm.enabled()}", flush=True)
    for cell in ns.cells.split(","):
        probe(cell, ns)
    return 0


def probe(cell: str, ns) -> None:
    import importlib

    import jax
    import jax.numpy as jnp

    from benchmarks import manifest
    from crosscoder_tpu.models import lm
    from crosscoder_tpu.ops import ssm

    arch_name, file = CELLS[cell]
    arch = importlib.import_module(f"benchmarks.arch.{arch_name}")
    config = manifest.load_json(manifest.BENCH_DIR / "configs" / file)
    if ns.tiny:
        cfg = arch.lm_config(config, dict(vocab_size=257, d_model=128, n_layers=1, n_heads=2,
                                          n_kv_heads=1, head_dim=128, d_ff=256, dtype="bf16"))
        P = 64 if arch_name == "nemotron_h" else 128
        cfg = cfg.replace(ssm_heads=16 if P == 64 else 4, ssm_head_dim=P, ssm_state=128,
                          ssm_groups=2, ssm_chunk=128)
        Bt, S = 1, 384
    else:
        cfg = arch.lm_config(config)          # its first layer alone: a mixer in both
        cfg = cfg.replace(n_layers=1, layer_types=cfg.layer_types[:1],
                          mlp_types=cfg.mlp_types[:1])
        Bt, S = config["crosscoder"]["model_batch_size"], config["crosscoder"]["seq_len"]
    H, P, G, N, Q = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_chunk
    di, gn, K = H * P, G * N, cfg.ssm_conv
    cd = di + 2 * gn
    tokens = Bt * S
    flops = tokens * arch.ssd_scan_flops_per_token(cfg, 1)
    bytes_ = tokens * arch.ssd_scan_bytes_per_token(cfg, 1)
    least = max(flops / 197e12, bytes_ / 819e9)
    print(f"[probe] {cell}: Bt {Bt}, S {S}, H {H}, P {P}, G {G}, N {N}, Q {Q}, conv {K}; kernel "
          f"supported {ssm.supported(H, P, G, N, Q, jnp.bfloat16)}, VMEM "
          f"{ssm._vmem_bytes(H, P, G, N, Q, 2) / 2**20:.2f} MiB, with the conv "
          f"{ssm._vmem_bytes(H, P, G, N, Q, 2, K) / 2**20:.2f} MiB; the scan's least "
          f"time {1e3 * least:.4f} ms", flush=True)

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
        print(f"[probe] {cell} {name}: {ms:.4f} ms (min {1e3 * min(ts):.4f}){share}", flush=True)
        return out

    k = jax.random.split(jax.random.key(0), 7)
    # the projection as it lies, z | x | B | C | dt in bf16, tokens minor; the
    # conv's weights at the fixture's scale; dt as the cell's weights give it:
    # softplus around a log-uniform [1e-3, 1e-1]
    proj_t = (0.5 * jax.random.normal(k[0], (Bt, di + cd + H, S))).astype(jnp.bfloat16)
    w = (K ** -0.5 * jax.random.normal(k[4], (K, cd))).astype(jnp.bfloat16)
    b = (0.1 * jax.random.normal(k[5], (cd,))).astype(jnp.bfloat16)
    step = jnp.exp(jax.random.uniform(k[1], (H,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    dt_t = jax.nn.softplus(0.5 * jax.random.normal(k[2], (Bt, H, S))
                           + (step + jnp.log(-jnp.expm1(-step)))[:, None])
    A = -jnp.arange(1, H + 1, dtype=jnp.float32)
    D = jnp.ones((H,), jnp.float32)

    def split(xbc):
        return (xbc[..., :di].reshape(Bt, S, H, P), xbc[..., di:di + gn].reshape(Bt, S, G, N),
                xbc[..., di + gn:].reshape(Bt, S, G, N))

    def tokens_minor(y):
        return jnp.swapaxes(y.reshape(Bt, S, di), 1, 2)              # as it is read

    def scan(form, dtype=jnp.bfloat16):
        # x | B | C as the conv leaves them (the projection's x | B | C
        # stands in), tokens minor
        def f(proj_t, dt_t):
            x, B, C = split(jnp.swapaxes(proj_t, 1, 2)[..., di:di + cd].astype(dtype))
            return tokens_minor(form(x, jnp.swapaxes(dt_t, 1, 2), A, B, C, D, Q))
        return f

    def conv_then(form, dtype=jnp.bfloat16):
        # XLA's conv, then the scan in ``form``, from the projection
        def f(proj_t, dt_t):
            v = jnp.swapaxes(proj_t, 1, 2).astype(dtype)
            xbc = ssm.causal_conv(v[..., di:di + cd], w, b)
            x, B, C = split(xbc)
            return tokens_minor(form(x, jnp.swapaxes(dt_t, 1, 2), A, B, C, D, Q))
        return f

    def fused(proj_t, dt_t):
        y = ssm.conv_ssd_scan(jnp.swapaxes(proj_t, 1, 2), w, b, jnp.swapaxes(dt_t, 1, 2), A, D,
                              G, N, Q)
        return tokens_minor(y)

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    y_xla = timed("scan, XLA's form              ", scan(ssm._ssd_scan_xla), proj_t, dt_t)
    y_kernel = timed("scan, kernel                  ", scan(ssm._ssd_scan_kernel), proj_t, dt_t)
    c_two = timed("conv + scan, XLA's conv, kernel", conv_then(ssm._ssd_scan_kernel), proj_t, dt_t)
    c_fused = timed("conv + scan, one kernel        ", fused, proj_t, dt_t)
    c_xla = jax.jit(conv_then(ssm._ssd_scan_xla))(proj_t, dt_t)
    with jax.default_matmul_precision("highest"):
        y_f32 = jax.jit(scan(ssm._ssd_scan_xla, jnp.float32))(proj_t, dt_t)
        c_f32 = jax.jit(conv_then(ssm._ssd_scan_xla, jnp.float32))(proj_t, dt_t)

    def planted(name, new, fn):
        real = getattr(ssm, name)
        setattr(ssm, name, new)
        try:        # a function of its own, so that jit traces it again
            return jax.jit(lambda *a: fn(*a))(proj_t, dt_t)
        finally:
            setattr(ssm, name, real)

    real_cumsum, real_conv = ssm._cumsum, ssm._conv
    y_fault = planted("_cumsum", lambda a: real_cumsum(a.astype(jnp.bfloat16).astype(jnp.float32)),
                      scan(ssm._ssd_scan_kernel))
    c_dropped = planted("_conv", lambda raw, prev, wb: real_conv(raw, jnp.zeros_like(prev), wb),
                        fused)
    c_reversed = planted("_conv", lambda raw, prev, wb: real_conv(
        raw, prev, [*wb[-2::-1], wb[-1]]), fused)
    print(f"[probe] {cell} scan alone, rel. difference: kernel from XLA's form "
          f"{rel(y_kernel, y_xla):.3e}; from float32: kernel {rel(y_kernel, y_f32):.3e}, XLA's "
          f"form {rel(y_xla, y_f32):.3e}; bf16-rounded cumulative sum {rel(y_fault, y_xla):.3e}",
          flush=True)
    print(f"[probe] {cell} conv + scan, rel. difference from XLA's conv and scan: one kernel "
          f"{rel(c_fused, c_xla):.3e}, XLA's conv then the kernel {rel(c_two, c_xla):.3e}; from "
          f"float32: one kernel {rel(c_fused, c_f32):.3e}, XLA's form {rel(c_xla, c_f32):.3e}; "
          f"planted: previous chunk not carried {rel(c_dropped, c_xla):.3e}, taps reversed "
          f"{rel(c_reversed, c_xla):.3e}", flush=True)

    # the whole mixer on a normed input, in each form
    params = jax.jit(lm.init_params, static_argnums=1)(jax.random.key(1), cfg)
    lp = {key: v[0] for key, v in params["layers"].items()}
    u = jax.random.normal(k[3], (Bt, S, cfg.d_model)).astype(lm.dtype_of(cfg.dtype))
    out_kernel = timed("mixer, kernel                 ", lambda u: lm._ssm(u, lp, cfg), u,
                       roof=False)
    was = ssm.enabled
    ssm.enabled = lambda: False
    try:
        out_xla = timed("mixer, XLA's form             ", lambda u: lm._ssm(u, lp, cfg), u,
                        roof=False)
    finally:
        ssm.enabled = was
    print(f"[probe] {cell} mixer rel. difference {rel(out_kernel, out_xla):.3e}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
