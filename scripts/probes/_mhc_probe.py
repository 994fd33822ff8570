#!/usr/bin/env python3
"""Each form of the stream maps and of the latent attention alone at the cell's
shape, and the cell's harvest quanta with the maps as kernels and as XLA's
form, on the chip:

    chiprun -- python3 scripts/probes/_mhc_probe.py [--tiny] [--reps N]

Prints milliseconds a call (the median of ``reps`` timed calls after one that
compiles) and, for the maps, the share of the HBM roofline their needed bytes
give. ``--tiny`` is the CPU rehearsal (kernels through the interpreter)."""

from __future__ import annotations

import argparse
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "tests")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--parts", default="maps,attn,quanta")
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmarks import manifest
    from benchmarks.arch import xing
    from crosscoder_tpu.models import lm
    from crosscoder_tpu.ops import flash_attention as fa
    from crosscoder_tpu.ops import mhc

    config = manifest.load_json(manifest.BENCH_DIR / "configs" / "xing4.0-pair-relu16k.json")
    if ns.tiny:
        for m in (mhc, fa):
            m.set_interpret(True)
        cfg = xing.lm_config(config, dict(
            vocab_size=257, d_model=128, n_layers=3, n_heads=2, n_kv_heads=2, head_dim=256,
            d_ff=256, dtype="bf16")).replace(head_dim=192, qk_rope_dim=64, v_head_dim=128)
        B, S = 1, 128
    else:
        cfg = xing.lm_config(config)
        B, S = config["crosscoder"]["model_batch_size"], config["crosscoder"]["seq_len"]
    T, n, C, hc = B * S, cfg.n_streams, cfg.d_model, cfg.hc
    dt = jnp.bfloat16

    def timed(name, fn, *args, bytes_needed=None, chain=False):
        # ``chain``: the first argument is donated and the result takes its
        # place in the next call (a kernel that writes into its input's buffer
        # is timed without the copy XLA makes of an input it may not clobber)
        f = jax.jit(fn, donate_argnums=(0,) if chain else ())
        first = args[0] + 0 if chain else args[0]
        out = jax.block_until_ready(f(first, *args[1:]))
        ts = []
        for _ in range(ns.reps):
            first = out if chain else args[0]
            t0 = time.perf_counter()
            out = jax.block_until_ready(f(first, *args[1:]))
            ts.append(time.perf_counter() - t0)
        ms = 1e3 * statistics.median(ts)
        roof = f", {100 * bytes_needed / 819e9 / (ms / 1e3):.1f}% of 819 GB/s" if bytes_needed else ""
        print(f"[probe] {name}: {ms:.3f} ms (min {1e3 * min(ts):.3f}){roof}", flush=True)
        return ms

    k = jax.random.split(jax.random.key(0), 8)
    x = jax.random.normal(k[0], (T, n * C), jnp.float32).astype(dt)
    y = jax.random.normal(k[1], (T, C), jnp.float32).astype(dt)
    phi = jax.random.normal(k[2], (n * C, hc.width), jnp.float32) * (n * C) ** -0.5
    alpha = jnp.ones((3,), jnp.float32)
    bias = jnp.concatenate([jnp.zeros((2 * n,)), 2.0 * jnp.eye(n).reshape(-1)])
    item = 2
    print(f"[probe] {jax.devices()[0].device_kind}; T {T}, n {n}, C {C}; "
          f"kernels supported {mhc.supported(T, hc, C, dt)}", flush=True)
    _, maps = mhc._read_xla(x, phi, alpha, bias, hc)
    if "maps" in ns.parts:
        timed("mhc read  XLA   ", lambda x: mhc._read_xla(x, phi, alpha, bias, hc), x,
              bytes_needed=T * (n + 1) * C * item)
        timed("mhc read  kernel", lambda x: mhc._read_kernel_call(x, phi, alpha, bias, hc), x,
              bytes_needed=T * (n + 1) * C * item)
        timed("mhc write XLA   ", lambda x, y, m: mhc._write_xla(x, y, m, hc), x, y, maps,
              bytes_needed=T * (2 * n + 1) * C * item, chain=True)
        timed("mhc write kernel", lambda x, y, m: mhc._write_kernel_call(x, y, m, hc), x, y, maps,
              bytes_needed=T * (2 * n + 1) * C * item, chain=True)

    H, dn, dr, dv = cfg.n_heads, cfg.head_dim - cfg.qk_rope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    qn = jax.random.normal(k[3], (B, S, H, dn), jnp.float32).astype(dt)
    qr = jax.random.normal(k[4], (B, S, H, dr), jnp.float32).astype(dt)
    kr = jax.random.normal(k[5], (B, S, 1, dr), jnp.float32).astype(dt)
    if "attn" in ns.parts and fa.latent_supported(S, dn, dr, dv, dt):
        ms = timed("latent attention kernel (a)", lambda *a: fa.flash_attention_latent(
            *a, scale=cfg.query_pre_attn_scalar ** -0.5), qn, qr, qn, kr, qn)
        flops = B * S * xing.attn_core_flops_per_token(cfg, 1, S)
        print(f"[probe]   needed {flops / 1e9:.1f} GFLOP: "
              f"{100 * flops / 197e12 / (ms / 1e3):.1f}% of 197 TFLOP/s", flush=True)
        # form (b): the score head concatenated and padded to 256 lanes, the rotary
        # key copied a head — through the kernel's plain instance at head 256
        if fa.supported(S, H, H, 256, dt):
            def form_b(qn, qr, kn, kr, v):
                pad = ((0, 0),) * 3 + ((0, 256 - dn - dr),)
                q = jnp.pad(jnp.concatenate([qn, qr], -1), pad)
                kk = jnp.pad(jnp.concatenate([kn, jnp.broadcast_to(kr, qr.shape)], -1), pad)
                vv = jnp.pad(v, ((0, 0),) * 3 + ((0, 256 - dv),))
                return fa.flash_attention(q, kk, vv, scale=cfg.query_pre_attn_scalar ** -0.5)
            timed("latent attention form (b), 256-wide heads", form_b, qn, qr, qn, kr, qn)

    if "quanta" not in ns.parts:
        return 0
    # the cell's quanta: one program a class, the maps as kernels and as XLA's form
    params = jax.jit(lm.init_params, static_argnums=1)(jax.random.key(1), cfg)
    capture = lm._hook_layers(cfg, (f"blocks.{cfg.n_layers}.hook_resid_pre",))
    tokens = jax.random.randint(k[6], (B, S), 1, cfg.vocab_size)
    classes = lm.layer_classes(cfg)
    for use_kernels in (True, False):
        real = mhc.enabled
        if not use_kernels:
            mhc.enabled = lambda: False
        jax.clear_caches()
        try:
            total = 0.0
            for c, cls in enumerate(classes):
                kq = len(cls.layers) if len(cls.layers) <= 2 else 2
                lo = cls.layers[0]

                def seg(params, resid, buf, c=c, kq=kq, lo=lo):
                    return lm._scan_blocks(params, cfg, capture, (resid, buf), kq,
                                           jnp.int32(lo), cls=c if len(classes) > 1 else None)[0]

                resid, buf = lm._fresh_carry(params, tokens, cfg, 1)
                ms = timed(f"{kq} {cls.mlp} layers, maps as "
                           f"{'kernels' if use_kernels else 'XLA'}", seg, params, resid, buf)
                total += ms * len(cls.layers) / kq
            print(f"[probe]   a model's {cfg.n_layers} layers: {total:.2f} ms", flush=True)
        finally:
            mhc.enabled = real
    return 0


if __name__ == "__main__":
    sys.exit(main())
