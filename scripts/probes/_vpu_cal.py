#!/usr/bin/env python3
"""Scratch calibration: what elementwise work costs in a Pallas kernel at the
stream maps' shape (PR 35's investigation; PERF.md §6)."""
import functools, statistics, sys, time
from pathlib import Path
sys.path[:0] = [str(Path(__file__).resolve().parents[2])]
import jax, jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

T, n, C = 8192, 4, 3584
TILE, ROWS, LANES = 128, 16, 128

def pieces(width, body):
    def rows(g, _):
        r0 = pl.multiple_of(g * ROWS, ROWS)
        def lanes(c, _):
            body(r0, pl.multiple_of(c * LANES, LANES)); return 0
        jax.lax.fori_loop(0, width // LANES, lanes, 0); return 0
    jax.lax.fori_loop(0, TILE // ROWS, rows, 0)

def k_copy(x_ref, o_ref):
    o_ref[...] = x_ref[...]
def k_conv_whole(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(jnp.float32).astype(o_ref.dtype)
def k_conv(x_ref, o_ref):
    def body(r0, off):
        o_ref[pl.ds(r0, ROWS), pl.ds(off, LANES)] = x_ref[pl.ds(r0, ROWS), pl.ds(off, LANES)].astype(jnp.float32).astype(o_ref.dtype)
    pieces(n * C, body)
def k_fma(x_ref, o_ref, reps=1):
    def body(r0, off):
        v = x_ref[pl.ds(r0, ROWS), pl.ds(off, LANES)].astype(jnp.float32)
        for _ in range(reps):
            v = v * 1.0001 + 0.5
        o_ref[pl.ds(r0, ROWS), pl.ds(off, LANES)] = v.astype(o_ref.dtype)
    pieces(n * C, body)
def k_fma_whole(x_ref, o_ref, reps=1):
    v = x_ref[...].astype(jnp.float32)
    for _ in range(reps):
        v = v * 1.0001 + 0.5
    o_ref[...] = v.astype(o_ref.dtype)
def k_f32(x_ref, o_ref, reps=8):      # f32 in, f32 out: no conversions
    def body(r0, off):
        v = x_ref[pl.ds(r0, ROWS), pl.ds(off, LANES)]
        for _ in range(reps):
            v = v * 1.0001 + 0.5
        o_ref[pl.ds(r0, ROWS), pl.ds(off, LANES)] = v
    pieces(n * C // 2, body)
def k_mxu(x_ref, bd_ref, o_ref):      # x as [(t, j), C]: out = BD @ x a 32-token group
    for g in range(TILE * n // 128):
        r = x_ref[g * 128:(g + 1) * 128, :]
        o_ref[g * 128:(g + 1) * 128, :] = jnp.dot(bd_ref[g], r, preferred_element_type=jnp.float32).astype(o_ref.dtype)

def run(name, kern, x, *extra, specs=(), dt=jnp.bfloat16, width=n * C, rows=T, tile=TILE):
    blk = pl.BlockSpec((tile, width), lambda t: (t, 0))
    f = jax.jit(lambda x, *e: pl.pallas_call(kern, grid=(rows // tile,), in_specs=[blk, *specs], out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((rows, width), dt), input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=64 << 20))(x, *e), donate_argnums=0)
    out = jax.block_until_ready(f(x + 0, *extra))
    ts = []
    for _ in range(8):
        t0 = time.perf_counter(); out = jax.block_until_ready(f(out, *extra)); ts.append(time.perf_counter() - t0)
    print(f"[cal] {name}: {1e3 * statistics.median(ts):.3f} ms", flush=True)

x = jax.random.normal(jax.random.key(0), (T, n * C), jnp.float32).astype(jnp.bfloat16)
run("copy (DMA floor)", k_copy, x)
run("convert round trip, whole tile", k_conv_whole, x)
run("convert round trip, pieces", k_conv, x)
run("convert + 1 fma, pieces", k_fma, x)
run("convert + 8 fma, pieces", functools.partial(k_fma, reps=8), x)
run("convert + 1 fma, whole", k_fma_whole, x)
run("convert + 8 fma, whole", functools.partial(k_fma_whole, reps=8), x)
xf = jax.random.normal(jax.random.key(0), (T, n * C // 2), jnp.float32)
run("f32 in/out 8 fma, pieces (same bytes)", k_f32, xf, dt=jnp.float32, width=n * C // 2)
run("f32 in/out 1 fma, pieces (same bytes)", functools.partial(k_f32, reps=1), xf, dt=jnp.float32, width=n * C // 2)
xr = x.reshape(T * n, C)
bd = (jax.random.normal(jax.random.key(1), (T // 32, 128, 128)) * 0.1).astype(jnp.bfloat16)
run("MXU: BD[128,128] @ R[128,C] a 32 tokens", k_mxu, xr, bd, specs=[pl.BlockSpec((TILE * n // 128, 128, 128), lambda t: (t, 0, 0))],
    width=C, rows=T * n, tile=TILE * n)
