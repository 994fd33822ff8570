"""PR 34 probe: what moving rows into and out of the expert tiles costs under
a held share, each form alone at the laguna cell's shape (T 8192, k 10,
D 3072, F 1024, 32 of 256 experts held), on the chip.

    chiprun -- python3 scripts/probes/_moe_rows_probe.py            # the cell's shape
    JAX_PLATFORMS=cpu python3 scripts/probes/_moe_rows_probe.py --tiny   # interpreter, a count check

Times (ms a call, the mean of ``--calls`` calls after one warm-up):

- ``layout_xla`` / ``layout_held``: the index math of ``_tile_layout`` /
  ``_held_layout`` alone;
- ``x_gather_xla``: ``x[token]`` with the index gather that feeds it;
- ``x_pack``: ``row_gather.packed(x)``;
- ``gate_up_plain`` (on rows already gathered) / ``gate_up_rows`` (fetching
  its own, over the real tiles alone), and ``gate_up_plain_dead``: the plain
  kernel with ONE real tile — what the skipped grid steps cost; ``down`` /
  ``down_held``: ``moe_down`` over the static grid / over the real tiles;
- ``combine_all_slots``: ``weighted_sum`` over all T·k slots (absent → row 0);
  ``combine_held``: ``held_sums`` over the held pairs;
- ``layer_xla_rows`` / ``layer_held_rows``: the whole expert layer either way,
  and their largest difference.
"""
from __future__ import annotations
import _bootstrap  # noqa: F401  (repo-root sys.path + cwd shim)

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from crosscoder_tpu.ops import moe, row_gather


def timed(fn, *args, calls):
    fn = jax.jit(fn)
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return round(1000 * (time.perf_counter() - t0) / calls, 4), out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    if a.tiny:
        T, k, D, F, E, held = 256, 4, 768, 128, 16, 4       # three lane tiles of words a row
        moe.set_interpret(True)
        a.calls = 1
    else:
        T, k, D, F, E, held = 8192, 10, 3072, 1024, 256, 32
    I = moe._INTERPRET
    ks = jax.random.split(jax.random.key(a.seed), 6)
    x = jax.random.normal(ks[0], (T, D), jnp.bfloat16)
    w_router = jax.random.normal(ks[1], (D, E), jnp.float32) * D ** -0.5
    wgu = (jax.random.normal(ks[2], (1, held, D, 2 * F)) * D ** -0.5).astype(jnp.bfloat16)
    wd = (jax.random.normal(ks[3], (1, held, F, D)) * F ** -0.5).astype(jnp.bfloat16)
    idx, gates = moe.route(x, w_router, k, True, 2.5)
    idx, gates = moe._held(idx, gates, 0, held)
    n_held = int((np.asarray(idx) < held).sum())
    out = {"shape": dict(T=T, k=k, D=D, F=F, E=E, held=held),
           "device": jax.devices()[0].device_kind, "held_pairs": n_held,
           "row_share": round(n_held / (T * k), 4)}
    layer = jnp.zeros((1,), jnp.int32)

    w_block = moe._w_block
    specs = [pl.BlockSpec((None, None, D, F), w_block(0)),
             pl.BlockSpec((None, None, D, F), w_block(1))]

    # --- the parent's form, from its pieces ---------------------------------
    def layout_xla(idx):
        return moe._tile_layout(idx, held, True)

    out["layout_xla"], (te, nv, token, rows) = timed(layout_xla, idx, calls=a.calls)
    out["real_tiles"], out["tiles"] = int(nv[0]), int(te.shape[0])
    out["x_gather_xla"], xs = timed(
        lambda x, idx: x[moe._tile_layout(idx, held, True)[2]], x, idx, calls=a.calls)
    out["x_gather_xla"] = round(out["x_gather_xla"] - out["layout_xla"], 4)

    def gate_up_plain(te, nv, xs, wgu):
        return moe._tile_call(moe._gate_up_kernel, "moe_gate_up", (te, nv, layer), xs,
                              (wgu, wgu), specs, F)

    out["gate_up_plain"], h = timed(gate_up_plain, te, nv, xs, wgu, calls=a.calls)

    def down(te, nv, h, wd):
        return moe._tile_call(moe._down_kernel, "moe_down", (te, nv, layer), h, (wd,),
                              [pl.BlockSpec((None, None, F, D), w_block(0))], D, packed=True)

    out["down"], y = timed(down, te, nv, h, wd, calls=a.calls)
    out["combine_all_slots"], got_old = timed(
        lambda rows, gates, y: row_gather.weighted_sum(
            rows, gates, y, D, name="expert_combine", interpret=I),
        rows, gates, y, calls=a.calls)

    def layer_xla_rows(x, idx, gates, wgu, wd):
        te, nv, token, rows = moe._tile_layout(idx, held, True)
        y = down(te, nv, gate_up_plain(te, nv, x[token], wgu), wd)
        return row_gather.weighted_sum(rows, gates, y, D, name="expert_combine", interpret=I)

    out["layer_xla_rows"], whole_old = timed(layer_xla_rows, x, idx, gates, wgu, wd,
                                             calls=a.calls)

    # --- the held form -------------------------------------------------------
    out["layout_held"], (te2, nv2, first, tok, pairs) = timed(
        lambda idx, gates: moe._held_layout(idx, gates, held), idx, gates, calls=a.calls)
    out["x_pack"], xp = timed(lambda x: row_gather.packed(x, interpret=I), x, calls=a.calls)

    def gate_up_rows(te, nv, first, tok, xp, wgu):
        return moe._gate_up_rows(te, nv, layer, first, tok, xp, wgu, x.dtype)

    out["gate_up_rows"], h2 = timed(gate_up_rows, te2, nv2, first, tok, xp, wgu, calls=a.calls)
    real = int(nv2[0]) * moe.TILE_ROWS
    out["gate_up_rows_equal"] = bool(
        (np.asarray(h2[:real], np.float32) == np.asarray(h[:real], np.float32)).all())
    out["gate_up_plain_dead"], _ = timed(gate_up_plain, te, jnp.ones_like(nv), xs, wgu,
                                         calls=a.calls)
    out["combine_held"], got_new = timed(
        lambda pairs, y: row_gather.held_sums(*pairs, y, T, D, name="expert_combine",
                                              interpret=I), pairs, y, calls=a.calls)
    d = np.abs(np.asarray(got_new, np.float32) - np.asarray(got_old, np.float32))
    out["combine_differs_share"] = float((d > 0).mean())
    out["combine_max_abs_diff"] = float(d.max())
    out["down_held"], _ = timed(
        lambda te, nv, h, wd: moe._tile_call(
            moe._down_kernel, "moe_down", (te, nv, layer), h, (wd,),
            [pl.BlockSpec((None, None, F, D), w_block(0))], D, packed=True, n_grid=nv[0]),
        te2, nv2, h2, wd, calls=a.calls)
    out["layer_held_rows"], whole_new = timed(
        lambda *a: moe._held_rows(*a, 0), x, idx, gates, wgu, wd, calls=a.calls)
    d = np.abs(np.asarray(whole_new, np.float32) - np.asarray(whole_old, np.float32))
    out["layer_max_abs_diff"] = float(d.max())
    out["layer_differs_share"] = float((d > 0).mean())
    print(json.dumps(out))


if __name__ == "__main__":
    main()
