#!/usr/bin/env python3
"""The planted faults of tests/test_nemotron_h_faults.py at the cell's widths,
on the chip: the hooked residual of one seeded 4096-token sequence, the
program in bf16 with a fault planted against the plain float32 reference of
the true model given the same share (the comparison that decides ``correct``
in train-live-nemotron-relu16k).

    python3 scripts/probes/_nemotron_h_faults.py [--seed N ...] [--by-layer] [--tiny] [fault ...]

Prints one line a seed and fault: its relative Frobenius error beside
``HARVEST_RTOL``; ``none`` is the program's own reading. Two readings are
not faults: ``float8_reference`` is the reference computed in the precision
below the stated one (every weight, each sublayer's input and output and the
stream rounded to float8_e4m3: the limit's upper side), and
``reference_choices`` the program with each expert layer's choices fixed to
the reference's own (its gates still its own scores), which takes the
router's discontinuity out of the reading. ``float8_weights`` rounds the
program's weights alone. ``--by-layer`` adds each program run's reading as
the stream enters each layer. Faults that swap a program function recompile
the forward; ``d_ssm_from_expand`` (another tree's shape) is held by the CPU
tests alone. ``--tiny`` is the CPU rehearsal (tiny widths, float32)."""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "tests")]

DEFAULT = ["none", "float8_reference", "reference_choices", "float8_weights",
           "routed_scale_dropped", "rope_applied", "bias_in_the_gates", "silu_gated_experts",
           "norm_ungrouped", "gate_after_the_norm"]


def fixed_choices(chosen):
    """``moe.route`` and ``moe.moe_mlp`` with the choices of the expert layer
    at stack slot ``s`` fixed to ``chosen[s]`` ``[T, k]`` (the model's expert
    ids), gated by the program's own unbiased scores of them."""
    import jax
    import jax.numpy as jnp

    from crosscoder_tpu.ops import moe

    real_mlp, slot = moe.moe_mlp, {}

    def route(x, w_router, top_k, norm_topk_prob, routed_scale=1.0, kind="softmax", bias=None):
        scores = jax.nn.sigmoid(jnp.einsum("td,de->te", x, w_router,
                                           preferred_element_type=jnp.float32))
        idx = jnp.take(chosen, slot["now"], axis=0)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        if norm_topk_prob:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        return idx, gates * routed_scale

    def moe_mlp(x, w_router, w_gate_up, w_down, layer=0, **kw):
        slot["now"] = layer
        return real_mlp(x, w_router, w_gate_up, w_down, layer, **kw)

    return [(moe, "route", route), (moe, "moe_mlp", moe_mlp)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, action="append")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--by-layer", action="store_true")
    ap.add_argument("faults", nargs="*")
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import test_nemotron_h_faults as t
    from benchmarks import common
    from benchmarks.arch import nemotron_h
    from benchmarks.reference import nemotron_h_ref as ref
    from crosscoder_tpu.models import lm

    cfg = nemotron_h.lm_config(t.CONFIG, t.TINY if ns.tiny else None)
    seq = 64 if ns.tiny else t.CONFIG["crosscoder"]["seq_len"]
    n = cfg.n_layers

    def rel(got, want):
        return float(jnp.linalg.norm(got.astype(jnp.float32) - want) / jnp.linalg.norm(want))

    for seed in ns.seed or [21]:
        s_tok, s_model = common.sub_seeds(seed, 2)
        params = common.init_lm_pair(cfg, [s_model])[0]
        tokens = jnp.asarray(np.random.default_rng(s_tok).integers(
            0, cfg.vocab_size, size=(1, seq)))
        t0 = time.perf_counter()
        chosen, real_routing = [], ref.routing      # the reference's choices, layer by layer

        def routing(*a, **kw):
            out = real_routing(*a, **kw)
            chosen.append(out[0])
            return out

        ref.routing = routing
        try:
            want = jax.block_until_ready(nemotron_h.resid_pre(params, tokens, cfg, n))
        finally:
            ref.routing = real_routing
        chosen = jnp.stack(chosen).astype(jnp.int32)
        print(f"[faults] {jax.devices()[0].device_kind}; seed {seed}; reference "
              f"{time.perf_counter() - t0:.1f} s; limit {nemotron_h.HARVEST_RTOL}", flush=True)
        refs = ([nemotron_h.resid_pre(params, tokens, cfg, i) for i in range(1, n)]
                if ns.by_layer else [])
        for fault in ns.faults or DEFAULT:
            t0 = time.perf_counter()
            if fault == "float8_reference":
                err = rel(ref.resid_pre(params, tokens, cfg, n, act=jnp.float8_e4m3fn), want)
                print(f"[faults] seed {seed} {fault}: {err:.4f} ({time.perf_counter() - t0:.1f} s)",
                      flush=True)
                continue
            if fault == "reference_choices":
                bad_cfg, bad_params, patches = cfg, params, fixed_choices(chosen)
            else:
                bad_cfg, bad_params, patches = t.plant(fault, cfg, params)
            hooks = tuple(f"blocks.{i}.hook_resid_pre" for i in (range(1, n + 1) if refs else [n]))
            with t.planted(patches):
                got = lm.run_with_cache_multi([bad_params], tokens, bad_cfg, hooks)
                err = rel(got[:, :, -1], want)
            print(f"[faults] seed {seed} {fault}: {err:.4f} ({time.perf_counter() - t0:.1f} s)",
                  flush=True)
            for i, ref_i in enumerate(refs, start=1):
                print(f"[faults] seed {seed} {fault} entering layer {i} (after "
                      f"{cfg.layer_types[i - 1]}/{cfg.mlp_types[i - 1]}): "
                      f"{rel(got[:, :, i - 1], ref_i):.4f}", flush=True)
            del bad_params, got
        del params, want, refs
    return 0


if __name__ == "__main__":
    sys.exit(main())
