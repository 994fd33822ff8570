"""EQuARX-style block-scaled int8 gradient all-reduce (cfg.quant_grads).

Under pure data parallelism the per-step collective is the gradient
all-reduce — byte volume ≈ the parameter pytree, constant in mesh width,
and the dominant ICI tenant of the train step (docs/SCALING.md: 1.2 GB/step
at dict 2^15 bf16). EQuARX (PAPERS.md) shows the standard two-phase ring
all-reduce can run its wire phases in int8 with per-block scales at ~2x
effective bandwidth and negligible quality loss. This module implements
that exchange explicitly inside a shard_map (XLA's implicit psum offers no
dtype hook):

phase 1 (reduce-scatter shaped): each device splits its local-mean
    gradient vector into ``n_dev`` segments, quantizes them (int8 +
    per-``block`` f32 scales), and an ``all_to_all`` delivers segment j of
    every device to device j, which dequantizes and sums in f32;
phase 2 (all-gather shaped): each device quantizes its fully-reduced
    segment and an ``all_gather`` replicates all segments; dequantize,
    divide by ``n_dev`` → the global-mean gradient everywhere.

Wire bytes per device ≈ 2·(n−1)/n · N·(1 + 4/block) vs the bf16 psum's
2·(n−1)/n · 2N — ~2x less (4x vs an fp32 psum). The scales ride as two
small f32 collectives (4/block of the payload).

**Error feedback** (the EF-SGD/1-bit-Adam recipe): quantization error
would otherwise bias the trajectory; instead each device carries a
residual the size of its padded gradient vector (``TrainState.aux
["quant_ef"]``, sharded ``P('data')`` so every device owns exactly its own
residual) and adds it to the next step's gradient before quantizing. Both
phases feed back: phase-1 error is the local quantize→dequantize residual;
phase-2 error (the reduced segment's re-quantization, known only to the
segment's owner) is credited to the owner's residual at that segment's
slot — summed across devices next step, that repays the whole fleet. The
compression therefore stays unbiased in the long run: the mean of the
compressed gradients converges to the exact mean (asserted in
tests/test_quant.py).

The trainer wires this in by computing per-device gradients inside a
shard_map over the ``data`` axis and calling :func:`quantized_pmean_tree`
in place of the implicit psum; optimizer, clipping, and schedules stay
outside, numerically identical to the bf16 path given the (now nearly
exact) mean gradient.

Known limitation (fine at the validated scales, revisit at pod scale):
the exchange runs PER LEAF, so every param pads to a multiple of
``n_dev*block`` and launches its own all_to_all+all_gather pair. Small
leaves (b_enc/b_dec/log_theta, a few K elements) inflate their wire and
``quant_ef`` bytes substantially at n_dev≥256, and ~6 extra
latency-bound collective pairs dispatch per step. The fix is a single
ravel-concat exchange over the whole flattened gradient tree (pad once,
2 collectives total) — it changes the ``quant_ef`` aux layout from
per-param to one vector, so it needs a checkpoint-compat shim.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from crosscoder_tpu.ops import quant


def padded_len(size: int, n_dev: int, block: int) -> int:
    """Flat gradient length rounded up so it splits into ``n_dev`` segments
    of whole ``block``s (zero padding quantizes exactly)."""
    unit = n_dev * block
    return -(-size // unit) * unit


def ef_init(params: dict, n_dev: int, block: int) -> dict:
    """Zero error-feedback residuals for a param pytree: one padded flat
    f32 vector per device per param, stored ``[n_dev, L]`` and sharded
    over the mesh ``data`` axis (each device holds only its own row)."""
    return {
        k: jnp.zeros((n_dev, padded_len(v.size, n_dev, block)), jnp.float32)
        for k, v in params.items()
    }


def _quantized_pmean_leaf(
    g: jax.Array, ef: jax.Array, axis_name: str, n_dev: int, block: int
) -> tuple[jax.Array, jax.Array]:
    """One gradient leaf through the two-phase quantized mean all-reduce.

    ``g``: this device's local-mean gradient (any float dtype, any shape);
    ``ef``: this device's residual, shape ``[1, L]`` (the local block of
    the ``P('data')``-sharded ``[n_dev, L]`` aux array). Returns the
    global-mean gradient (same shape/dtype as ``g``) and the updated
    residual.
    """
    L = ef.shape[-1]
    gf = g.ravel().astype(jnp.float32)
    v = jnp.zeros((L,), jnp.float32).at[: gf.size].set(gf) + ef.reshape(L)
    seg = v.reshape(n_dev, L // n_dev)

    # phase 1: quantize local segments, deliver segment j to device j
    q, s = quant.quantize_blocks(seg, block)
    new_ef = seg - quant.dequantize_blocks(q, s, jnp.float32)   # local error
    qj = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
    sj = jax.lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0)
    partial = quant.dequantize_blocks(qj, sj, jnp.float32).sum(axis=0)

    # phase 2: re-quantize the reduced segment, replicate all segments
    q2, s2 = quant.quantize_blocks(partial[None], block)
    e2 = partial - quant.dequantize_blocks(q2, s2, jnp.float32)[0]
    # the reduced segment's re-quantization error is known only here (the
    # segment's owner) — credit it to THIS device's residual at the
    # segment's slot; next step it rides this device's contribution and
    # repays the whole sum
    my = jax.lax.axis_index(axis_name)
    new_ef = new_ef.at[my].add(e2)
    qg = jax.lax.all_gather(q2[0], axis_name, axis=0)           # [n_dev, seg]
    sg = jax.lax.all_gather(s2[0], axis_name, axis=0)
    out = quant.dequantize_blocks(qg, sg, jnp.float32).reshape(L)[: gf.size]
    out = (out / n_dev).reshape(g.shape).astype(g.dtype)
    return out, new_ef.reshape(ef.shape)


def quantized_pmean_fn(mesh, block: int, axis_name: str = "data"):
    """Jitted single-leaf exchange over an explicit DP mesh, for callers
    OUTSIDE the trainer (bench, tests): takes ``g [n_dev, ...]`` stacked
    per-device local gradients and ``ef [n_dev, L]`` residuals, runs the
    real :func:`_quantized_pmean_leaf` collective under shard_map, and
    returns ``(out [n_dev, ...], new_ef)`` — every row of ``out`` holds
    the same global-mean gradient."""
    from jax.sharding import PartitionSpec as P

    n_dev = mesh.shape[axis_name]

    def local(gl, ef):
        out, new_ef = _quantized_pmean_leaf(gl[0], ef, axis_name, n_dev, block)
        return out[None], new_ef

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name)), check_vma=False,
    ))


def quantized_pmean_tree(
    grads: dict, ef: dict, axis_name: str, n_dev: int, block: int
) -> tuple[dict, dict]:
    """Quantized mean all-reduce over a gradient dict (call INSIDE a
    shard_map over ``axis_name``). Returns (mean grads, new residuals)."""
    out, new_ef = {}, {}
    for k, g in grads.items():
        out[k], new_ef[k] = _quantized_pmean_leaf(
            g, ef[k], axis_name, n_dev, block
        )
    return out, new_ef
