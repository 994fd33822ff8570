"""Manifold-constrained hyper-connections (mHC): the two maps between a
token's ``n`` residual streams and a sublayer (arXiv:2512.24880, after
arXiv:2409.19606).

A token's state is ``X [n, C]``, held as ONE row ``[n·C]`` (its streams side
by side). Before a sublayer ``F`` (with its own
``phi [n·C, n² + 2n]``, ``alpha = (a_pre, a_post, a_res)`` and ``bias =
(b_pre [n] | b_post [n] | B_res [n·n], row-major)``, all float32):

    z      = rsqrt(mean(vec(X)²) + rms_eps) · (vec(X) phi)
    h_pre  = σ(a_pre z[:n] + b_pre)         h_post = 2 σ(a_post z[n:2n] + b_post)
    A      = clip(a_res · mat(z[2n:]) + B_res, clamp)            # n × n
    M      = exp(A); iters times: M ← M / (colsum(M) + eps); M ← M / (rowsum(M) + eps)
    u      = Σ_i h_pre[i] X[i]                                   # READ  → F sees norm(u)
    X'[i]  = Σ_j M[i, j] X[j] + h_post[i] · F(...)               # WRITE

Maps are float32; the streams keep their dtype (bf16 in production) with
float32 sums, rounded once. ``vec(X) phi`` keeps ``phi``'s float32 under bf16
streams at the price of one MXU pass: ``phi`` is split into three bf16 parts
laid side by side in one 128-lane tile (the 24 columns are padded to a tile
anyway), the stream values are exact in bf16, and the three partial products
are summed in float32.

Two forms of each map, chosen in :func:`read` / :func:`write` — when the
program is traced — from what the code can observe, as
``ops/flash_attention`` and ``ops/moe`` choose theirs:

- kernels (a one-device TPU backend at a :func:`supported` shape):
  ``pallas:mhc_read`` makes ONE pass over a token tile's ``[tile, n·C]`` rows
  in VMEM — the statistic and the product on the MXU, the three maps with
  the Sinkhorn loop (tokens along lanes, so a 4 × 4 matrix a token costs two
  vregs a tile, not sixteen columns), the weighted read on the MXU — and
  ``pallas:mhc_write`` reads X and y once and writes X' once (X's buffer
  reused), the mixing on the MXU (see "the kernels" below for how a
  per-token coefficient gets there);
- XLA's form (everything else — the CPU, a mesh, an unsupported shape),
  also the oracle the kernels are pinned against (tests/test_mhc.py). It
  keeps tokens minor through the Sinkhorn loop for the same reason.

The choice is counted once a trace: ``harvest/mhc_kernel_traces`` /
``harvest/mhc_xla_traces``. No environment gate, no config field.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# token rows of one tile: the X tile ([tile, n·C], double-buffered) and phi
# ([n·C, 128]) share VMEM; a constant of the kernels, not a knob
_TILE = 128
_VMEM_LIMIT_BYTES = 64 << 20

# test-only: route the kernels through the Pallas interpreter (and let them
# dispatch on the CPU backend) — same pattern as ops/flash_attention.
_INTERPRET = False


def set_interpret(flag: bool) -> None:
    global _INTERPRET
    _INTERPRET = flag


class HC(NamedTuple):
    """The static side of the maps (published keys, under the repo's names)."""

    n: int                  # streams a token (hc_mult)
    iters: int              # Sinkhorn iterations (hc_sinkhorn_iters)
    eps: float              # in the Sinkhorn denominators (hc_eps)
    clamp: tuple[float, float]      # on the mixing logits, before the exp
    rms_eps: float          # in the weightless norm over all n·C

    @property
    def width(self) -> int:
        return self.n * self.n + 2 * self.n


class Maps(NamedTuple):
    """What a read hands to its write, token-major: ``h_post [T, n]`` and the
    mixing matrix ``mix [T, n·n]`` (row-major: ``M[i, j]`` at ``n·i + j``)."""

    h_post: jax.Array
    mix: jax.Array


def enabled() -> bool:
    """Whether the kernels may dispatch from this process: the interpreter
    (CPU tests), or a TPU backend with exactly one device (a ``pallas_call``
    is not partitioned by the SPMD partitioner)."""
    return _INTERPRET or (
        jax.default_backend() == "tpu" and jax.device_count() == 1
    )


def supported(tokens: int, hc: HC, width: int, dtype) -> bool:
    """Shapes the kernels handle: whole token tiles, streams of whole lanes,
    the three parts of ``phi``'s columns inside one lane tile, and the X tile
    double-buffered beside ``phi`` within the raised VMEM limit."""
    if tokens % _TILE or width % _LANES or 3 * hc.width > _LANES:
        return False
    if _LANES % hc.n or (_LANES // hc.n) % 16:      # a group's streams: one MXU tile of rows
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    item = jnp.dtype(dtype).itemsize
    tile = _TILE * hc.n * width * item
    phi = hc.n * width * _LANES * item
    return 2 * 2 * tile + 2 * phi + 6 * _TILE * width * item <= _VMEM_LIMIT_BYTES


# ---------------------------------------------------------------------------
# shared pieces (traced by both forms; every op is one Mosaic lowers)


def _phi_lanes(phi: jax.Array, dtype) -> jax.Array:
    """``phi [n·C, W]`` float32 as the ``[n·C, 128]`` operand of the product:
    under bf16 streams its three bf16 parts side by side (hi | mid | lo,
    summing to ``phi`` to 2^-24 of it), else itself; zero columns beyond."""
    W = phi.shape[1]
    if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16):
        hi = phi.astype(jnp.bfloat16)
        r1 = phi - hi.astype(jnp.float32)
        mid = r1.astype(jnp.bfloat16)
        lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
        cols = jnp.concatenate([hi, mid, lo], axis=1)
    else:
        cols = phi.astype(dtype)
    return jnp.pad(cols, ((0, 0), (0, _LANES - cols.shape[1])))


def _affine_lanes(alpha: jax.Array, bias: jax.Array, hc: HC) -> tuple[jax.Array, jax.Array]:
    """``alpha [3]`` and ``bias [W]`` as two ``[1, 128]`` lane vectors, so
    that ``z · a + b`` is all three maps' pre-activations at once."""
    n = hc.n
    a = jnp.concatenate([jnp.full((n,), alpha[0]), jnp.full((n,), alpha[1]),
                         jnp.full((n * n,), alpha[2])]).astype(jnp.float32)
    pad = _LANES - hc.width
    return (jnp.pad(a, (0, pad))[None], jnp.pad(bias.astype(jnp.float32), (0, pad))[None])


def _sinkhorn(rows: list[jax.Array], hc: HC, unroll: bool = False) -> list[jax.Array]:
    """``rows[i] [n, T]`` = row ``i`` of every token's positive matrix (its
    columns along the second-minor axis, tokens minor) → the same after
    ``iters`` rounds of columns-then-rows normalisation. ONE loop, traced
    once; a kernel has the lowering ``unroll`` it."""
    def body(_, rows):
        col = functools.reduce(jnp.add, rows) + hc.eps              # [n, T]: by column j
        rows = [r / col for r in rows]
        return [r / (jnp.sum(r, axis=0, keepdims=True) + hc.eps) for r in rows]

    return list(jax.lax.fori_loop(0, hc.iters, body, list(rows), unroll=unroll or None))


def _maps_t(pre_t: jax.Array, hc: HC, unroll: bool = False) -> tuple[jax.Array, list[jax.Array]]:
    """The pre-activations with tokens minor, ``pre_t [≥ W, T]`` (rows as
    ``bias``: pre | post | res row-major) → ``h_post [n, T]`` and the rows of
    the mixing matrix, each ``[n, T]``."""
    n = hc.n
    h_post = 2.0 * jax.nn.sigmoid(pre_t[n:2 * n])
    rows = [jnp.exp(jnp.clip(pre_t[2 * n + n * i: 2 * n + n * (i + 1)], *hc.clamp))
            for i in range(n)]
    return h_post, _sinkhorn(rows, hc, unroll)


# ---------------------------------------------------------------------------
# XLA's form


def _pre_xla(x: jax.Array, phi, alpha, bias, hc: HC) -> jax.Array:
    """``x [T, n·C]`` → the three maps' pre-activations ``[T, W]`` (float32)."""
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + hc.rms_eps)
    W = hc.width
    if x.dtype == jnp.bfloat16:
        p = jnp.dot(x, _phi_lanes(phi, x.dtype), preferred_element_type=jnp.float32)
        z = p[:, :W] + p[:, W:2 * W] + p[:, 2 * W:3 * W]
    else:
        z = jnp.dot(xf, phi, precision=jax.lax.Precision.HIGHEST)
    a, b = _affine_lanes(alpha, bias, hc)
    return (r * z) * a[:, :W] + b[:, :W]


def _read_xla(x: jax.Array, phi, alpha, bias, hc: HC) -> tuple[jax.Array, Maps]:
    T, n = x.shape[0], hc.n
    pre = _pre_xla(x, phi, alpha, bias, hc)
    h_pre = jax.nn.sigmoid(pre[:, :n])
    u = jnp.einsum("tn,tnc->tc", h_pre, x.reshape(T, n, -1).astype(jnp.float32))
    h_post, rows = _maps_t(pre.T, hc)
    return u.astype(x.dtype), Maps(h_post.T, jnp.concatenate(rows, axis=0).T)


def _write_xla(x: jax.Array, y: jax.Array, maps: Maps, hc: HC) -> jax.Array:
    T, n = x.shape[0], hc.n
    out = jnp.einsum("tij,tjc->tic", maps.mix.reshape(T, n, n),
                     x.reshape(T, n, -1).astype(jnp.float32))
    out = out + maps.h_post[:, :, None] * y.astype(jnp.float32)[:, None, :]
    return out.astype(x.dtype).reshape(x.shape)


# ---------------------------------------------------------------------------
# the kernels
#
# A per-token coefficient times a row of values is, on the vector unit, a lane
# broadcast and a float32 multiply-add a value — and the vector unit, not HBM,
# then sets the time (PERF.md §6, PR 35: 2.2 and 3.1 ms a call where the
# bytes need 0.4 and 0.65). Both maps therefore go through the MXU: a group of
# G = 128 / n tokens' streams is STACKED in VMEM as rows (stream j, token t)
# — one MXU tile of 128 rows — and the per-token coefficients become a
# matrix of n × n diagonal G × G blocks, ``L[(i, t), (j, t')] = M_t[i, j] ·
# [t = t']``, so that ``L @ stack`` is every token's mixing at once, in the
# streams' row layout, float32-accumulated. The float32 coefficients meet the
# bf16 MXU as two bf16 parts (hi + lo: 2^-17 of a coefficient), two products
# summed; the statistic is the diagonal of ``X Xᵀ``, on the MXU too.


def _group(n: int) -> int:
    return _LANES // n


def _diag_blocks(coefs: list, G: int, width: int, start: int = 0) -> jax.Array:
    """``[G, width]`` float32: ``coefs[j] [G, 1]`` on the diagonal of the
    j-th ``G``-wide block of lanes from ``start``, 0 elsewhere."""
    row = jax.lax.broadcasted_iota(jnp.int32, (G, width), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (G, width), 1)
    out = jnp.zeros((G, width), jnp.float32)
    for j, c in enumerate(coefs):
        out = jnp.where(lane == row + (start + G * j), c, out)
    return out


def _coef_dot(coef: jax.Array, stack_ref, dtype) -> jax.Array:
    """``coef [rows, K]`` float32 times the stacked values ``[K, C]``,
    float32-accumulated: under bf16 values the coefficients as hi + lo bf16
    parts, else one float32 product."""
    stack = stack_ref[...]
    if dtype != jnp.bfloat16:
        return jnp.dot(coef, stack, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    hi = coef.astype(jnp.bfloat16)
    lo = (coef - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return (jnp.dot(hi, stack, preferred_element_type=jnp.float32)
            + jnp.dot(lo, stack, preferred_element_type=jnp.float32))


def _stack_streams(x_ref, stack_ref, g: int, n: int, C: int) -> None:
    """Group ``g``'s streams as rows (stream, token) of ``stack_ref``."""
    G = _group(n)
    for j in range(n):
        stack_ref[G * j:G * (j + 1), :] = x_ref[G * g:G * (g + 1), j * C:(j + 1) * C]


def _read_kernel(x_ref, phi_ref, a_ref, b_ref, u_ref, maps_ref, stack_ref, *,
                 hc: HC, width: int):
    n, C, W, G = hc.n, width, hc.width, _group(hc.n)
    tile = x_ref.shape[0]
    precision = None if x_ref.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST
    acc = jnp.zeros((tile, _LANES), jnp.float32)
    gram = jnp.zeros((tile, tile), jnp.float32)
    for i in range(n):                  # vec(X) phi and X Xᵀ: the MXU, a stream a pass
        xi = x_ref[:, i * C:(i + 1) * C]
        acc = acc + jnp.dot(xi, phi_ref[i * C:(i + 1) * C, :], precision=precision,
                            preferred_element_type=jnp.float32)
        gram = gram + jax.lax.dot_general(xi, xi, (((1,), (1,)), ((), ())),
                                          precision=precision,
                                          preferred_element_type=jnp.float32)
    if x_ref.dtype == jnp.bfloat16:
        # the three parts' partial products lie W lanes apart
        acc = (acc + pltpu.roll(acc, _LANES - W, axis=1)
               + pltpu.roll(acc, _LANES - 2 * W, axis=1))
    eye = (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1))
    ss = jnp.sum(jnp.where(eye, gram, 0.0), axis=1, keepdims=True)
    r = jax.lax.rsqrt(ss * (1.0 / (n * C)) + hc.rms_eps)
    pre = (r * acc) * a_ref[...] + b_ref[...]                   # [tile, 128]
    h_pre = jax.nn.sigmoid(pre)
    for g in range(tile // G):          # u = sum_i h_pre[i] X[i], a group a product
        _stack_streams(x_ref, stack_ref, g, n, C)
        h = h_pre[G * g:G * (g + 1)]
        coef = _diag_blocks([h[:, j:j + 1] for j in range(n)], G, _LANES)
        u_ref[G * g:G * (g + 1), :] = _coef_dot(coef, stack_ref, x_ref.dtype).astype(u_ref.dtype)
    h_post, rows = _maps_t(pre.T, hc, unroll=True)              # tokens along lanes
    maps_ref[0:n, :] = h_post
    for i in range(n):
        maps_ref[n * (i + 1): n * (i + 2), :] = rows[i]


def _write_kernel(x_ref, y_ref, maps_ref, o_ref, stack_ref, *, n: int, width: int):
    C, G = width, _group(n)
    K = stack_ref.shape[0]              # n·G stream rows | G rows of y | zeros
    stack_ref[_LANES + G:, :] = jnp.zeros((K - _LANES - G, C), stack_ref.dtype)
    for g in range(x_ref.shape[0] // G):
        _stack_streams(x_ref, stack_ref, g, n, C)
        stack_ref[_LANES:_LANES + G, :] = y_ref[G * g:G * (g + 1), :]
        m = maps_ref[G * g:G * (g + 1), :]                      # [G, n + n·n]
        coef = jnp.concatenate([                                # rows (stream i, token t)
            _diag_blocks([m[:, n + n * i + j:n + n * i + j + 1] for j in range(n)]
                         + [m[:, i:i + 1]], G, K)
            for i in range(n)], axis=0)
        out = _coef_dot(coef, stack_ref, x_ref.dtype)           # [n·G, C]
        for i in range(n):
            o_ref[G * g:G * (g + 1), i * C:(i + 1) * C] = out[G * i:G * (i + 1)].astype(o_ref.dtype)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _read_kernel_call(x: jax.Array, phi, alpha, bias, hc: HC) -> tuple[jax.Array, Maps]:
    T, n = x.shape[0], hc.n
    C = x.shape[1] // n
    rows = n + n * n
    a, b = _affine_lanes(alpha, bias, hc)
    lane = pl.BlockSpec((1, _LANES), lambda t: (0, 0))
    u, maps_t = pl.pallas_call(
        functools.partial(_read_kernel, hc=hc, width=C),
        grid=(T // _TILE,),
        in_specs=[pl.BlockSpec((_TILE, n * C), lambda t: (t, 0)),
                  pl.BlockSpec((n * C, _LANES), lambda t: (0, 0)), lane, lane],
        out_specs=[pl.BlockSpec((_TILE, C), lambda t: (t, 0)),
                   pl.BlockSpec((rows, _TILE), lambda t: (0, t))],
        out_shape=[jax.ShapeDtypeStruct((T, C), x.dtype),
                   jax.ShapeDtypeStruct((rows, T), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_LANES, C), x.dtype)],      # a group's stacked streams
        compiler_params=_params(),
        name="mhc_read",
        interpret=_INTERPRET,
    )(x, _phi_lanes(phi, x.dtype), a, b)
    maps = maps_t.T                                             # [T, n + n·n]: tiny
    return u, Maps(maps[:, :n], maps[:, n:])


def _write_kernel_call(x: jax.Array, y: jax.Array, maps: Maps, hc: HC) -> jax.Array:
    T, n = x.shape[0], hc.n
    C = x.shape[1] // n
    m = jnp.concatenate([maps.h_post, maps.mix], axis=1)
    tile = pl.BlockSpec((_TILE, n * C), lambda t: (t, 0))
    return pl.pallas_call(
        functools.partial(_write_kernel, n=n, width=C),
        grid=(T // _TILE,),
        in_specs=[tile, pl.BlockSpec((_TILE, C), lambda t: (t, 0)),
                  pl.BlockSpec((_TILE, n + n * n), lambda t: (t, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((T, n * C), x.dtype),
        scratch_shapes=[pltpu.VMEM((2 * _LANES, C), x.dtype)],  # the streams, y, zeros
        input_output_aliases={0: 0},            # X' takes X's buffer
        compiler_params=_params(),
        name="mhc_write",
        interpret=_INTERPRET,
    )(x, y, m)


# ---------------------------------------------------------------------------
# entry points: the streams of a token lie SIDE BY SIDE, ``x [..., n·C]``
# (stream i at ``[i·C, (i+1)·C)``): one row a token, whole lane tiles a
# stream, and no relayout between a forward's carry and the kernels' tiles


def _use_kernels(x: jax.Array, hc: HC) -> bool:
    return enabled() and supported(x.shape[0], hc, x.shape[1] // hc.n, x.dtype)


def read(x: jax.Array, phi: jax.Array, alpha: jax.Array, bias: jax.Array,
         hc: HC) -> tuple[jax.Array, Maps]:
    """The streams ``x [..., n·C]`` and one sublayer's map parameters →
    ``u [..., C]`` (what the sublayer's own norm then sees, in ``x``'s
    dtype) and the :class:`Maps` its :func:`write` needs."""
    from crosscoder_tpu import obs

    x2 = x.reshape(-1, x.shape[-1])
    if _use_kernels(x2, hc):
        obs.count("harvest/mhc_kernel_traces")
        u, maps = _read_kernel_call(x2, phi, alpha, bias, hc)
    else:
        obs.count("harvest/mhc_xla_traces")
        u, maps = _read_xla(x2, phi, alpha, bias, hc)
    return u.reshape(x.shape[:-1] + u.shape[-1:]), maps


def write(x: jax.Array, y: jax.Array, maps: Maps, hc: HC) -> jax.Array:
    """``X'[i] = Σ_j M[i, j] X[j] + h_post[i] · y`` on ``x [..., n·C]`` and
    the sublayer's output ``y [..., C]``."""
    x2 = x.reshape(-1, x.shape[-1])
    fn = _write_kernel_call if _use_kernels(x2, hc) else _write_xla
    return fn(x2, y.reshape(-1, y.shape[-1]), maps, hc).reshape(x.shape)


def streams_of(x: jax.Array, n: int) -> list[jax.Array]:
    """The ``n`` streams of ``x [..., n·C]``, each ``[..., C]``."""
    C = x.shape[-1] // n
    return [x[..., i * C:(i + 1) * C] for i in range(n)]


def mean_gain(maps: Maps, lead: tuple[int, ...]) -> jax.Array:
    """``mean_i h_post[i]`` ``[..., 1]``: what multiplies ``y`` in the stream
    MEAN's update (``m' = m + mean(h_post) · y`` when M's columns sum to 1)."""
    return jnp.mean(maps.h_post, axis=-1).reshape(lead + (1,))


def col_err(maps: Maps, n: int) -> jax.Array:
    """``max |colsum(M) − 1|`` over the tokens: Sinkhorn's remainder, by
    which the mean identity holds."""
    M = maps.mix.reshape(-1, n, n)
    return jnp.max(jnp.abs(jnp.sum(M, axis=1) - 1.0))


def head_read(x: jax.Array, phi: jax.Array, alpha: jax.Array, bias: jax.Array,
              rms_eps: float) -> jax.Array:
    """After the last layer: ``Σ_i σ(a z[i] + b[i]) X[i]`` on ``x [..., n·C]``
    with ``z`` as in :func:`read` for ``phi [n·C, n]`` (not on the harvest's
    path: XLA only)."""
    n = phi.shape[1]
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + rms_eps)
    z = r * jnp.dot(xf, phi, precision=jax.lax.Precision.HIGHEST)
    h = jax.nn.sigmoid(alpha * z + bias)
    return sum(h[..., i:i + 1] * s for i, s in enumerate(streams_of(xf, n))).astype(x.dtype)
