"""Round-5 probe: isolate the costs that decide the factored-TopK design.

Verdict item 3 wants topk_pallas step <= relu step at dict 2^15..2^17.
The step is matmul-dominated; TopK only wins if sparsity removes dense
matmuls (decode fwd + df backward) for less than the kernel overhead it
adds. This probe times each candidate building block on the real chip:

- enc:        the [B,nd]x[nd,H] encode matmul (the unavoidable baseline)
- top_k:      jax.lax.top_k(hp, 32)           (the known-slow extractor)
- approx:     jax.lax.approx_max_k at several k'/recall settings, plus
              an exactness-rate estimate vs top_k (how often the true
              top-32 set survives)
- kernel:     the existing Pallas masked topk (bisect+emit)
- gatherW:    jnp.take(W_dec, idx) [B,k] rows + einsum  (factored fwd)
- gatherW_g:  same + backward wrt vals (the df replacement)
- scatterBk:  scatter [B,k] -> [B,H]  (dh / f_dense rebuild cost)
- dense_dec:  f[B,H] @ W_dec          (what factored fwd would replace)
- dense_df:   g[B,nd] @ W_dec^T       (what factored bwd would replace)

Writes artifacts/TOPK_PROBE_r05.json.
"""
from __future__ import annotations
import _bootstrap  # noqa: F401  (repo-root sys.path + cwd shim)

import json
import time

import jax
import jax.numpy as jnp

B, K, ND = 4096, 32, 2 * 2304


def timeit(fn, *args, n=20, warmup=1):
    """Device-time of fn: chain n applications inside ONE jit via a carry
    dependency (per-call dispatch cost would otherwise be timed along
    with every short op)."""
    x0 = args[0]

    @jax.jit
    def chained(*a):
        def body(i, x):
            r = fn(x, *a[1:])
            # consume EVERY element of every output (a partial consume lets
            # XLA slice the op down to one element — measured 875 TFLOP/s
            # "matmuls" before this fix); the reduce adds ~one HBM sweep,
            # reported separately as `one_sweep` for calibration
            bump = sum(
                jnp.sum(leaf.astype(jnp.float32))
                for leaf in jax.tree_util.tree_leaves(r)
            ) * 1e-30
            return x + bump.astype(x.dtype)
        return jax.lax.fori_loop(0, n, body, a[0])

    for _ in range(warmup):
        r = chained(*args)
    float(jax.device_get(r.reshape(-1)[0]).astype(jnp.float32))
    t0 = time.perf_counter()
    r = chained(*args)
    float(jax.device_get(r.reshape(-1)[0]).astype(jnp.float32))
    return 1000 * (time.perf_counter() - t0) / n


def probe(H: int) -> dict:
    out: dict = {"dict_size": H}
    key = jax.random.key(0)
    x = jax.random.normal(jax.random.key(1), (B, ND), jnp.bfloat16)
    W_enc = jax.random.normal(key, (ND, H), jnp.bfloat16) * 0.02
    W_dec = jax.random.normal(jax.random.key(2), (H, ND), jnp.bfloat16) * 0.02
    hp = jax.nn.relu(x @ W_enc)
    g = jax.random.normal(jax.random.key(3), (B, ND), jnp.bfloat16)

    out["enc"] = timeit(jax.jit(lambda x, w: x @ w), x, W_enc)
    out["dense_dec"] = timeit(jax.jit(lambda f, w: f @ w), hp, W_dec)
    out["dense_df"] = timeit(jax.jit(lambda g, w: g @ w.T), g, W_dec)

    out["top_k"] = timeit(jax.jit(lambda h: jax.lax.top_k(h, K)), hp)

    for kp, rt in ((K, 0.95), (2 * K, 0.95), (4 * K, 0.95), (4 * K, 0.99)):
        label = f"approx_k{kp}_r{rt}"
        try:
            out[label] = timeit(
                jax.jit(lambda h: jax.lax.approx_max_k(h, kp, recall_target=rt)),
                hp,
            )
        except Exception as e:
            out[label] = f"ERR {type(e).__name__}"

    # exactness rate: fraction of rows whose true top-K SET is contained in
    # the approx candidates (over a few random draws)
    vals_t, idx_t = jax.jit(lambda h: jax.lax.top_k(h, K))(hp)
    for kp, rt in ((2 * K, 0.95), (4 * K, 0.95), (4 * K, 0.99)):
        try:
            _, idx_a = jax.jit(
                lambda h: jax.lax.approx_max_k(h, kp, recall_target=rt)
            )(hp)
            hit = (idx_t[:, :, None] == idx_a[:, None, :]).any(-1).all(-1)
            out[f"rows_exact_k{kp}_r{rt}"] = float(jnp.mean(hit))
        except Exception:
            pass

    from crosscoder_tpu.ops import topk_pallas

    if topk_pallas.supported(hp, K):
        out["kernel_masked"] = timeit(
            jax.jit(lambda h: topk_pallas.topk(h, K)), hp
        )

    vals, idx = vals_t, idx_t

    def gather_fwd(vals, idx, W):
        w = jnp.take(W, idx, axis=0)                 # [B, k, nd]
        return jnp.einsum("bk,bkd->bd", vals, w)

    out["gatherW"] = timeit(jax.jit(gather_fwd), vals, idx, W_dec)

    # dvals[b,k] = dot(g[b], W[idx[b,k]])
    def gather_dvals2(g, idx, W):
        w = jnp.take(W, idx, axis=0)                 # [B, k, nd]
        return jnp.einsum("bd,bkd->bk", g, w)

    out["gatherW_g"] = timeit(jax.jit(gather_dvals2), g, idx, W_dec)

    def scatter_bk(vals, idx):
        rows = jnp.arange(B)[:, None]
        return jnp.zeros((B, H), vals.dtype).at[rows, idx].set(
            vals, mode="drop", unique_indices=True
        )

    out["scatterBk"] = timeit(jax.jit(scatter_bk), vals, idx)

    # segment-sum style dW_dec: scatter f_dense then dense matmul (current
    # sparse-path bwd) vs pure dense f^T @ g
    f_dense = jax.jit(scatter_bk)(vals, idx)
    out["dense_dWdec"] = timeit(
        jax.jit(lambda f, g: jnp.einsum("bh,bd->hd", f, g,
                                        preferred_element_type=jnp.float32)),
        f_dense, g)

    # one-pass fused reductions over [B,H] for reference (what a bisect
    # sweep costs at the XLA level)
    out["one_sweep"] = timeit(
        jax.jit(lambda h: jnp.sum((h > 0.1).astype(jnp.int32), axis=-1)), hp
    )
    for k_, v in out.items():
        if isinstance(v, float):
            out[k_] = round(v, 3)
    return out


def main():
    res = [probe(H) for H in (2**15, 2**16, 2**17)]
    with open("artifacts/TOPK_PROBE_r05.json", "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res, indent=1))


if __name__ == "__main__":
    main()
