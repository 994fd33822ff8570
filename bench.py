"""Benchmark: crosscoder pipeline throughput on one TPU chip.

Fourteen sections (env ``BENCH_SECTIONS``, default all; progress on
stderr).
Output contract: stdout carries exactly ONE machine-parseable JSON line,
guaranteed last and guaranteed **compact** (≤2 KB: headline, per-section
key numbers, gate booleans) — the driver truncates the line at 2000
chars, so the full per-section detail goes to an artifact file instead
($BENCH_ARTIFACT, default BENCH_DETAIL.json). Stray prints are rerouted
to stderr for the whole run:

- **step**: the bare train step on device-resident batches (round-1's
  headline; BASELINE.json config 1 — dict 2^15, batch 4096, bf16).
- **matrix**: the sparse tier at the training-step level — activation
  {relu, topk dense, topk pallas, topk+sparse_decode, topk+sparse_bwd,
  batchtopk (dense + pallas)} × dict {2^15, 2^16, 2^17} (BASELINE.json
  config 2 is TopK k=32 @ 2^15). Kernel-heavy legs also report a
  fwd/bwd split (``fwd_ms``/``bwd_ms`` of the model loss alone) — the
  sparse backward plane (cfg.sparse_bwd) only changes bwd_ms, so the
  split is the attribution the step-level number can't give.
- **configs**: all five BASELINE.json scale-out configs at the
  train-step level (ref shape / topk / 9B-width / 3-way / multi-layer).
- **e2e**: the pipeline the reference actually runs (reference
  buffer.py:66-122 + trainer.py:41-49): harvest→buffer→train, Gemma-2-2B
  shapes, interleaved incremental refill. Harvest uses REAL-SHAPE random
  weights truncated to the scanned depth (layers 0-13; the stop-at-layer
  harvest never executes layers above the hook, so FLOPs are identical to
  the full model — weights are random because this environment is
  air-gapped, which changes no matmul shapes). Reports steady-state
  acts/sec and the refresh-bubble profile (max vs median step).
- **refill_overlap**: zero-bubble refill engine A/B (docs/SCALING.md
  "Zero-bubble refill") — the e2e leg with ``refill_overlap`` off vs on;
  gates on bubble_frac ≤ 0.10 with no throughput loss.
- **harvest**: the LM-harvest side (the dominant per-step cost outside
  the crosscoder) on a mixed-length synthetic corpus: padded-vs-paged
  runtime A/B — tokens/s over REAL tokens, padding-efficiency %, and the
  paged speedup (docs/SCALING.md "Harvest cost model").
- **quant**: the int8 data-plane quality gates (docs/SCALING.md
  "Quantized data plane"): roundtrip per-row MSE on a Gemma-shaped
  heavy-tailed probe, store-byte ratio, and the quantized grad
  all-reduce's one-shot + error-feedback accuracy on the local mesh.
- **obs**: the telemetry plane's cost gates (docs/OBSERVABILITY.md):
  SpanTracer spans/s, per-step overhead of ``cfg.obs`` on vs off at the
  reference shape (gate: <1%), and the ``perf/refill_bubble_frac`` a
  standard training leg emits.
- **dash**: dashboard generation at the reference's recorded workload
  (128 seqs × 3 features, minibatch 4 — BASELINE.md: ≈19 s on A100).
- **elastic**: the recovery SLO of elastic membership
  (docs/resilience.md "Elastic membership") — the 2-process CPU
  preemption drill (``resilience/elastic_drill.py``): chaos ``die@7``
  kills one host mid-run, the survivor re-meshes and
  restore-with-respecs; reports ``remesh_ms`` (detect → resumed wall
  time) and the bitwise-equal recovery gate.
- **serve**: the online model-diffing request path (docs/SERVING.md) —
  per-request p50/p99/max latency at batch 1/8/64 through the
  continuous-batched harvest→encode loop, saturated req/s, the
  p99 ≤ 3×p50 SLO gate at batch 8, and the zero-compiles-after-warmup
  assertion (AOT bucket reuse).

Headline metric = e2e acts/sec/chip. ``vs_baseline`` divides by an
analytic single-A100 torch estimate, documented here so it stays fixed:
train step ≈ 3× forward FLOPs ⇒ 1.81 GFLOP/row at dict 2^15 ⇒ 77k rows/s
at 45% of A100 bf16 peak (312 TFLOP/s); harvest = 2 models × 2·P FLOP/row
over the layers below the hook (P = params in layers 0-13 of Gemma-2-2B
≈ 1.09 G ⇒ 4.36 GFLOP/row — a resid_pre hook at block 14 executes blocks
0-13) at the same 45% ⇒ 32.2k rows/s; serial e2e = 1/(1/77k + 1/32.2k)
≈ 22.7k rows/s. (North star: ≥8× via 8-chip DP at
per-chip parity — BASELINE.json.)

Env knobs (debug/CI only): BENCH_SECTIONS, BENCH_DICT, BENCH_BATCH,
BENCH_STEPS, BENCH_CPU=1, BENCH_MASTER_DTYPE, BENCH_QUANT=1 (e2e with
the int8 replay store), QUANT_RELMSE_BOUND, BENCH_SERVE_REPS,
BENCH_TUNE_STEPS (calibration window for the tune leg),
BENCH_ARTIFACT (detail file path).
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

A100_PEAK = 312e12
A100_UTIL = 0.45
BASELINE_A100_STEP = 77_000.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _sync(x) -> float:
    # wait for the device and hand back the scalar the caller wants anyway
    return float(jax.device_get(x))


def _harvest_flops_per_row(lm_cfg, n_layers_scanned: int, n_models: int) -> float:
    """2·params FLOP per token per scanned layer, per model."""
    d, hd = lm_cfg.d_model, lm_cfg.head_dim
    per_layer = (
        d * lm_cfg.n_heads * hd            # W_q
        + 2 * d * lm_cfg.n_kv_heads * hd   # W_k, W_v
        + lm_cfg.n_heads * hd * d          # W_o
        + 3 * d * lm_cfg.d_ff              # gate/up/down
    )
    return 2.0 * per_layer * n_layers_scanned * n_models


def _make_cfg(**overrides):
    from crosscoder_tpu.config import CrossCoderConfig

    base = dict(
        d_in=int(os.environ.get("BENCH_DIN", 2304)),
        dict_size=int(os.environ.get("BENCH_DICT", 2**15)),
        n_models=2,
        batch_size=int(os.environ.get("BENCH_BATCH", 4096)),
        enc_dtype="bf16",
        # bf16 masters+moments = the reference's exact dtype regime
        # (train.py:5: all-bf16 params and torch-Adam state); fp32 masters
        # are this framework's quality-upgrade default but a different
        # workload than the A100 baseline estimate.
        master_dtype=os.environ.get("BENCH_MASTER_DTYPE", "bf16"),
        log_backend="null",
    )
    base.update(overrides)
    return CrossCoderConfig(**base)


def bench_step(cfg, n_steps: int, warmup: int = 3) -> dict:
    """Time the donated jitted train step on device-resident batches."""
    from crosscoder_tpu.parallel import mesh as mesh_lib
    from crosscoder_tpu.train import schedules
    from crosscoder_tpu.train.state import init_train_state, make_optimizer
    from crosscoder_tpu.train.trainer import make_train_step
    from jax.sharding import NamedSharding, PartitionSpec

    n_dev = len(jax.devices())
    mesh = mesh_lib.make_mesh(data_axis_size=n_dev, model_axis_size=1)
    tx = make_optimizer(cfg, schedules.lr_schedule(cfg))
    state = init_train_state(jax.random.key(cfg.seed), cfg, tx)
    shardings = mesh_lib.state_shardings(mesh, state)
    state = jax.device_put(state, shardings)
    # production mix: metric-only reductions (l0/EV) are gated to log_every
    # steps (1% at the reference cadence), so the bare step is the
    # throughput-defining variant.
    # AuxK amortization (cfg.aux_every > 1) and dead-mask caching
    # (cfg.aux_mask_every != 1): alternate the compiled variants exactly as
    # the Trainer does, so the timed mix IS the production step cost.
    track_fired = cfg.aux_k > 0 or cfg.resample_every > 0
    cached_mask = track_fired and cfg.aux_mask_every != 1
    variants: dict = {}

    def key_of(i: int) -> tuple[bool, bool]:
        aux_on = cfg.aux_k == 0 or cfg.aux_every <= 1 or i % cfg.aux_every == 0
        refresh = not cached_mask or i % cfg.aux_mask_cadence == 0
        return (aux_on, refresh)

    def pick(i: int):
        key = key_of(i)
        fn = variants.get(key)
        if fn is None:
            fn = variants[key] = make_train_step(
                cfg, mesh, tx, shardings, with_metrics=False,
                aux_on=key[0], mask_refresh=key[1],
            )
        return fn

    batch_sh = mesh_lib.batch_sharding(mesh)
    key = jax.random.key(0)
    batches = [
        jax.device_put(
            jax.random.normal(
                jax.random.fold_in(key, i),
                (cfg.batch_size, cfg.n_sources, cfg.d_in),
                dtype=jnp.bfloat16,
            ),
            batch_sh,
        )
        for i in range(4)
    ]
    # production serve path: raw bf16 rows + on-device per-source norm scale
    # (length tracks cfg.n_sources; 0.26 ≈ the Gemma-2-2B calibration
    # factors, BASELINE.md)
    scale = jax.device_put(
        jnp.full((cfg.n_sources,), 0.26, jnp.float32),
        NamedSharding(mesh, PartitionSpec()),
    )

    for i in range(warmup):
        state, metrics = pick(i)(state, batches[i % 4], scale)
    # any variant the timed window alternates onto must already be
    # compiled, or its first hit would time a compile, not a step
    warmed = {key_of(i) for i in range(warmup)}
    for i in range(n_steps):
        if key_of(i) not in warmed:
            warmed.add(key_of(i))
            state, metrics = pick(i)(state, batches[i % 4], scale)
    _sync(metrics["loss"])

    t0 = time.perf_counter()
    for i in range(n_steps):
        state, metrics = pick(i)(state, batches[i % 4], scale)
    loss = _sync(metrics["loss"])   # one sync, at the end of the window
    dt = time.perf_counter() - t0
    del state, batches
    return {
        "step_ms": round(1000 * dt / n_steps, 2),
        "acts_per_sec_chip": round(cfg.batch_size * n_steps / dt / n_dev, 1),
        "loss_finite": bool(jnp.isfinite(loss)),
        "n_devices": n_dev,
    }


@contextlib.contextmanager
def _env(overrides: dict):
    """Set env vars for one bench leg (the kernel opt-in gates —
    CROSSCODER_SPARSE_GRAD_PALLAS etc. are read at trace time), restoring
    the previous values on exit so legs can't leak into each other."""
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def bench_fwd_bwd(cfg, n_steps: int, warmup: int = 2) -> dict:
    """Forward/backward split of the MODEL cost: the jitted bare loss
    (``training_loss``, no optimizer/collectives — so fwd+bwd < step_ms)
    and its grad, timed separately; ``bwd_ms`` is the difference. This is
    the attribution the step-level number can't give: the sparse backward
    plane (cfg.sparse_bwd, docs/SCALING.md "Sparse backward plane")
    replaces backward matmuls only, so its whole win must land in
    ``bwd_ms`` while ``fwd_ms`` stays put."""
    from crosscoder_tpu.models import crosscoder as cc

    params = cc.init_params(jax.random.key(cfg.seed), cfg)
    x = jax.random.normal(
        jax.random.key(1), (cfg.batch_size, cfg.n_sources, cfg.d_in),
        dtype=jnp.float32,
    )
    l1 = float(cfg.l1_coeff)

    def loss(p, xb):
        return cc.training_loss(p, xb, l1, cfg, with_metrics=False)[0]

    out = {}
    for name, fn in (("fwd_ms", jax.jit(loss)),
                     ("fwdbwd_ms", jax.jit(jax.grad(loss)))):
        r = None
        for _ in range(warmup):
            r = fn(params, x)
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            r = fn(params, x)
        jax.block_until_ready(r)
        out[name] = round(1000 * (time.perf_counter() - t0) / n_steps, 2)
    out["bwd_ms"] = round(out["fwdbwd_ms"] - out["fwd_ms"], 2)
    return out


def section_step() -> dict:
    cfg = _make_cfg()
    out = bench_step(cfg, int(os.environ.get("BENCH_STEPS", 50)))
    out["workload"] = (
        f"d_in {cfg.d_in}, dict {cfg.dict_size}, batch {cfg.batch_size}, "
        f"relu, bf16 compute, {cfg.master_dtype} masters"
    )
    out["vs_a100_step"] = round(out["acts_per_sec_chip"] / BASELINE_A100_STEP, 3)
    log(f"[step] {out}")
    return out


def _kernel_parity(dict_size: int) -> dict:
    """On-DEVICE parity asserts (VERDICT round-2 weak #4: CI runs the
    Pallas interpreter; a Mosaic miscompile producing plausible garbage
    would pass ``loss_finite``). Executed on the live backend right before
    the timed variants:

    - pallas TopK output == dense ``lax.top_k`` scatter, bit-exact;
    - sparse-decode loss == dense-decode loss (same math re-associated, so
      tolerance is a few fp32 ulps, max-abs-diff recorded).
    """
    import numpy as np

    from crosscoder_tpu.models import crosscoder as cc
    from crosscoder_tpu.ops import activations as act_ops
    from crosscoder_tpu.ops import topk_pallas

    k = 32
    h = jax.random.normal(jax.random.key(7), (256, dict_size), jnp.bfloat16)
    if not topk_pallas.supported(h, k):
        # unsupported width ≠ miscompile: report the skip, not a failure
        return {"dict_size": dict_size,
                "skipped": "kernel unsupported at this width"}
    out_p = jax.jit(lambda x: topk_pallas.topk(x, k))(h)
    out_d = jax.jit(lambda x: act_ops._topk_dense(x, k))(h)
    topk_ok = bool(jax.device_get(jax.jit(lambda a, b: (a == b).all())(out_p, out_d)))

    cfg_d = _make_cfg(dict_size=dict_size, activation="topk", topk_k=k,
                      l1_coeff=0.0, batch_size=256)
    cfg_s = cfg_d.replace(sparse_decode=True)
    params = cc.init_params(jax.random.key(3), cfg_d)
    x = jax.random.normal(jax.random.key(8), (256, cfg_d.n_sources, cfg_d.d_in),
                          jnp.bfloat16)
    l_d = jax.jit(lambda p, b: cc.get_losses(p, b, cfg_d).l2_loss)(params, x)
    l_s = jax.jit(lambda p, b: cc.get_losses(p, b, cfg_s).l2_loss)(params, x)
    l_d, l_s = float(jax.device_get(l_d)), float(jax.device_get(l_s))
    denom = max(abs(l_d), 1e-30)
    sparse_rel = abs(l_s - l_d) / denom
    entry = {
        "dict_size": dict_size,
        "topk_pallas_bitexact": topk_ok,
        "sparse_decode_l2_rel_diff": float(np.format_float_scientific(
            sparse_rel, precision=3, unique=False)),
        "parity_ok": bool(topk_ok and sparse_rel < 1e-4),
    }
    log(f"[parity] {entry}")
    return entry


def _encoder_hbm_bytes(cfg) -> dict:
    """Predicted step HBM traffic, fused vs dense encoder — the PR 5
    compile-span HLO cost analysis ("bytes accessed" of the compiled
    bare model loss+grad) applied to the A/B the fused megakernel
    claims: same FLOPs, [B, dict] pre-acts never round-tripping HBM.
    Reported beside wall time so the bytes win is first-class in BENCH
    output, not an inference from step_ms."""
    from crosscoder_tpu.models import crosscoder as cc

    def bytes_of(c) -> float:
        # abstract operands only: .lower() accepts ShapeDtypeStruct
        # pytrees, and a real 2^17-dict param set would add GBs of HBM
        # pressure right after the timed leg ran
        params = jax.eval_shape(lambda key: cc.init_params(key, c),
                                jax.random.key(0))
        x = jax.ShapeDtypeStruct(
            (c.batch_size, c.n_sources, c.d_in), jnp.bfloat16)

        def loss(p, xb):
            return cc.training_loss(p, xb, 0.0, c, with_metrics=False)[0]

        from crosscoder_tpu.utils import compile_cache

        compiled = jax.jit(jax.grad(loss)).lower(params, x).compile()
        return compile_cache.extract_cost(compiled)["bytes_accessed"]

    fused_b = bytes_of(cfg)
    dense_b = bytes_of(cfg.replace(fused_encoder="off",
                                   quant_encoder=False))
    out = {"hbm_bytes_fused": fused_b, "hbm_bytes_dense": dense_b}
    if dense_b > 0:
        out["hbm_bytes_ratio"] = round(fused_b / dense_b, 4)
    return out


def section_matrix() -> list[dict]:
    """The sparse tier, at the training-step level (VERDICT round-1: the
    in-code perf claims were unverifiable; BASELINE config 2 had no
    measured number). Includes the full activation zoo (VERDICT round-2
    weak #6: jumprelu/batchtopk were implemented but never measured)."""
    from crosscoder_tpu.ops import activations as act_ops

    on_tpu = jax.default_backend() == "tpu"
    # (label, cfg overrides, topk impl, env for the leg). A non-empty env
    # is a kernel opt-in gate (ships conservative-default, see
    # ops/dispatch.py / topk_pallas.batchtopk_kernel_enabled) — those
    # legs are TPU-only: timing the interpret path or a silent dense
    # fallback under a kernel label would be a lie.
    variants = [
        ("relu", dict(activation="relu"), "auto", {}),
        ("topk_dense", dict(activation="topk", topk_k=32, l1_coeff=0.0),
         "dense", {}),
        ("topk_pallas", dict(activation="topk", topk_k=32, l1_coeff=0.0),
         "pallas", {}),
        ("topk_sparse_decode",
         dict(activation="topk", topk_k=32, l1_coeff=0.0, sparse_decode=True),
         "auto", {}),
        # the sparse backward plane: identical forward to topk_pallas +
        # factored tier, backward through ops/row_gather.py where its
        # kernels are live (one TPU device) — step_ms vs topk_pallas is the
        # headline A/B, bwd_ms vs topk_pallas's carries the attribution
        ("topk_sparse_bwd",
         dict(activation="topk", topk_k=32, l1_coeff=0.0, sparse_bwd="on",
              factored_decode="on"),
         "pallas", {}),
        # the fused encoder→TopK megakernel (PR "melt the dense floor"):
        # identical math to topk_sparse_bwd with the encode+TopK+sparsify
        # chain fused so [B, dict] pre-acts never hit HBM — step_ms vs
        # topk_sparse_bwd and vs relu is the headline (ROADMAP item-2
        # target: TopK <= 1.0x ReLU at dict 2^16/2^17); the
        # encoder_hbm_* fields carry the HLO cost-analysis bytes A/B
        ("topk_fused",
         dict(activation="topk", topk_k=32, l1_coeff=0.0, sparse_bwd="on",
              factored_decode="on", fused_encoder="on"),
         "pallas", {"CROSSCODER_SPARSE_GRAD_PALLAS": "1",
                    "CROSSCODER_FUSED_TOPK_PALLAS": "1"}),
        # + the int8 block-scaled in-kernel encoder matmul (the
        # --quant-encoder quality gate rides this leg: selection
        # agreement vs the exact fused leg)
        ("topk_fused_int8",
         dict(activation="topk", topk_k=32, l1_coeff=0.0, sparse_bwd="on",
              factored_decode="on", fused_encoder="on", quant_encoder=True),
         "pallas", {"CROSSCODER_SPARSE_GRAD_PALLAS": "1",
                    "CROSSCODER_FUSED_TOPK_PALLAS": "1"}),
        ("batchtopk", dict(activation="batchtopk", topk_k=32, l1_coeff=0.0),
         "auto", {}),
        # BatchTopK through the chunked Pallas global-threshold kernels
        # (bit-identical mask; closes the "BatchTopK unkerneled at wide
        # dicts" residue)
        ("batchtopk_pallas",
         dict(activation="batchtopk", topk_k=32, l1_coeff=0.0),
         "auto", {"CROSSCODER_BATCHTOPK_PALLAS": "1"}),
        # fused BatchTopK: global bisection + emit recomputed over
        # streamed encoder tiles (FLOPs ~3x the single matmul, HBM bytes
        # ~1 masked write instead of ~7 [B, dict] round-trips)
        ("batchtopk_fused",
         dict(activation="batchtopk", topk_k=32, l1_coeff=0.0,
              fused_encoder="on"),
         "auto", {"CROSSCODER_FUSED_TOPK_PALLAS": "1"}),
        ("jumprelu", dict(activation="jumprelu", l1_coeff=0.0), "auto", {}),
        # AuxK step cost: aux_dead_steps=1 keeps the dead set non-empty so
        # aux-on steps include the full aux path (approx_max_k ranking
        # over the masked [B,H] pre-acts, dense-matmul aux decode, fired
        # scatter) — the worst case. `topk_auxk` is the production
        # recommendation (aux_every=8 amortization; quality within noise
        # of per-step, artifacts/ACT_QUALITY_r05.json); `_perstep` keeps
        # the aux loss on EVERY step (the Gao recipe, the BENCH_r05
        # 391 ms number) but caches the dead mask at log_every cadence
        # (aux_mask_every=0): reuse steps drop the tracker compare and the
        # serial dependency on the previous step's fired scatter.
        # `_perstep_exact` is the fully unamortized per-step-mask recipe
        # for comparison.
        ("topk_auxk",
         dict(activation="topk", topk_k=32, l1_coeff=0.0, aux_k=256,
              aux_dead_steps=1, aux_every=8),
         "auto", {}),
        ("topk_auxk_perstep",
         dict(activation="topk", topk_k=32, l1_coeff=0.0, aux_k=256,
              aux_dead_steps=1, aux_mask_every=0),
         "auto", {}),
        ("topk_auxk_perstep_exact",
         dict(activation="topk", topk_k=32, l1_coeff=0.0, aux_k=256,
              aux_dead_steps=1),
         "auto", {}),
        # sparse backward under the per-step AuxK recipe: the main tier
        # runs the (h, W_dec)-scoped sparse variant, the aux term reuses
        # the scatter plane when use_sparse_aux's gates pass (at B=4096,
        # aux_k=256 the 1M-pair aux list exceeds the kernel's VMEM cap,
        # so the aux VJP stays dense — the partial win of the
        # "re-measure topk_auxk_perstep" satellite; BENCH_r05: 391.43 ms)
        ("topk_auxk_perstep_sparse_bwd",
         dict(activation="topk", topk_k=32, l1_coeff=0.0, aux_k=256,
              aux_dead_steps=1, aux_mask_every=0, sparse_bwd="on",
              factored_decode="on"),
         "pallas", {"CROSSCODER_SPARSE_GRAD_PALLAS": "1"}),
    ]
    # legs that also report the fwd/bwd model-loss split (compiles two
    # extra programs per entry, so only where the split answers a
    # question: the sparse-backward A/B pair and the dense floor)
    split_fwd_bwd = {"topk_pallas", "topk_sparse_bwd", "jumprelu",
                     "batchtopk", "batchtopk_pallas", "topk_fused",
                     "topk_fused_int8", "batchtopk_fused"}
    steps = int(os.environ.get("BENCH_MATRIX_STEPS", 16))
    dicts = tuple(
        int(x) for x in os.environ.get(
            "BENCH_MATRIX_DICTS", f"{2**15},{2**16},{2**17}"
        ).split(",")
    )
    out = []
    for dict_size in dicts:
        if on_tpu:
            try:
                out.append(_kernel_parity(dict_size))
            except Exception as e:
                out.append({"dict_size": dict_size, "parity_ok": False,
                            "error": f"{type(e).__name__}: {str(e)[:200]}"})
        for label, overrides, impl, env in variants:
            if env and not on_tpu:
                continue               # kernel opt-in legs are TPU-only
            cfg = _make_cfg(dict_size=dict_size, **overrides)
            if impl == "pallas":
                from crosscoder_tpu.ops import topk_pallas

                if not on_tpu:
                    continue           # interpret mode is not a benchmark
                probe = jax.ShapeDtypeStruct((1, dict_size), jnp.bfloat16)
                if not topk_pallas.supported(probe, 32):
                    # custom BENCH_MATRIX_DICTS width outside both kernel
                    # variants: don't silently time the dense fallback
                    # under the pallas label
                    out.append({"variant": label, "dict_size": dict_size,
                                "skipped": "kernel unsupported at this width"})
                    continue
            if cfg.sparse_bwd == "on":
                # sparse_bwd="on" with an unsupported scatter shape falls
                # back to the XLA scatter — sparse math but the measured-
                # slow path; don't time it under the sparse_bwd label
                from crosscoder_tpu.models import crosscoder as cc
                from crosscoder_tpu.ops import topk_pallas

                if not (topk_pallas.sparsify_supported(dict_size, cfg.topk_k)
                        and cc.use_sparse_bwd(cfg.replace(sparse_bwd="auto"),
                                              cfg.batch_size)):
                    out.append({"variant": label, "dict_size": dict_size,
                                "skipped": "scatter kernel unsupported at "
                                           "this shape"})
                    continue
            if label == "batchtopk_pallas":
                from crosscoder_tpu.ops import topk_pallas

                probe = jax.ShapeDtypeStruct(
                    (cfg.batch_size, dict_size), jnp.bfloat16)
                if not topk_pallas.batchtopk_supported(probe, cfg.topk_k):
                    out.append({"variant": label, "dict_size": dict_size,
                                "skipped": "batchtopk kernel unsupported at "
                                           "this width"})
                    continue
            if cfg.fused_encoder == "on":
                # forced-fused legs must actually time the megakernel,
                # not its dense fallback
                from crosscoder_tpu.ops import fused_encoder_topk as fek

                qb = cfg.quant_block if cfg.quant_encoder else 0
                if not fek.supported(cfg.batch_size,
                                     cfg.n_sources * cfg.d_in, dict_size,
                                     cfg.topk_k, jnp.bfloat16, qb):
                    out.append({"variant": label, "dict_size": dict_size,
                                "skipped": "fused kernel unsupported at "
                                           "this shape"})
                    continue
            act_ops.set_topk_impl(impl)
            try:
                with _env(env):
                    r = bench_step(cfg, steps, warmup=2)
                    entry = {"variant": label, "dict_size": dict_size, **r}
                    if label in split_fwd_bwd:
                        entry.update(bench_fwd_bwd(cfg, steps))
                    if cfg.fused_encoder == "on":
                        try:
                            entry.update(_encoder_hbm_bytes(cfg))
                        except Exception as e:   # cost analysis is best-effort
                            entry["hbm_bytes_error"] = (
                                f"{type(e).__name__}: {str(e)[:120]}")
            except Exception as e:     # one OOM must not kill the bench
                entry = {"variant": label, "dict_size": dict_size,
                         "error": f"{type(e).__name__}: {str(e)[:200]}"}
            finally:
                act_ops.set_topk_impl("auto")
            log(f"[matrix] {entry}")
            out.append(entry)
    return out


def section_configs() -> list[dict]:
    """All five BASELINE.json scale-out configs at the train-step level —
    each config's acts/s/chip on one chip (the 8× path is per-chip parity
    × DP, so the per-chip number is the comparable unit):

    1. 2-model L13, dict 2^14 (the reference's exact trained shape);
    2. dict 2^15 + TopK(k=32) via the Pallas kernel;
    3. Gemma-2-9B width (d_in 3584), dict 2^16;
    4. 3-way diff (n_models=3);
    5. multi-layer {6,13,20} jointly (n_sources = 2×3 = 6).
    """
    steps = int(os.environ.get("BENCH_CONFIG_STEPS", 12))
    hp3 = ("blocks.6.hook_resid_pre", "blocks.13.hook_resid_pre",
           "blocks.20.hook_resid_pre")
    configs = [
        ("1_ref_shape", dict(d_in=2304, dict_size=2**14)),
        ("2_topk_pallas", dict(d_in=2304, dict_size=2**15, activation="topk",
                               topk_k=32, l1_coeff=0.0)),
        ("3_9b_width", dict(d_in=3584, dict_size=2**16)),
        ("4_three_way", dict(d_in=2304, dict_size=2**14, n_models=3)),
        ("5_multilayer", dict(d_in=2304, dict_size=2**14, hook_points=hp3)),
    ]
    out = []
    for label, overrides in configs:
        try:
            r = bench_step(_make_cfg(**overrides), steps, warmup=2)
            entry = {"config": label, **r}
        except Exception as e:
            entry = {"config": label,
                     "error": f"{type(e).__name__}: {str(e)[:200]}"}
        log(f"[configs] {entry}")
        out.append(entry)
    return out


def section_e2e() -> dict:
    """harvest→buffer→train on one chip — the number the reference pipeline
    actually bounds (harvest ≈ 2.5× the train step's FLOPs per row)."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from crosscoder_tpu.data.buffer import make_buffer
    from crosscoder_tpu.models import lm
    from crosscoder_tpu.parallel import mesh as mesh_lib
    from crosscoder_tpu.train.trainer import Trainer

    overrides = {}
    e2e_act = os.environ.get("BENCH_E2E_ACTIVATION", "")
    if e2e_act == "topk":              # BASELINE config 2's e2e number
        overrides = dict(activation="topk", topk_k=32, l1_coeff=0.0)
    elif e2e_act:
        # other activations would need their own loss knobs — refuse
        # rather than silently benching a mislabeled objective
        raise ValueError(f"BENCH_E2E_ACTIVATION supports 'topk', got {e2e_act!r}")

    tiny = os.environ.get("BENCH_TINY") == "1"    # CI/debug only
    if tiny:
        hook_layer, full = 2, lm.LMConfig.tiny()
        lm_cfg = full
        cfg = _make_cfg(
            d_in=lm_cfg.d_model, dict_size=256, batch_size=256, buffer_mult=16,
            model_batch_size=4, norm_calib_batches=2, seq_len=17,
            hook_point="blocks.2.hook_resid_pre",
            num_tokens=10**12, save_every=10**9, prefetch=True,
            **overrides,
        )
    else:
        hook_layer = 14
        full = lm.LMConfig.gemma2_2b()
        # a resid_pre hook at block L executes blocks 0..L-1 and captures at
        # the virtual layer L (lm._forward_impl n_scan), so only L layers of
        # params are ever touched; dropping the rest changes no executed op,
        # saves ~7.5 GB HBM
        lm_cfg = full.replace(n_layers=hook_layer)
        cfg = _make_cfg(
            batch_size=4096, buffer_mult=32, model_batch_size=4,
            norm_calib_batches=8, seq_len=1024,
            hook_point=f"blocks.{hook_layer}.hook_resid_pre",
            num_tokens=10**12, save_every=10**9, prefetch=True,
            # 0.5 = reference-parity harvest:serve; lower trades data
            # freshness for harvest FLOPs (see cfg.refill_frac)
            refill_frac=float(os.environ.get("BENCH_REFILL_FRAC", 0.5)),
            **overrides,
        )
    n_dev = len(jax.devices())
    mesh = mesh_lib.make_mesh(data_axis_size=n_dev, model_axis_size=1)

    shape_tag = "tiny" if tiny else "gemma-2-2b"
    log(f"[e2e] initializing 2× {shape_tag}-shaped params ...")
    params = [lm.init_params(jax.random.key(i), lm_cfg) for i in (0, 1)]
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, lm_cfg.vocab_size, size=(2048, cfg.seq_len),
                          dtype=np.int32)

    # store placement: HBM by default on a single chip — zero host<->device
    # row traffic. BENCH_BUFFER=host measures the host-RAM path instead.
    buffer_device = os.environ.get("BENCH_BUFFER", "hbm")
    cfg = cfg.replace(buffer_device=buffer_device)
    # BENCH_QUANT=1: the block-scaled int8 store (cfg.quant_buffer) — the
    # acceptance A/B is this run vs the default at equal buffer_mult
    if os.environ.get("BENCH_QUANT") == "1":
        block = 256 if cfg.d_in % 256 == 0 else 16
        cfg = cfg.replace(quant_buffer=True, quant_block=block)
    t0 = time.perf_counter()
    buffer = make_buffer(
        cfg, lm_cfg, params, tokens,
        batch_sharding=NamedSharding(mesh, P("data", None)),
    )
    fill_s = time.perf_counter() - t0
    log(f"[e2e] calibration + first fill ({buffer.buffer_size} rows): {fill_s:.1f}s")

    trainer = Trainer(cfg, buffer, mesh=mesh)
    # warmup: compile both step variants + the serve path
    m = trainer.step()
    _sync(m["loss"])
    m = trainer.step(full_metrics=False)
    _sync(m["loss"])

    # phase A — steady-state throughput: enqueue, sync once at the end
    n_steps = int(os.environ.get("BENCH_E2E_STEPS", 40))
    t0 = time.perf_counter()
    for _ in range(n_steps):
        m = trainer.step(full_metrics=False)
    loss = _sync(m["loss"])
    dt = time.perf_counter() - t0

    # phase B — per-step profile (synced every step; the refresh bubble
    # shows up as max − median)
    times = []
    for _ in range(16):
        t1 = time.perf_counter()
        m = trainer.step(full_metrics=False)
        _sync(m["loss"])
        times.append(1000 * (time.perf_counter() - t1))
    trainer.close()
    times_sorted = sorted(times)
    median_ms = times_sorted[len(times) // 2]

    harvest_flops = _harvest_flops_per_row(full, hook_layer, cfg.n_models)
    a100_harvest = A100_PEAK * A100_UTIL / harvest_flops
    a100_e2e = 1.0 / (1.0 / BASELINE_A100_STEP + 1.0 / a100_harvest)
    acts = cfg.batch_size * n_steps / dt / n_dev
    out = {
        "acts_per_sec_chip": round(acts, 1),
        "vs_a100_e2e": round(acts / a100_e2e, 3),
        "a100_e2e_estimate": round(a100_e2e, 1),
        "harvest_gflop_per_row": round(harvest_flops / 1e9, 2),
        "first_fill_s": round(fill_s, 1),
        "step_ms_median": round(median_ms, 2),
        "step_ms_max": round(max(times), 2),
        "refresh_bubble_ms": round(max(times) - median_ms, 2),
        "n_steps_measured": n_steps,
        "loss_finite": bool(jnp.isfinite(loss)),
        "buffer_device": buffer_device,
        "quant_buffer": cfg.quant_buffer,
        "store_mbytes": round(buffer.store_nbytes() / 2**20, 1),
        "refill_frac": cfg.refill_frac,
        "workload": (
            f"{shape_tag} pair → blocks.{hook_layer} harvest → {buffer_device} "
            f"buffer(mult {cfg.buffer_mult}) → train dict {cfg.dict_size}, "
            f"batch {cfg.batch_size}"
        ),
    }
    log(f"[e2e] {out}")
    return out


def section_refill_overlap() -> dict:
    """Zero-bubble refill engine A/B (docs/SCALING.md "Zero-bubble
    refill"): the ``e2e`` harvest→buffer→train leg run with
    ``refill_overlap`` off vs on, at the library's harvest quantum
    (``SegmentedHarvest.SEG_LAYERS``). Per leg: the measured refill
    bubble fraction (obs ``refill_wait`` span total / wall — exactly what
    ``perf/refill_bubble_frac`` logs), the max/median step ratio (the
    refresh spike), and acts/s/chip. Gate (ISSUE 14 acceptance): with
    overlap ON, bubble_frac <= 0.10 AND acts/s no worse than overlap-off."""
    import tempfile

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from crosscoder_tpu.data.buffer import make_buffer
    from crosscoder_tpu.models import lm
    from crosscoder_tpu.parallel import mesh as mesh_lib
    from crosscoder_tpu.train.trainer import Trainer

    tiny = os.environ.get("BENCH_TINY") == "1"    # CI/debug only
    if tiny:
        lm_cfg = lm.LMConfig.tiny()
        # dict_size is deliberately large relative to the tiny LM: the leg
        # needs the train step to dominate harvest compute per cycle, or
        # there is no window to hide the refill in (on real TPUs the e2e
        # config is train-dominated; see docs/SCALING.md cost model)
        base = dict(
            d_in=lm_cfg.d_model, dict_size=4096, batch_size=256,
            buffer_mult=16, model_batch_size=4, norm_calib_batches=2,
            seq_len=17, hook_point="blocks.2.hook_resid_pre",
        )
    else:
        hook_layer = 14
        # only the executed blocks' params, as in section_e2e
        lm_cfg = lm.LMConfig.gemma2_2b().replace(n_layers=hook_layer)
        base = dict(
            batch_size=4096, buffer_mult=32, model_batch_size=4,
            norm_calib_batches=8, seq_len=1024,
            hook_point=f"blocks.{hook_layer}.hook_resid_pre",
        )
    n_dev = len(jax.devices())
    mesh = mesh_lib.make_mesh(data_axis_size=n_dev, model_axis_size=1)
    params = [lm.init_params(jax.random.key(i), lm_cfg) for i in (0, 1)]
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, lm_cfg.vocab_size,
                          size=(2048, base["seq_len"]), dtype=np.int32)

    n_steps = int(os.environ.get("BENCH_OVERLAP_STEPS", 48 if tiny else 32))
    out: dict = {}
    for ov in ("off", "on"):
        cfg = _make_cfg(
            **base, num_tokens=10**12, save_every=10**9,
            prefetch=True, obs="on", refill_overlap=ov,
            checkpoint_dir=tempfile.mkdtemp(),
        )
        buffer = make_buffer(
            cfg, lm_cfg, params, tokens,
            batch_sharding=NamedSharding(mesh, P("data", None)),
        )
        trainer = Trainer(cfg, buffer, mesh=mesh)
        m = trainer.step()            # compile both variants
        _sync(m["loss"])
        m = trainer.step(full_metrics=False)
        _sync(m["loss"])
        trainer._obs.tracer.take_interval()     # reset the span totals
        # per-step sync on every step of both arms: its cost
        # cancels in the A/B, and per-step times expose the
        # refresh spike as max - median
        times = []
        t0 = time.perf_counter()
        for _ in range(n_steps):
            t1 = time.perf_counter()
            m = trainer.step(full_metrics=False)
            _sync(m["loss"])
            times.append(1000 * (time.perf_counter() - t1))
        wall = time.perf_counter() - t0
        blocked = trainer._obs.tracer.take_interval().get(
            "refill_wait", (0.0, 0))[0]
        trainer.close()
        median_ms = sorted(times)[len(times) // 2]
        leg = {
            "bubble_frac": round(min(1.0, blocked / wall), 4),
            "acts_per_sec_chip": round(
                cfg.batch_size * n_steps / wall / n_dev, 1),
            "step_ms_median": round(median_ms, 2),
            "step_ms_max": round(max(times), 2),
            "max_over_median": round(max(times) / median_ms, 2),
        }
        log(f"[refill_overlap] overlap={ov}: {leg}")
        out[ov] = leg
    out["n_steps_measured"] = n_steps
    out["gate_ok"] = bool(
        out["on"]["bubble_frac"] <= 0.10
        and out["on"]["acts_per_sec_chip"] >= out["off"]["acts_per_sec_chip"])
    log(f"[refill_overlap] gate_ok={out['gate_ok']}")
    return out


def section_harvest() -> dict:
    """The LM-harvest side on a mixed-length synthetic corpus — the
    dominant per-step cost outside the crosscoder, invisible in every
    BENCH_*.json before this section. A/B of the padded forward
    (run_with_cache_multi: every document padded to seq_len) vs the paged
    runtime (run_with_cache_multi_paged: documents packed into a dense
    token plane, per-document ragged attention — docs/SCALING.md "Harvest
    cost model"). Tokens/s counts REAL tokens for both arms, so the
    speedup is exactly the padding waste reclaimed."""
    import numpy as np

    from crosscoder_tpu.data import paging
    from crosscoder_tpu.models import lm

    tiny = os.environ.get("BENCH_TINY") == "1"    # CI/debug only
    if tiny:
        lm_cfg = lm.LMConfig.tiny()
        S, n_docs, reps, page = 16, 16, 2, 8
        hook = f"blocks.{lm_cfg.n_layers}.hook_resid_pre"
    else:
        # mid shape in the production FLOP regime — attention ~4% of the
        # per-token cost (Gemma-2-2B at seq 1024 is ~5%), matmuls dominate
        # — small enough that the CPU fallback finishes in seconds
        lm_cfg = lm.LMConfig(
            vocab_size=1024, d_model=384, n_layers=4, n_heads=6,
            n_kv_heads=2, head_dim=64, d_ff=1536, sliding_window=64,
            query_pre_attn_scalar=64.0, dtype="fp32",
        )
        S = int(os.environ.get("BENCH_HARVEST_SEQ", 128))
        n_docs = int(os.environ.get("BENCH_HARVEST_DOCS", 32))
        reps = int(os.environ.get("BENCH_HARVEST_STEPS", 4))
        page = 32
        hook = f"blocks.{lm_cfg.n_layers}.hook_resid_pre"
    params = [lm.init_params(jax.random.key(i), lm_cfg) for i in (0, 1)]
    rng = np.random.default_rng(5)
    # chat-shaped mixed-length corpus (most documents well under seq_len,
    # a few at it — the LmSys half of the production mix): ~40% padding
    # efficiency, inside the acceptance criterion's <= 60% regime;
    # single-token and max-length docs included
    lengths = rng.integers(max(1, S // 16), max(2, (5 * S) // 8), size=n_docs)
    lengths[0], lengths[1] = 1, S
    tokens = rng.integers(1, lm_cfg.vocab_size, size=(n_docs, S), dtype=np.int64)
    for d, ln in enumerate(lengths):
        tokens[d, ln:] = 0
    hooks = (hook,)
    eff = paging.padding_efficiency(lengths, S)

    def run_padded():
        return lm.run_with_cache_multi(params, jnp.asarray(tokens), lm_cfg, hooks)

    def run_paged():
        # packing runs per call — the host-side cost is part of the runtime
        return lm.run_with_cache_multi_paged(
            params, tokens, lengths, lm_cfg, hooks, page_size=page,
        )

    times = {}
    for name, fn in (("padded", run_padded), ("paged", run_paged)):
        jax.block_until_ready(fn())                   # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            r = fn()
        jax.block_until_ready(r)
        times[name] = (time.perf_counter() - t0) / reps
    real_tokens = int(lengths.sum())
    out = {
        "padding_efficiency": round(eff, 4),
        "padded_step_ms": round(1000 * times["padded"], 2),
        "paged_step_ms": round(1000 * times["paged"], 2),
        "tokens_per_sec_padded": round(real_tokens / times["padded"], 1),
        "tokens_per_sec_paged": round(real_tokens / times["paged"], 1),
        "paged_speedup": round(times["padded"] / times["paged"], 3),
        "page_size": page,
        "workload": (
            f"2 models x {n_docs} docs, seq {S}, d_model {lm_cfg.d_model}, "
            f"{lm_cfg.n_layers} layers, mixed lengths "
            f"[{int(lengths.min())}, {int(lengths.max())}]"
        ),
    }
    log(f"[harvest] {out}")
    return out


def section_quant() -> dict:
    """The int8 data-plane quality gates (docs/SCALING.md "Quantized data
    plane"), recorded in the bench JSON so every round carries them:

    - roundtrip: per-row relative MSE of quantize→dequantize on a
      Gemma-2-2B-shaped activation probe ([4096 rows, 2 sources, d_in
      2304], heavy-tailed like calibrated residual streams), gated at
      QUANT_RELMSE_BOUND (1e-3): ~2x above the probe's measured 4.7e-4
      so outlier-distribution drift trips the gate, and still an order of
      magnitude below any arm-to-arm delta the `_act_quality` probe
      family resolves.
    - store bytes: quantized/bf16 ratio at the production block size
      (the HBM budget table's headline number).
    - grad all-reduce: quantized-mean vs exact-mean relative error on an
      8-virtual-device CPU mesh (compile+execute of the real
      parallel/quant_ar exchange), plus the error-feedback check — the
      RUNNING MEAN of compressed gradients converges to the exact mean.
    """
    import numpy as np
    from jax.sharding import Mesh

    from crosscoder_tpu.ops import quant
    from crosscoder_tpu.parallel import quant_ar

    block, d_in, n_sources, rows = 256, 2304, 2, 4096
    bound = float(os.environ.get("QUANT_RELMSE_BOUND", 1e-3))
    rng = np.random.default_rng(11)
    # heavy-tailed rows: gaussian bulk + sparse outlier features, the shape
    # that breaks per-TENSOR scaling and the reason scales are per block
    x = rng.normal(size=(rows, n_sources, d_in)).astype(np.float32)
    outliers = rng.random((1, n_sources, d_in)) < 0.01
    x = x * (1.0 + 9.0 * outliers)
    q, s = jax.device_get(quant.quantize_blocks(jnp.asarray(x), block))
    deq = quant.dequantize_np(np.asarray(q), np.asarray(s), np.float32)
    err = np.sum((deq - x) ** 2, axis=(-2, -1))
    power = np.sum(x ** 2, axis=(-2, -1))
    rel_mse = float(np.mean(err / power))

    store_ratio = quant.store_bytes((rows, n_sources, d_in), block) / (
        2.0 * rows * n_sources * d_in
    )

    # quantized grad all-reduce vs the exact mean, on however many devices
    # this process has (8 virtual in CI, 1 on a lone TPU chip → skipped)
    n_dev = len(jax.devices())
    ar = {}
    if n_dev >= 2:
        mesh = Mesh(np.asarray(jax.devices()), ("data",))
        g = rng.normal(size=(n_dev, 8, d_in)).astype(np.float32)
        ef0 = np.zeros((n_dev, quant_ar.padded_len(8 * d_in, n_dev, block)),
                       np.float32)
        fn = quant_ar.quantized_pmean_fn(mesh, block)
        exact = g.mean(axis=0)
        got, ef1 = fn(jnp.asarray(g), jnp.asarray(ef0))
        got = np.asarray(jax.device_get(got))
        one_shot = float(np.abs(got - exact).max() / np.abs(exact).max())
        # error feedback: same gradient re-reduced with the carried
        # residual — the running mean must converge on the exact mean
        acc, ef_dev = np.zeros_like(exact), jnp.asarray(ef0)
        steps = 8
        for i in range(steps):
            out, ef_dev = fn(jnp.asarray(g), ef_dev)
            acc += np.asarray(jax.device_get(out))[0]
        ef_mean = float(np.abs(acc / steps - exact).max() / np.abs(exact).max())
        ar = {
            "n_devices": n_dev,
            "one_shot_rel_err": round(one_shot, 7),
            "ef_running_mean_rel_err": round(ef_mean, 7),
            "ef_improves": bool(ef_mean < one_shot),
        }

    out = {
        "block": block,
        "roundtrip_rel_mse": float(np.format_float_scientific(
            rel_mse, precision=3, unique=False)),
        "rel_mse_bound": bound,
        "quality_gate_ok": bool(rel_mse < bound),
        "store_bytes_ratio_vs_bf16": round(store_ratio, 4),
        "grad_allreduce": ar,
        "workload": f"[{rows}, {n_sources}, {d_in}] heavy-tailed probe, "
                    f"block {block}",
    }
    log(f"[quant] {out}")
    return out


def section_obs() -> dict:
    """Observability-plane gates (docs/OBSERVABILITY.md), recorded every
    round so tracer cost can never silently regress:

    - **spans/s**: raw SpanTracer record throughput (enter + exit +
      event append + per-name total);
    - **per-step overhead**: the Trainer stepped with obs off vs on at the
      reference shape on a fixed pre-generated batch (so both arms time
      step dispatch + telemetry, not synthetic-data generation). Gate:
      <1% step-time overhead (``overhead_gate_ok``).
    - **bubble fraction**: a short standard training leg with obs on —
      the ``perf/refill_bubble_frac`` the plane emits at every log point.
    """
    import tempfile

    from crosscoder_tpu.data.synthetic import SyntheticActivationSource
    from crosscoder_tpu.obs.trace import SpanTracer
    from crosscoder_tpu.train.trainer import Trainer

    tiny = os.environ.get("BENCH_TINY") == "1"    # CI/debug only
    shape = dict(d_in=32, dict_size=256, batch_size=64) if tiny else {}

    # tracer microbenchmark
    tracer = SpanTracer(os.path.join(tempfile.mkdtemp(), "t.json"))
    n_spans = 20_000
    t0 = time.perf_counter()
    for _ in range(n_spans):
        with tracer.span("bench"):
            pass
    spans_per_sec = n_spans / (time.perf_counter() - t0)

    class FixedSource:
        """One pre-generated batch, re-served — production cost ~0, so
        the on/off A/B isolates the telemetry on the step path."""

        def __init__(self, cfg):
            self._batch = SyntheticActivationSource(cfg).next()

        def next(self):
            return self._batch

    steps = int(os.environ.get("BENCH_OBS_STEPS", 20 if tiny else 16))
    step_ms = {"off": float("inf"), "on": float("inf")}
    # two rounds per arm, min taken: the first Trainer in a process pays
    # one-time backend/init costs that would masquerade as (negative)
    # overhead on fast-step shapes
    for _round in range(2):
        for mode in ("off", "on"):
            cfg = _make_cfg(**shape, num_tokens=10**12, save_every=10**9,
                            obs=mode, prefetch=False,
                            checkpoint_dir=tempfile.mkdtemp())
            tr = Trainer(cfg, buffer=FixedSource(cfg))
            for _ in range(5):
                m = tr.step(full_metrics=False)
            _sync(m["loss"])
            t0 = time.perf_counter()
            for _ in range(steps):
                m = tr.step(full_metrics=False)
            _sync(m["loss"])
            step_ms[mode] = min(
                step_ms[mode], 1000 * (time.perf_counter() - t0) / steps
            )
            tr.close()
    overhead = step_ms["on"] / step_ms["off"] - 1.0

    # bubble fraction on a standard (synthetic-production) training leg
    cfg = _make_cfg(**shape, num_tokens=10**12, save_every=10**9, obs="on",
                    log_every=8, prefetch=False,
                    checkpoint_dir=tempfile.mkdtemp())
    tr = Trainer(cfg)
    tr.train(num_steps=17)                      # logs at 0, 8, 16
    bubble = tr._obs.registry.get_gauge("perf/refill_bubble_frac")

    out = {
        "spans_per_sec": round(spans_per_sec, 1),
        "span_overhead_us": round(1e6 / spans_per_sec, 3),
        "step_ms_obs_off": round(step_ms["off"], 3),
        "step_ms_obs_on": round(step_ms["on"], 3),
        "obs_overhead_frac": round(overhead, 5),
        "overhead_gate_ok": bool(overhead < 0.01),
        "refill_bubble_frac": (round(float(bubble), 4)
                               if bubble is not None else None),
        "workload": (f"{'tiny' if tiny else 'reference'} shape, "
                     f"{steps}-step on/off A/B on a fixed batch"),
    }
    log(f"[obs] {out}")
    return out


def section_dash() -> dict:
    """Dashboard generation at the reference's recorded sae_vis workload:
    128 seqs × 3 features, minibatch 4 (BASELINE.md: fwd 14.08 s + feature
    acts 3.71 s ≈ 19 s total on A100)."""
    import numpy as np

    from crosscoder_tpu.analysis.dashboards import FeatureVisConfig, FeatureVisData
    from crosscoder_tpu.models import crosscoder as cc
    from crosscoder_tpu.models import lm

    tiny = os.environ.get("BENCH_TINY") == "1"    # CI/debug only
    if tiny:
        hook_layer, lm_cfg = 2, lm.LMConfig.tiny()
        cfg = _make_cfg(d_in=lm_cfg.d_model, dict_size=256, enc_dtype="fp32")
        n_seqs, seq_len = 16, 24
    else:
        hook_layer = 14
        lm_cfg = lm.LMConfig.gemma2_2b().replace(n_layers=hook_layer)
        cfg = _make_cfg(dict_size=2**14, enc_dtype="bf16")   # published shape
        n_seqs, seq_len = 128, 1024
    params = [lm.init_params(jax.random.key(i), lm_cfg) for i in (0, 1)]
    cc_params = cc.init_params(jax.random.key(2), cfg)
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, lm_cfg.vocab_size, size=(n_seqs, seq_len), dtype=np.int32)
    vis_cfg = FeatureVisConfig(
        hook_point=f"blocks.{hook_layer}.hook_resid_pre",
        features=(7, 11, 13), minibatch_size_tokens=4,
    )

    def run() -> float:
        t0 = time.perf_counter()
        FeatureVisData.create(cc_params, cfg, lm_cfg, params, tokens, vis_cfg)
        return time.perf_counter() - t0

    first = run()
    warm = run()
    out = {
        # includes whatever trace/compile cost remains; depends on the
        # persistent compile cache state (headline compile_cache field)
        "first_call_s": round(first, 2),
        "steady_s": round(warm, 2),
        "reference_a100_s": 19.0,
        "vs_reference": round(19.0 / warm, 2),
        "workload": f"{n_seqs} seqs × 3 features, minibatch 4, "
                    f"{'tiny' if tiny else 'gemma-2-2b'} shapes",
    }
    log(f"[dash] {out}")
    return out


def section_elastic() -> dict:
    """Recovery SLO of elastic membership (docs/resilience.md "Elastic
    membership"): the 2-process preemption drill — chaos ``die@7`` kills
    one host mid-run; the survivor must detect, re-mesh over its local
    devices, restore-with-respec, and finish with a post-remesh loss
    trajectory bitwise equal to a clean restart. The drill always runs
    CPU subprocesses with their own virtual-device worlds, so this leg
    behaves identically on a TPU box."""
    from crosscoder_tpu.resilience.elastic_drill import (run_autoscale_drill,
                                                         run_drill)

    report = run_drill()
    out = {
        "remesh_ms": report["remesh_ms"],
        "bitwise_equal": bool(report["bitwise_equal"]),
        "resume_step": report["resume_step"],
        "post_steps": len(report["post_losses"]),
        "workload": "2-proc CPU drill: die@7 → detect → remesh → "
                    "respec-restore → bitwise-equal finish; then the full "
                    "autoscale cycle (die → shrink → return-grant → "
                    "debounced rejoin → grow → bitwise-equal finish)",
    }
    # scale-UP SLO (docs/resilience.md "Elastic scale-up"): the full
    # grow/shrink/grow cycle, with the grow recovery (boundary save +
    # rendezvous + wider-world re-formation + restore) timed separately
    # from the end-to-end drill wall time
    t0 = time.perf_counter()
    cycle = run_autoscale_drill()
    out.update({
        "grow_ms": cycle["grow_ms"],
        "autoscale_bitwise_equal": bool(cycle["bitwise_equal"]),
        "joiner_equal": bool(cycle["joiner_equal"]),
        "autoscale_cycle_s": round(time.perf_counter() - t0, 2),
        "autoscale_resume_step": cycle["resume_step"],
    })
    log(f"[elastic] {out}")
    return out


def section_fleet() -> dict:
    """Fleet amortization A/B (docs/SCALING.md "Fleet amortization"): N
    shape-identical tenants trained as ONE vmapped cohort off one harvest
    stream vs N sequential solo runs, each paying its own calibration,
    fill, and per-step refill harvest. Reported as aggregate acts/s/chip
    both ways plus ``harvest_amortization`` (their ratio — the
    sweep-level speedup). Gate (ISSUE 17 acceptance): ratio >= 3.0 with
    every loss finite; dict is kept small so harvest dominates, the
    regime the fleet exists for."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from crosscoder_tpu.data.buffer import make_buffer
    from crosscoder_tpu.models import lm
    from crosscoder_tpu.parallel import mesh as mesh_lib
    from crosscoder_tpu.train.fleet import FleetScheduler
    from crosscoder_tpu.train.trainer import Trainer

    tiny = os.environ.get("BENCH_TINY") == "1"    # CI/debug only
    n_tenants = int(os.environ.get("BENCH_FLEET_TENANTS", 4))
    n_steps = int(os.environ.get("BENCH_FLEET_STEPS", 40))
    if tiny:
        # 12 scanned layers: deep enough that the harvest (the shared
        # cost) dominates the tiny crosscoder step, the production regime
        hook_layer, lm_cfg = 12, lm.LMConfig.tiny(n_layers=12)
        shape = dict(d_in=lm_cfg.d_model, dict_size=64, batch_size=256,
                     buffer_mult=16, model_batch_size=4,
                     norm_calib_batches=2, seq_len=17,
                     hook_point="blocks.12.hook_resid_pre")
    else:
        hook_layer = 14
        lm_cfg = lm.LMConfig.gemma2_2b().replace(n_layers=hook_layer)
        shape = dict(dict_size=2048, batch_size=4096, buffer_mult=32,
                     model_batch_size=4, norm_calib_batches=8,
                     seq_len=1024,
                     hook_point=f"blocks.{hook_layer}.hook_resid_pre")
    n_dev = len(jax.devices())
    mesh = mesh_lib.make_mesh(data_axis_size=n_dev, model_axis_size=1)
    batch_sh = NamedSharding(mesh, P("data", None))
    params = [lm.init_params(jax.random.key(i), lm_cfg) for i in (0, 1)]
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, lm_cfg.vocab_size,
                          size=(2048, shape["seq_len"]), dtype=np.int32)

    def cfg_for(**kw):
        return _make_cfg(num_tokens=10**12, save_every=10**9,
                         **{**shape, **kw})

    # N sequential solo runs: each pays its own per-step refill harvest —
    # exactly the cost the fleet amortizes. Steady-state measurement:
    # compiles and the first fill stay outside the timed window on BOTH
    # sides (acts/s is a rate; one-time setup is reported separately).
    solo_wall = 0.0
    fill_s = 0.0
    losses = []
    for i in range(n_tenants):
        cfg = cfg_for(seed=i + 1)
        t0 = time.perf_counter()
        buf = make_buffer(cfg, lm_cfg, params, tokens,
                          batch_sharding=batch_sh)
        tr = Trainer(cfg, buf, mesh=mesh)
        for _ in range(4):
            tr.step(full_metrics=False)       # warmup: compile + serve path
        fill_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(n_steps):
            m = tr.step(full_metrics=False)
        losses.append(_sync(m["loss"]))
        solo_wall += time.perf_counter() - t0
        tr.close()
        log(f"[fleet] solo {i + 1}/{n_tenants}: "
            f"cumulative {solo_wall:.1f}s steady + {fill_s:.1f}s setup")

    tenants = ";".join(f"t{i}:seed={i + 1}" for i in range(n_tenants))
    cfg = cfg_for(fleet="on", fleet_tenants=tenants)
    t0 = time.perf_counter()
    buf = make_buffer(cfg, lm_cfg, params, tokens, batch_sharding=batch_sh)
    fl = FleetScheduler(cfg, buffer=buf, mesh=mesh, checkpoint=False)
    for _ in range(4):
        fl.step_all(full_metrics=False)
    fleet_fill_s = time.perf_counter() - t0
    mets: dict = {}
    t0 = time.perf_counter()
    for _ in range(n_steps):
        mets = fl.step_all(full_metrics=False)
    losses += [_sync(mets[n]["loss"]) for n in fl.active()]
    fleet_wall = time.perf_counter() - t0
    buf.close()

    total_acts = n_tenants * n_steps * cfg.batch_size
    fleet_agg = total_acts / fleet_wall / n_dev
    solo_agg = total_acts / solo_wall / n_dev
    ratio = fleet_agg / solo_agg
    finite = all(bool(jnp.isfinite(x)) for x in losses)
    out = {
        "n_tenants": n_tenants,
        "n_steps": n_steps,
        "agg_acts_per_sec_chip": round(fleet_agg, 1),
        "solo_agg_acts_per_sec_chip": round(solo_agg, 1),
        "harvest_amortization": round(ratio, 2),
        "fleet_gate_ok": bool(ratio >= 3.0 and finite),
        "loss_finite": finite,
        "solo_setup_s": round(fill_s, 1),
        "fleet_setup_s": round(fleet_fill_s, 1),
        "workload": (
            f"{n_tenants}× seed tenants as one vmapped cohort off one "
            f"{'tiny' if tiny else 'gemma-2-2b'}-shaped harvest stream vs "
            f"{n_tenants} sequential solo runs (dict {cfg.dict_size}, "
            f"batch {cfg.batch_size})"
        ),
    }
    log(f"[fleet] {out}")
    return out


def section_serve() -> dict:
    """The serving path's latency SLO (docs/SERVING.md): per-request
    p50/p99/max through the continuous-batched harvest→encode loop at
    batch 1/8/64, saturated req/s, and the two gates the path promises —
    p99 <= 3*p50 at batch 8 (tail discipline: with AOT buckets and
    deadline flushes there is no legitimate source of a fat tail at a
    fixed batch) and ZERO compiles after warmup (every request hits a
    prewarmed bucket executable). Tiny-LM shapes: the section measures
    the engine's batching/dispatch machinery, which is shape-independent;
    the harvest cost model for real shapes is section ``harvest``."""
    from crosscoder_tpu.serve import smoke as serve_smoke

    tiny = os.environ.get("BENCH_TINY") == "1"    # CI/debug only
    reps = int(os.environ.get("BENCH_SERVE_REPS", 8 if tiny else 30))
    t0 = time.perf_counter()
    eng, cfg, lm_cfg, _lm_params, _cc_params = serve_smoke.build_engine(
        serve_max_batch=64)
    warm_compiles = eng.warmup()
    warmup_s = time.perf_counter() - t0
    log(f"[serve] warmup: {warm_compiles} executables over "
        f"{len(eng.buckets)} buckets in {warmup_s:.1f}s")

    legs = [serve_smoke.latency_leg(eng, cfg, lm_cfg, b, reps)
            for b in (1, 8, 64)]
    at8 = next(l for l in legs if l["batch"] == 8)
    out = {
        "batches": {str(l["batch"]): {k: l[k] for k in
                                      ("p50_ms", "p99_ms", "max_ms")}
                    for l in legs},
        "req_s_saturated": legs[-1]["req_s"],   # batch-64 = packed planes
        "p50_ms_b8": at8["p50_ms"],
        "p99_ms_b8": at8["p99_ms"],
        "serve_gate_ok": at8["p99_ms"] <= 3.0 * at8["p50_ms"],
        "warmup_s": round(warmup_s, 1),
        "warmup_compiles": warm_compiles,
        "compiles_after_warmup": eng.compiles_after_warmup,
        "zero_compiles_ok": eng.compiles_after_warmup == 0,
    }
    log(f"[serve] {out}")
    return out


def section_tune() -> dict:
    """The autotuner end to end (docs/TUNING.md): the full two-stage
    search over the train data-plane lattice at the bench shape, the
    tuned-vs-default measured comparison, and the stage-1 serve-p99
    prediction for the serve knob ladder. Gates: the pinned winner's
    measured acts/s/chip ≥ the default knobs' (holds by construction —
    the default candidate is always calibrated and the winner is chosen
    on measured score) and stage-1 pricing added exactly ONE step
    compile for the whole data-plane lattice (the ``aot_get`` reuse the
    zero-cost-off contract promises)."""
    import tempfile

    from crosscoder_tpu.obs.registry import MetricsRegistry
    from crosscoder_tpu.tune import tune
    from crosscoder_tpu.tune.lattice import (default_axes, enumerate_lattice,
                                             rank_candidates)
    from crosscoder_tpu.utils import compile_cache

    tiny = os.environ.get("BENCH_TINY") == "1"    # CI/debug only
    shape = dict(d_in=32, dict_size=256, batch_size=64) if tiny else {}
    cfg = _make_cfg(**shape, num_tokens=10**12, save_every=10**9,
                    prefetch=False, checkpoint_dir=tempfile.mkdtemp())
    axes = {
        "prefetch": (False, True),
        "refill_frac": (0.25, 0.5),
        "refill_dispatch_batch": (4, 8),
    }
    steps = int(os.environ.get("BENCH_TUNE_STEPS", 3 if tiny else 8))
    reg = MetricsRegistry()

    def tune_step_compiles() -> int:
        return sum(1 for k in compile_cache._AOT_CACHE
                   if isinstance(k, tuple) and k and k[0] == "tune_step")

    before = tune_step_compiles()
    out_path = os.path.join(tempfile.mkdtemp(), "TUNED.json")
    art = tune(cfg, "train", axes=axes, top_k=2, out_path=out_path,
               steps=steps, warmup=1, seed=0, registry=reg)
    pricing_compiles = tune_step_compiles() - before

    default_knobs = {k: getattr(cfg, k) for k in axes}
    rows = art.search.get("candidates", [])
    default_row = next((r for r in rows if r.get("knobs") == default_knobs),
                       None)
    tuned_score = float(art.measured.get("score", 0.0))
    default_score = (float(default_row["measured_score"])
                     if default_row and default_row.get("measured_score")
                     is not None else None)

    # serve objective: stage-1 ranking over the bucket/wait/page ladder
    # (prediction only — the measured serve p99 is section ``serve``'s
    # job; here we report what the tuner would pin and why)
    scfg = cfg.replace(serve="on")
    serve_cands, _ = enumerate_lattice(scfg, default_axes(scfg, "serve"))
    serve_ranked = rank_candidates(serve_cands, "serve", 1, seed=0)
    serve_default = {k: getattr(scfg, k)
                     for k in ("serve_max_batch", "serve_max_wait_ms",
                               "page_size")}
    sdef = next((c for c in serve_ranked if c.knobs == serve_default), None)
    out = {
        "tuned_knobs": art.knobs,
        "tuned_acts_per_sec_chip": round(tuned_score, 2),
        "default_acts_per_sec_chip": (round(default_score, 2)
                                      if default_score is not None else None),
        "tuned_vs_default": (round(tuned_score / default_score, 4)
                             if default_score else None),
        "tune_gate_ok": bool(default_score is None
                             or tuned_score >= default_score),
        "pricing_step_compiles": pricing_compiles,
        "aot_reuse_ok": pricing_compiles <= 1,
        "rejected_contract": reg.get_count("tune/rejected_contract"),
        "n_candidates": art.search["n_candidates"],
        "serve_p99_tuned_ms": (round(-serve_ranked[0].score, 3)
                               if serve_ranked else None),
        "serve_p99_default_ms": (round(-sdef.score, 3)
                                 if sdef is not None else None),
        "serve_knobs_tuned": serve_ranked[0].knobs if serve_ranked else None,
        "artifact": out_path,
        "workload": (f"{'tiny' if tiny else 'reference'} shape, "
                     f"{len(axes)}-knob lattice, {steps}-step windows"),
    }
    log(f"[tune] {out}")
    return out


def section_compile_cache() -> dict:
    """The persistent AOT tier end to end (docs/SCALING.md "Persistent
    compile cache"): two REAL processes run the serve warmup against one
    ``compile_cache_dir`` — the first cold (populates the tier), the
    second warm. Gates: the warm process performs ZERO XLA compiles
    (the whole bucket ladder deserializes from disk) and its warmup
    wall is ≤ 0.3× the cold process's.

    A CPU leg, by construction: this parent has touched JAX and holds the
    chip, and a chip belongs to one process at a time, so the children
    are pinned to the CPU (as the elastic drill's are). Its numbers are
    counts and a CPU wall ratio, never device timings."""
    import subprocess
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="bench_compile_cache_")
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"

    def one(tag: str) -> dict:
        proc = subprocess.run(
            [sys.executable, "-m", "crosscoder_tpu.serve.warm_start",
             "--cache-dir", cache_dir],
            capture_output=True, text=True, cwd=here, env=env, timeout=900)
        if proc.returncode != 0:
            raise RuntimeError(
                f"{tag} warm_start failed: {proc.stderr[-300:]}")
        return json.loads(proc.stdout.strip().splitlines()[-1])["warm_start"]

    cold = one("cold")
    warm = one("warm")
    speedup = (cold["warmup_ms"] / warm["warmup_ms"]
               if warm["warmup_ms"] else float("inf"))
    out = {
        "cold_warmup_ms": cold["warmup_ms"],
        "warm_warmup_ms": warm["warmup_ms"],
        "warm_vs_cold": round(warm["warmup_ms"] / cold["warmup_ms"], 4)
        if cold["warmup_ms"] else None,
        "cold_compiles": cold["compiles"],
        "warm_compiles": warm["compiles"],
        "disk_entries": warm["disk_entries"],
        "warm_disk_hits": warm["disk_hits"],
        "warm_speedup": round(speedup, 2),
        "zero_compiles_warm_ok": warm["compiles"] == 0,
        "warm_wall_gate_ok": warm["warmup_ms"] <= 0.3 * cold["warmup_ms"],
        "platform": "cpu",
        "workload": "tiny-LM serve warmup ladder, 2 CPU processes (the "
                    "parent holds the chip), 1 cache dir",
    }
    log(f"[compile_cache] {out}")
    return out


# stdout-summary projection: per section, the fields worth the 2 KB line
_SUMMARY_KEYS = {
    "step": ("acts_per_sec_chip", "vs_a100_step"),
    "e2e": ("acts_per_sec_chip", "vs_a100_e2e", "step_ms_median",
            "refresh_bubble_ms", "loss_finite"),
    "refill_overlap": ("gate_ok",),
    "harvest": ("padding_efficiency", "paged_step_ms", "paged_speedup"),
    "quant": ("roundtrip_rel_mse", "quality_gate_ok"),
    "obs": ("obs_overhead_frac", "overhead_gate_ok"),
    "dash": ("steady_s", "vs_reference"),
    "elastic": ("remesh_ms", "bitwise_equal", "grow_ms",
                "autoscale_cycle_s"),
    "fleet": ("agg_acts_per_sec_chip", "solo_agg_acts_per_sec_chip",
              "harvest_amortization", "fleet_gate_ok"),
    "serve": ("p50_ms_b8", "p99_ms_b8", "req_s_saturated",
              "serve_gate_ok", "zero_compiles_ok"),
    "tune": ("tuned_acts_per_sec_chip", "default_acts_per_sec_chip",
             "tuned_vs_default", "serve_p99_tuned_ms",
             "serve_p99_default_ms", "tune_gate_ok", "aot_reuse_ok"),
    "compile_cache": ("cold_warmup_ms", "warm_warmup_ms", "warm_vs_cold",
                      "warm_compiles", "disk_entries",
                      "zero_compiles_warm_ok", "warm_wall_gate_ok"),
}
_GATES = (("refill_overlap", "gate_ok"), ("quant", "quality_gate_ok"),
          ("obs", "overhead_gate_ok"), ("e2e", "loss_finite"),
          ("elastic", "bitwise_equal"),
          ("elastic", "autoscale_bitwise_equal"),
          ("fleet", "fleet_gate_ok"),
          ("serve", "serve_gate_ok"), ("serve", "zero_compiles_ok"),
          ("tune", "tune_gate_ok"), ("tune", "aot_reuse_ok"),
          ("compile_cache", "zero_compiles_warm_ok"),
          ("compile_cache", "warm_wall_gate_ok"))


def _compact(headline: dict, results: dict) -> dict:
    """The ≤2 KB stdout summary: headline + per-section key numbers +
    gate booleans + per-dict step-time ratios vs relu. Everything else
    lives in the detail artifact."""
    out = dict(headline)
    out["gates"] = {f"{name}.{key}": bool(sec[key])
                    for name, key in _GATES
                    if isinstance(sec := results.get(name), dict)
                    and key in sec}
    for name, keys in _SUMMARY_KEYS.items():
        sec = results.get(name)
        if not isinstance(sec, dict):
            continue
        if "error" in sec:
            out[name] = {"error": sec["error"][:120]}
        else:
            out[name] = {k: sec[k] for k in keys if k in sec}
    matrix = results.get("matrix")
    if isinstance(matrix, list):
        relu = {e.get("dict_size"): e.get("acts_per_sec_chip")
                for e in matrix if e.get("variant") == "relu"}
        out["relu_acts_per_dict"] = relu
        ratios = {}
        for e in matrix:
            if e.get("variant") == "relu":
                continue
            key = f"{e.get('variant', '?')}@{e.get('dict_size', '?')}"
            acts = e.get("acts_per_sec_chip")
            base = relu.get(e.get("dict_size"))
            if acts and base:
                ratios[key] = round(base / acts, 3)   # >1 = slower than relu
            else:
                ratios[key] = "skip" if "skipped" in e else "err"
        out["step_ratio_vs_relu"] = ratios
    configs = results.get("configs")
    if isinstance(configs, list):
        out["configs"] = {e.get("config", "?"):
                          e.get("acts_per_sec_chip",
                                "skip" if "skipped" in e else "err")
                          for e in configs}
    # the driver truncates the line at 2000 chars — drop the widest
    # tables first rather than ship an unparseable line
    for drop in ("step_ratio_vs_relu", "configs", "relu_acts_per_dict"):
        if len(json.dumps(out)) <= 1900:
            break
        out.pop(drop, None)
    return out


def main() -> None:
    # Output contract: stdout carries EXACTLY ONE machine-parseable JSON
    # line, emitted last AND compact — the driver truncates it at 2000
    # chars (BENCH_r05 shipped "parsed": null because the full-detail
    # line was ~8 KB). Full per-section detail goes to the artifact file.
    # Library/trainer progress prints go through plain print() → reroute
    # the whole module-level stdout to stderr for the run and write the
    # summary to the real stream at the very end.
    real_stdout = sys.stdout
    sys.stdout = sys.stderr
    try:
        headline, results = _run_sections()
        artifact = os.environ.get("BENCH_ARTIFACT", "BENCH_DETAIL.json")
        detail = dict(headline)
        detail.update(results)
        with open(artifact, "w") as f:
            json.dump(detail, f, indent=1, default=str)
        summary = _compact(headline, results)
        summary["detail"] = artifact
    finally:
        sys.stdout = real_stdout
    line = json.dumps(summary)
    assert len(line) <= 2000, (
        f"summary line is {len(line)} B; the driver caps at 2000")
    print(line, flush=True)
    failed = _failed_sections(results)
    if failed:
        log(f"FAILED sections: {failed}")
        sys.exit(1)


def _failed_sections(results: dict) -> list[str]:
    """Sections (or legs of a list-shaped section) that raised: their
    exception was caught so the others could run and the summary line
    could be written, but the run as a whole has failed."""
    out = []
    for name, sec in results.items():
        legs = sec if isinstance(sec, list) else [sec]
        if any(isinstance(leg, dict) and "error" in leg for leg in legs):
            out.append(name)
    return out


def _run_sections() -> dict:
    if os.environ.get("BENCH_CPU") == "1":
        jax.config.update("jax_platforms", "cpu")
    # persistent compile cache: a cold run compiles every section's
    # programs; a warm one skips that ($JAX_COMPILATION_CACHE_DIR places
    # it, empty disables — JAX's own variable).
    from crosscoder_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    try:
        cache_state = ("warm" if cache_dir and os.listdir(cache_dir) else
                       "cold" if cache_dir else "disabled")
    except OSError:
        cache_state = "cold"
    sections = os.environ.get(
        "BENCH_SECTIONS",
        "step,matrix,configs,e2e,refill_overlap,harvest,quant,obs,dash,"
        "elastic,fleet,serve,tune,compile_cache"
    ).split(",")
    results: dict = {}
    for name, fn in (("step", section_step), ("matrix", section_matrix),
                     ("configs", section_configs),
                     ("e2e", section_e2e),
                     ("refill_overlap", section_refill_overlap),
                     ("harvest", section_harvest),
                     ("quant", section_quant), ("obs", section_obs),
                     ("dash", section_dash),
                     ("elastic", section_elastic),
                     ("fleet", section_fleet),
                     ("serve", section_serve),
                     ("tune", section_tune),
                     ("compile_cache", section_compile_cache)):
        if name not in sections:
            continue
        try:
            results[name] = fn()
        except Exception as e:
            results[name] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            log(f"[{name}] FAILED: {results[name]['error']}")

    e2e = results.get("e2e", {})
    step = results.get("step", {})
    if "acts_per_sec_chip" in e2e:
        headline = {
            "metric": "end-to-end harvest→buffer→train acts/sec/chip "
                      f"({e2e['workload']})",
            "value": e2e["acts_per_sec_chip"],
            "unit": "activations/s/chip",
            "vs_baseline": e2e["vs_a100_e2e"],
        }
    else:   # e2e skipped/failed: fall back to round-1's step-only headline
        headline = {
            "metric": "crosscoder train acts/sec/chip "
                      f"({step.get('workload', 'step section failed')})",
            "value": step.get("acts_per_sec_chip"),
            "unit": "activations/s/chip",
            "vs_baseline": step.get("vs_a100_step"),
        }
    headline["compile_cache"] = cache_state
    return headline, results


if __name__ == "__main__":
    main()
