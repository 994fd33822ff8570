#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the repo's main path once on ONE TPU chip,
through the objects the normal entry point (``crosscoder_tpu/train/main.py``)
wires, at the full width of the Gemma-2-2B pair: ``make_buffer`` (HBM store)
→ ``Trainer(..., checkpointer=Checkpointer(cfg))`` → ``Trainer.train()`` with
a refill cycle and the final save → ``restore()`` into a fresh Trainer and one
more step — once for ReLU+L1 at dict 2^14 and once for TopK at dict 2^15 (the
Pallas TopK tier) — then a few dozen requests through ``InferenceEngine``,
checked against the offline padded path. Weights are random, made from a
seed; depth is cut to the blocks the hook executes.

``python chip_smoke.py --chips 4`` runs ONLY the sharded path and what it is
compared with: the train config for a few steps on a 4x1 and a 2x2
('data','model') mesh with the mesh HBM store, against the same seeded steps
on a 1x1 mesh over device 0 of the same host.

Contract of the output: the LAST line on stdout is exactly
``{"ok": <bool>, "device": {"platform": ..., "kind": ..., "count": ...}}`` and
nothing follows it; the exit code is 0 only when ok is true. A platform other
than "tpu", or a device count other than the one asked for, fails before any
phase runs — nothing here ever continues on the CPU. Everything runs in this
one process (a chip belongs to one process at a time).

Earlier stdout lines (``[smoke] ...``) are smoke observations: one run, no
spread, never benchmark numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import tempfile
import threading
import time
import traceback
from typing import Any

SEED = 0
_OUT = None     # main() parks the real stdout here while the run owns sys.stdout


def final_line(ok: bool, platform: str, kind: str, count: int) -> str:
    """The one line the driver reads: two top-level keys, three device keys."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(platform), "kind": str(kind), "count": int(count)}})


def say(msg: str) -> None:
    print(f"[smoke] {msg}", file=_OUT or sys.stdout, flush=True)


# ---------------------------------------------------------------------------
# sizes: what the script runs (real) and what the CPU rehearsal runs (tiny)


@dataclasses.dataclass(frozen=True)
class Sizes:
    lm_cfg: Any                 # subject LM, depth already cut to the hook
    full_layers: int            # the published depth (for the printed cut)
    base: dict                  # CrossCoderConfig fields shared by every leg
    legs: tuple                 # (label, per-leg CrossCoderConfig fields)
    steps: int                  # Trainer.train() steps per leg
    log_every: int
    token_rows: int             # seeded corpus rows [token_rows, seq_len]
    serve: dict                 # CrossCoderConfig fields of the serve plane
    serve_groups: tuple         # requests submitted together, per flush
    serve_min_len: int
    mesh_steps: int             # --chips 4: steps per mesh shape


def real_sizes() -> Sizes:
    """Gemma-2-2B published widths, the reference's production crosscoder
    shape, the only buffer size with a chip record (bench.py e2e)."""
    from crosscoder_tpu.models import lm

    full = lm.LMConfig.gemma2_2b()
    hook_layer = 14             # blocks.14.hook_resid_pre runs blocks 0..13
    return Sizes(
        lm_cfg=full.replace(n_layers=hook_layer), full_layers=full.n_layers,
        base=dict(d_in=full.d_model, n_models=2, batch_size=4096,
                  seq_len=1024, model_batch_size=4, enc_dtype="bf16",
                  buffer_mult=32, norm_calib_batches=8,
                  hook_point=f"blocks.{hook_layer}.hook_resid_pre"),
        legs=(("relu-2^14", dict(dict_size=2**14)),
              ("topk-2^15", dict(dict_size=2**15, activation="topk",
                                 topk_k=32, l1_coeff=0.0))),
        steps=40, log_every=4, token_rows=2048,
        serve=dict(serve="on", serve_max_batch=8),
        serve_groups=(8, 8, 8, 4, 3, 2, 1), serve_min_len=8, mesh_steps=8,
    )


def tiny_sizes() -> Sizes:
    """Rehearsal 1 and 2 (CPU, kernels in interpret mode): same phases, same
    checks, toy widths. Never run by the script itself."""
    from crosscoder_tpu.models import lm

    full = lm.LMConfig.tiny()
    hook_layer = 2
    return Sizes(
        lm_cfg=full.replace(n_layers=hook_layer), full_layers=full.n_layers,
        base=dict(d_in=full.d_model, n_models=2, batch_size=64, seq_len=17,
                  model_batch_size=4, enc_dtype="bf16", buffer_mult=8,
                  norm_calib_batches=2,
                  hook_point=f"blocks.{hook_layer}.hook_resid_pre"),
        legs=(("relu", dict(dict_size=256)),
              ("topk", dict(dict_size=512, activation="topk", topk_k=4,
                            l1_coeff=0.0))),
        steps=14, log_every=2, token_rows=256,
        serve=dict(serve="on", serve_max_batch=4, page_size=8,
                   serve_queue=16),
        serve_groups=(4, 3, 2, 1), serve_min_len=2, mesh_steps=4,
    )


# ---------------------------------------------------------------------------
# what the process compiled, and when


class CompileLog:
    """Counts this process's XLA compile requests (every new program a jit
    or an AOT lower().compile() asks for — hit in the persistent cache or
    not) and the persistent cache's hits, from JAX's own monitoring events."""

    def __init__(self) -> None:
        self.requests = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self._lock = threading.Lock()   # prefetch and warmup threads compile too

    def install(self) -> "CompileLog":
        import jax.monitoring

        def on_duration(event: str, duration: float, **_: Any) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self.requests += 1
                    self.seconds += duration

        def on_event(event: str, **_: Any) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                with self._lock:
                    self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self


class StepLog:
    """The run's ``MetricsLogger`` plus a copy of every row it was handed,
    each stamped with the process's compile count at that moment."""

    def __init__(self, inner: Any, compiles: CompileLog) -> None:
        self.inner, self.compiles = inner, compiles
        self.rows: list[tuple[int, dict, int]] = []

    def log(self, metrics: dict, step: int) -> None:
        self.inner.log(metrics, step)
        self.rows.append((step, dict(metrics), self.compiles.requests))

    def close(self) -> None:
        self.inner.close()


# ---------------------------------------------------------------------------
# shared pieces


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def init_lm_pair(lm_cfg: Any, sharding: Any = None) -> list:
    """Two subject models' weights from ``lm.init_params`` and a seed (the
    chip machine has no network, so ``lm.from_hf`` cannot be reached).
    ``sharding`` places them on a mesh as they are made. Under jit: run
    eagerly, the float32 temporaries of every leaf pile up to a 13.9 GiB
    spike that no real job pays and that would hide the phases' own peak."""
    import jax

    from crosscoder_tpu.models import lm

    init = jax.jit(lm.init_params, static_argnums=1, out_shardings=sharding)
    return [init(jax.random.key(SEED + i), lm_cfg) for i in (0, 1)]


def make_tokens(sizes: Sizes) -> Any:
    import numpy as np

    return np.random.default_rng(SEED).integers(
        0, sizes.lm_cfg.vocab_size,
        size=(sizes.token_rows, sizes.base["seq_len"]), dtype=np.int32)


def params_checksum(params: dict) -> int:
    """Exact, order-free: the wrapping sum of every leaf's raw bits."""
    import jax
    import jax.numpy as jnp

    total = 0
    for name in sorted(params):
        a = params[name]
        bits = jax.lax.bitcast_convert_type(
            a, jnp.uint32 if a.dtype.itemsize == 4 else jnp.uint16)
        total += int(jnp.sum(bits.astype(jnp.uint32), dtype=jnp.uint32))
    return total % (1 << 32)


def resolved_tiers(cfg: Any) -> dict:
    """Which implementation each kernel family on the TRAIN step's path
    resolves to under the defaults (nothing here opts a kernel in)."""
    import jax

    from crosscoder_tpu.models import crosscoder as cc
    from crosscoder_tpu.ops import activations as act_ops
    from crosscoder_tpu.ops import topk_pallas
    from crosscoder_tpu.utils.dtypes import dtype_of

    if cfg.activation != "topk":
        return {"activation": f"{cfg.activation} (XLA, no kernel family)"}
    probe = jax.ShapeDtypeStruct((1, cfg.dict_size), dtype_of(cfg.enc_dtype))
    kernel = act_ops._default_use_pallas() and topk_pallas.supported(
        probe, cfg.topk_k)
    return {
        "topk": "pallas" if kernel else "xla",
        "factored_decode": cc.use_factored_decode(cfg, cfg.batch_size),
        "sparse_bwd": cc.use_sparse_bwd(cfg, cfg.batch_size),
        "fused_encoder": cc.use_fused_encoder(cfg, cfg.batch_size),
    }


def compiled_step(trainer: Any, with_metrics: bool) -> Any:
    """A train step as this Trainer compiled it (its own jitted function,
    lowered for the state it holds)."""
    import jax
    import jax.numpy as jnp

    cfg = trainer.cfg
    fn = trainer._step_fns[(with_metrics, True, True)]
    return fn.lower(
        trainer.state,
        jax.ShapeDtypeStruct((cfg.batch_size, cfg.n_sources, cfg.d_in),
                             jnp.bfloat16),
        jax.ShapeDtypeStruct((cfg.n_sources,), jnp.float32),
    ).compile()


def peak_hbm() -> str:
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        if not stats:
            return "not reported by this backend"
        out.append(f"{stats['peak_bytes_in_use'] / 2**30:.2f}")
    return "/".join(out) + " GiB"


def build_trainer(cfg: Any, sizes: Sizes, lm_params: list, tokens: Any,
                  mesh: Any, compiles: CompileLog, *, lazy: bool = False,
                  checkpoint: bool = True):
    """buffer → Trainer exactly as ``train/main.py`` wires them (minus the
    network: weights and tokens arrive from the seed)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from crosscoder_tpu.checkpoint.ckpt import Checkpointer
    from crosscoder_tpu.data.buffer import make_buffer
    from crosscoder_tpu.train.trainer import Trainer
    from crosscoder_tpu.utils.logging import MetricsLogger

    buffer = make_buffer(
        cfg, sizes.lm_cfg, lm_params, tokens,
        batch_sharding=NamedSharding(mesh, P("data", None)), lazy=lazy)
    log = StepLog(MetricsLogger(cfg), compiles)
    trainer = Trainer(
        cfg, buffer, mesh=mesh, logger=log,
        checkpointer=Checkpointer(cfg=cfg) if checkpoint else None)
    return trainer, buffer, log


# ---------------------------------------------------------------------------
# phase: train (one leg)


def train_leg(sizes: Sizes, label: str, leg: dict, lm_params: list,
              tokens: Any, compiles: CompileLog, workdir: str) -> dict:
    """make_buffer → Trainer.train() (≥ 1 refill cycle, final save) →
    restore() into a fresh Trainer → one more step. Returns what the serve
    phase reuses: the restored params and the calibrated norm factors."""
    import jax
    import numpy as np

    from crosscoder_tpu import native
    from crosscoder_tpu.config import CrossCoderConfig
    from crosscoder_tpu.data.buffer import DevicePairedActivationBuffer
    from crosscoder_tpu.parallel import mesh as mesh_lib

    batch = sizes.base["batch_size"]
    cfg = CrossCoderConfig(
        **sizes.base, **leg, seed=SEED, num_tokens=batch * sizes.steps,
        log_every=sizes.log_every, buffer_device="hbm", log_backend="jsonl",
        log_print_every=0, checkpoint_dir=os.path.join(workdir, label))
    mesh = mesh_lib.mesh_from_cfg(cfg)
    tiers = resolved_tiers(cfg)
    say(f"{label}: dict {cfg.dict_size}, batch {batch}, seq {cfg.seq_len}, "
        f"{cfg.activation}, {cfg.enc_dtype} compute, {cfg.master_dtype} "
        f"masters, buffer_mult {cfg.buffer_mult} (hbm store); tiers {tiers}")

    t0 = time.perf_counter()
    trainer, buffer, log = build_trainer(cfg, sizes, lm_params, tokens, mesh,
                                         compiles)
    fill_s = time.perf_counter() - t0
    peak_filled = peak_hbm()
    # the HBM store never touches the C++ host gather; if it ever did, a
    # silent NumPy stand-in would hide a missing compiler on this host
    check(isinstance(buffer, DevicePairedActivationBuffer) or native.available(),
          "host store on the path and the native gather fell back to NumPy")
    serves_per_cycle = (buffer.buffer_size // 2 - batch) // batch + 1
    try:
        t0 = time.perf_counter()
        trainer.train()
        train_s = time.perf_counter() - t0
    finally:
        trainer.close()

    rows = log.rows
    losses = [m["loss"] for _, m, _ in rows]
    check(len(rows) >= 4 and all(np.isfinite(losses)),
          f"{label}: non-finite or missing losses {losses}")
    if cfg.activation == "topk":
        l0 = [m["l0_loss"] for _, m, _ in rows]
        check(all(v == cfg.topk_k for v in l0), f"{label}: L0 {l0} != k")
        check(tiers["topk"] == "pallas",
              f"{label}: default TopK tier resolved to {tiers['topk']}")
    harvested = buffer.token_pointer
    check(harvested >= buffer.buffer_batches + buffer._refill_batches(),
          f"{label}: no full refill cycle ({harvested} sequences harvested)")
    # steady window: from the first logged step after the first refill
    # cycle closed (every program of the loop has run by then) to the last
    steady = [r for r in rows if r[0] > serves_per_cycle]
    check(len(steady) >= 2 and
          steady[-1][0] - steady[0][0] >= serves_per_cycle,
          f"{label}: steady window {[r[0] for r in steady]} spans no cycle")
    n_steady = steady[-1][2] - steady[0][2]
    check(n_steady == 0, f"{label}: {n_steady} compile(s) in the steady window")
    step_ms = float(np.median([m["step_time_ms"] for _, m, _ in steady[1:]]))

    saved_step = trainer.step_counter
    saved_sum = params_checksum(trainer.state.params)
    check(saved_step == sizes.steps, f"{label}: ended at step {saved_step}")
    has_kernel = "tpu_custom_call" in compiled_step(trainer, False).as_text()
    if jax.default_backend() == "tpu":
        # interpret mode (the CPU rehearsal) lowers a kernel to plain HLO
        check(has_kernel == (cfg.activation == "topk"),
              f"{label}: tpu_custom_call in the compiled step: {has_kernel}")
    del trainer, buffer
    gc.collect()

    # the final save is on disk; a fresh process would now do exactly this
    t0 = time.perf_counter()
    trainer, buffer, _ = build_trainer(cfg, sizes, lm_params, tokens, mesh,
                                       compiles, lazy=True)
    try:
        meta = trainer.restore()
        restore_s = time.perf_counter() - t0
        check(meta["step"] == saved_step == trainer.step_counter,
              f"{label}: restored step {meta['step']} != saved {saved_step}")
        check(params_checksum(trainer.state.params) == saved_sum,
              f"{label}: restored parameters differ from the saved ones")
        loss = float(jax.block_until_ready(trainer.step()["loss"]))
        check(np.isfinite(loss), f"{label}: post-restore loss {loss}")
    finally:
        trainer.close()

    say(f"{label}: calibrate+first fill {fill_s:.1f}s (compiles included, "
        f"peak HBM after it {peak_filled}); "
        f"train() {sizes.steps} steps {train_s:.1f}s; loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}; {harvested} sequences "
        f"harvested ({serves_per_cycle} serves/cycle); steady window steps "
        f"{steady[0][0]}..{steady[-1][0]}: 0 compiles, median "
        f"{step_ms:.2f} ms/step = {1000 * batch / step_ms:.0f} rows/s "
        f"(one run, harvest included); tpu_custom_call in step: "
        f"{has_kernel}; save+restore ok at step {saved_step} "
        f"(restore+refill {restore_s:.1f}s), post-restore loss {loss:.4f}; "
        f"peak HBM {peak_hbm()}")
    out = {"cfg": cfg, "params": trainer.state.params,
           "norm": np.asarray(buffer.normalisation_factor)}
    del trainer, buffer
    gc.collect()
    return out


# ---------------------------------------------------------------------------
# phase: serve


# Served answers come from the paged prefill, the reference from the padded
# forward: two different compiled programs in bf16, where every block rounds
# its activations to 8 mantissa bits and the two lowerings need not round
# alike, so values agree to a few bf16 ulps, not bitwise. 2^-5 of the
# request's largest activation is 8 ulps (seen on a v5e: 6e-3 to 9e-3, and
# 1.25e-2 since the padded reference's attention is the fused kernel while
# the served path's is the XLA form);
# a wrong token position, norm factor or model changes values by O(1) of
# it. Latents whose activation sits within that band of the k-th may swap
# in or out of the top k.
SERVE_RTOL = 2.0 ** -5


def compare_served(got: Any, want_vals: Any, want_idx: Any, k: int) -> float:
    """One request against the padded-path reference; returns the largest
    deviation seen, as a fraction of the request's largest activation."""
    import numpy as np

    g_vals = np.asarray(got.vals, np.float32)
    w_vals = np.asarray(want_vals, np.float32)
    scale = float(np.abs(w_vals).max())
    band = SERVE_RTOL * scale
    g = dict(zip(got.idx.tolist(), g_vals.tolist()))
    w = dict(zip(np.asarray(want_idx).tolist(), w_vals.tolist()))
    common = g.keys() & w.keys()
    check(len(common) >= k - k // 4,
          f"request {got.request_id}: only {len(common)}/{k} latents shared")
    worst = max(abs(g[i] - w[i]) for i in common)
    # a latent on one side only must be a near-tie at the other's threshold
    for mine, theirs in ((g, w), (w, g)):
        floor = min(theirs.values())
        for i in mine.keys() - common:
            worst = max(worst, mine[i] - floor)
    check(worst <= band, f"request {got.request_id}: deviation {worst:.4g} "
                         f"exceeds {band:.4g} (2^-5 of the largest value)")
    return worst / scale if scale else 0.0


def serve_phase(sizes: Sizes, lm_params: list, trained: dict) -> None:
    """InferenceEngine at the same LM widths over the TopK leg's crosscoder:
    warmup, mixed-length requests in full and partial buckets, one extend;
    every answer against ``lm.run_with_cache_multi`` + ``encode_topk_diff``."""
    import numpy as np

    from crosscoder_tpu.models import crosscoder as cc
    from crosscoder_tpu.ops import paged_attention as pa
    from crosscoder_tpu.serve import InferenceEngine
    from crosscoder_tpu.serve.smoke import oracle, serve_batch

    cfg = trained["cfg"].replace(**sizes.serve, log_backend="null")
    lm_cfg, S, nb = sizes.lm_cfg, cfg.seq_len, cfg.serve_max_batch
    eng = InferenceEngine(cfg, lm_cfg, lm_params, trained["params"],
                          norm_factors=trained["norm"])
    say(f"serve: dict {cfg.dict_size} topk {cfg.topk_k}, buckets "
        f"{eng.buckets}, page {cfg.page_size}; tiers "
        f"{{'paged_attention': '{'pallas' if pa.kernel_enabled() else 'xla'}',"
        f" 'fused_encoder': {cc.use_fused_encoder(cfg, nb)}}}")
    t0 = time.perf_counter()
    n_warm = eng.warmup()
    warm_s = time.perf_counter() - t0

    def reference(docs: list) -> tuple:
        # always the full bucket height: one reference program for the run
        tokens = np.zeros((nb, S), np.int64)
        lengths = np.ones(nb, np.int64)
        for d, doc in enumerate(docs):
            tokens[d, : doc.shape[0]] = doc
            lengths[d] = doc.shape[0]
        return oracle(eng, cfg, lm_cfg, lm_params, trained["params"],
                      tokens, lengths)

    rng = np.random.default_rng(SEED + 1)

    def doc(n: int) -> Any:
        return rng.integers(1, lm_cfg.vocab_size, size=n, dtype=np.int32)

    worst, lat, buckets = 0.0, [], set()
    for n in sizes.serve_groups:
        docs = [doc(int(rng.integers(sizes.serve_min_len, S + 1)))
                for _ in range(n)]
        docs[0] = doc(S if n == nb else sizes.serve_min_len)  # both extremes
        res = serve_batch(eng, docs)
        vals, idx, _ = reference(docs)
        for i, r in enumerate(res):
            worst = max(worst, compare_served(r, vals[i], idx[i], cfg.topk_k))
            lat.append(r.queue_wait_ms + r.prefill_ms + r.encode_ms)
            buckets.add(r.bucket)
    full = doc(S - 2)
    rid = eng.submit(full[: S // 2], keep=True)
    eng.step(force=True)                       # serve the prefix
    eng.extend(rid, full[S // 2:])
    ext = eng.step(force=True)[0]
    eng.release(rid)
    vals, idx, _ = reference([full])
    check(ext.extended, "the extend ticket was not served as an extend")
    worst = max(worst, compare_served(ext, vals[0], idx[0], cfg.topk_k))
    check(eng.compiles_after_warmup == 0,
          f"serve: {eng.compiles_after_warmup} compile(s) after warmup")
    say(f"serve: warmup {warm_s:.1f}s ({n_warm} executables); "
        f"{len(lat)} requests of {sizes.serve_min_len}..{S} tokens in buckets "
        f"{sorted(buckets)} + 1 extend; worst deviation from the padded path "
        f"{worst:.2e} of the largest activation (limit 2^-5); request "
        f"latency median {np.median(lat):.1f} ms (one run, closed loop); "
        f"compiles after warmup 0; peak HBM {peak_hbm()}")


# ---------------------------------------------------------------------------
# phase: four chips


# The same seeded steps on three meshes: the harvest is batch-sharded, the
# loss mean and the gradients are reduced across devices, and under TP the
# decode contracts a sharded dictionary axis — the same sums in another
# order, in bf16 compute (seen on four v5e chips: 1.8e-5 over 8 steps).
# Batches that differed (a sharding bug in the store) move the loss by
# several 1e-3, the batch-to-batch spread at 4096 rows.
MESH_LOSS_RTOL = 1e-3


def mesh_phase(sizes: Sizes, compiles: CompileLog, workdir: str) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from crosscoder_tpu.config import CrossCoderConfig
    from crosscoder_tpu.data.buffer import MeshPairedActivationBuffer
    from crosscoder_tpu.parallel import comm_model
    from crosscoder_tpu.parallel import mesh as mesh_lib

    label, leg = sizes.legs[0]
    batch = sizes.base["batch_size"]
    tokens = make_tokens(sizes)
    runs: dict[tuple, list] = {}
    for shape in ((1, 1), (4, 1), (2, 2)):
        data, model = shape
        cfg = CrossCoderConfig(
            **sizes.base, **leg, seed=SEED,
            num_tokens=batch * sizes.mesh_steps, log_every=1,
            data_axis_size=data, model_axis_size=model, buffer_device="hbm",
            log_backend="jsonl", log_print_every=0,
            checkpoint_dir=os.path.join(workdir, f"mesh{data}x{model}"))
        devices = jax.devices()[: data * model]
        mesh = mesh_lib.make_mesh(data, model, devices=devices)
        lm_params = init_lm_pair(sizes.lm_cfg, NamedSharding(mesh, P()))
        t0 = time.perf_counter()
        trainer, buffer, log = build_trainer(
            cfg, sizes, lm_params, tokens, mesh, compiles, checkpoint=False)
        # state a device should already hold (the LM weights, the store, the
        # parameters) must not be re-sent to it by every dispatch. Set
        # process-wide, not as a context: the prefetch thread dispatches too
        guard = "jax_transfer_guard_device_to_device"
        prev = getattr(jax.config, guard)
        jax.config.update(guard, "disallow")
        try:
            trainer.train()
        finally:
            jax.config.update(guard, prev)
            trainer.close()
        wall = time.perf_counter() - t0
        losses = [m["loss"] for _, m, _ in log.rows]
        check(len(losses) == sizes.mesh_steps and all(np.isfinite(losses)),
              f"mesh {shape}: losses {losses}")
        runs[shape] = losses

        # is the state really spread over the devices?
        W = trainer.state.params["W_enc"]
        store = buffer._store_dev
        w_shards = {s.device.id: s.data.shape for s in W.addressable_shards}
        s_shards = {s.device.id: s.data.shape[0]
                    for s in store.addressable_shards}
        check(len(w_shards) == len(s_shards) == data * model,
              f"mesh {shape}: state on devices {sorted(w_shards)} only")
        check(all(sh[-1] == cfg.dict_size // model for sh in w_shards.values()),
              f"mesh {shape}: W_enc shards {w_shards} (dict axis not /{model})")
        check(all(r == store.shape[0] // data for r in s_shards.values()),
              f"mesh {shape}: store rows per device {s_shards} (not /{data})")
        if data > 1:
            check(isinstance(buffer, MeshPairedActivationBuffer),
                  f"mesh {shape}: store is {type(buffer).__name__}")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
        if all(b is not None for b in in_use):
            check(min(in_use) >= 0.5 * max(in_use),
                  f"mesh {shape}: bytes in use per device {in_use}")
        text = compiled_step(trainer, True).as_text()
        wire = {k: v for k, v in comm_model.collective_bytes(text).items() if v}
        check(bool(wire.get("all-reduce")) == (data * model > 1),
              f"mesh {shape}: collectives in the compiled step: {wire}")
        say(f"mesh {data}x{model}: {sizes.mesh_steps} steps in {wall:.1f}s "
            f"(compiles included), loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
            f"W_enc shard {next(iter(w_shards.values()))} x{len(w_shards)}, "
            f"store rows/device {next(iter(s_shards.values()))}; collective "
            f"bytes in the step {wire}; bytes in use per device "
            f"{[round(b / 2**30, 2) if b else None for b in in_use]} GiB; "
            f"peak HBM {peak_hbm()}")
        del trainer, buffer, lm_params, W, store
        gc.collect()

    ref = np.asarray(runs[(1, 1)])
    for shape in ((4, 1), (2, 2)):
        rel = float(np.max(np.abs(np.asarray(runs[shape]) - ref) / np.abs(ref)))
        check(rel <= MESH_LOSS_RTOL,
              f"mesh {shape}: losses {runs[shape]} vs 1x1 {runs[(1, 1)]} "
              f"(max rel {rel:.2e} > {MESH_LOSS_RTOL})")
        say(f"mesh {shape[0]}x{shape[1]} vs 1x1: loss trajectory max rel diff "
            f"{rel:.2e} (limit {MESH_LOSS_RTOL})")


# ---------------------------------------------------------------------------


def run(chips: int) -> None:
    import jax

    from crosscoder_tpu import native
    from crosscoder_tpu.utils import compile_cache

    compiles = CompileLog().install()
    cache_dir = compile_cache.enable()      # once, before the first compile
    warm = bool(cache_dir and os.path.isdir(cache_dir) and os.listdir(cache_dir))
    import jaxlib

    say(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}; XLA cache "
        f"{cache_dir} ({'warm' if warm else 'cold'}); native host gather "
        f"available: {native.available()} (not on this path: HBM store)")
    sizes = real_sizes()
    say(f"subject pair: Gemma-2-2B widths (d_model {sizes.lm_cfg.d_model}, "
        f"{sizes.lm_cfg.n_heads}Q/{sizes.lm_cfg.n_kv_heads}KV x "
        f"{sizes.lm_cfg.head_dim}, d_ff {sizes.lm_cfg.d_ff}, vocab "
        f"{sizes.lm_cfg.vocab_size}), seeded random weights; reduced: depth "
        f"{sizes.full_layers} -> {sizes.lm_cfg.n_layers} blocks (all that "
        f"{sizes.base['hook_point']} executes)")
    t_all = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if chips == 4:
            mesh_phase(sizes, compiles, workdir)
        else:
            lm_params = init_lm_pair(sizes.lm_cfg)
            say(f"LM pair initialised; peak HBM {peak_hbm()}")
            tokens = make_tokens(sizes)
            trained = None
            for label, leg in sizes.legs:
                trained = train_leg(sizes, label, leg, lm_params, tokens,
                                    compiles, workdir)
            serve_phase(sizes, lm_params, trained)
    say(f"all phases ok in {time.perf_counter() - t_all:.1f}s; "
        f"{compiles.requests} compile requests took {compiles.seconds:.1f}s, "
        f"{compiles.cache_hits} served by the XLA cache")


def main(argv: list[str] | None = None) -> int:
    global _OUT
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): train + serve on one chip; 4: only "
                         "the sharded train path against its 1x1 reference")
    chips = ap.parse_args(argv).chips
    # library prints and warnings go to stderr for the whole run; only
    # say() and the final line reach the real stdout
    _OUT, sys.stdout = sys.stdout, sys.stderr
    ok, device = False, ("none", "", 0)
    try:
        import jax

        devs = jax.devices()
        device = (devs[0].platform, devs[0].device_kind, len(devs))
        if device[0] != "tpu" or device[2] != chips:
            raise RuntimeError(
                f"need {chips} tpu device(s), found {device[2]} x "
                f"{device[0]} ({device[1]}); no phase was run")
        run(chips)
        ok = True
    except Exception:   # noqa: BLE001 — every failure ends in the line
        traceback.print_exc(file=sys.stderr)
    finally:
        # also on an interrupt: the line is written, then the exception
        # goes on to end the process with a non-zero code
        sys.stdout, _OUT = _OUT, None
        print(final_line(ok, *device), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
