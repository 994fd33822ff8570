"""Runner ``train``: harvest -> store -> train, wired as ``train/main.py`` wires it.

``make_buffer`` -> ``Trainer`` -> ``Trainer.train()``; the loop is the
program's own and is stopped the way a job is stopped (SIGTERM). The
benchmark is the loop's logger: ``log_every`` is one refill cycle, so the
time between two calls of the logger is one whole cycle between two device
syncs, and ``train_rows_per_s`` is every row of the window over every second
of it (``cycles.py``).

Traffic parameters (``traffic/<name>.json``): ``generator`` and its own
keys, ``schedule_steps`` (the job length the schedules are ramped over),
``warm_cycles`` (whole cycles run as set-up before the window opens),
``trace_cycles`` (cycles the traced run captures), ``min_cycles`` (fewer
whole cycles in the window and the run is refused; 10 unless the mix says),
``reference_seqs`` (sequences of the corpus compared with the plain LM after
the window).
"""

from __future__ import annotations

import gc
import importlib
import math
import signal
import tempfile
import time
from pathlib import Path
from typing import Any

import numpy as np

from benchmarks import arch as arch_lib
from benchmarks import common, cycles, shapes, trace_reduce
from benchmarks.common import say

# First-step losses against the float32 reference. The step computes in
# bf16 (8 mantissa bits) with float32 accumulation: every product carries a
# relative rounding of up to 2^-9 and the reductions average them, so l2, l1
# and l0 land within a few 1e-3 of float32 (seen on a v5e: see PERF.md). A
# missing bias, a wrong norm factor or a dropped source moves them by
# several percent; computing in 8-bit floats would move them by over 2e-2.
FIRST_STEP_RTOL = 1e-2
# The program selects on bf16 pre-activations, the reference on float32
# ones: latents whose pre-activations sit within a bf16 rounding (2^-9 of
# the value) of the k-th swap in or out. At k=32 of 2^15 that is about one
# latent in a few rows (seen on a v5e: PERF.md); a wrong bias or a selection
# on the wrong axis shares next to nothing.
TOPK_SHARED = 0.9
# (The harvest's limit against its plain reference, ``HARVEST_RTOL``, is the
# architecture's: ``benchmarks/arch/<arch>.py``.)


class FirstBatchTap:
    """The buffer, plus a reference to the first batch it serves (for the
    comparison with the reference after the window). Everything else is the
    buffer's own: attribute access falls through."""

    def __init__(self, inner: Any) -> None:
        self._inner = inner
        self.first = None

    def next_raw(self) -> Any:
        batch = self._inner.next_raw()
        if self.first is None:
            self.first = batch
        return batch

    def __getattr__(self, name: str) -> Any:
        return getattr(self._inner, name)


class CycleLog:
    """The Trainer's logger. Opens the window at the log step that closes
    the last warm-up cycle, records one cycle wall per later log step, and
    asks the job to stop (SIGTERM, as a preemption would) at the first log
    step after ``seconds``."""

    def __init__(self, spc: int, warm_cycles: int, seconds: float,
                 compiles: common.CompileLog, rec: common.RunRecord) -> None:
        self.spc, self.seconds = spc, seconds
        self.open_step = warm_cycles * spc
        self.compiles, self.rec = compiles, rec
        self.host = common.HostLoad()
        self.rows: list[dict] = []
        self.t_open = self.t_close = None
        self.compile_setup: dict = {}       # the compile log at window open
        self.window_compiles = None
        self.host_load: dict = {}

    def log(self, metrics: dict, step: int) -> None:
        now = time.perf_counter()
        row = {"step": step, "t": now, **{k: float(v) for k, v in metrics.items()}}
        row["in_window"] = self.t_open is not None and self.t_close is None
        self.rows.append(row)
        if step == self.open_step:
            self.rec.phase(f"warm-up: {self.open_step + 1} steps "
                           f"({self.open_step // self.spc} whole cycles)")
            self.t_open, self.compile_setup = now, self.compiles.snapshot()
            self.host.open()
        elif row["in_window"] and now - self.t_open >= self.seconds:
            self.t_close = now
            self.window_compiles = (self.compiles.requests
                                    - self.compile_setup["requests"])
            self.host_load = self.host.close()
            signal.raise_signal(signal.SIGTERM)

    def close(self) -> None:
        pass

    def window_rows(self) -> list[dict]:
        return [r for r in self.rows if r["in_window"]]


def crosscoder_config(config: dict, traffic: dict, seed: int, workdir: str,
                      overrides: dict | None, trace: int) -> tuple[Any, int]:
    """The cell's ``CrossCoderConfig`` and its serves per refill cycle.
    ``log_every`` is one cycle; the traced run turns the program's spans on
    and has ``cfg.profile_steps`` capture ``trace_cycles`` whole cycles."""
    from crosscoder_tpu.config import CrossCoderConfig

    kw = {**config["crosscoder"], **(overrides or {})}
    cfg = CrossCoderConfig(
        **kw, seed=seed, log_backend="null", log_print_every=0,
        checkpoint_dir=str(Path(workdir) / "ckpt"),
        num_tokens=kw["batch_size"] * int(traffic["schedule_steps"]))
    rows_per_seq = cfg.seq_len - 1
    buffer_rows = cfg.batch_size * cfg.buffer_mult // rows_per_seq * rows_per_seq
    spc = cycles.serves_per_cycle(buffer_rows, cfg.batch_size)
    cfg = cfg.replace(log_every=spc)
    if trace:
        first = int(traffic["warm_cycles"]) * spc + 1
        cfg = cfg.replace(
            obs="on", obs_dir=str(Path(workdir) / "obs"),
            profile_dir=str(Path(workdir) / "profile"),
            profile_steps=f"{first}:{first + int(traffic['trace_cycles']) * spc}")
    return cfg, spc


def _build(cfg: Any, lm_cfg: Any, lm_params: list, tokens: Any, mesh: Any,
           logger: Any):
    """buffer -> Trainer as ``train/main.py`` wires them (minus the network:
    weights and tokens arrive from the seed; and with no checkpointer, so
    the job saves nothing inside or after the window)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from crosscoder_tpu.data.buffer import make_buffer
    from crosscoder_tpu.train.trainer import Trainer

    buffer = make_buffer(cfg, lm_cfg, lm_params, tokens,
                         batch_sharding=NamedSharding(mesh, P("data", None)))
    tap = FirstBatchTap(buffer)
    return Trainer(cfg, tap, mesh=mesh, logger=logger), tap


def _kernel_in_step(trainer: Any) -> bool | None:
    """Whether the compiled bare step holds a Pallas kernel; None where it
    cannot be asked (not a TPU; or ``cfg.obs`` on, which wraps the step — the
    traced run shows the kernel in its trace instead)."""
    import jax
    import jax.numpy as jnp

    cfg = trainer.cfg
    fn = trainer._step_fns.get((False, True, True))
    if jax.default_backend() != "tpu" or not hasattr(fn, "lower"):
        return None
    text = fn.lower(
        trainer.state,
        jax.ShapeDtypeStruct((cfg.batch_size, cfg.n_sources, cfg.d_in), jnp.bfloat16),
        jax.ShapeDtypeStruct((cfg.n_sources,), jnp.float32)).compile().as_text()
    return "tpu_custom_call" in text


def _against_references(cfg: Any, lm_cfg: Any, arch: Any, lm_params: list,
                        tokens: Any, first: Any, norm: Any, row0: dict,
                        n_seq: int, check: Any) -> dict:
    """Outside the window, at the cell's widths: the first step's losses and
    the TopK selection against the plain crosscoder, the hooked activations
    of a seeded sample against the plain LM of the configuration's ``arch``."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import crosscoder_ref
    from crosscoder_tpu.models import crosscoder as cc
    from crosscoder_tpu.models import lm
    from crosscoder_tpu.utils.dtypes import dtype_of

    topk = cfg.activation == "topk"
    compared: dict[str, dict] = {}      # each number compared, beside its limit
    p0 = cc.init_params(jax.random.key(cfg.seed), cfg, dtype=jnp.float32)
    x = first.astype(jnp.float32) * norm[None, :, None]
    ref = crosscoder_ref.losses(p0, x, cfg.topk_k if topk else None)
    for key in ("l2", "l0") if topk else ("l2", "l1", "l0"):   # TopK: l1_coeff 0
        got, want = row0[f"{key}_loss"], float(ref[key])
        dev = abs(got - want) / max(abs(want), 1e-12)
        say(f"first step {key}_loss: program {got:.6g}, float32 reference "
            f"{want:.6g} (relative deviation {dev:.2e}, limit {FIRST_STEP_RTOL})")
        check(dev <= FIRST_STEP_RTOL, f"first-step {key}_loss deviates by {dev:.2e}")
        compared[f"first_step_{key}_dev"] = {"value": dev, "limit": FIRST_STEP_RTOL}
    out = {"compared": compared}
    if topk:
        # the program's selection (the default tier: the Pallas kernel on a
        # chip) against the reference's k largest, on the same batch
        enc = dtype_of(cfg.enc_dtype)
        chosen = cc.encode(cc.cast_params(p0, enc), x.astype(enc), cfg) > 0
        row_ix = jnp.arange(chosen.shape[0])[:, None]
        wanted = jnp.zeros_like(chosen).at[row_ix, ref["topk_idx"]].set(True)
        shared = float(jnp.mean(jnp.sum(chosen & wanted, axis=-1)) / cfg.topk_k)
        say(f"TopK selection: {100 * shared:.2f}% of the program's latents are "
            f"the reference's (limit {100 * TOPK_SHARED:.0f}%)")
        check(shared >= TOPK_SHARED, f"TopK selection shares only {shared:.3f}")
        out["topk_shared"] = shared
        compared["topk_shared_floor"] = {"value": shared, "limit": TOPK_SHARED}
        del chosen, wanted
    del p0, x, ref
    hook_layer = int(cfg.hook_point.split(".")[1])
    sample = jnp.asarray(tokens[:n_seq])
    got = lm.run_with_cache_multi(lm_params, sample, lm_cfg, cfg.resolved_hook_points())
    worst = 0.0
    for m, p in enumerate(lm_params):
        want = arch.resid_pre(p, sample, lm_cfg, hook_layer)
        diff = got[:, :, m].astype(jnp.float32) - want
        worst = max(worst, float(jnp.linalg.norm(diff) / jnp.linalg.norm(want)))
    say(f"harvest against the float32 reference: relative error {worst:.3e} "
        f"over {n_seq} x {cfg.seq_len} tokens x {len(lm_params)} models "
        f"(limit {arch.HARVEST_RTOL})")
    check(worst <= arch.HARVEST_RTOL, f"hooked activations deviate by {worst:.3e}")
    out["harvest_rel_err"] = worst
    compared["harvest_rel_err"] = {"value": worst, "limit": arch.HARVEST_RTOL}
    return out


def run(cell: dict, args: Any, rec: common.RunRecord, compiles: common.CompileLog,
        sizes: dict | None = None) -> dict:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from crosscoder_tpu.parallel import mesh as mesh_lib

    sizes = sizes or {}
    config, traffic = cell["config"], {**cell["traffic"], **sizes.get("traffic", {})}
    chips = cell["workload"]["chips"]
    seed_cc, seed_tok, seed_a, seed_b = common.sub_seeds(args.seed)
    arch = arch_lib.of(config)
    lm_cfg = arch.lm_config(config, sizes.get("lm"))
    problems: list[str] = []

    def check(cond: bool, what: str) -> None:
        if not cond:
            problems.append(what)
            say(f"CHECK FAILED: {what}")

    with tempfile.TemporaryDirectory(prefix="bench_train_") as workdir:
        cfg, spc = crosscoder_config(config, traffic, seed_cc, workdir,
                                     sizes.get("crosscoder"), args.trace)
        data, model = cfg.data_axis_size, cfg.model_axis_size
        devices = jax.devices()[: data * model]
        if len(devices) != chips:
            raise RuntimeError(f"mesh {data}x{model} on a cell of {chips} chip(s)")
        mesh = mesh_lib.make_mesh(data, model, devices=devices)
        gen = importlib.import_module(f"benchmarks.generators.{traffic['generator']}")
        tokens = gen.make(traffic, cfg.seq_len, lm_cfg.vocab_size, seed_tok)
        rec.phase("program imports, corpus")
        lm_params = common.init_lm_pair(
            lm_cfg, [seed_a, seed_b],
            NamedSharding(mesh, P()) if chips > 1 else None)
        jax.block_until_ready(lm_params)
        rec.phase("LM pair made on the device")

        log = CycleLog(spc, int(traffic["warm_cycles"]), float(args.seconds),
                       compiles, rec)
        trainer, tap = _build(cfg, lm_cfg, lm_params, tokens, mesh, log)
        rec.phase("calibration, first fill, Trainer")
        say(f"{cell['workload']['name']}: dict {cfg.dict_size} {cfg.activation}, "
            f"batch {cfg.batch_size}, seq {cfg.seq_len}, {spc} serves/cycle, "
            f"store {tap.store_nbytes() / 2**30:.2f} GiB, mesh {data}x{model}, "
            f"window opens at step {log.open_step}")
        guard = "jax_transfer_guard_device_to_device"
        prev_guard = getattr(jax.config, guard)
        if chips > 1:
            # state a device already holds must not be re-sent by a dispatch
            jax.config.update(guard, "disallow")
        try:
            trainer.train()
        finally:
            jax.config.update(guard, prev_guard)
            trainer.close()
        t_ended = time.perf_counter()

        # ---- the window ----------------------------------------------
        check(log.t_close is not None, "the job ended before the window closed "
                                       "(schedule_steps too small for --seconds)")
        rows = log.window_rows()
        # one cycle = the benchmark's own clock between two log steps (each
        # closed by the loop's device sync); their sum is the whole window
        stamps = [log.t_open] + [row["t"] for row in rows] if rows else []
        walls = [b - a for a, b in zip(stamps, stamps[1:])]
        floor = int(traffic.get("min_cycles", cycles.MIN_CYCLES))
        r = cycles.rate(walls, cfg.batch_size * spc, chips, floor)
        rec.series("cycle_wall_s", walls, "s")
        rec.note("cycle_log", [{k: row[k] for k in ("step", "t", "step_time_ms", "loss")}
                               for row in log.rows])
        rec.note("host_load", log.host_load)
        common.say_host(log.host_load)
        if r["cycles"]:
            say(f"train_rows_per_s: {r['rows_per_s']:.2f} rows/s/chip = "
                f"{r['cycles']} cycles x {cfg.batch_size * spc} rows over "
                f"{r['window_s']:.4f} s; off the median cycle "
                f"{r['rows_per_s_median_cycle']:.2f}; median cycle "
                f"{r['cycle_s_median']:.5f} s, slowest {r['cycle_s_max']:.5f} s "
                f"(x{r['cycle_max_over_median']:.4f})")
        quiet = r
        if args.trace:
            # stopping the profiler holds the loop for seconds at the end of
            # the last traced cycle, and the cycle after it is short (the
            # refill's thread went on meanwhile). The traced run reports no
            # rate: the per-layer cycle readings are of the cycles after
            # those, and the floor is theirs
            skip = int(traffic["trace_cycles"]) + 1
            quiet = cycles.rate(walls[skip:], cfg.batch_size * spc, chips)
            say(f"cycles after the profiler's {skip}: n={quiet['cycles']}"
                + (f", median {quiet['cycle_s_median']:.5f} s, slowest "
                   f"{quiet['cycle_s_max']:.5f} s" if quiet["cycles"] else ""))
            check(quiet["cycles"] >= 3, f"{quiet['cycles']} whole cycles after the "
                                        "profiler's, under 3")
        else:
            check(r["ok"], f"{r['cycles']} whole cycles in the window, under {floor}")
        check(not log.window_compiles,
              f"{log.window_compiles} compile(s) inside the window")
        check(bool(log.rows) and all(math.isfinite(row["loss"]) for row in log.rows),
              "non-finite loss at a log step")
        if cfg.activation == "topk":
            l0 = sorted({row["l0_loss"] for row in log.rows})
            check(l0 == [cfg.topk_k], f"L0 {l0} != k")
            check(_kernel_in_step(trainer) is not False,
                  "no tpu_custom_call in the compiled TopK step")
        metrics = {"setup_s": (log.t_open or t_ended) - rec.t_start}
        if r["cycles"]:
            metrics["train_rows_per_s"] = r["rows_per_s"]
        obs: dict[str, Any] = {
            "cycle": quiet, "window": r, "window_rows": rows, "spc": spc, "chips": chips,
            "compile_setup": log.compile_setup,
            "shapes": shapes.train_shapes(cfg, lm_cfg, spc, (data, model), arch),
        }

        # ---- outside the window: the references ----------------------
        norm = np.asarray(tap.normalisation_factor, np.float32)
        first = tap.first
        del trainer, tap
        gc.collect()
        reference: dict = {}
        if log.rows:
            reference = _against_references(
                cfg, lm_cfg, arch, lm_params, tokens, first, norm, log.rows[0],
                int(traffic.get("reference_seqs", 1)), check)
            rec.note("reference", reference)
        del first
        if args.trace:
            obs["trace"] = trace_reduce.load_profile(
                Path(cfg.profile_dir),
                trace_reduce.attribution(cell["root"], cell["paths"]))
            obs["host_spans"] = trace_reduce.load_host_spans(
                Path(cfg.obs_dir) / "trace.json")
            obs["traced_steps"] = int(traffic["trace_cycles"]) * spc
    obs["memory_peak_bytes"] = common.device_block(devices)["memory_peak_bytes"] or None
    return {"metrics": metrics, "problems": problems, "observations": obs,
            "compared": reference.get("compared", {}),
            "attempted": r["cycles"],
            "failed": sum(not math.isfinite(row["loss"]) for row in rows),
            "devices": devices}
