"""The Trainer: one jitted, donated, mesh-sharded train step + host loop.

Reproduces the training semantics of the reference Trainer (reference
``trainer.py:7-82``) with a TPU-native execution model:

- The entire step body — encode/decode einsums, losses, backward, global-norm
  clip, Adam, schedules — is ONE ``jax.jit``-compiled function over the
  ``('data','model')`` mesh, with the TrainState donated (no host round-trip,
  no per-step ``.item()`` syncs; the reference forces a device sync every
  step at ``trainer.py:51-63``). Metrics stay on device and are only pulled
  to host at ``log_every`` granularity (SURVEY.md §3.2 "TPU mapping").
- Step math parity: ``loss = l2 + l1_coeff(step)·l1`` (``trainer.py:44``),
  grad clip at global-norm 1.0 (``trainer.py:46``), Adam(β1, β2, eps 1e-8)
  (``trainer.py:16-20``), LR/L1 schedules (``trainer.py:28-39``),
  ``total_steps = num_tokens // batch_size`` (``trainer.py:14``).
- Loop behavior parity: log every ``log_every`` steps, checkpoint every
  ``save_every`` steps and once more in a ``finally:`` on any exit
  (``trainer.py:72-82``) — plus real resume, which the reference lacks.

The data source is any object with ``next() -> [batch, n_sources, d_in]``
(the paired-activation Buffer in :mod:`crosscoder_tpu.data.buffer`, or the
synthetic generator for tests/benchmarks), so the trainer is independent of
how activations are harvested.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import functools
import math
import sys
import threading
import time
import types
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.experimental.layout import with_layout_constraint
from jax.sharding import NamedSharding, PartitionSpec

from crosscoder_tpu import obs
from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.models import crosscoder as cc
from crosscoder_tpu.parallel import mesh as mesh_lib
from crosscoder_tpu.parallel import multihost
from crosscoder_tpu.obs import trace
from crosscoder_tpu.resilience.elastic import PeerLoss
from crosscoder_tpu.train import schedules
from crosscoder_tpu.train.state import TrainState, init_train_state, make_optimizer
from crosscoder_tpu.utils import compile_cache, pipeline
from crosscoder_tpu.utils.logging import MetricsLogger, ResilienceCounters, source_tag


def variant_for_step(
    cfg: CrossCoderConfig, host_step: int, full_metrics: bool = True,
) -> tuple[bool, bool, bool]:
    """The compiled-variant key ``(with_metrics, aux_on, mask_refresh)``
    that step ``host_step`` of a run under ``cfg`` executes. The single
    definition of the cadence logic — the Trainer's per-step variant
    choice and the fleet scheduler's (train/fleet.py) lockstep tenant
    steps both select through here, so they cannot drift."""
    # aux_on=True is the canonical variant when AuxK is off or per-step
    aux_on = (cfg.aux_k == 0 or cfg.aux_every <= 1
              or host_step % cfg.aux_every == 0)
    # mask_refresh=True is canonical when masks are per-step
    # (aux_mask_every == 1, the default) or no mask exists at all;
    # cached-mask runs refresh at the cadence and reuse in between
    cached_mask = ((cfg.aux_k > 0 or cfg.resample_every > 0)
                   and cfg.aux_mask_every != 1)
    mask_refresh = (not cached_mask
                    or host_step % cfg.aux_mask_cadence == 0)
    return (full_metrics, aux_on, mask_refresh)


def make_step_body(
    cfg: CrossCoderConfig, mesh, tx, with_metrics: bool = True,
    aux_on: bool = True, mask_refresh: bool = True, l1_input: bool = False,
    held_in: dict[str, NamedSharding] | None = None,
) -> Callable[..., tuple[TrainState, dict[str, jax.Array]]]:
    """The UNJITTED train-step body :func:`make_train_step` compiles.

    Split out so the fleet scheduler (train/fleet.py) can ``jax.vmap`` the
    same body over a stacked cohort of shape-identical tenants before
    jitting — one compile, one dispatch for the whole cohort — while the
    solo Trainer's trace stays byte-identical (it jits exactly this
    function, same jaxpr as before the split).

    ``l1_input=True`` swaps the baked ``cfg.l1_coeff`` for a traced
    scalar: the returned function takes ``(state, batch, scale, l1_base)``
    and computes ``l1_coeff = l1_base * warmup_ramp(state.step)`` — the
    same f32 multiply :func:`schedules.l1_coeff_schedule` performs with
    the constant, so a tenant's loss trajectory is bitwise the solo run's.
    That lets one vmapped cohort sweep l1 without recompiling per value.
    Incompatible with ``cfg.quant_grads`` (the shard_map path bakes its
    spec list; config validation rejects fleet+quant_grads anyway).

    The returned function is ``step_fn(state, batch, scale)``: ``batch`` may
    be fp32 rows already normalized (``scale`` of ones), or — the TPU fast
    path — RAW bf16 rows straight out of the replay store with the
    per-source norm factors in ``scale``; the upcast and multiply then run
    on device, fused by XLA into the encode (numerically identical to the
    reference's host-side ``acts.float() * factor``, reference
    ``buffer.py:123-124``, at half the host→device bytes).

    ``mask_refresh`` only matters under cached dead masks
    (``cfg.aux_mask_every != 1``): the refresh variant recomputes the
    dead-latent mask from ``steps_since_fired`` and stores it in
    ``aux["dead_mask"]``; the reuse variant reads the cached mask — the
    Trainer alternates them at ``cfg.aux_mask_cadence``, exactly like the
    ``aux_on`` pair.

    ``cfg.quant_grads`` (pure DP only, validated in config) swaps the
    implicit XLA gradient psum for the explicit block-scaled int8
    all-reduce in :mod:`crosscoder_tpu.parallel.quant_ar`: per-device
    gradients are computed inside a shard_map over the ``data`` axis and
    exchanged quantized with error feedback; optimizer, clipping, and
    schedules run outside on the (near-exact) mean gradient, so the step's
    update math is otherwise identical.

    ``held_in`` (the parameters' shardings, given by
    :func:`make_train_step` on a TPU mesh) hands each gradient to the
    optimizer in the layout its parameter — and with it both Adam moments
    — is held in and crosses the step's boundary in: the one the mesh's
    devices keep such a shard in (``mesh_lib.held_layout``, asked of the
    device when the step is traced). Without it the TPU compiler computes
    the update of a leaf whose default tiling the products do not want
    (``W_dec [H, n, d]`` with ``n = 2`` second-minor) in the gradient's
    layout and then copies the new master and both new moments, whole,
    back into the layout they are donated in — three relayouts a step of
    the step's largest arrays; with it the one array relaid is the bf16
    gradient (docs/TUNING.md "The optimizer update's layout"). None (the
    CPU; the fleet) traces exactly the step it always traced, and so does
    the quantized-gradient step whatever is given.

    ``cfg.sparse_bwd`` (the scatter-accumulate backward plane,
    docs/SCALING.md "Sparse backward plane") needs no key of its own in
    the compiled-variant cache: its tier SCOPE rides the ``aux_on`` pair
    already keyed here. ``aux_on=False`` steps pass no dead_mask, so
    :func:`crosscoder_tpu.models.crosscoder.get_losses` traces the
    full-step sparse variant (encode+decode in one custom vjp — zero
    dense backward matmuls); ``aux_on=True`` steps need the pre-acts for
    the AuxK ranking and trace the (h, W_dec)-scoped variant. Both are
    static trace-time decisions off (cfg, batch shape), so each cached
    variant is internally consistent.
    """
    if cfg.batchtopk_threshold > 0:
        # the frozen threshold is EVAL-only (calibrate_batchtopk_threshold):
        # training with it would ignore topk_k and never adapt as weights
        # move — refuse rather than silently train a different objective
        raise ValueError(
            "cfg.batchtopk_threshold is an eval-mode setting; clear it "
            "(0.0) before building a train step"
        )
    lr_fn = schedules.lr_schedule(cfg)
    l1_fn = schedules.l1_coeff_schedule(cfg)
    # fired-tracking runs on EVERY aux-enabled step; the aux loss itself
    # only on aux_on steps (``cfg.aux_every`` amortization — the Trainer
    # compiles both variants and alternates)
    track_fired = cfg.aux_k > 0 or cfg.resample_every > 0
    cached_mask = track_fired and cfg.aux_mask_every != 1
    n_data = int(mesh.shape.get("data", 1))
    use_qgrads = cfg.quant_grads and n_data > 1
    loss_fn = functools.partial(
        cc.training_loss, cfg=cfg, with_metrics=with_metrics,
        track_fired=track_fired,
    )
    if cfg.remat:
        loss_fn = jax.checkpoint(loss_fn)

    warm_fn = schedules.sparsity_warmup_schedule(cfg)

    def _dead_mask(state: TrainState):
        """The dead-latent mask this step trains against: recomputed from
        the tracker (per-step mode, or a cached-mode refresh step) or read
        from the cache (cfg.aux_mask_every reuse steps — saves the compare
        AND breaks the serial dependency on the previous step's fired
        scatter)."""
        if not track_fired:
            return None
        if cached_mask and not mask_refresh:
            return state.aux["dead_mask"]
        thresh = (cfg.aux_dead_steps if cfg.aux_k > 0
                  else cfg.resample_threshold_steps)
        return state.aux["steps_since_fired"] >= thresh

    def _finish(state, grads, l1_coeff, dead, new_ef, loss, mets):
        """Shared tail: optimizer update, aux bookkeeping, metric dict.
        ``mets`` carries the loss surface pieces (already globally reduced
        on the quantized path)."""
        with jax.named_scope("cc/adam"):
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        metrics = {
            "loss": loss,
            "l2_loss": mets["l2_loss"],
            "l1_loss": mets["l1_loss"],
            "l1_coeff": l1_coeff,
            "lr": lr_fn(state.step),
        }
        new_aux = state.aux
        if track_fired or new_ef is not None:
            new_aux = dict(state.aux)
        if track_fired:
            new_aux["steps_since_fired"] = jnp.where(
                mets["fired"], 0, state.aux["steps_since_fired"] + 1
            )
            if cached_mask:
                new_aux["dead_mask"] = dead
            metrics["dead_frac"] = jnp.mean(dead.astype(jnp.float32))
            if "aux_loss" in mets:
                metrics["aux_loss"] = mets["aux_loss"]
        if new_ef is not None:
            new_aux["quant_ef"] = new_ef
        if with_metrics:
            metrics["l0_loss"] = mets["l0_loss"]
            metrics["explained_variance"] = mets["explained_variance"]
            # [n_sources]
            metrics["explained_variance_per_source"] = mets[
                "explained_variance_per_source"
            ]
        new_state = TrainState(new_params, new_opt, state.step + 1, new_aux)
        return new_state, metrics

    def _dense_step(state: TrainState, batch: jax.Array, scale: jax.Array,
                    l1_coeff: jax.Array):
        x = batch.astype(jnp.float32) * scale[None, :, None]
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        kwargs: dict[str, Any] = {}
        if cfg.l0_coeff > 0:
            # L0 warms up over the same window as L1 (reference
            # trainer.py:34-39's ramp, applied to both sparsity terms)
            kwargs["l0_coeff"] = cfg.l0_coeff * warm_fn(state.step)
        # AuxK (dead-latent revival): latents quiet for aux_dead_steps
        # are "dead"; the aux loss reconstructs the step's residual
        # with the top aux_k of them. Same warmup ramp as the other
        # sparsity terms (and naturally inert for the first
        # aux_dead_steps — nothing can be dead yet). ``aux_on=False``
        # (the off-steps of cfg.aux_every amortization) keeps the
        # deadness metric and fired-tracking but compiles the aux
        # ranking+decode out entirely. Resampling-only configs
        # (aux_k == 0, resample_every > 0) track deadness at their
        # own threshold for the metric + the resample fn.
        dead = _dead_mask(state)
        if dead is not None and cfg.aux_k > 0 and aux_on:
            kwargs["dead_mask"] = dead
            kwargs["aux_coeff"] = cfg.aux_k_coeff * warm_fn(state.step)
        (loss, losses), grads = grad_fn(state.params, x, l1_coeff, **kwargs)
        if held_in is not None:
            grads = {name: with_layout_constraint(g, mesh_lib.held_layout(
                state.params[name], held_in[name]))
                     for name, g in grads.items()}
        mets = {
            "l2_loss": losses.l2_loss,
            "l1_loss": losses.l1_loss,
            "fired": losses.fired,
        }
        if dead is not None and cfg.aux_k > 0 and aux_on:
            mets["aux_loss"] = losses.aux_loss
        if with_metrics:
            mets["l0_loss"] = losses.l0_loss
            mets["explained_variance"] = jnp.mean(losses.explained_variance)
            mets["explained_variance_per_source"] = jnp.mean(
                losses.explained_variance_per_source, axis=-1
            )
        return _finish(state, grads, l1_coeff, dead, None, loss, mets)

    def step_fn(state: TrainState, batch: jax.Array, scale: jax.Array):
        return _dense_step(state, batch, scale, l1_fn(state.step))

    def step_fn_l1(state: TrainState, batch: jax.Array, scale: jax.Array,
                   l1_base: jax.Array):
        # same multiply l1_coeff_schedule performs, with the constant
        # replaced by a traced scalar — per-tenant bitwise parity
        return _dense_step(state, batch, scale, l1_base * warm_fn(state.step))

    def quant_step_fn(state: TrainState, batch: jax.Array, scale: jax.Array):
        from jax.sharding import PartitionSpec as P

        from crosscoder_tpu.parallel import quant_ar

        l1_coeff = l1_fn(state.step)
        dead = _dead_mask(state)
        have_l0 = cfg.l0_coeff > 0
        have_aux = dead is not None and cfg.aux_k > 0 and aux_on
        # positional extras keep the shard_map spec list aligned with the
        # actually-engaged loss knobs (all replicated scalars/masks)
        args = [state.params, batch, scale, state.aux["quant_ef"], l1_coeff]
        specs = [P(), mesh_lib.BATCH_SPEC, P(), P("data"), P()]
        if have_l0:
            args.append(cfg.l0_coeff * warm_fn(state.step))
            specs.append(P())
        if have_aux:
            args.append(dead)
            specs.append(P())
            args.append(cfg.aux_k_coeff * warm_fn(state.step))
            specs.append(P())

        def local_fn(params, xb, sc, ef, l1c, *extras):
            """Per-device: loss+grads on the local batch shard, then the
            quantized mean all-reduce; every returned metric is globally
            reduced (pmean of equal-sized shard means = the global mean
            the unquantized step computes)."""
            i = 0
            kw: dict[str, Any] = {}
            if have_l0:
                kw["l0_coeff"] = extras[i]
                i += 1
            if have_aux:
                kw["dead_mask"] = extras[i]
                kw["aux_coeff"] = extras[i + 1]
                i += 2
            x = xb.astype(jnp.float32) * sc[None, :, None]
            (loss, losses), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, x, l1c, **kw
            )
            g, new_ef = quant_ar.quantized_pmean_tree(
                g, ef, "data", n_data, cfg.quant_block
            )
            pm = functools.partial(jax.lax.pmean, axis_name="data")
            mets = {"l2_loss": pm(losses.l2_loss),
                    "l1_loss": pm(losses.l1_loss)}
            if track_fired:
                mets["fired"] = jax.lax.psum(
                    losses.fired.astype(jnp.int32), "data"
                ) > 0
            if have_aux:
                mets["aux_loss"] = pm(losses.aux_loss)
            if with_metrics:
                mets["l0_loss"] = pm(losses.l0_loss)
                mets["explained_variance"] = pm(
                    jnp.mean(losses.explained_variance)
                )
                mets["explained_variance_per_source"] = pm(
                    jnp.mean(losses.explained_variance_per_source, axis=-1)
                )
            return g, new_ef, pm(loss), mets

        grads, new_ef, loss, mets = jax.shard_map(
            local_fn, mesh=mesh, in_specs=tuple(specs),
            out_specs=(P(), P("data"), P(), P()), check_vma=False,
        )(*args)
        if not track_fired:
            mets["fired"] = None
        return _finish(state, grads, l1_coeff, dead, new_ef, loss, mets)

    if l1_input:
        if use_qgrads:
            raise ValueError(
                "l1_input (fleet stacked step) is incompatible with "
                "quant_grads' shard_map path"
            )
        fn = step_fn_l1
    else:
        fn = quant_step_fn if use_qgrads else step_fn
    # the jitted program takes the function's name: say which variant ran
    # (XLA module ``jit_step_fn_bare`` / ``jit_step_fn_full``), so a device
    # trace tells the two apart — trace-time metadata only
    fn.__name__ += "_full" if with_metrics else "_bare"
    return fn


def make_train_step(
    cfg: CrossCoderConfig, mesh, tx, state_shardings, with_metrics: bool = True,
    aux_on: bool = True, mask_refresh: bool = True,
) -> Callable[..., tuple[TrainState, dict[str, jax.Array]]]:
    """Build the compiled train step for a given mesh/optimizer: the
    :func:`make_step_body` body jitted with donated state and the mesh's
    batch/state shardings (see that function's docstring for the step's
    semantics and the variant knobs). On a mesh of TPU devices
    (:func:`update_in_held_layout`) the optimizer update is computed in
    the layout the state is held in — every step built for that mesh, by
    whomever (the Trainer's variants, its remesh prewarm, the bench), is
    that one program."""
    fn = make_step_body(
        cfg, mesh, tx, with_metrics=with_metrics, aux_on=aux_on,
        mask_refresh=mask_refresh,
        held_in=state_shardings.params if update_in_held_layout(mesh) else None,
    )
    batch_sh = mesh_lib.batch_sharding(mesh)
    replicated = NamedSharding(mesh, PartitionSpec())
    return jax.jit(
        fn,
        in_shardings=(state_shardings, batch_sh, replicated),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )


def update_in_held_layout(mesh) -> bool:
    """Whether a step built for ``mesh`` hands its gradients to the
    optimizer in the layouts the state is held in (``make_step_body``'s
    ``held_in``) — the one place that decides, from the mesh the step is
    built FOR (a described topology's and a remesh target's too), never
    from the process that builds it: on TPU devices; the CPU's compiler
    has one layout an array and keeps the step it always had."""
    return mesh.devices.flat[0].platform == "tpu"


def expand_metrics(host_metrics: dict[str, Any], n_sources: int) -> dict[str, float]:
    """Flatten per-source EV into the reference's scalar names
    (``explained_variance_A``/``_B`` for the 2-model case, ``trainer.py:58-60``;
    indexed beyond that)."""
    out: dict[str, float] = {}
    for k, v in host_metrics.items():
        if k == "explained_variance_per_source":
            arr = np.asarray(v)
            for i in range(n_sources):
                out[f"explained_variance_{source_tag(i)}"] = float(arr[i])
        else:
            out[k] = float(v)
    return out


class Trainer:
    """Host-side loop around the compiled step.

    Parameters
    ----------
    cfg: full config.
    buffer: activation source with ``next()``; defaults to the synthetic
        generator (tests/benchmarks) so the trainer is runnable end-to-end
        with no LM in the loop (SURVEY.md §7 "minimum end-to-end slice").
    mesh: optional pre-built device mesh (defaults to all devices, DP-only
        unless ``cfg.model_axis_size`` says otherwise).
    checkpointer: optional; see :mod:`crosscoder_tpu.checkpoint`.
    """

    def __init__(
        self,
        cfg: CrossCoderConfig,
        buffer: Any | None = None,
        mesh=None,
        logger: MetricsLogger | None = None,
        checkpointer: Any | None = None,
        chaos: Any | None = None,
    ) -> None:
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else mesh_lib.mesh_from_cfg(cfg)
        if buffer is None:
            from crosscoder_tpu.data.synthetic import SyntheticActivationSource

            buffer = SyntheticActivationSource(cfg)
        self.buffer = buffer
        self.logger = logger
        self.checkpointer = checkpointer
        self.total_steps = cfg.total_steps
        # --- resilience (docs/resilience.md) ---------------------------
        # chaos: fault-injection hooks on the batch-production path; None
        # (default and all production configs) costs one is-None check
        self.chaos = chaos
        # recovery counters, shared with the checkpointer so its corrupt-
        # artifact skips land in the same resilience/* metric channel
        self.resilience = ResilienceCounters()
        if checkpointer is not None and getattr(checkpointer, "counters", None) is None:
            checkpointer.counters = self.resilience
        self._serve_count = 0       # monotone batch-production index (chaos keys)
        self._rollbacks = 0         # divergence rollbacks this Trainer
        self._loss_ref: float | None = None   # last healthy logged loss
        self._watchdog = None
        if cfg.harvest_timeout_s > 0:
            if jax.process_count() > 1:
                # watchdog retries re-dispatch device programs at host-
                # local times — the same SPMD dispatch-order violation
                # that disables prefetch below
                print("[crosscoder_tpu] harvest watchdog disabled on a "
                      "multi-process mesh (retries would desync cross-host "
                      "dispatch order)", flush=True, file=sys.stderr)
            else:
                from crosscoder_tpu.resilience.watchdog import Watchdog

                self._watchdog = Watchdog(
                    cfg.harvest_timeout_s, retries=cfg.harvest_retries,
                    backoff_s=cfg.harvest_backoff_s, name="harvest",
                    counters=self.resilience,
                )
        # elastic membership (cfg.elastic; resilience/elastic.py): liveness
        # probes at the stop-poll cadence + survivor re-mesh on confirmed
        # peer loss. None when off (default): the loop carries only is-None
        # checks and the step HLO is byte-identical (contracts rule
        # hlo-elastic-off-identity).
        self._elastic = None
        if cfg.elastic == "on":
            from crosscoder_tpu.resilience.elastic import ElasticController

            # chaos rides along for the probe-path faults (flaky/slow) —
            # the controller's hysteresis is what they must exercise
            self._elastic = ElasticController(
                cfg, counters=self.resilience, chaos=chaos
            )
        # --- observability (cfg.obs; docs/OBSERVABILITY.md) ------------
        # None when off (the default): every hook below is a plain
        # is-None check — the compiled step HLO and the transfer counts
        # are byte-identical to a build without the plane
        # (tests/test_obs.py). When on: span tracer installed process-
        # globally (buffer/checkpointer/watchdog spans light up), perf/*
        # and comm/* registry metrics merge into the log stream, and step
        # compiles are AOT'd + reported via utils.compile_cache.observed.
        # The plane belongs to the job: make_buffer has usually created it
        # already (set-up is traced too) and this adopts it; close() ends it.
        self._obs = obs.acquire(cfg, mesh=self.mesh)
        # persistent AOT disk tier (cfg.compile_cache_dir; docs/SCALING.md
        # "Persistent compile cache"): off (the default) configures
        # nothing and every compile path below stays byte-identical
        compile_cache.configure(
            cfg, registry=self._obs.registry if self._obs is not None
            else None)
        # batch dtype actually served this run — the remesh prewarm keys
        # its target-topology avals with it (None until the first step)
        self._batch_dtype = None

        self._tx = tx = make_optimizer(cfg, schedules.lr_schedule(cfg))
        with trace.span("init_state"):
            # n_data pins the quant_grads error-feedback residual shapes to
            # THIS mesh (checkpoints of quant runs restore on a same-width mesh)
            state = init_train_state(
                jax.random.key(cfg.seed), cfg, tx,
                n_data=int(self.mesh.shape.get("data", 1)),
            )
            self._state_shardings = mesh_lib.state_shardings(self.mesh, state, cfg.shard_sources)
            self.state = multihost.put_global(state, self._state_shardings)
        # the sparse backward plane's dispatch is static per cfg/batch —
        # announce it once so runs record WHICH backward they measured
        # (cfg.sparse_bwd="auto" stays dense off-TPU, on a mesh and at
        # shapes the row kernels refuse), and flag the forced-"on"
        # XLA-scatter fallback: sound, but it is the measured-slow path
        # the kernels exist to beat
        if cc.use_sparse_bwd(cfg, cfg.batch_size):
            kind = ("rows fetched by DMA (ops/row_gather.py)"
                    if cc.use_sparse_bwd(cfg.replace(sparse_bwd="auto"),
                                         cfg.batch_size)
                    else "XLA scatter fallback (forced; expect the dense "
                         "backward to be faster)")
            print(f"[crosscoder_tpu] sparse backward plane active: {kind}",
                  flush=True, file=sys.stderr)
        # likewise the layout the optimizer update is computed in: on a TPU
        # each gradient is handed to the optimizer in the layout its master
        # is held in (make_train_step; a flag, not a count: which leaves'
        # relayouts that spares is the compiler's business)
        held = update_in_held_layout(self.mesh)
        if self._obs is not None:
            self._obs.registry.gauge("perf/step_update_in_held_layout",
                                     float(held))
        if held:
            print("[crosscoder_tpu] optimizer update computed in the layout "
                  "each master is held in: " + "; ".join(
                      f"{name} major_to_minor={lay.major_to_minor} "
                      f"tiling={lay.tiling}" for name, lay in (
                          (name, mesh_lib.held_layout(
                              x, self._state_shardings.params[name]))
                          for name, x in self.state.params.items())),
                  flush=True, file=sys.stderr)
        # compiled step variants, keyed (with_metrics, aux_on, mask_refresh);
        # built lazily except the default. aux_on alternates per
        # cfg.aux_every (AuxK amortization), mask_refresh per
        # cfg.aux_mask_cadence (cached dead masks); the host-side step
        # mirror picks the variant without a device sync. cfg.sparse_bwd
        # adds no key: its tier scope follows aux_on (see make_train_step).
        self._step_fns: dict[tuple[bool, bool, bool], Callable] = {
            (True, True, True): self._wrap_step(
                (True, True, True),
                make_train_step(cfg, self.mesh, tx, self._state_shardings),
            )
        }
        self._host_step = 0
        self._batch_sharding = mesh_lib.batch_sharding(self.mesh)
        # device-resident per-source scale for the raw-bf16 serve path; ones
        # when the source already serves normalized fp32 (synthetic, tests)
        self._scale_dev = None
        self._scale_src = None
        # one-deep prefetch: gather+transfer of batch i+1 overlaps the device
        # executing step i (the C++ gather releases the GIL; see
        # crosscoder_tpu/native). Single worker => the served stream and
        # refresh schedule are byte-identical to the unprefetched loop.
        self._prefetch_pool = None
        self._pending = None
        self._buffer_snapshot = None
        # id of the current refill_wait span (one per step): a production
        # names the wait that consumes it, so the trace joins the producer
        # thread's ``produce`` spans to the main thread's waits
        self._wait_seq = 0
        # Narrows the window of interleaved jax enqueues between the main
        # thread (step) and the prefetch worker (batch device_put). JAX
        # dispatch is documented thread-safe — the buffer's own harvest
        # dispatches intentionally stay concurrent with steps — but the
        # trainer's two per-step enqueues are cheap to serialize.
        self._dispatch_lock = threading.Lock()
        # multi-process SPMD requires every process to enqueue the same
        # programs in the same order; a prefetch thread racing its
        # (collective) serve gather against the main thread's step would
        # resolve differently on each host — a cross-process rendezvous
        # mismatch. Historically that disabled prefetch on pods; the
        # launch sequencer fixes the ORDER instead: every launch site
        # reserves a ticket on the main thread in program order (identical
        # across processes by SPMD construction) and executes under that
        # ticket's turn (utils/pipeline.LaunchSequencer).
        self._sequencer = None
        if cfg.prefetch:
            if multihost.needs_launch_tickets():
                self._sequencer = pipeline.LaunchSequencer()
                print("[crosscoder_tpu] multi-process prefetch: program "
                      "launches run under ticketed dispatch ordering",
                      flush=True, file=sys.stderr)
            self._prefetch_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="batch-prefetch"
            )

    def restore(self, version_dir=None, save: int | None = None) -> dict:
        """Resume from a checkpoint: full TrainState + data-pipeline state
        (the capability the reference lacks — its ``load`` is analysis-only,
        reference crosscoder.py:207-217)."""
        if self.checkpointer is None:
            raise ValueError("Trainer has no checkpointer to restore from")
        # Quiesce the prefetch worker but don't discard its batch yet:
        # whether that batch is stale depends on whether this checkpoint
        # carries buffer stream state to rewind to. For a source without
        # load_state_dict (any object with next() is allowed), the stream
        # is NOT rewound, so discarding would silently skip one batch.
        self._drain_prefetch()
        # the state being replaced is dead weight from here on: release it
        # BEFORE the checkpoint's arrays land, or the device holds two full
        # TrainStates at once — next to the LM pair that does not fit a
        # 16 GB chip at dict 2^15 with fp32 masters (save() and the
        # rollback loop already tolerate a Trainer whose restore raised)
        self.state = None
        # n_data pins the respec template to THIS mesh (restore-with-respec:
        # a checkpoint from a different layout restores fine, quant_ef
        # residuals reset — see Checkpointer.restore)
        state, meta = self.checkpointer.restore(
            self.cfg, self._tx, version_dir, save,
            n_data=int(self.mesh.shape.get("data", 1)),
        )
        self.state = multihost.put_global(state, self._state_shardings)
        # host mirror of the device step counter (aux_every variant choice
        # without a per-step sync); one sync here at restore is fine
        self._host_step = int(self.state.step)
        if "buffer" in meta and hasattr(self.buffer, "load_state_dict"):
            # the stream rewinds to the checkpoint position — the prefetched
            # batch belongs to the abandoned position; now it is stale
            self._drain_prefetch(discard=True)
            self.buffer.load_state_dict(meta["buffer"])
        elif hasattr(self.buffer, "ensure_filled"):
            # checkpoint carries no buffer state (foreign/weights-only save):
            # fall back to a fresh calibrate+fill now, not a crash mid-loop
            print("[crosscoder_tpu] checkpoint has no buffer state; refilling fresh", file=sys.stderr)
            self.buffer.ensure_filled()
        return meta

    @property
    def step_counter(self) -> int:
        return int(self.state.step)

    def _variant_label(self, key: tuple[bool, bool, bool]) -> str:
        """The canonical compile-event label for one step variant,
        including the encoder tier traced into it (trace-time static):
        aux-on steps keep the dense encode (the h-residual escape
        hatch), so the enc tag follows the aux key."""
        enc = "dense"
        if not (key[1] and self.cfg.aux_k > 0) and cc.use_fused_encoder(
                self.cfg, self.cfg.batch_size):
            enc = "fused-int8" if self.cfg.quant_encoder else "fused"
        return compile_cache.variant_key(*key, enc=enc)

    def _compile_scope(self, mesh=None):
        """``(mesh topology, step-knob projection hash)`` — the scope
        half of this trainer's persistent compile-cache keys; ``None``
        (no disk lookups) when the tier is off. A step whose update is
        computed in the held layouts says so in a third element: a cache
        directory filled before that form existed holds, under the first
        two, the step that copies its state back every step."""
        if not compile_cache.disk_enabled():
            return None
        mesh = self.mesh if mesh is None else mesh
        scope = (tuple(sorted(mesh.shape.items())),
                 compile_cache.step_digest(self.cfg.to_dict()))
        if update_in_held_layout(mesh):
            scope += ("update-in-held-layout",)
        return scope

    def _wrap_step(self, key: tuple[bool, bool, bool], fn: Callable) -> Callable:
        """Compile-event observation + persistent-cache scoping for one
        step variant. With obs off AND the disk tier off (the default)
        the jitted fn is returned untouched, so that path calls exactly
        what it always called."""
        if self._obs is None and not compile_cache.disk_enabled():
            return fn
        label = self._variant_label(key)
        scope = self._compile_scope()
        if self._obs is not None:
            return self._obs.observe_step(label, fn, disk_scope=scope)
        # disk tier without the obs plane: spans go to the (null) global
        # tracer and no compile event is reported — but warm starts work
        return compile_cache.observed(fn, label, None, disk_scope=scope)

    def _device_scale(self) -> jax.Array:
        """Replicated per-source scale, re-uploaded only when the factors'
        VALUES change (calibration / resume) — cached by value, not object
        identity, since numpy can reuse a freed allocation's id."""
        src = getattr(self.buffer, "normalisation_factor", None)
        if hasattr(self.buffer, "next_raw") and src is not None:
            vec = np.asarray(src, np.float32)
        else:
            vec = np.ones((self.cfg.n_sources,), np.float32)
        if self._scale_src is None or not np.array_equal(self._scale_src, vec):
            self._scale_dev = multihost.put_global(
                vec, NamedSharding(self.mesh, PartitionSpec())
            )
            self._scale_src = vec.copy()
        return self._scale_dev

    def _serve_once(self, serve: int) -> Any:
        """One buffer serve, with the chaos hooks around it (both no-ops
        unless a chaos plan was injected — tests/staging only)."""
        if self.chaos is not None:
            self.chaos.on_serve(serve)
            if self._elastic is not None and self.chaos.take_return(serve):
                # return@serve: the fleet granted capacity back — open
                # the rejoin window (the board write is atomic, so this
                # is safe from the prefetch worker too); the grow itself
                # happens at the controller's next poll boundary
                self._elastic.open_rejoin_window(serve)
        if hasattr(self.buffer, "next_raw"):
            batch = self.buffer.next_raw()
        else:
            batch = self.buffer.next()
        if self.chaos is not None:
            batch = self.chaos.poison_batch(batch, serve)
        return batch

    def _reserve_ticket(self) -> int | None:
        """Claim the next pod-wide launch slot. None without a sequencer
        (single-process, or prefetch off): only one thread launches there,
        so program order needs no tickets."""
        if self._sequencer is None:
            return None
        return self._sequencer.reserve()

    def _launch_turn(self, ticket: int | None):
        """Context for executing launches under a reserved slot (a
        nullcontext for ``ticket=None`` — the zero-cost single-process
        path)."""
        if ticket is None:
            return contextlib.nullcontext()
        return self._sequencer.turn(ticket)

    def _produce_batch(self, ticket: int | None = None,
                       wait: int | None = None) -> tuple[jax.Array, jax.Array]:
        """Gather the next batch and start its host→device transfer.

        Runs on the prefetch worker when prefetching is on. Raw-bf16 serving
        (``next_raw``) is preferred: the norm factors ride separately and are
        applied inside the compiled step. With ``cfg.harvest_timeout_s``
        set, the serve runs under the watchdog (stall detection + backoff
        retry of exceptions; chaos faults raise/stall at the serve's entry,
        before buffer state moves, so a retried serve is safe). On a
        ticketed (multi-process) run the whole production executes under
        its reserved launch slot — the serve gather's collectives then
        land in the pod-wide enqueue order the ticket fixed.

        The ``produce`` span brackets the whole production on whichever
        thread runs it; ``wait`` is the id of the ``refill_wait`` span (on
        the main thread) that will consume this batch — its cause.
        """
        with trace.span("produce", wait=wait), self._launch_turn(ticket):
            serve = self._serve_count
            self._serve_count += 1
            if self._watchdog is not None:
                batch = self._watchdog.call(lambda: self._serve_once(serve))
            else:
                batch = self._serve_once(serve)
            if self._obs is not None:
                # measured transfer accounting (comm/*): one host→device batch
                # upload per produced batch (a no-op put for device-resident
                # stores — still the serve path's dispatch, counted as such)
                self._obs.registry.count("comm/h2d_transfers")
            with self._dispatch_lock:
                return (multihost.put_global(batch, self._batch_sharding),
                        self._device_scale())

    def _submit_prefetch(self, wait: int) -> None:
        # Stream-state snapshot BEFORE producing the next batch: a checkpoint
        # written while batch i+1 sits prefetched must record the stream at
        # position i+1's start, or resume would skip that batch (the buffer
        # is quiescent here — the previous production was just consumed).
        if hasattr(self.buffer, "state_dict"):
            self._buffer_snapshot = self.buffer.state_dict()
        ticket = self._reserve_ticket()
        try:
            self._pending = self._prefetch_pool.submit(
                self._produce_batch, ticket, wait)
        except BaseException:
            if ticket is not None:
                # a reservation that never runs would wedge every later
                # turn — release it before propagating
                self._sequencer.skip(ticket)
            raise

    def _next_batch(self) -> tuple[tuple[jax.Array, jax.Array], int | None]:
        """The consumed batch plus the launch ticket for the step that will
        train on it (None on unticketed runs)."""
        if self._prefetch_pool is None:
            return (self._produce_batch(wait=self._wait_seq),
                    self._reserve_ticket())
        if self._pending is None:
            self._submit_prefetch(self._wait_seq)
        out = self._pending.result()
        # reserve the step's launch slot BEFORE submitting the next
        # production: the step's enqueue then precedes the worker's in the
        # pod-wide launch order, so the production overlaps the step's
        # device execution instead of serializing in front of it
        ticket = self._reserve_ticket()
        self._submit_prefetch(self._wait_seq + 1)     # the next wait's batch
        return out, ticket

    def _drain_prefetch(self, discard: bool = False) -> None:
        """Wait for in-flight batch production so buffer state is quiescent
        (checkpointing); ``discard`` additionally drops the produced batch
        (restore: the stream position it came from is being replaced).

        A failure in the SPECULATIVE batch (one past what training consumed —
        e.g. an exhausted source) must not abort the checkpoint being
        written; it is swallowed here and will re-raise on the main thread
        if and when that batch is actually consumed by ``step()``.

        A production that has not started yet is cancelled instead of
        awaited — it may hide a multi-second half-buffer re-harvest whose
        result would be thrown away (restore) or never consumed (final
        save); on successful cancel the live buffer state IS the snapshot.
        Ticketed (multi-process) runs never cancel: cancel-if-not-started
        is thread-timing dependent, so it would diverge per process (and
        leak the production's reserved ticket, wedging every later turn).
        """
        if self._pending is not None:
            if self._sequencer is None and self._pending.cancel():
                self._pending = None
                self._buffer_snapshot = None
                return
            try:
                self._pending.result()
            except Exception:
                pass
            finally:
                if discard:
                    self._pending = None
                    self._buffer_snapshot = None

    def close(self) -> None:
        """Release worker threads and land background writes. Idempotent:
        train() closes in its ``finally`` and main()'s own try/finally
        closes again on early exits — the second call is a no-op."""
        if self._prefetch_pool is not None:
            self._prefetch_pool.shutdown(wait=True)
            self._prefetch_pool = None
            self._pending = None
        if hasattr(self.buffer, "close"):
            # stop the buffer's refill dispatcher thread (overlap engine;
            # a no-op with refill_overlap off — buffer.close is idempotent)
            self.buffer.close()
        if self._watchdog is not None:
            self._watchdog.close()
            self._watchdog = None
        if self.checkpointer is not None and hasattr(self.checkpointer, "wait"):
            # land any background checkpoint write before process exit
            self.checkpointer.wait()
        if self._obs is not None:
            # write the trace file and hand the process-global tracer back
            self._obs.close()
            self._obs = None

    def step(self, full_metrics: bool = True) -> dict[str, jax.Array]:
        """One optimizer step; returns device-resident metrics (no sync).

        ``full_metrics=False`` runs the bare variant — identical parameter
        update, but the metric-only reductions (l0, explained variances;
        ~13% of the step on TPU) are compiled out and absent from the
        returned dict. ``train()`` uses it off log-steps.
        """
        cfg = self.cfg
        key = variant_for_step(cfg, self._host_step, full_metrics)
        fn = self._step_fns.get(key)
        if fn is None:
            fn = self._step_fns[key] = self._wrap_step(key, make_train_step(
                cfg, self.mesh, self._tx, self._state_shardings,
                with_metrics=key[0], aux_on=key[1], mask_refresh=key[2],
            ))
        # refill_wait: the train loop blocked on batch production — the
        # numerator of perf/refill_bubble_frac. With prefetch on this is
        # only the non-overlapped residue of the production (where a
        # device-bound loop parks); with it off, the full production time.
        self._wait_seq += 1
        with trace.span("refill_wait", id=self._wait_seq):
            (batch, scale), ticket = self._next_batch()
        if self._batch_dtype is None:
            # the dtype the stream actually serves — the remesh prewarm
            # keys its target-topology batch aval with it
            self._batch_dtype = batch.dtype
        # the resample + step launches run under this step's reserved
        # launch slot on ticketed (multi-process) runs — a nullcontext
        # otherwise. Lock order: turn (outermost) → dispatch lock → guard;
        # the worker takes its own turn before the dispatch lock too, so
        # the ordering is acyclic.
        with self._launch_turn(ticket):
            n_resampled = None
            if (cfg.resample_every > 0 and self._host_step > 0
                    and self._host_step % cfg.resample_every == 0):
                # dead-latent resampling on the batch about to be trained on
                # (train/resample.py); runs BEFORE the step so the revived
                # latents' first gradients come from this same batch
                if getattr(self, "_resample_fn", None) is None:
                    from crosscoder_tpu.train.resample import make_resample_fn

                    self._resample_fn = make_resample_fn(
                        cfg, self.mesh, self._state_shardings
                    )
                rkey = jax.random.fold_in(
                    jax.random.key(cfg.seed + 0x5EED), self._host_step
                )
                with self._dispatch_lock, pipeline.sharded_program_guard():
                    self.state, n_resampled = self._resample_fn(
                        self.state, batch, scale, rkey
                    )
                    pipeline.finish_on_cpu((self.state, n_resampled))
            # the step program runs under the process-wide guard: on XLA:CPU
            # its collectives must not execute concurrently with another
            # sharded program (a second trainer's step, a producer thread's
            # harvest) — see pipeline.sharded_program_guard
            with self._dispatch_lock, pipeline.sharded_program_guard(), \
                    trace.span("step", step=self._host_step,
                               variant="full" if key[0] else "bare"):
                self.state, metrics = fn(self.state, batch, scale)
                pipeline.finish_on_cpu((self.state, metrics))
        if n_resampled is not None:
            metrics["resampled"] = n_resampled
        self._host_step += 1
        return metrics

    def log(self, metrics: dict[str, Any], step: int) -> None:
        if self.logger is not None:
            scalars = expand_metrics(metrics, self.cfg.n_sources)
            # resilience/* counters ride along only when a recovery has
            # actually happened (snapshot of an untouched instance is {}),
            # so fault-free runs log exactly the reference's scalar surface
            scalars.update(self.resilience.snapshot())
            # paged harvest runtime only (padded runs log exactly the
            # reference's scalar surface): the running real-token fraction
            # of everything harvested — the live denominator of the
            # runtime's matmul win (docs/SCALING.md "Harvest cost model")
            eff = getattr(self.buffer, "padding_efficiency", None)
            eff = eff() if callable(eff) else None
            if eff is not None:
                scalars["harvest/padding_efficiency"] = eff
            # perf/* + comm/* telemetry (cfg.obs="on" only; an untouched
            # registry snapshots to {} exactly like the resilience channel)
            if self._obs is not None:
                scalars.update(self._obs.snapshot())
            self.logger.log(scalars, step)

    # --- divergence guard + rollback (cfg.guard_loss; docs/resilience.md) --

    def _loss_diverged(self, loss_val: float) -> bool:
        """Divergence test on the loss the log step ALREADY fetched — the
        guard adds no host sync anywhere. Non-finite always diverges; a
        finite loss diverges when it spikes past ``cfg.loss_spike_factor``
        × the last healthy logged loss (None right after start/rollback,
        so the first log of each stretch re-establishes the reference)."""
        if not math.isfinite(loss_val):
            return True
        ref = self._loss_ref
        if ref is not None and loss_val > self.cfg.loss_spike_factor * max(ref, 1e-12):
            return True
        self._loss_ref = loss_val
        return False

    def _params_finite(self) -> bool:
        """All-finite check of the (restored) params — a device sync, used
        only inside rollback, never on the step fast path."""
        return all(
            bool(jnp.all(jnp.isfinite(v.astype(jnp.float32))))
            for v in self.state.params.values()
        )

    def _rollback(self, detect_step: int) -> None:
        """Recover from a diverged step: restore the newest intact save
        whose params are finite (a save can itself carry poisoned state if
        the NaN landed just before it fired), then skip the poisoned data
        window — the batches between the restored step and the detection
        point are consumed unserved, so the retrained stretch runs on
        fresh data past the fault instead of replaying it. Bounded by
        ``cfg.max_rollbacks`` per train(); exhausting the budget aborts
        loudly (a fault that reproduces past the skipped window is a bug,
        not a transient)."""
        cfg = self.cfg
        self._rollbacks += 1
        if self._rollbacks > cfg.max_rollbacks:
            raise RuntimeError(
                f"loss diverged at step {detect_step} and the rollback "
                f"budget (max_rollbacks={cfg.max_rollbacks}) is exhausted; "
                f"aborting. resilience counters: {self.resilience.snapshot()}"
            )
        if self.checkpointer is None:
            raise RuntimeError(
                f"loss diverged at step {detect_step} but the trainer has "
                "no checkpointer to roll back to"
            )
        self.resilience.bump("rollbacks")
        print(f"[crosscoder_tpu] divergence at step {detect_step}: rolling "
              f"back ({self._rollbacks}/{cfg.max_rollbacks})", flush=True, file=sys.stderr)
        meta = self.restore()   # newest checksum-verified save
        cand_v = meta["save_version"]
        while not self._params_finite():
            self.resilience.bump("poisoned_save_skips")
            vdir = self.checkpointer.save_dir
            older = sorted(
                s for s in self.checkpointer.complete_saves(vdir) if s < cand_v
            )
            restored = False
            while older and not restored:
                cand_v = older.pop()          # newest remaining first
                try:
                    meta = self.restore(version_dir=vdir, save=cand_v)
                    restored = True
                except (ValueError, FileNotFoundError):
                    continue                  # corrupt/torn: try older
            if not restored:
                raise RuntimeError(
                    f"divergence rollback found no intact save with finite "
                    f"params under {vdir}; aborting"
                )
        # branch truncation: saves newer than the one restored may carry
        # the poisoned state this rollback escaped — a later auto-resume
        # must not pick them
        if hasattr(self.checkpointer, "discard_saves_after"):
            self.checkpointer.discard_saves_after(
                self.checkpointer.save_dir, cand_v
            )
        # skip the poisoned window: the serves covering (restored_step,
        # detect_step] are consumed and discarded, so the fault's batch
        # never reaches a step again
        n_skip = max(0, detect_step + 1 - self.step_counter)
        for _ in range(n_skip):
            serve = self._serve_count
            self._serve_count += 1
            self._serve_once(serve)
        if n_skip:
            self.resilience.bump("skipped_batches", n_skip)
        self._loss_ref = None   # re-establish the spike reference fresh
        print(f"[crosscoder_tpu] rolled back to step {self.step_counter} "
              f"(save {cand_v}), skipped {n_skip} poisoned batches",
              flush=True, file=sys.stderr)

    def _final_save_agreed(self, clean: bool) -> bool:
        """All-processes-clean agreement for the final collective save,
        WITHOUT risking an indefinite hang.

        A process that failed must never enter an unbounded collective:
        parking it in an allgather keeps it alive, masks the failure from
        the distributed runtime's heartbeat, and hangs every healthy
        host's next collective forever. So: local failure → return False
        immediately (fast-fail, the runtime's failure detection unblocks
        the others). Clean processes agree through the coordination
        service's host-level barrier, which is TIMEOUT-BOUNDED — if any
        peer died or skipped the barrier, the wait expires and the
        healthy hosts skip the save instead of deadlocking in it.
        """
        if not clean:
            return False
        # jax._src is a private namespace: a jax upgrade can move it. That
        # must degrade to "skip the final save, periodic saves already
        # landed" with a loud warning — not an ImportError out of train()'s
        # finally block that turns an otherwise clean run into a failure.
        # Import failure is detected SEPARATELY from the barrier try below
        # so a missing client is never mistaken for a barrier timeout.
        try:
            from jax._src import distributed
            client = distributed.global_state.client
        except (ImportError, AttributeError) as e:
            print(f"[crosscoder_tpu] coordination-service client lookup "
                  f"failed ({type(e).__name__}: {e}); this jax version moved "
                  f"the private jax._src.distributed path — skipping the "
                  f"final collective save (periodic saves already landed)",
                  flush=True, file=sys.stderr)
            return False
        if client is None:
            # no coordination client on a multi-process mesh (should not
            # happen — multihost.initialize creates one): any agreement
            # collective here would be UNBOUNDED and recreate the pod
            # deadlock this function exists to prevent; skip the save
            print("[crosscoder_tpu] no coordination-service client: "
                  "skipping the final collective save (periodic saves "
                  "already landed)", flush=True, file=sys.stderr)
            return False
        try:
            # same id on every process at a clean exit (same step);
            # step-suffixed so a retried/looped train() reuses nothing
            client.wait_at_barrier(
                f"crosscoder_tpu_final_save_{int(self.state.step)}",
                timeout_in_ms=60_000,
            )
            return True
        except Exception as e:  # timeout or a peer died mid-barrier
            print(f"[crosscoder_tpu] final-save barrier not reached by all "
                  f"processes ({e}); skipping the collective save", flush=True, file=sys.stderr)
            return False

    def save(self, background: bool = False) -> None:
        """Checkpoint now. ``background=True`` (the train loop's periodic
        saves) returns after the device→host fetch and streams the file
        write concurrently with subsequent steps; callers that need the
        files on disk when this returns (tests, scripts) use the default.

        ALL processes enter: the state fetch inside Checkpointer.save is
        a collective on a multi-host mesh (process_allgather of
        non-addressable leaves); only process 0 writes files.
        """
        if self.checkpointer is not None and self.state is not None:
            # quiesce the prefetch worker (no mid-next() device contention)
            # AND the buffer's offloaded refill dispatcher (overlap engine:
            # its thread mutates cycle state the stream snapshot reads —
            # without the drain a save racing a dispatch could record a
            # TORN snapshot), then checkpoint the PRE-prefetch stream
            # snapshot so resume replays the in-flight batch instead of
            # skipping it
            self._drain_prefetch()
            self._quiesce_refill()
            buffer = self.buffer
            if self._pending is not None and self._buffer_snapshot is not None:
                snap = self._buffer_snapshot
                buffer = types.SimpleNamespace(state_dict=lambda: snap)
            self.checkpointer.save(
                self.state, self.cfg, buffer=buffer, background=background
            )

    def _quiesce_refill(self) -> None:
        """Drain the buffer's refill dispatcher so no background thread
        mutates cycle state under a snapshot. A harvest error surfacing
        from the drain must NOT abort the save in progress — the stream
        snapshot is consistent either way (the cycle bookkeeping only
        advances under the drained pump), and the final/SIGTERM save is
        exactly when losing the checkpoint hurts most; the error is
        reported and otherwise dropped (the run is exiting or will hit it
        again on the next serve)."""
        q = getattr(self.buffer, "_quiesce_dispatch", None)
        if q is None:
            return
        try:
            q()
        except Exception as e:
            print(f"[crosscoder_tpu] refill drain raised during save "
                  f"quiesce ({type(e).__name__}: {e}); saving anyway"[:400],
                  flush=True, file=sys.stderr)

    def _start_remesh_prewarm(self) -> threading.Thread | None:
        """Kick off the background compile-prewarm for the post-shrink
        topology (persistent tier on only — with ``compile_cache_dir``
        unset this returns ``None`` and the remesh path is byte-for-byte
        the pre-tier sequence). The thread runs concurrently with the
        quiesce/drain below and MUST be joined before the backend reset:
        it lowers against the dying backend's devices."""
        if not compile_cache.disk_enabled():
            return None
        t = threading.Thread(
            target=self._prewarm_for_local_mesh,
            args=(list(self._step_fns),),
            name="remesh-prewarm", daemon=True)
        t.start()
        return t

    def _prewarm_for_local_mesh(self, keys: list) -> None:
        """Best-effort: compile the step variants this run uses for the
        survivor-local mesh — the topology ``_elastic.shrink()`` will
        produce — and persist them to the disk tier, so the re-meshed
        world's first step deserializes instead of compiling (the
        compile falls out of the ``remesh_ms`` downtime window). Every
        failure is swallowed: prewarm may only ever remove compile time,
        never add faults; a wrong topology guess just leaves an unused
        entry behind."""
        try:
            cfg = self.cfg
            disk = compile_cache.disk_cache()
            mesh = mesh_lib.make_mesh(devices=jax.local_devices())
            template = jax.eval_shape(
                lambda k: init_train_state(
                    k, cfg, self._tx,
                    n_data=int(mesh.shape.get("data", 1))),
                jax.random.key(cfg.seed))
            shardings = mesh_lib.state_shardings(
                mesh, template, cfg.shard_sources)
            state_sh = jax.tree_util.tree_map(
                lambda s, sh: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=sh),
                template, shardings)
            batch = jax.ShapeDtypeStruct(
                (cfg.batch_size, cfg.n_sources, cfg.d_in),
                self._batch_dtype or jnp.float32,
                sharding=mesh_lib.batch_sharding(mesh))
            scale = jax.ShapeDtypeStruct(
                (cfg.n_sources,), jnp.float32,
                sharding=NamedSharding(mesh, PartitionSpec()))
            scope = self._compile_scope(mesh)
            for key in keys:
                label = self._variant_label(key)
                dk = compile_cache.observed_digest(
                    label, scope, (state_sh, batch, scale))
                if dk is None or disk is None or disk.has(dk):
                    continue
                fn = make_train_step(
                    cfg, mesh, self._tx, shardings,
                    with_metrics=key[0], aux_on=key[1],
                    mask_refresh=key[2])
                lowered = fn.lower(state_sh, batch, scale)
                disk.store(dk, lowered.compile(), variant=label,
                           topology=str(dict(mesh.shape)),
                           lower=lambda lw=lowered: lw)
                print(f"[crosscoder_tpu] elastic: prewarmed {label} for "
                      f"mesh {dict(mesh.shape)}",
                      file=sys.stderr, flush=True)
        except Exception as e:
            print(f"[crosscoder_tpu] elastic: remesh prewarm skipped "
                  f"({type(e).__name__}: {e})"[:300],
                  file=sys.stderr, flush=True)

    def _remesh_and_resume(self, cause: BaseException) -> None:
        """Survivor recovery (cfg.elastic; docs/resilience.md "Elastic
        membership"): quiesce every consumer of the dying backend, shrink
        the world to this host's local devices, re-derive the mesh-coupled
        trainer pieces, and restore from the newest verified checkpoint.
        On hosts that cannot survive (non-coordinator — the coordination
        service died with its host) the shrink raises :class:`PeerLoss`,
        which propagates and ends the run there. Full recovery wall time
        accumulates in ``resilience/remesh_ms``."""
        t0 = time.perf_counter()
        with trace.span("remesh"):
            print(f"[crosscoder_tpu] elastic: peer loss confirmed "
                  f"({type(cause).__name__}); re-meshing over survivors",
                  flush=True, file=sys.stderr)
            # 0. prewarm (persistent tier only): compile the target
            #    topology's step variants to disk IN THE BACKGROUND while
            #    the quiesce below drains — the post-rebuild first step
            #    then deserializes, and compile wall falls out of the
            #    remesh downtime window
            prewarm = self._start_remesh_prewarm()
            # 1. quiesce: nothing may touch the dying backend past here.
            #    The prefetched batch (if any) belongs to the dead world;
            #    its production may itself have died on the torn collective.
            #    Tickets reserved before the epoch change are invalidated
            #    FIRST: a worker parked in a turn that will never come
            #    would wedge the drain below behind it (the stale-epoch
            #    ticket hazard — LaunchSequencer.invalidate).
            if self._sequencer is not None:
                self._sequencer.invalidate()
            try:
                self._drain_prefetch(discard=True)
            except Exception:
                pass
            self._pending = None
            self._buffer_snapshot = None
            self._quiesce_refill()
            if hasattr(self.buffer, "prepare_reshard"):
                # park the LM params to host BEFORE the backend reset
                # invalidates every live device array
                self.buffer.prepare_reshard()
            if self.checkpointer is not None:
                try:
                    self.checkpointer.wait()  # land any background write
                except Exception:
                    pass
            if prewarm is not None:
                # joined BEFORE the reset: the prewarm thread lowers
                # against the dying backend's devices
                prewarm.join(timeout=300.0)
            # 2. shrink: tear down the distributed runtime, bump the mesh
            #    epoch, reset the backend (all device buffers die here)
            mesh = self._elastic.shrink()
            # 3. re-derive everything the old mesh shaped
            self._rebuild_for_mesh(mesh)
            if hasattr(self.buffer, "reshard"):
                # refill=False: restore() below replays the CHECKPOINT's
                # buffer snapshot, not the dead live stream
                self.buffer.reshard(self._batch_sharding, refill=False)
            # 4. restore-with-respec from the newest verified checkpoint
            meta = self.restore()
        ms = 1000 * (time.perf_counter() - t0)
        # which world the survivor resumed from — drills/tests read this to
        # replay the identical restore on a clean restart
        self.last_remesh = {
            "step": int(meta.get("step", -1)),
            "save": int(meta.get("save_version", -1)),
            "epoch": self._elastic.epoch(),
            "remesh_ms": int(ms),
        }
        self.resilience.bump("remesh_ms", int(ms))
        # anchor the grow controller's dwell clock at the resumed step so
        # a rejoin cannot re-mesh again before cfg.elastic_dwell_steps
        self._elastic.note_remesh(self._host_step)
        print(f"[crosscoder_tpu] elastic: resumed at step "
              f"{self._host_step} on mesh {dict(self.mesh.shape)} "
              f"({ms:.0f} ms recovery)", flush=True, file=sys.stderr)

    def _rebuild_for_mesh(self, mesh) -> None:
        """Point every mesh-coupled trainer piece at ``mesh``: shardings,
        the compiled step-variant cache (cleared — ``step()`` recompiles
        lazily on the new mesh), the batch sharding, the serve-path scale
        cache, the resample fn, and the launch sequencer (the post-shrink
        world is single-process, so ticketed dispatch ordering retires).
        The live ``state`` is dropped — its buffers died with the old
        backend; the caller restores from checkpoint."""
        cfg = self.cfg
        self.mesh = mesh
        template = init_train_state(
            jax.random.key(cfg.seed), cfg, self._tx,
            n_data=int(mesh.shape.get("data", 1)),
        )
        self._state_shardings = mesh_lib.state_shardings(
            mesh, template, cfg.shard_sources
        )
        self.state = None
        self._step_fns = {}
        self._host_step = 0
        self._batch_sharding = mesh_lib.batch_sharding(mesh)
        self._scale_dev = None
        self._scale_src = None
        self._resample_fn = None
        if self._sequencer is not None:
            # idempotent with the quiesce-path invalidate: no ticket of
            # the old epoch may survive into the new world's ordering
            self._sequencer.invalidate()
        self._sequencer = None
        if cfg.prefetch and multihost.needs_launch_tickets():
            self._sequencer = pipeline.LaunchSequencer()

    def _grow_and_resume(self, step: int) -> None:
        """Scale-UP recovery (cfg.elastic_grow; docs/resilience.md
        "Elastic scale-up"): the shrunk survivor admits its debounced
        rejoin candidates, writes the admission BOUNDARY save (state +
        stream snapshot at exactly this step), re-forms the wider world,
        and every member — survivor included — restores that save. Zero
        lost steps, no fleet-wide restart, and a post-grow trajectory
        bitwise-identical to a clean start at the wide shape from the
        same save (the acceptance drill's equality). A failed rendezvous
        falls back to the narrow world and keeps training. Wall time
        accumulates in ``resilience/grow_ms``."""
        t0 = time.perf_counter()
        with trace.span("grow"):
            print(f"[crosscoder_tpu] elastic: rejoin candidates debounced; "
                  f"growing at step {step}", flush=True, file=sys.stderr)
            if compile_cache.disk_enabled():
                # the wide mesh is not locally constructible before the
                # rendezvous (its devices don't exist here yet), so no
                # compile prewarm — warm starts come from entries a
                # previous wide-world run persisted; the post-rebuild
                # lookups deserialize on hit exactly like the shrink path
                n = compile_cache.disk_entry_count()
                print(f"[crosscoder_tpu] elastic: persistent compile "
                      f"cache holds {n} entr{'y' if n == 1 else 'ies'} "
                      f"for the post-grow warm start",
                      file=sys.stderr, flush=True)
            # 1. quiesce, exactly like the shrink path: invalidate stale
            #    tickets first, then drain every consumer of the backend
            #    that is about to be reset
            if self._sequencer is not None:
                self._sequencer.invalidate()
            try:
                self._drain_prefetch(discard=True)
            except Exception:
                pass
            self._pending = None
            self._buffer_snapshot = None
            self._quiesce_refill()
            # 2. the boundary save: the survivor's whole trajectory (and
            #    the stream position) becomes the joiners' hydration
            #    point — nothing to replay, nothing to broadcast live
            self.save()
            self.checkpointer.wait()
            boundary = self.checkpointer.save_version - 1
            vdir = str(self.checkpointer.save_dir)
            if hasattr(self.buffer, "prepare_reshard"):
                # park the LM params to host BEFORE the backend reset
                self.buffer.prepare_reshard()
            # 3. admit + re-form the wider world (mesh epoch +1); on a
            #    failed rendezvous this returns the narrow survivor mesh
            #    and the run continues at the old width
            mesh, admit = self._elastic.grow(
                step, save_version=boundary, version_dir=vdir,
                save_step=step,
            )
            # 4. re-derive the mesh-coupled pieces and restore the
            #    boundary save on the new world (grown or re-shrunk) —
            #    the explicit (version_dir, save) pin keeps the restore
            #    SPMD-symmetric with the joiners' (no negotiation)
            self._rebuild_for_mesh(mesh)
            if hasattr(self.buffer, "reshard"):
                self.buffer.reshard(self._batch_sharding, refill=False)
            meta = self.restore(version_dir=vdir, save=boundary)
            if admit is not None:
                # hydration barrier: nobody trains until every member has
                # restored the boundary save — without it the survivor's
                # first probe would time out on a joiner still compiling,
                # burning a suspect for pure startup stagger
                if not multihost.probe_liveness(
                        f"r{int(admit['epoch'])}", timeout_s=120.0):
                    print("[crosscoder_tpu] elastic: hydration barrier "
                          "timed out; training on (the probe path will "
                          "catch a dead joiner)", flush=True,
                          file=sys.stderr)
        ms = 1000 * (time.perf_counter() - t0)
        self._elastic.note_remesh(self._host_step)
        self.last_grow = {
            "step": int(meta.get("step", -1)),
            "save": int(boundary),
            "version_dir": vdir,
            "epoch": self._elastic.epoch(),
            "grow_ms": int(ms),
            "grown": admit is not None,
            "n_data": int(self.mesh.shape.get("data", 1)),
        }
        self.resilience.bump("grow_ms", int(ms))
        print(f"[crosscoder_tpu] elastic: resumed at step "
              f"{self._host_step} on mesh {dict(self.mesh.shape)} "
              f"({ms:.0f} ms grow recovery)", flush=True, file=sys.stderr)

    def train(self, num_steps: int | None = None) -> dict[str, float]:
        """Run the training loop (reference ``trainer.py:72-82`` semantics:
        periodic log/save, final save in ``finally``).

        Observability the reference lacks (SURVEY.md §5 tracing;
        docs/OBSERVABILITY.md): wall-clock ``step_time_ms`` (mean between
        logs, device-synced only at log points) rides along with every log
        record; ``cfg.profile_steps="start:stop"`` (or a ``SIGUSR1``, or a
        bare non-empty ``cfg.profile_dir`` = the legacy steps-10..14
        window) captures a ``jax.profiler`` device trace around exactly
        those steps; and ``cfg.obs="on"`` adds host span tracing plus
        ``perf/*``/``comm/*`` registry metrics — including
        ``perf/refill_bubble_frac``, the fraction of each log interval the
        loop spent blocked on batch production.

        Failure handling (SURVEY.md §5 "failure detection"): beyond the
        reference's save-in-``finally`` (reference ``trainer.py:74-82``),
        SIGTERM — the preemption notice on TPU VMs/pods — is caught for the
        duration of the loop and triggers a clean stop: finish the current
        step, write a resumable checkpoint, exit. A second SIGTERM falls
        through to the previous handler.

        Divergence recovery (``cfg.guard_loss``; docs/resilience.md): at
        each log step the already-fetched loss is checked for non-finite
        values or a ``cfg.loss_spike_factor`` spike; on divergence the
        trainer restores the last intact finite checkpoint, skips the
        poisoned data window, and re-enters the loop at the restored step
        — bounded by ``cfg.max_rollbacks`` before aborting loudly. With
        the guard off (default) the loop body is unchanged and no host
        sync is added anywhere."""
        import signal
        import time

        num_steps = self.total_steps if num_steps is None else num_steps
        metrics: dict[str, Any] = {}
        guard = self.cfg.guard_loss
        # device-profile windows (obs/profiler.py): cfg.profile_steps
        # captures exactly [start, stop); SIGUSR1 an on-demand window; a
        # bare cfg.profile_dir keeps the legacy steps-10..14 capture. None
        # when nothing is configured and obs is off — the loop body then
        # carries no profiler branch at all.
        profiler = None
        if (self._obs is not None or self.cfg.profile_dir
                or self.cfg.profile_steps):
            from crosscoder_tpu.obs.profiler import ProfilerWindow

            profiler = ProfilerWindow(
                self.cfg,
                registry=self._obs.registry if self._obs is not None else None,
            )

        stop_requested = False
        prev_handler = None

        def _on_sigterm(signum, frame):
            nonlocal stop_requested
            if stop_requested:
                # second signal: give control back — reinstall the previous
                # disposition and re-raise so escalation actually escalates
                signal.signal(signal.SIGTERM, prev_handler or signal.SIG_DFL)
                signal.raise_signal(signal.SIGTERM)
                return
            stop_requested = True
            print("[crosscoder_tpu] SIGTERM: stopping after this step, "
                  "writing checkpoint", flush=True, file=sys.stderr)

        multi_process = jax.process_count() > 1
        poll_every = int(self.cfg.stop_poll_every)  # validated >= 1 in config

        def _stop_agreed(i: int) -> bool:
            # Checkpointer.save is a COLLECTIVE on a multi-host mesh, so the
            # decision to stop-and-save must be agreed by every process — a
            # SIGTERM (preemption notice) often reaches only one host. A
            # tiny allgathered flag makes the stop point SPMD-consistent.
            # The allgather is a host-blocking cross-host collective, so it
            # runs only every ``cfg.stop_poll_every`` steps (same step on
            # every process → still SPMD-consistent); single-process runs
            # skip the sync entirely.
            if not multi_process:
                return stop_requested
            if i % poll_every != 0:
                return False
            import numpy as _np

            from jax.experimental import multihost_utils

            flag = _np.array([1 if stop_requested else 0], _np.int32)
            # the allgather is a program launch too: on a ticketed run it
            # must hold a launch slot or it races the prefetch worker's
            # collectives. Poll steps are the same ``i`` on every process,
            # so the reservation order stays SPMD-consistent.
            with self._launch_turn(self._reserve_ticket()):
                return bool(multihost_utils.process_allgather(flag).max())

        in_main_thread = threading.current_thread() is threading.main_thread()
        if in_main_thread:
            prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)
            if profiler is not None:
                # kill -USR1 <pid>: capture an on-demand profiler window
                # starting at the next step (live-pod diagnosis, no restart)
                profiler.install_sigusr1()
        clean = False
        try:
            if (guard and self.checkpointer is not None
                    and self.checkpointer.save_version == 0):
                # baseline save: the guard's first rollback must have an
                # intact save to land on even if divergence hits before
                # the first periodic save
                self.save()
            # outer retry loop: one iteration per training stretch — the
            # whole run when nothing diverges (guard off: exactly one
            # iteration, with the identical per-step body as before), one
            # extra iteration per rollback, re-entered at the restored step
            while True:
                rolled_back = False
                start = self.step_counter  # nonzero after restore()/rollback
                progress = _progress_bar(start, num_steps)
                last_log_ns, last_log_i = time.perf_counter_ns(), start
                if self._obs is not None:
                    # drop span time accumulated before the stretch (set-up;
                    # the steps a rollback abandoned) — the first interval's
                    # totals must cover only its own log interval
                    self._obs.tracer.take_interval()
                if profiler is not None:
                    profiler.begin_stretch(start)
                try:
                    for i in progress:
                        # elastic liveness probe (cfg.elastic; one
                        # bounded membership barrier at the stop-poll
                        # cadence — same steps on every process, so the
                        # barrier keys stay SPMD-consistent)
                        if (self._elastic is not None
                                and self._elastic.should_probe(i)
                                and not self._elastic.probe(i)):
                            raise PeerLoss(
                                f"peer lost (liveness probe, step {i})"
                            )
                        # elastic scale-UP (cfg.elastic_grow): only the
                        # shrunk single-process survivor polls the
                        # rendezvous board; when candidates have passed
                        # debounce + dwell it grows the world at this
                        # step boundary and restarts the epoch loop on
                        # the wider mesh
                        if (self._elastic is not None
                                and self.checkpointer is not None
                                and self._elastic.grow_ready(i)):
                            if profiler is not None:
                                profiler.stop_if_active()
                            getattr(progress, "close", lambda: None)()
                            self._grow_and_resume(i)
                            multi_process = jax.process_count() > 1
                            rolled_back = True
                            break
                        if _stop_agreed(i):
                            break
                        if profiler is not None:
                            profiler.before_step(i)
                        metrics = self.step(full_metrics=(i % self.cfg.log_every == 0))
                        if profiler is not None:
                            # the sync fetch runs only when a window actually
                            # closes at this step — the fast path stays free
                            # of device round-trips
                            profiler.after_step(
                                i, sync=lambda: float(jax.device_get(metrics["loss"]))
                            )
                        if i % self.cfg.log_every == 0:
                            # the fetch is the loop's one device sync per log
                            # interval (and the value the guard and logger need)
                            with trace.span("log_sync"):
                                loss_val = float(jax.device_get(metrics["loss"]))
                            if self._obs is not None:
                                self._obs.registry.count("comm/d2h_transfers")
                            if guard and self._loss_diverged(loss_val):
                                # the guard reuses the loss this log step just
                                # fetched — detection itself adds no host sync
                                if profiler is not None:
                                    # end an active capture before the stretch
                                    # restarts, or the next start_trace raises
                                    # mid-recovery
                                    profiler.stop_if_active()
                                getattr(progress, "close", lambda: None)()
                                self._rollback(i)
                                rolled_back = True
                                break
                            now_ns = time.perf_counter_ns()
                            n_steps = max(i - last_log_i, 1)
                            metrics = dict(metrics)
                            metrics["step_time_ms"] = (now_ns - last_log_ns) / 1e6 / n_steps
                            if self._obs is not None:
                                # the log interval closes here, after the loss
                                # fetch: its span, and the span time since the
                                # last log step as per-interval totals — among
                                # them the fraction of the interval the loop
                                # spent BLOCKED on batch production
                                # (perf/refill_bubble_frac)
                                self._obs.registry.gauge(
                                    "perf/step_wall_ms", metrics["step_time_ms"])
                                self._obs.publish_interval(last_log_ns, now_ns, n_steps)
                            last_log_ns, last_log_i = now_ns, i
                            self.log(metrics, step=i)
                        if (i + 1) % self.cfg.save_every == 0:
                            # background: the file write overlaps subsequent steps;
                            # only the device→host fetch blocks the loop
                            self.save(background=True)
                except Exception as exc:
                    # elastic membership: was that a DYING PEER tearing
                    # a collective out from under this process, or an
                    # ordinary software error? PeerLoss (a failed
                    # liveness probe) is already confirmed; anything
                    # else asks one more bounded membership barrier.
                    # Unconfirmed errors re-raise unchanged — with
                    # elastic off this handler is a bare re-raise.
                    if self._elastic is None or not (
                        isinstance(exc, PeerLoss)
                        or self._elastic.confirm_peer_loss(exc)
                    ):
                        raise
                    if profiler is not None:
                        profiler.stop_if_active()
                    getattr(progress, "close", lambda: None)()
                    self._remesh_and_resume(exc)
                    # the world changed shape: the survivor runs single-
                    # process now, so the stop/final-save paths must
                    # re-read the binding
                    multi_process = jax.process_count() > 1
                    rolled_back = True
                if not rolled_back:
                    break
            clean = True
        finally:
            if in_main_thread:
                signal.signal(signal.SIGTERM, prev_handler or signal.SIG_DFL)
                if profiler is not None:
                    profiler.uninstall_sigusr1()
            if profiler is not None:
                profiler.stop_if_active()
                profiler.join_reader()
            if not multi_process:
                # background + the close() below joining the writer: on
                # SIGTERM the fetch and the write both still land before
                # exit, but a mid-write kill can no longer tear the save
                self.save(background=True)
            elif self._final_save_agreed(clean):
                # every process reached this point cleanly (same step on
                # every process — SPMD-consistent), so the collective save
                # is safe; without the agreement, a process-LOCAL exception
                # would leave the OTHER hosts entering the collective save
                # and deadlocking the pod
                self.save()
            else:
                print("[crosscoder_tpu] not all processes exited cleanly: "
                      "skipping the final (collective) checkpoint to avoid "
                      "a cross-host deadlock", flush=True, file=sys.stderr)
            self.close()
            if self.logger is not None:
                self.logger.close()
        return expand_metrics(jax.device_get(metrics), self.cfg.n_sources) if metrics else {}


def _progress_bar(start: int, n: int):
    with contextlib.suppress(Exception):
        import tqdm  # type: ignore

        return tqdm.trange(start, n)
    return range(start, n)
