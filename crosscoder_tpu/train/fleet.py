"""Multi-tenant fleet scheduler: one harvest stream, N crosscoders.

A hyperparameter sweep over crosscoders (seeds, l1 strengths, dictionary
sizes) traditionally re-pays the expensive part N times: the LM forward
that harvests paired activations dwarfs the crosscoder step (two
multi-hundred-M-param transformer forwards vs a few dict_size·d_in
einsums). The :class:`FleetScheduler` amortizes it — N *tenants* train
off ONE replay buffer:

- **One gather, one transfer per round.** Every admitted tenant holds a
  deterministic cursor into the shared serve stream (the buffer's
  multi-consumer fan-out, :meth:`PairedActivationBuffer.next_raw_for`);
  the scheduler steps all tenants in lockstep, so each round performs one
  real ``next_raw`` gather and ONE host→device transfer, handed to every
  tenant step. A tenant's sample sequence is bitwise what a solo run at
  the same seed would see from the same stream position.
- **Shape-identical tenants stack.** Tenants equal in everything but
  ``seed`` / ``l1_coeff`` share one ``jax.vmap``-ed donated step over a
  stacked TrainState (:mod:`crosscoder_tpu.models.stacked`): one compile,
  one dispatch per cohort, with the per-tenant l1 base as a traced vector
  (the ``l1_input`` mode of :func:`trainer.make_step_body`).
- **Heterogeneous tenants bucket.** Different dict_size/activation means
  a different compiled program: each distinct step signature is one
  *bucket*, capped at ``cfg.fleet_max_buckets``, keyed through
  :func:`compile_cache.variant_key(..., tenant=...)` and AOT-prebuilt at
  admission via :func:`compile_cache.aot_get` — admission compiles before
  the tenant joins the round, never stalling the serving loop.
- **Independent lifecycles.** Tenants admit and retire mid-run (different
  dict sizes finish at different step counts); a retired tenant frees its
  compile bucket and lands its checkpoint writer. Checkpoints are
  namespaced per tenant (``<ckpt_dir>/tenants/<name>/`` via
  ``Checkpointer(tenant=...)`` — retention prunes per tenant), metrics
  under ``tenant/<name>/...``, and the round dispatch runs under a
  ``tenant_step`` span per group (docs/OBSERVABILITY.md).
- **Elastic.** :meth:`save_all`/:meth:`restore_all` quiesce and restore
  ALL tenants from the same boundary save — the fleet analog of the
  Trainer's ``_remesh_and_resume``/``_grow_and_resume`` contract; a
  preempted fleet rebuilds and resumes every tenant plus the shared
  stream position from its tenant-namespaced checkpoints.

``cfg.fleet`` is off by default and ZERO-COST off: nothing here is
imported, the solo Trainer's step HLO is byte-identical (contracts rule
``hlo-fleet-off-identity``). Incompatible with ``cfg.quant_grads``
(config validation: the shard_map gradient path can't stack).

Cost model and the vmap-vs-bucket decision table: docs/SCALING.md
"Fleet amortization". Sweep recipe: docs/RUNBOOK.md §7.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.models import stacked
from crosscoder_tpu.obs import trace
from crosscoder_tpu.parallel import mesh as mesh_lib
from crosscoder_tpu.parallel import multihost
from crosscoder_tpu.train import schedules, trainer as trainer_lib
from crosscoder_tpu.train.state import init_train_state, make_optimizer
from crosscoder_tpu.utils import compile_cache

# cfg fields a tenant may vary while still STACKING with its cohort:
# seed only changes init (not the trace) and l1_coeff rides as the traced
# l1_base vector. Everything else — shapes, activation, schedules' baked
# constants, aux hyperparameters — is part of the stack signature; a
# mismatch there means a different compiled program, i.e. a bucket.
_STACKABLE = ("seed", "l1_coeff")
# fields that never participate in grouping at all (run plumbing)
_NONSEMANTIC = ("checkpoint_dir", "fleet", "fleet_tenants",
                "fleet_max_buckets")


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant: a name plus cfg-field overrides on the base config."""

    name: str
    overrides: dict[str, Any] = dataclasses.field(default_factory=dict)


def _parse_value(raw: str) -> Any:
    if raw.lower() in ("true", "false"):
        return raw.lower() == "true"
    for cast in (int, float):
        try:
            return cast(raw)
        except ValueError:
            continue
    return raw


def parse_tenants(spec: str) -> list[TenantSpec]:
    """Parse the ``cfg.fleet_tenants`` sweep spec:
    ``"name:k=v,k=v;name2:k=v"`` (overrides optional — ``"a;b:seed=7"``).
    """
    out: list[TenantSpec] = []
    seen: set[str] = set()
    for part in filter(None, (p.strip() for p in spec.split(";"))):
        name, _, kv = part.partition(":")
        name = name.strip()
        if not name or "/" in name:
            raise ValueError(f"invalid tenant name in fleet_tenants: {part!r}")
        if name in seen:
            raise ValueError(f"duplicate tenant name {name!r} in fleet_tenants")
        seen.add(name)
        overrides: dict[str, Any] = {}
        for item in filter(None, (i.strip() for i in kv.split(","))):
            k, eq, v = item.partition("=")
            if not eq:
                raise ValueError(f"malformed override {item!r} (want k=v)")
            overrides[k.strip()] = _parse_value(v.strip())
        out.append(TenantSpec(name, overrides))
    return out


def tenant_config(base: CrossCoderConfig, spec: TenantSpec) -> CrossCoderConfig:
    """The tenant's effective solo config: base + overrides, with the
    fleet knobs cleared (a tenant cfg IS a valid solo-run cfg — the
    bitwise baseline tests train exactly it) and the batch plane pinned
    to the base (the shared stream serves ONE batch shape)."""
    cfg = dataclasses.replace(
        base, fleet="off", fleet_tenants="", **spec.overrides
    )
    for field in ("batch_size", "d_in", "n_sources", "num_tokens",
                  "enc_dtype"):
        if getattr(cfg, field) != getattr(base, field):
            # num_tokens stays shared too: total_steps bakes schedule
            # constants AND defines the shared stream's length; per-tenant
            # durations come from dict-size-driven early retirement or an
            # explicit retire()
            raise ValueError(
                f"tenant {spec.name!r} overrides {field}, which is pinned "
                "by the shared harvest stream"
            )
    if cfg.quant_grads:
        raise ValueError(
            f"tenant {spec.name!r} enables quant_grads, which the fleet "
            "step cannot stack (config validation rejects it fleet-wide)"
        )
    return cfg


def stack_signature(cfg: CrossCoderConfig) -> str:
    """Canonical signature of everything that shapes the compiled step:
    two tenants stack iff their signatures match (they may then differ
    only in the :data:`_STACKABLE` fields)."""
    d = dataclasses.asdict(cfg)
    for k in _STACKABLE + _NONSEMANTIC:
        d.pop(k, None)
    return json.dumps(d, sort_keys=True, default=str)


class _Tenant:
    """Book-keeping for one admitted tenant."""

    def __init__(self, spec: TenantSpec, cfg: CrossCoderConfig,
                 checkpointer: Any | None) -> None:
        self.spec = spec
        self.name = spec.name
        self.cfg = cfg
        self.checkpointer = checkpointer
        self.steps_done = 0
        self.retired = False
        self.group: Any = None      # _Cohort or _Bucket


class _Cohort:
    """A stacked group of shape-identical tenants: one vmapped program."""

    def __init__(self, sig: str, tag: str, members: list[_Tenant]) -> None:
        self.sig = sig
        self.tag = tag
        self.members = members
        self.state = None           # stacked TrainState on device
        self.l1_vec = None          # [N] f32, replicated
        self.solo_shardings = None
        self.stacked_shardings = None
        self.tx = None
        self.fns: dict[tuple, Any] = {}

    @property
    def cfg(self) -> CrossCoderConfig:
        return self.members[0].cfg


class _Bucket:
    """A solo-compiled tenant (unique step signature)."""

    def __init__(self, sig: str, tag: str, tenant: _Tenant) -> None:
        self.sig = sig
        self.tag = tag
        self.tenant = tenant
        self.state = None
        self.shardings = None
        self.tx = None
        self.fns: dict[tuple, Any] = {}


class FleetScheduler:
    """Run N crosscoder tenants in lockstep off one activation stream.

    Parameters
    ----------
    cfg: base config with ``fleet="on"``; tenants come from
        ``cfg.fleet_tenants`` and/or :meth:`admit`.
    buffer: shared activation source. Anything exposing the fan-out
        protocol works: the replay buffer (``next_raw_for`` — raw rows +
        norm factors applied in-step) or the synthetic source
        (``next_for`` — normalized rows, unit scale). Defaults to the
        synthetic source over the BASE cfg: the base seed drives the
        stream, tenant seeds only shape their init.
    """

    def __init__(
        self,
        cfg: CrossCoderConfig,
        buffer: Any | None = None,
        mesh=None,
        logger: Any | None = None,
        registry: Any | None = None,
        checkpoint: bool = True,
    ) -> None:
        if cfg.fleet != "on":
            raise ValueError("FleetScheduler requires cfg.fleet='on'")
        if getattr(cfg, "tuned", ""):
            # pin the fleet/data-plane knobs from the tuned artifact
            # (docs/TUNING.md): idempotent when from_cli already applied
            # it; also covers schedulers constructed programmatically
            from crosscoder_tpu.tune.artifact import apply_tuned

            cfg = apply_tuned(cfg)
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else mesh_lib.mesh_from_cfg(cfg)
        if buffer is None:
            from crosscoder_tpu.data.synthetic import SyntheticActivationSource

            buffer = SyntheticActivationSource(cfg)
        self.buffer = buffer
        self.logger = logger
        self.registry = registry
        # persistent AOT tier (cfg.compile_cache_dir): bucket/cohort
        # admission compiles dedupe across fleet processes — one
        # compiles (claim-by-rename leader), peers deserialize
        compile_cache.configure(cfg, registry=registry)
        self._checkpoint = checkpoint and bool(cfg.checkpoint_dir)
        self._raw_serving = hasattr(buffer, "next_raw_for")
        if not self._raw_serving and not hasattr(buffer, "next_for"):
            raise ValueError(
                "fleet buffer must expose the fan-out protocol "
                "(next_raw_for / next_for)"
            )
        self._batch_sharding = mesh_lib.batch_sharding(self.mesh)
        self._replicated = NamedSharding(self.mesh, PartitionSpec())
        self._n_data = int(self.mesh.shape.get("data", 1))
        self._scale_src: np.ndarray | None = None
        self._scale_dev: jax.Array | None = None
        self.rounds = 0
        self._tenants: dict[str, _Tenant] = {}
        self._cohorts: list[_Cohort] = []
        self._buckets: list[_Bucket] = []
        self._bucket_sigs: dict[str, int] = {}      # sig -> live tenant count
        self._group_seq = 0
        specs = parse_tenants(cfg.fleet_tenants)
        if specs:
            self._admit_initial(specs)

    # -- admission / retirement ----------------------------------------

    def _admit_initial(self, specs: list[TenantSpec]) -> None:
        """Group the launch roster: signatures shared by >=2 tenants form
        vmapped cohorts; singletons and heterogeneous tenants bucket."""
        by_sig: dict[str, list[_Tenant]] = {}
        for spec in specs:
            t = self._new_tenant(spec)
            by_sig.setdefault(stack_signature(t.cfg), []).append(t)
        for sig, members in by_sig.items():
            if len(members) >= 2:
                self._build_cohort(sig, members)
            else:
                self._build_bucket(sig, members[0])

    def admit(self, spec: TenantSpec) -> None:
        """Mid-run admission: the tenant joins as a bucketed singleton
        (its cursor starts at the CURRENT stream position — equal to a
        solo run launched now against the same stream). Its program is
        AOT-compiled here, before it joins the round loop."""
        t = self._new_tenant(spec)
        self._build_bucket(stack_signature(t.cfg), t)

    def _new_tenant(self, spec: TenantSpec) -> _Tenant:
        if spec.name in self._tenants:
            raise ValueError(f"tenant {spec.name!r} already admitted")
        cfg = tenant_config(self.cfg, spec)
        ckpt = None
        if self._checkpoint:
            from crosscoder_tpu.checkpoint import Checkpointer

            ckpt = Checkpointer(
                self.cfg.checkpoint_dir, cfg=cfg, tenant=spec.name
            )
        t = _Tenant(spec, cfg, ckpt)
        self.buffer.attach_consumer(spec.name)
        self._tenants[spec.name] = t
        return t

    def retire(self, name: str, save: bool = True) -> None:
        """Retire one tenant: optionally land a final save, free its
        compile bucket (or restack its cohort at N-1), detach its stream
        cursor, and release its checkpoint writer."""
        t = self._tenants[name]
        if t.retired:
            return
        if save and t.checkpointer is not None:
            t.checkpointer.save(self._tenant_state(t), t.cfg,
                                buffer=self._buffer_for_save())
        group = t.group
        if isinstance(group, _Bucket):
            self._buckets.remove(group)
            self._bucket_sigs[group.sig] -= 1
            if self._bucket_sigs[group.sig] <= 0:
                del self._bucket_sigs[group.sig]    # bucket slot freed
        else:
            i = group.members.index(t)
            group.members.pop(i)
            if group.members:
                group.state = stacked.restack_without(group.state, i)
                group.l1_vec = stacked.stacked_l1_vector(
                    [m.cfg.l1_coeff for m in group.members]
                )
                group.fns.clear()       # cohort recompiles at N-1
            else:
                self._cohorts.remove(group)
        t.group = None
        t.retired = True
        self.buffer.detach_consumer(name)
        if t.checkpointer is not None:
            t.checkpointer.wait()
        if self.registry is not None:
            self.registry.count("tenant/retirements")

    def active(self) -> list[str]:
        return [n for n, t in self._tenants.items() if not t.retired]

    # -- group construction --------------------------------------------

    def _next_tag(self, kind: str) -> str:
        self._group_seq += 1
        return f"{kind}{self._group_seq}"

    def _build_cohort(self, sig: str, members: list[_Tenant]) -> None:
        co = _Cohort(sig, self._next_tag("cohort"), members)
        rep = co.cfg
        co.tx = make_optimizer(rep, schedules.lr_schedule(rep))
        solo_states = [
            init_train_state(jax.random.key(m.cfg.seed), m.cfg, co.tx,
                             n_data=self._n_data)
            for m in members
        ]
        co.solo_shardings = mesh_lib.state_shardings(
            self.mesh, solo_states[0], rep.shard_sources
        )
        co.stacked_shardings = stacked.stacked_shardings(
            self.mesh, co.solo_shardings
        )
        host = stacked.stack_states(solo_states)
        co.state = multihost.put_global(host, co.stacked_shardings)
        co.l1_vec = stacked.stacked_l1_vector(
            [m.cfg.l1_coeff for m in members]
        )
        for m in members:
            m.group = co
        self._cohorts.append(co)
        # prebuild the canonical variant so the first round doesn't stall
        self._cohort_fn(co, trainer_lib.variant_for_step(rep, 0))
        if self.registry is not None:
            self.registry.count("tenant/admissions", len(members))

    def _build_bucket(self, sig: str, t: _Tenant) -> None:
        if (sig not in self._bucket_sigs
                and len(self._bucket_sigs) >= self.cfg.fleet_max_buckets):
            self.buffer.detach_consumer(t.name)
            del self._tenants[t.name]
            raise ValueError(
                f"admitting tenant {t.name!r} needs a new compile bucket "
                f"but fleet_max_buckets={self.cfg.fleet_max_buckets} are "
                "in use; retire a tenant or raise the cap"
            )
        b = _Bucket(sig, self._next_tag("bucket"), t)
        b.tx = make_optimizer(t.cfg, schedules.lr_schedule(t.cfg))
        state = init_train_state(jax.random.key(t.cfg.seed), t.cfg, b.tx,
                                 n_data=self._n_data)
        b.shardings = mesh_lib.state_shardings(
            self.mesh, state, t.cfg.shard_sources
        )
        b.state = multihost.put_global(state, b.shardings)
        t.group = b
        self._buckets.append(b)
        self._bucket_sigs[sig] = self._bucket_sigs.get(sig, 0) + 1
        self._bucket_fn(b, trainer_lib.variant_for_step(t.cfg, 0))
        if self.registry is not None:
            self.registry.count("tenant/admissions")

    # -- compiled steps (AOT, keyed through variant_key(tenant=...)) ----

    def _batch_struct(self, cfg: CrossCoderConfig) -> jax.ShapeDtypeStruct:
        dtype = jnp.bfloat16 if self._raw_serving else jnp.float32
        return jax.ShapeDtypeStruct(
            (cfg.batch_size, cfg.n_sources, cfg.d_in), dtype,
            sharding=self._batch_sharding,
        )

    def _scale_struct(self, cfg: CrossCoderConfig) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(
            (cfg.n_sources,), jnp.float32, sharding=self._replicated
        )

    def _mesh_tag(self) -> tuple:
        return tuple(sorted(self.mesh.shape.items()))

    def _cohort_fn(self, co: _Cohort, key: tuple) -> Any:
        fn = co.fns.get(key)
        if fn is None:
            n = len(co.members)
            body = trainer_lib.make_step_body(
                co.cfg, self.mesh, co.tx, with_metrics=key[0],
                aux_on=key[1], mask_refresh=key[2], l1_input=True,
            )
            jfn = jax.jit(
                stacked.vmap_step(body),
                in_shardings=(co.stacked_shardings, self._batch_sharding,
                              self._replicated, self._replicated),
                out_shardings=(co.stacked_shardings, None),
                donate_argnums=(0,),
            )
            label = compile_cache.variant_key(*key, tenant=f"{co.tag}x{n}")
            state = co.state

            def build():
                with trace.span("compile", variant=label):
                    return jfn.lower(
                        state, self._batch_struct(co.cfg),
                        self._scale_struct(co.cfg), co.l1_vec,
                    ).compile()

            fn = co.fns[key] = compile_cache.aot_get(
                (label, co.sig, n, self._mesh_tag()), build
            )
        return fn

    def _bucket_fn(self, b: _Bucket, key: tuple) -> Any:
        fn = b.fns.get(key)
        if fn is None:
            cfg = b.tenant.cfg
            body = trainer_lib.make_step_body(
                cfg, self.mesh, b.tx, with_metrics=key[0], aux_on=key[1],
                mask_refresh=key[2],
            )
            jfn = jax.jit(
                body,
                in_shardings=(b.shardings, self._batch_sharding,
                              self._replicated),
                out_shardings=(b.shardings, None),
                donate_argnums=(0,),
            )
            label = compile_cache.variant_key(*key, tenant=b.tag)
            state = b.state

            def build():
                with trace.span("compile", variant=label):
                    return jfn.lower(
                        state, self._batch_struct(cfg),
                        self._scale_struct(cfg),
                    ).compile()

            fn = b.fns[key] = compile_cache.aot_get(
                (label, b.sig, self._mesh_tag()), build
            )
        return fn

    # -- serving --------------------------------------------------------

    def _serve_round(self) -> np.ndarray:
        """Advance every active tenant's cursor one position. ONE real
        gather: the first cursor pays it, the rest read the fan-out cache
        (the returned arrays are the same object)."""
        serve = (self.buffer.next_raw_for if self._raw_serving
                 else self.buffer.next_for)
        batch = None
        for name in self.active():
            batch = serve(name)
        if batch is None:
            raise RuntimeError("fleet round with no active tenants")
        return batch

    def _device_scale(self) -> jax.Array:
        src = getattr(self.buffer, "normalisation_factor", None)
        if self._raw_serving and src is not None:
            vec = np.asarray(src, np.float32)
        else:
            vec = np.ones((self.cfg.n_sources,), np.float32)
        if self._scale_src is None or not np.array_equal(self._scale_src, vec):
            self._scale_src = vec.copy()
            self._scale_dev = multihost.put_global(vec, self._replicated)
        return self._scale_dev

    # -- the lockstep round ---------------------------------------------

    def step_all(self, full_metrics: bool = True) -> dict[str, dict[str, jax.Array]]:
        """One fleet round: serve once, transfer once, step every group.

        Returns per-tenant device-resident metric dicts (no host sync) —
        ``{tenant_name: {"loss": ..., ...}}``; cohort metrics are sliced
        per member from the vmapped output's leading axis."""
        batch = self._serve_round()
        dev_batch = multihost.put_global(batch, self._batch_sharding)
        scale = self._device_scale()
        if self.registry is not None:
            # one H2D per round regardless of tenant count — the
            # amortization the fleet exists for
            self.registry.count("comm/h2d_transfers")
        out: dict[str, dict[str, jax.Array]] = {}
        for co in self._cohorts:
            key = trainer_lib.variant_for_step(
                co.cfg, co.members[0].steps_done, full_metrics
            )
            fn = self._cohort_fn(co, key)
            with trace.span("tenant_step", group=co.tag,
                            n=len(co.members)):
                co.state, mets = fn(co.state, dev_batch, scale, co.l1_vec)
            views = stacked.unstack_metrics(mets, len(co.members))
            for i, m in enumerate(co.members):
                m.steps_done += 1
                out[m.name] = views[i]
        for b in self._buckets:
            t = b.tenant
            key = trainer_lib.variant_for_step(t.cfg, t.steps_done,
                                               full_metrics)
            fn = self._bucket_fn(b, key)
            with trace.span("tenant_step", group=b.tag, n=1):
                b.state, mets = fn(b.state, dev_batch, scale)
            t.steps_done += 1
            out[t.name] = mets
        self.rounds += 1
        return out

    def _auto_retire(self) -> None:
        for name in list(self.active()):
            t = self._tenants[name]
            if t.steps_done >= t.cfg.total_steps:
                self.retire(name, save=self._checkpoint)

    def run(self, rounds: int | None = None) -> int:
        """Drive lockstep rounds until every tenant retires (or ``rounds``
        elapse), logging and checkpointing at the base cfg's cadences.
        Returns the number of rounds executed."""
        cfg = self.cfg
        done = 0
        while self.active() and (rounds is None or done < rounds):
            log_now = cfg.log_every > 0 and self.rounds % cfg.log_every == 0
            mets = self.step_all(full_metrics=log_now)
            done += 1
            if log_now:
                self.publish(mets)
            if (cfg.save_every > 0 and self._checkpoint
                    and self.rounds % cfg.save_every == 0):
                self.save_all(background=True)
            self._auto_retire()
        if self._checkpoint:
            self.save_all()
        self.quiesce()
        return done

    def publish(self, mets: dict[str, dict[str, jax.Array]]) -> None:
        """Pull one round's metrics to host and emit them under the
        ``tenant/<name>/...`` namespace (registry gauges + logger)."""
        host = jax.device_get(mets)
        flat: dict[str, float] = {}
        for name, md in host.items():
            for k, v in trainer_lib.expand_metrics(
                    md, self._tenants[name].cfg.n_sources).items():
                flat[f"tenant/{name}/{k}"] = v
        if self.registry is not None:
            for k, v in flat.items():
                self.registry.gauge(k, v)
        if self.logger is not None:
            self.logger.log(flat, step=self.rounds)

    # -- state / checkpoint / elastic ------------------------------------

    def _tenant_state(self, t: _Tenant):
        g = t.group
        if isinstance(g, _Bucket):
            return g.state
        return stacked.unstack_state(g.state, g.members.index(t))

    def _buffer_for_save(self) -> Any | None:
        return self.buffer if hasattr(self.buffer, "state_dict") else None

    def quiesce(self) -> None:
        """Land every tenant's in-flight checkpoint write (the boundary
        the elastic paths save/restore across)."""
        for t in self._tenants.values():
            if t.checkpointer is not None:
                t.checkpointer.wait()

    def save_all(self, background: bool = False) -> None:
        """One boundary save per active tenant, all carrying the SAME
        shared-stream snapshot (nothing serves between them), into the
        tenant's namespaced ``<ckpt_dir>/tenants/<name>/``."""
        buf = self._buffer_for_save()
        for name in self.active():
            t = self._tenants[name]
            if t.checkpointer is not None:
                t.checkpointer.save(self._tenant_state(t), t.cfg,
                                    buffer=buf, background=background)

    def restore_all(self) -> dict[str, int]:
        """Restore EVERY active tenant from its newest verified save and
        the shared stream from the common boundary snapshot — the fleet's
        preemption/remesh recovery path. Returns per-tenant restored
        steps (they agree for cohort members by construction)."""
        self.quiesce()
        restored: dict[str, int] = {}
        stream_meta: dict | None = None
        per_tenant: dict[str, Any] = {}
        for name in self.active():
            t = self._tenants[name]
            if t.checkpointer is None:
                raise ValueError("restore_all needs tenant checkpointers")
            g = t.group
            tx = g.tx
            state, meta = t.checkpointer.restore(
                t.cfg, tx, n_data=self._n_data
            )
            per_tenant[name] = state
            t.steps_done = int(meta["step"])
            restored[name] = t.steps_done
            if stream_meta is None and "buffer" in meta:
                stream_meta = meta["buffer"]
        for co in self._cohorts:
            host = stacked.stack_states(
                [per_tenant[m.name] for m in co.members]
            )
            co.state = multihost.put_global(host, co.stacked_shardings)
        for b in self._buckets:
            b.state = multihost.put_global(
                per_tenant[b.tenant.name], b.shardings
            )
        if stream_meta is not None and hasattr(self.buffer, "load_state_dict"):
            # rewinds the stream AND re-aligns every fan-out cursor to the
            # restored position (buffer.load_state_dict's fleet contract)
            self.buffer.load_state_dict(stream_meta)
        self._scale_src = None      # norm factors may have been restored
        return restored

    def remesh(self, mesh) -> None:
        """Elastic re-mesh: quiesce, re-derive every mesh-coupled piece
        (shardings, compiled programs, the shared buffer's store), and
        restore ALL tenants from the boundary save — the fleet analog of
        the Trainer's ``_remesh_and_resume``/``_grow_and_resume``
        quiesce-then-rebuild order (docs/resilience.md)."""
        self.quiesce()
        if hasattr(self.buffer, "prepare_reshard"):
            self.buffer.prepare_reshard()
        self.mesh = mesh
        self._batch_sharding = mesh_lib.batch_sharding(mesh)
        self._replicated = NamedSharding(mesh, PartitionSpec())
        self._n_data = int(mesh.shape.get("data", 1))
        self._scale_src = None
        self._scale_dev = None
        if hasattr(self.buffer, "reshard"):
            # refill=False: restore_all replays the CHECKPOINT's stream
            # snapshot, not the live one (the elastic restore contract)
            self.buffer.reshard(self._batch_sharding, refill=False)
        for co in self._cohorts:
            probe = init_train_state(
                jax.random.key(co.cfg.seed), co.cfg, co.tx,
                n_data=self._n_data,
            )
            co.solo_shardings = mesh_lib.state_shardings(
                mesh, probe, co.cfg.shard_sources
            )
            co.stacked_shardings = stacked.stacked_shardings(
                mesh, co.solo_shardings
            )
            co.fns.clear()
        for b in self._buckets:
            probe = init_train_state(
                jax.random.key(b.tenant.cfg.seed), b.tenant.cfg, b.tx,
                n_data=self._n_data,
            )
            b.shardings = mesh_lib.state_shardings(
                mesh, probe, b.tenant.cfg.shard_sources
            )
            b.fns.clear()
        self.restore_all()
        print(f"[crosscoder_tpu] fleet: re-meshed onto "
              f"{dict(mesh.shape)} and restored "
              f"{len(self.active())} tenant(s)", flush=True, file=sys.stderr)
