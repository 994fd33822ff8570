"""HLO/jaxpr contract rules over AOT-lowered train-step variants.

The framework's central scaling claim is that every knob is zero-cost
off and every fusion's byte win is structural, not incidental. Those are
*compiler-level* facts: they live in the lowered step program, the same
artifact ``utils/compile_cache.observed`` AOT-compiles and reports at
runtime. This module lowers a lattice of step variants once (tiny
shapes, CPU) and runs declarative checks over the StableHLO text and
the traced jaxpr:

- **knob-off identity** — a knob that is present-but-off lowers the
  byte-identical program (generalizes the scattered asserts of
  ``tests/test_quant.py`` / ``test_obs.py`` into one parametrized sweep,
  which those tests now wrap);
- **no-s8-when-quant-off** / **no-f64-anywhere** — dtype hygiene;
- **donation honored** — every donated train-state leaf carries an
  input/output alias (``tf.aliasing_output``) in the lowered signature;
- **no-host-transfers** — no infeed/outfeed/send/recv/host-callback
  inside the step;
- **no large captured constants** — closed-over concrete arrays above a
  size threshold in the step jaxpr (the classic silent-bloat bug where
  a traced-in array is baked into every compiled variant).

Rules here are pure functions of :class:`StepContext` data so the
mutation self-tests (``mutations.py``) can prove each rule fires on a
seeded violation without recompiling anything.
"""

from __future__ import annotations

import contextlib
import re
from dataclasses import dataclass, field
from typing import Any

from crosscoder_tpu.analysis.contracts.engine import Finding, Rule

# a captured constant this large in the step jaxpr is a bug: step inputs
# arrive as arguments (donated or streamed), never baked into the program
LARGE_CONST_BYTES = 1 << 18

# callback/transfer markers that must never appear inside the step: the
# train step is a pure device program (the obs plane's zero-transfer
# guarantee, tests/test_obs.py::test_obs_adds_no_host_device_transfers,
# made static)
HOST_TRANSFER_TOKENS = (
    "stablehlo.infeed", "stablehlo.outfeed", "stablehlo.send",
    "stablehlo.recv", "cpu_callback", "python_callback", "io_callback",
)

_I8_RE = re.compile(r"(?:<|x)i8>")
_F64_RE = re.compile(r"(?:<|x)f64>")


@dataclass
class VariantMeta:
    """What the checks need to know about one lowered variant."""

    n_donated_leaves: int = 0
    quant_off: bool = True                  # no int8 may appear


@dataclass
class StepContext:
    """Lowered step variants + jaxpr const inventory for the HLO rules."""

    texts: dict[str, str] = field(default_factory=dict)
    meta: dict[str, VariantMeta] = field(default_factory=dict)
    # label -> [(nbytes, description)] of closed-over jaxpr constants
    jaxpr_consts: dict[str, list[tuple[int, str]]] = field(default_factory=dict)
    # (label_a, label_b, what-knob) pairs that must be byte-identical
    identity_pairs: list[tuple[str, str, str]] = field(default_factory=list)


# ---------------------------------------------------------------------------
# variant construction (the only part that touches jax)


def lower_step_text(cfg, n_devices: int = 1) -> str:
    """Lower one train-step variant and return its StableHLO text.

    This is THE shared harness the step-HLO-identity tests deduplicate
    onto (previously copy-pasted as ``_lower_step_text`` in three test
    modules): eval-shape state init, mesh shardings, AOT lower of
    ``make_train_step`` — no device execution, CPU-safe.
    """
    text, _ = lower_step(cfg, n_devices)
    return text


def lower_step(cfg, n_devices: int = 1) -> tuple[str, int]:
    """``(stablehlo_text, n_donated_state_leaves)`` for one variant."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from crosscoder_tpu.parallel import mesh as mesh_lib
    from crosscoder_tpu.train import schedules
    from crosscoder_tpu.train.state import init_train_state, make_optimizer
    from crosscoder_tpu.train.trainer import make_train_step

    mesh = mesh_lib.make_mesh(devices=jax.devices()[:n_devices])
    tx = make_optimizer(cfg, schedules.lr_schedule(cfg))
    state = jax.eval_shape(lambda k: init_train_state(k, cfg, tx),
                           jax.random.key(0))
    shardings = mesh_lib.state_shardings(mesh, state, cfg.shard_sources)
    step = make_train_step(cfg, mesh, tx, shardings)
    state_sh = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, shardings,
    )
    batch = jax.ShapeDtypeStruct(
        (cfg.batch_size, cfg.n_sources, cfg.d_in), jnp.float32,
        sharding=mesh_lib.batch_sharding(mesh),
    )
    scale = jax.ShapeDtypeStruct((cfg.n_sources,), jnp.float32,
                                 sharding=NamedSharding(mesh, P()))
    text = step.lower(state_sh, batch, scale).as_text()
    return text, len(jax.tree_util.tree_leaves(state_sh))


def step_jaxpr_consts(cfg) -> list[tuple[int, str]]:
    """``(nbytes, description)`` for every concrete array closed over by
    the traced step jaxpr. A clean step captures nothing: all tensors
    arrive as arguments."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from crosscoder_tpu.parallel import mesh as mesh_lib
    from crosscoder_tpu.train import schedules
    from crosscoder_tpu.train.state import init_train_state, make_optimizer
    from crosscoder_tpu.train.trainer import make_train_step

    mesh = mesh_lib.make_mesh(devices=jax.devices()[:1])
    tx = make_optimizer(cfg, schedules.lr_schedule(cfg))
    state = jax.eval_shape(lambda k: init_train_state(k, cfg, tx),
                           jax.random.key(0))
    shardings = mesh_lib.state_shardings(mesh, state, cfg.shard_sources)
    step = make_train_step(cfg, mesh, tx, shardings)
    state_sh = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, shardings,
    )
    batch = jax.ShapeDtypeStruct(
        (cfg.batch_size, cfg.n_sources, cfg.d_in), jnp.float32,
        sharding=mesh_lib.batch_sharding(mesh),
    )
    scale = jax.ShapeDtypeStruct((cfg.n_sources,), jnp.float32,
                                 sharding=NamedSharding(mesh, P()))
    traced = step.trace(state_sh, batch, scale)
    out = []
    for c in traced.jaxpr.consts:
        nbytes = getattr(c, "nbytes", 0) or 0
        out.append((int(nbytes),
                    f"{getattr(c, 'dtype', type(c).__name__)}"
                    f"{list(getattr(c, 'shape', []))}"))
    return out


@contextlib.contextmanager
def _kernels_dead():
    """Every step-path kernel module's interpreter latch off, restored on
    exit: an "auto" tier then resolves dead on the CPU."""
    from crosscoder_tpu.ops import row_gather, topk_pallas

    mods = (row_gather, topk_pallas)
    prev = [m._INTERPRET for m in mods]
    for m in mods:
        m.set_interpret(False)
    try:
        yield
    finally:
        for m, p in zip(mods, prev):
            m.set_interpret(p)


def _cfg(**kw):
    from crosscoder_tpu.config import CrossCoderConfig

    base = dict(d_in=8, dict_size=32, batch_size=32, enc_dtype="fp32")
    base.update(kw)
    return CrossCoderConfig(**base)


# knob lattice: each entry is (label, overrides) that must lower the
# byte-identical program to the bare baseline — the zero-cost-off
# contract for every host-side / data-plane knob, singly and combined
KNOB_OFF_LATTICE: tuple[tuple[str, dict[str, Any]], ...] = (
    ("quant", dict(quant_buffer=True, quant_block=8)),
    ("obs", dict(obs="on", obs_dir="/tmp/obs", profile_steps="3:5",
                 log_print_every=7)),
    ("paged_harvest", dict(harvest_runtime="paged", page_size=16,
                           seq_len=1024)),
    ("resilience", dict(guard_loss=True, harvest_timeout_s=2.0,
                        keep_saves=2)),
    ("logging", dict(log_backend="jsonl", profile_dir="/tmp/prof")),
    ("refill_overlap", dict(refill_overlap="on", refill_dispatch_batch=8)),
    ("elastic", dict(elastic="on", elastic_heartbeat_s=2.0,
                     elastic_grace_s=9.0)),
    ("elastic_grow", dict(elastic="on", elastic_grow="on",
                          checkpoint_dir="/tmp/ckpt",
                          elastic_suspect_probes=3, elastic_dwell_steps=5,
                          elastic_grow_debounce=4, elastic_policy="score")),
    ("fleet", dict(fleet="on", fleet_tenants="a:seed=1;b:seed=2",
                   fleet_max_buckets=4, checkpoint_dir="/tmp/ckpt")),
    ("serve", dict(serve="on", serve_max_batch=8, serve_max_wait_ms=2.0,
                   serve_queue=32, serve_shed_ms=50.0)),
    ("compile_cache", dict(compile_cache_dir="/tmp/compile_cache_contract",
                           compile_cache_max_bytes=1 << 20,
                           compile_cache_verify="strict")),
    ("all_knobs", dict(quant_buffer=True, quant_block=8, obs="on",
                       harvest_runtime="paged", page_size=16, seq_len=1024,
                       guard_loss=True, log_backend="jsonl",
                       refill_overlap="on", refill_dispatch_batch=8,
                       elastic="on", elastic_grow="on", serve="on",
                       compile_cache_dir="/tmp/compile_cache_contract",
                       checkpoint_dir="/tmp/ckpt")),
)

# the sparse backward plane: "off" vs a dead "auto" (no kernel live) must
# be byte-identical — the knob's PRESENCE costs nothing
_SPARSE_SHAPE = dict(d_in=128, dict_size=256, batch_size=32, topk_k=8,
                     l1_coeff=0.0)


def build_step_context() -> StepContext:
    """Lower the variant lattice."""
    ctx = StepContext()

    def add(label, cfg):
        text, n_leaves = lower_step(cfg)
        ctx.texts[label] = text
        ctx.meta[label] = VariantMeta(n_donated_leaves=n_leaves)
        ctx.jaxpr_consts[label] = []
        return label

    with _kernels_dead():
        add("base", _cfg())
        ctx.jaxpr_consts["base"] = step_jaxpr_consts(_cfg())
        for label, overrides in KNOB_OFF_LATTICE:
            add(f"off:{label}", _cfg(**overrides))
            ctx.identity_pairs.append(("base", f"off:{label}", label))
        # the tuned-artifact path (hlo-tuned-config-identity): loading a
        # REAL TUNED.json whose knobs equal the defaults must lower the
        # byte-identical step — the artifact machinery (apply_tuned +
        # the cfg.tuned field itself) adds no hidden config drift
        import tempfile

        from crosscoder_tpu.tune.artifact import TunedArtifact, apply_tuned

        with tempfile.TemporaryDirectory(prefix="contracts_tuned_") as td:
            art = TunedArtifact(
                objective="train",
                knobs={"refill_frac": 0.5, "refill_dispatch_batch": 4,
                       "prefetch": True, "quant_buffer": False},
                mesh={"n_devices": 1, "n_model": 1},
            )
            path = art.save(f"{td}/TUNED.json")
            add("off:tuned", apply_tuned(_cfg(), path))
            ctx.identity_pairs.append(("base", "off:tuned", "tuned"))
        a = add("topk:sparse_off",
                _cfg(activation="topk", sparse_bwd="off", **_SPARSE_SHAPE))
        b = add("topk:sparse_auto_dead",
                _cfg(activation="topk", sparse_bwd="auto", **_SPARSE_SHAPE))
        ctx.identity_pairs.append((a, b, "sparse_bwd"))
    return ctx


# ---------------------------------------------------------------------------
# rules (pure functions of StepContext)


def _is_step_ctx(ctx: Any) -> bool:
    return isinstance(ctx, StepContext) and bool(ctx.texts)


def _check_identity(ctx: StepContext) -> list[Finding]:
    out = []
    for a, b, knob in ctx.identity_pairs:
        if ctx.texts[a] != ctx.texts[b]:
            out.append(Finding(
                rule="hlo-knob-off-identity", location=f"{a} vs {b}",
                message=f"knob '{knob}' present-but-off changes the "
                        f"compiled step ({len(ctx.texts[a])} vs "
                        f"{len(ctx.texts[b])} chars) — the zero-cost-off "
                        f"contract is broken",
            ))
    return out


def _check_refill_overlap_off(ctx: StepContext) -> list[Finding]:
    """The zero-bubble refill engine is pure data plane: with
    ``cfg.refill_overlap``/``refill_dispatch_batch`` set, the TRAIN STEP
    must lower byte-identically to the bare baseline (docs/SCALING.md
    "Zero-bubble refill") — the engine may only change how batches are
    produced, never what the step computes. Split out from the generic
    knob-off rule so the overlap contract has its own mutation self-test
    and its own name in the report."""
    out = []
    for a, b, knob in ctx.identity_pairs:
        if knob != "refill_overlap" or ctx.texts[a] == ctx.texts[b]:
            continue
        out.append(Finding(
            rule="hlo-refill-overlap-off-identity", location=f"{a} vs {b}",
            message="refill_overlap/refill_dispatch_batch changed the "
                    "compiled step program — the overlap engine must be "
                    "invisible to the step lowering",
        ))
    return out


def _check_elastic_off(ctx: StepContext) -> list[Finding]:
    """Elastic membership is pure control plane: with ``cfg.elastic="on"``
    (plus its heartbeat/grace knobs) the TRAIN STEP must lower
    byte-identically to the bare baseline — liveness probes and the
    re-mesh path live entirely outside the compiled program
    (docs/resilience.md "Elastic membership"). Split out from the generic
    knob-off rule so the elastic contract has its own mutation self-test
    and its own name in the report."""
    out = []
    for a, b, knob in ctx.identity_pairs:
        if knob != "elastic" or ctx.texts[a] == ctx.texts[b]:
            continue
        out.append(Finding(
            rule="hlo-elastic-off-identity", location=f"{a} vs {b}",
            message="elastic/elastic_heartbeat_s/elastic_grace_s changed "
                    "the compiled step program — membership must be "
                    "invisible to the step lowering",
        ))
    return out


def _check_elastic_grow_off(ctx: StepContext) -> list[Finding]:
    """The scale-UP plane (``cfg.elastic_grow`` plus the hysteresis and
    fleet-policy knobs) is pure control plane on top of elastic
    membership: rendezvous-board polling, debounce/dwell bookkeeping, and
    the mesh-shape policy all run on the host between steps, so with
    every grow knob set the TRAIN STEP must still lower byte-identically
    to the bare baseline (docs/resilience.md "Elastic scale-up"). Own
    rule, own mutation self-test, own name in the report."""
    out = []
    for a, b, knob in ctx.identity_pairs:
        if knob != "elastic_grow" or ctx.texts[a] == ctx.texts[b]:
            continue
        out.append(Finding(
            rule="hlo-elastic-grow-off-identity", location=f"{a} vs {b}",
            message="elastic_grow/suspect_probes/dwell/debounce/policy "
                    "changed the compiled step program — the autoscale "
                    "plane must be invisible to the step lowering",
        ))
    return out


def _check_fleet_off(ctx: StepContext) -> list[Finding]:
    """The multi-tenant fleet (``cfg.fleet`` and its tenant-roster /
    bucket-cap knobs) is a SCHEDULER around the step, not a step change:
    tenant fan-out, stacked cohorts, and compile buckets all live in
    train/fleet.py's host loop, so with every fleet knob set the SOLO
    train step must still lower byte-identically to the bare baseline
    (docs/SCALING.md "Fleet amortization"). Own rule, own mutation
    self-test, own name in the report."""
    out = []
    for a, b, knob in ctx.identity_pairs:
        if knob != "fleet" or ctx.texts[a] == ctx.texts[b]:
            continue
        out.append(Finding(
            rule="hlo-fleet-off-identity", location=f"{a} vs {b}",
            message="fleet/fleet_tenants/fleet_max_buckets changed the "
                    "compiled step program — the fleet scheduler must be "
                    "invisible to the solo step lowering",
        ))
    return out


def _check_serve_off(ctx: StepContext) -> list[Finding]:
    """The serving path (``cfg.serve`` and its batching/queue/shed knobs)
    is a separate request loop AROUND the models, never a train-step
    change: the engine reuses the paged harvest forward and the encoder
    the trainer already compiles, so with every serve knob set the TRAIN
    STEP must lower byte-identically to the bare baseline
    (docs/SERVING.md "Zero-cost off"). Own rule, own mutation self-test,
    own name in the report."""
    out = []
    for a, b, knob in ctx.identity_pairs:
        if knob != "serve" or ctx.texts[a] == ctx.texts[b]:
            continue
        out.append(Finding(
            rule="hlo-serve-off-identity", location=f"{a} vs {b}",
            message="serve/serve_max_batch/serve_max_wait_ms/serve_queue/"
                    "serve_shed_ms changed the compiled step program — the "
                    "serving plane must be invisible to the step lowering",
        ))
    return out


def _check_tuned_identity(ctx: StepContext) -> list[Finding]:
    """Loading a ``TUNED.json`` whose knobs equal the defaults must be a
    no-op on the step lowering: the autotuner artifact path
    (``apply_tuned`` through config resolution, plus the ``cfg.tuned``
    field itself) may pin knob VALUES but must never introduce config
    drift of its own (docs/TUNING.md "The artifact adds no hidden
    drift"). Own rule, own mutation self-test, own name in the report."""
    out = []
    for a, b, knob in ctx.identity_pairs:
        if knob != "tuned" or ctx.texts[a] == ctx.texts[b]:
            continue
        out.append(Finding(
            rule="hlo-tuned-config-identity", location=f"{a} vs {b}",
            message="a TUNED.json carrying the default knob values "
                    "changed the compiled step program — the tuned-"
                    "artifact path is drifting the config it claims to "
                    "merely pin",
        ))
    return out


def _check_no_s8(ctx: StepContext) -> list[Finding]:
    out = []
    for label, text in ctx.texts.items():
        if ctx.meta[label].quant_off and _I8_RE.search(text):
            out.append(Finding(
                rule="hlo-no-s8-when-quant-off", location=label,
                message="int8 tensor in a quant-off step variant",
            ))
    return out


def _check_no_f64(ctx: StepContext) -> list[Finding]:
    out = []
    for label, text in ctx.texts.items():
        if _F64_RE.search(text):
            out.append(Finding(
                rule="hlo-no-f64", location=label,
                message="f64 tensor in the step (a silent 2x bytes/flops "
                        "upcast — x64 must stay disabled end to end)",
            ))
    return out


def _check_donation(ctx: StepContext) -> list[Finding]:
    out = []
    for label, text in ctx.texts.items():
        want = ctx.meta[label].n_donated_leaves
        got = text.count("tf.aliasing_output")
        if got < want:
            out.append(Finding(
                rule="hlo-donation-honored", location=label,
                message=f"only {got}/{want} donated train-state leaves "
                        f"carry an input/output alias — a dropped "
                        f"donation silently doubles that leaf's HBM",
            ))
    return out


def _check_host_transfers(ctx: StepContext) -> list[Finding]:
    out = []
    for label, text in ctx.texts.items():
        for tok in HOST_TRANSFER_TOKENS:
            if tok in text:
                out.append(Finding(
                    rule="hlo-no-host-transfers", location=label,
                    message=f"host-transfer marker '{tok}' inside the "
                            f"compiled step (steps must be pure device "
                            f"programs; telemetry is host-side only)",
                ))
    return out


def _check_large_consts(ctx: StepContext) -> list[Finding]:
    out = []
    for label, consts in ctx.jaxpr_consts.items():
        for nbytes, descr in consts:
            if nbytes > LARGE_CONST_BYTES:
                out.append(Finding(
                    rule="jaxpr-no-large-captured-consts", location=label,
                    message=f"step jaxpr closes over a {nbytes}-byte "
                            f"constant {descr} (> {LARGE_CONST_BYTES}) — "
                            f"baked into every compiled variant instead "
                            f"of passed as an argument",
                ))
    return out


HLO_RULES: list[Rule] = [
    Rule("hlo-knob-off-identity",
         "present-but-off knobs lower the byte-identical step program",
         _is_step_ctx, _check_identity),
    Rule("hlo-no-s8-when-quant-off",
         "no int8 tensor appears in any quant-off step variant",
         _is_step_ctx, _check_no_s8),
    Rule("hlo-no-f64",
         "no f64 tensor appears in any step variant",
         _is_step_ctx, _check_no_f64),
    Rule("hlo-donation-honored",
         "every donated train-state leaf has an input/output alias",
         _is_step_ctx, _check_donation),
    Rule("hlo-no-host-transfers",
         "no infeed/outfeed/send/recv/callback inside the step",
         _is_step_ctx, _check_host_transfers),
    Rule("jaxpr-no-large-captured-consts",
         "the step jaxpr closes over no large concrete arrays",
         _is_step_ctx, _check_large_consts),
    Rule("hlo-refill-overlap-off-identity",
         "the refill overlap engine never changes the step lowering",
         _is_step_ctx, _check_refill_overlap_off),
    Rule("hlo-elastic-off-identity",
         "elastic membership never changes the step lowering",
         _is_step_ctx, _check_elastic_off),
    Rule("hlo-elastic-grow-off-identity",
         "the elastic scale-up plane never changes the step lowering",
         _is_step_ctx, _check_elastic_grow_off),
    Rule("hlo-fleet-off-identity",
         "the multi-tenant fleet scheduler never changes the step lowering",
         _is_step_ctx, _check_fleet_off),
    Rule("hlo-serve-off-identity",
         "the serving plane never changes the train-step lowering",
         _is_step_ctx, _check_serve_off),
    Rule("hlo-tuned-config-identity",
         "a default-knob TUNED.json never changes the step lowering",
         _is_step_ctx, _check_tuned_identity),
]


def check_compiled_text(key: str, text: str) -> list[Finding]:
    """The runtime hook surface for ``utils/compile_cache.observed``:
    the subset of HLO rules that apply to a single already-lowered
    program (no baseline to compare against, donation count unknown).
    Never raises."""
    ctx = StepContext(texts={key: text}, meta={key: VariantMeta()},
                      jaxpr_consts={key: []})
    findings = []
    findings.extend(_check_no_f64(ctx))
    findings.extend(_check_host_transfers(ctx))
    return findings
