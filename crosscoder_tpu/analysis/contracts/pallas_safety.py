"""Static safety analyzer for six ops/ Pallas kernel families.

Every kernel family that moves its blocks through BlockSpecs alone (topk,
sparsify, batchtopk, quant, paged_attention, flash_attention; not
ops/moe and ops/row_gather, whose kernels place
rows by their own DMAs and are held by tests/test_chip_compile.py and
their oracles) is probed once at a canonical
supported shape with a recording ``pallas_call`` shim: the probe runs the
real entry point, the shim captures every ``pallas_call``'s grid,
BlockSpecs, scratch shapes and compiler params *as the non-interpret TPU
path would issue them*, then executes the interpreter so the probe stays
CPU-safe. The captured specs are then checked statically:

- **grid/BlockSpec consistency** — index-map arity matches the grid rank,
  block rank matches the operand rank, one spec per operand;
- **VMEM footprint** — Σ (VMEM block bytes + VMEM scratch bytes) per
  call vs. the owning module's declared budget (``_VMEM_BUDGET[_BYTES]``,
  13 MiB everywhere except quant's 12 MiB) and a 16 MiB hard ceiling
  (the per-core VMEM size the budget model assumes — docs/SCALING.md);
- **index-map OOB** — every grid point's block index must land in
  ``[0, ceil(dim/block))`` for every blocked dimension, which is exactly
  what breaks on non-divisible tails;
- **grid-axis write races** — a grid axis declared ``parallel`` whose
  programs all map to the same output block is a data race (revisits are
  only legal on sequential/arbitrary axes, where Mosaic keeps the block
  resident and the kernel accumulates);
- **scratch hygiene** — scratch buffers are f32/i32 working sets only
  (an f64 or implicit-dtype scratch is a silent 2x VMEM bill).

Capture notes: the TPU branch guards ``pltpu.CompilerParams`` behind
``not interpret``, so the shim forces the *hardware* branch (backend
probe + dispatch gate patched) and then flips each issued call back to
``interpret=True`` for execution — the analyzed specs are the deployed
ones, not the interpreter's. Everything downstream of capture is pure
data, so mutation self-tests seed violations without touching jax.
"""

from __future__ import annotations

import contextlib
import inspect
import itertools
import math
from dataclasses import dataclass, field
from typing import Any, Callable

from crosscoder_tpu.analysis.contracts.engine import Finding, Rule

VMEM_HARD_LIMIT = 16 << 20          # per-core VMEM the budget model assumes
MAX_GRID_POINTS = 8192              # OOB/race enumeration cap per call

# the kernel families under ops/, with the VMEM
# budget each module declares for itself
KERNEL_BUDGETS = {
    "topk": 13 << 20,
    "sparsify": 13 << 20,
    "batchtopk": 13 << 20,
    "quant": 12 << 20,
    "paged_attention": 13 << 20,
    "flash_attention": 13 << 20,
}

ALLOWED_SCRATCH_DTYPES = ("float32", "int32")


@dataclass
class SpecView:
    """One BlockSpec, normalized: shapes resolved against the operand."""

    block_shape: tuple[int, ...] | None      # None = whole operand
    index_map: Callable[..., tuple] | None
    memory_space: str                        # "vmem" | "smem" | "any" | ""
    aval_shape: tuple[int, ...]
    itemsize: int

    @property
    def resolved_block(self) -> tuple[int, ...]:
        if self.block_shape is None:
            return self.aval_shape
        return tuple(1 if b is None else int(b) for b in self.block_shape)

    @property
    def block_bytes(self) -> int:
        return math.prod(self.resolved_block) * self.itemsize


@dataclass
class CapturedCall:
    """One recorded ``pallas_call``: everything the checks consume."""

    kernel: str                              # family label ("topk", ...)
    name: str                                # kernel function __name__
    grid: tuple[int, ...]
    in_specs: list[SpecView] = field(default_factory=list)
    out_specs: list[SpecView] = field(default_factory=list)
    # (shape, dtype_name, nbytes, memory_space)
    scratch: list[tuple[tuple[int, ...], str, int, str]] = field(
        default_factory=list)
    dimension_semantics: tuple[str, ...] | None = None
    n_prefetch: int = 0       # scalar-prefetch args index maps also receive

    def vmem_bytes(self) -> int:
        total = sum(s.block_bytes for s in self.in_specs + self.out_specs
                    if s.memory_space in ("vmem", ""))
        total += sum(nbytes for _, _, nbytes, space in self.scratch
                     if space in ("vmem", ""))
        return total


@dataclass
class PallasContext:
    """All captured calls, grouped by kernel family."""

    calls: list[CapturedCall] = field(default_factory=list)
    # family -> note about specs the static pass could not evaluate
    dynamic_notes: dict[str, str] = field(default_factory=dict)

    def families(self) -> set[str]:
        return {c.kernel for c in self.calls}


# ---------------------------------------------------------------------------
# capture (the only part that touches jax)


def _space_str(space: Any) -> str:
    if space is None:
        return ""
    s = str(space).lower()
    for known in ("vmem", "smem", "any", "semaphore"):
        if known in s:
            return known
    return s


def _spec_views(specs: Any, avals: list[tuple[tuple[int, ...], int]]
                ) -> list[SpecView]:
    if specs is None:
        specs = []
    if not isinstance(specs, (list, tuple)):
        specs = [specs]
    views = []
    for spec, (shape, itemsize) in zip(specs, avals):
        views.append(SpecView(
            block_shape=getattr(spec, "block_shape", None),
            index_map=getattr(spec, "index_map", None),
            memory_space=_space_str(getattr(spec, "memory_space", None)),
            aval_shape=tuple(int(d) for d in shape),
            itemsize=itemsize,
        ))
    return views


def _kernel_name(fn: Any) -> str:
    inner = getattr(fn, "func", fn)       # unwrap functools.partial
    return getattr(inner, "__name__", repr(fn))


@contextlib.contextmanager
def capture_pallas_calls(family: str, records: list[CapturedCall],
                         notes: dict[str, str]):
    """Record every ``pallas_call`` issued under this context as the TPU
    path would issue it, executing via the interpreter.

    Patches, all restored on exit: ``pl.pallas_call`` (the recorder),
    ``jax.default_backend`` -> "tpu" and ``dispatch.hw_kernel_enabled``
    -> True (so entry points take the kernel branch, not the XLA
    fallback), and a ``pltpu.CompilerParams`` alias for the TPU-only
    branch on jax versions that ship it as ``TPUCompilerParams``.
    """
    import functools
    import sys

    import jax
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from crosscoder_tpu.ops import dispatch

    real_call = pl.pallas_call
    real_backend = jax.default_backend
    real_enabled = dispatch.hw_kernel_enabled
    # an ops module that ran `from ...dispatch import hw_kernel_enabled`
    # at module level (paged_attention) holds the real function in its own
    # globals, so patching the dispatch attr alone only reaches call-site
    # imports — rebind every already-imported module carrying the original,
    # or the probe's result would depend on import order (first import
    # inside this context binds the patch; any earlier import doesn't).
    value_bound = [m for m in list(sys.modules.values())
                   if getattr(m, "hw_kernel_enabled", None) is real_enabled
                   and m is not dispatch]
    had_cp = hasattr(pltpu, "CompilerParams")
    if not had_cp:
        pltpu.CompilerParams = pltpu.TPUCompilerParams

    def recording_call(kernel, *pos, **kw):
        rec_kw = dict(kw)
        if pos:                              # out_shape passed positionally
            rec_kw.setdefault("out_shape", pos[0])
        grid_spec = rec_kw.get("grid_spec")
        n_prefetch = 0
        if grid_spec is not None:
            grid = tuple(grid_spec.grid)
            in_specs, out_specs = grid_spec.in_specs, grid_spec.out_specs
            n_prefetch = int(getattr(grid_spec, "num_scalar_prefetch", 0))
        else:
            grid = rec_kw.get("grid", ())
            grid = tuple(grid) if isinstance(grid, (tuple, list)) else (grid,)
            in_specs, out_specs = rec_kw.get("in_specs"), rec_kw.get("out_specs")

        cp = rec_kw.get("compiler_params")
        semantics = getattr(cp, "dimension_semantics", None)
        rec = CapturedCall(
            kernel=family, name=_kernel_name(kernel), grid=grid,
            dimension_semantics=(tuple(semantics) if semantics else None),
            n_prefetch=n_prefetch,
        )
        out_shape = rec_kw.get("out_shape")
        outs = out_shape if isinstance(out_shape, (list, tuple)) else [out_shape]
        out_avals = [(tuple(o.shape), np.dtype(o.dtype).itemsize)
                     for o in outs if o is not None]
        rec.out_specs = _spec_views(out_specs, out_avals)
        scratch = rec_kw.get("scratch_shapes")
        if scratch is None and grid_spec is not None:
            scratch = getattr(grid_spec, "scratch_shapes", None)
        for s in scratch or []:
            shape = getattr(s, "shape", None)
            dt = getattr(s, "dtype", None)
            if shape is None or dt is None:
                continue                     # semaphores etc.: no footprint
            dt = np.dtype(dt)
            rec.scratch.append((
                tuple(int(d) for d in shape), dt.name,
                math.prod(shape) * dt.itemsize,
                _space_str(getattr(s, "memory_space", None)),
            ))
        records.append(rec)

        run_kw = dict(kw)
        run_kw.pop("compiler_params", None)
        run_kw["interpret"] = True
        inner = real_call(kernel, *pos, **run_kw)

        @functools.wraps(inner)
        def wrapped(*args):
            blocked = args[n_prefetch:]
            in_avals = [(tuple(a.shape), np.dtype(a.dtype).itemsize)
                        for a in blocked]
            rec.in_specs = _spec_views(in_specs, in_avals)
            return inner(*args)

        return wrapped

    always_on = lambda env_var, interpret: True  # noqa: E731
    pl.pallas_call = recording_call
    jax.default_backend = lambda: "tpu"
    dispatch.hw_kernel_enabled = always_on
    for m in value_bound:
        m.hw_kernel_enabled = always_on
    try:
        yield
    except Exception as e:  # noqa: BLE001 — probe faults become notes
        notes[family] = f"probe failed: {type(e).__name__}: {e}"
    finally:
        pl.pallas_call = real_call
        jax.default_backend = real_backend
        dispatch.hw_kernel_enabled = real_enabled
        for m in value_bound:
            m.hw_kernel_enabled = real_enabled
        if not had_cp:
            del pltpu.CompilerParams


def run_kernel_probes() -> PallasContext:
    """Run each kernel family once at a canonical supported shape (the
    same geometries the kernel tests pin), recording every issued
    ``pallas_call``."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    # several ops wrap their pallas_call in jax.jit (e.g. paged_attention's
    # _rpa_call): if an earlier test in the same process already traced the
    # probe's exact shape, the cached executable would serve the call and
    # the recording pallas_call patch would capture nothing — a false
    # "probe issued no pallas_call" coverage finding. Force retracing.
    jax.clear_caches()

    ctx = PallasContext()
    rng = np.random.default_rng(0)

    def probe(family):
        return capture_pallas_calls(family, ctx.calls, ctx.dynamic_notes)

    h = jnp.asarray(rng.normal(size=(64, 512)).astype(np.float32))
    with probe("topk"):
        from crosscoder_tpu.ops import topk_pallas
        f = topk_pallas.topk(h, 32)
        # the wide-row tier: chunked bisect + emit (3-axis grid)
        h2 = jnp.asarray(rng.normal(size=(64, 1024)).astype(np.float32))
        topk_pallas._topk_chunked_impl(h2, 32, False, chunk_width=512)
    with probe("sparsify"):
        topk_pallas.sparsify(f, 32)
    with probe("batchtopk"):
        topk_pallas.batchtopk(h, 8)
    with probe("quant"):
        from crosscoder_tpu.ops import quant
        x = jnp.asarray(rng.normal(size=(512, 512)).astype(np.float32))
        assert quant.rows_supported(512, 512, 128)
        quant.quantize_rows(x, 128)
    with probe("paged_attention"):
        from crosscoder_tpu.ops import paged_attention as pa
        D, S, H, KV, hd, page = 4, 16, 4, 2, 8, 8
        assert pa.supported(D, S, H, KV, hd, page)
        q = jnp.asarray(rng.normal(size=(D, S, H, hd)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(D, S, KV, hd)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(D, S, KV, hd)).astype(np.float32))
        lengths = jnp.asarray([1, 16, 7, 9], jnp.int32)
        pa.paged_attention(q, k, v, lengths, page_size=page, scale=0.35)
    with probe("flash_attention"):
        from crosscoder_tpu.ops import flash_attention as fa
        S, H, KV, hd = 384, 4, 2, 128           # three 128-tiles, GQA g=2
        assert fa.supported(S, H, KV, hd, jnp.float32)
        q = jnp.asarray(rng.normal(size=(2, S, H, hd)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(2, S, KV, hd)).astype(np.float32))
        fa.flash_attention(q, k, k, scale=0.09, softcap=50.0, window=200)
    return ctx


# ---------------------------------------------------------------------------
# checks (pure functions of PallasContext)


def _is_pallas_ctx(ctx: Any) -> bool:
    return isinstance(ctx, PallasContext) and bool(ctx.calls)


def _grid_points(grid: tuple[int, ...]):
    if math.prod(grid) > MAX_GRID_POINTS:
        step = max(1, round(math.prod(grid) / MAX_GRID_POINTS))
        pts = list(itertools.product(*(range(g) for g in grid)))
        return pts[::step]
    return list(itertools.product(*(range(g) for g in grid)))


def _eval_map(spec: SpecView, point: tuple[int, ...]):
    """Block indices at one grid point, or None when the map is dynamic
    (e.g. closes over scalar-prefetch refs)."""
    if spec.index_map is None:
        return None
    try:
        out = spec.index_map(*point)
    except Exception:  # noqa: BLE001 — dynamic maps are skipped, not errors
        return None
    if not isinstance(out, tuple):
        out = (out,)
    try:
        return tuple(int(i) for i in out)
    except Exception:  # noqa: BLE001
        return None


def _check_probe_health(ctx: PallasContext) -> list[Finding]:
    out = []
    for family, note in sorted(ctx.dynamic_notes.items()):
        if note.startswith("probe failed"):
            out.append(Finding(
                rule="pallas-probe-coverage", location=family, message=note,
            ))
    missing = sorted(set(KERNEL_BUDGETS) - ctx.families()
                     - set(ctx.dynamic_notes))
    for family in missing:
        out.append(Finding(
            rule="pallas-probe-coverage", location=family,
            message="probe issued no pallas_call — the kernel path was "
                    "not exercised (fallback took over?)",
        ))
    return out


def _check_consistency(ctx: PallasContext) -> list[Finding]:
    out = []
    for call in ctx.calls:
        loc = f"{call.kernel}/{call.name}"
        if call.dimension_semantics is not None and \
                len(call.dimension_semantics) != len(call.grid):
            out.append(Finding(
                rule="pallas-grid-blockspec-consistency", location=loc,
                message=f"dimension_semantics rank "
                        f"{len(call.dimension_semantics)} != grid rank "
                        f"{len(call.grid)}",
            ))
        for kind, specs in (("in", call.in_specs), ("out", call.out_specs)):
            for j, spec in enumerate(specs):
                if spec.block_shape is not None and \
                        len(spec.block_shape) != len(spec.aval_shape):
                    out.append(Finding(
                        rule="pallas-grid-blockspec-consistency",
                        location=f"{loc}:{kind}[{j}]",
                        message=f"block rank {len(spec.block_shape)} != "
                                f"operand rank {len(spec.aval_shape)} "
                                f"({spec.block_shape} vs {spec.aval_shape})",
                    ))
                if spec.index_map is not None:
                    try:
                        params = inspect.signature(
                            spec.index_map).parameters.values()
                    except (TypeError, ValueError):
                        continue
                    arity = sum(1 for p in params if p.kind in
                                (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD))
                    variadic = any(p.kind == p.VAR_POSITIONAL for p in params)
                    want = len(call.grid) + call.n_prefetch
                    if (arity > want) or (arity != want and not variadic):
                        out.append(Finding(
                            rule="pallas-grid-blockspec-consistency",
                            location=f"{loc}:{kind}[{j}]",
                            message=f"index-map arity {arity} != grid "
                                    f"rank {len(call.grid)} + "
                                    f"{call.n_prefetch} prefetch args",
                        ))
    return out


def _check_vmem(ctx: PallasContext) -> list[Finding]:
    out = []
    for call in ctx.calls:
        loc = f"{call.kernel}/{call.name}"
        used = call.vmem_bytes()
        budget = KERNEL_BUDGETS.get(call.kernel, VMEM_HARD_LIMIT)
        if used > VMEM_HARD_LIMIT:
            out.append(Finding(
                rule="pallas-vmem-budget", location=loc,
                message=f"VMEM working set {used} B exceeds the "
                        f"{VMEM_HARD_LIMIT} B per-core ceiling",
            ))
        elif used > budget:
            out.append(Finding(
                rule="pallas-vmem-budget", location=loc,
                message=f"VMEM working set {used} B exceeds the module's "
                        f"declared budget {budget} B (docs/SCALING.md)",
            ))
    return out


def _check_oob(ctx: PallasContext) -> list[Finding]:
    out = []
    for call in ctx.calls:
        loc = f"{call.kernel}/{call.name}"
        pts = _grid_points(call.grid)
        for kind, specs in (("in", call.in_specs), ("out", call.out_specs)):
            for j, spec in enumerate(specs):
                block = spec.resolved_block
                n_blocks = [max(1, -(-dim // b)) for dim, b
                            in zip(spec.aval_shape, block)]
                bad = None
                for pt in pts:
                    idx = _eval_map(spec, pt)
                    if idx is None:
                        break                 # dynamic map: skip this spec
                    if len(idx) != len(block):
                        bad = (pt, idx, "rank mismatch")
                        break
                    for d, (i, n) in enumerate(zip(idx, n_blocks)):
                        if not 0 <= i < n:
                            bad = (pt, idx,
                                   f"dim {d}: block {i} outside [0, {n}) "
                                   f"(operand {spec.aval_shape}, block "
                                   f"{block})")
                            break
                    if bad:
                        break
                if bad:
                    pt, idx, why = bad
                    out.append(Finding(
                        rule="pallas-indexmap-oob",
                        location=f"{loc}:{kind}[{j}]",
                        message=f"index map at grid point {pt} -> {idx} "
                                f"is out of bounds: {why}",
                    ))
    return out


def _check_races(ctx: PallasContext) -> list[Finding]:
    out = []
    for call in ctx.calls:
        sem = call.dimension_semantics
        if sem is None:
            continue                          # default semantics: sequential
        loc = f"{call.kernel}/{call.name}"
        for axis, s in enumerate(sem):
            if s != "parallel" or call.grid[axis] <= 1:
                continue
            for j, spec in enumerate(call.out_specs):
                base = [0] * len(call.grid)
                seen = set()
                dynamic = False
                for v in range(call.grid[axis]):
                    base[axis] = v
                    idx = _eval_map(spec, tuple(base))
                    if idx is None:
                        dynamic = True
                        break
                    seen.add(idx)
                if not dynamic and len(seen) < call.grid[axis]:
                    out.append(Finding(
                        rule="pallas-write-race",
                        location=f"{loc}:out[{j}]",
                        message=f"grid axis {axis} is 'parallel' "
                                f"({call.grid[axis]} programs) but maps "
                                f"to only {len(seen)} distinct output "
                                f"blocks — concurrent programs write the "
                                f"same block without accumulation "
                                f"semantics",
                    ))
    return out


def _check_scratch(ctx: PallasContext) -> list[Finding]:
    out = []
    for call in ctx.calls:
        loc = f"{call.kernel}/{call.name}"
        for j, (shape, dtype, _, _) in enumerate(call.scratch):
            if dtype not in ALLOWED_SCRATCH_DTYPES:
                out.append(Finding(
                    rule="pallas-scratch-dtype",
                    location=f"{loc}:scratch[{j}]",
                    message=f"scratch {shape} has dtype {dtype}; kernels "
                            f"declare f32/i32 working sets only "
                            f"(docs/SCALING.md VMEM model)",
                ))
    return out


PALLAS_RULES: list[Rule] = [
    Rule("pallas-probe-coverage",
         "every kernel family's probe exercises its Pallas path",
         _is_pallas_ctx, _check_probe_health),
    Rule("pallas-grid-blockspec-consistency",
         "index-map arity and block ranks agree with grid and operands",
         _is_pallas_ctx, _check_consistency),
    Rule("pallas-vmem-budget",
         "per-call VMEM working set fits the module budget and 16 MiB core",
         _is_pallas_ctx, _check_vmem),
    Rule("pallas-indexmap-oob",
         "every grid point's block index lands inside the operand",
         _is_pallas_ctx, _check_oob),
    Rule("pallas-write-race",
         "parallel grid axes never write the same output block twice",
         _is_pallas_ctx, _check_races),
    Rule("pallas-scratch-dtype",
         "scratch buffers are declared f32/i32 working sets",
         _is_pallas_ctx, _check_scratch),
]


def vmem_summary(ctx: PallasContext) -> dict[str, str]:
    """Per-family VMEM estimate for ``Report.info`` — the acceptance
    surface: an estimate plus clean OOB/race status for every family."""
    by_family: dict[str, int] = {}
    for call in ctx.calls:
        by_family[call.kernel] = max(by_family.get(call.kernel, 0),
                                     call.vmem_bytes())
    out = {}
    for family in sorted(KERNEL_BUDGETS):
        if family in by_family:
            used = by_family[family]
            out[f"vmem/{family}"] = (
                f"{used / (1 << 20):.2f} MiB peak of "
                f"{KERNEL_BUDGETS[family] >> 20} MiB budget"
            )
        else:
            out[f"vmem/{family}"] = ctx.dynamic_notes.get(
                family, "no pallas_call captured")
    return out
