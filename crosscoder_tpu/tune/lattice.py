"""Stage-1 autotuner: enumerate the valid knob lattice, price it statically.

Candidates are real ``CrossCoderConfig`` objects: the lattice is the
cartesian product of the knob axes filtered by ``config.py``'s OWN
validation (``__post_init__`` raising prunes the point — no shadow copy
of the constraint rules lives here, so a new config constraint prunes
the lattice the day it lands). Pricing is compile-time-only analytics:

- **device terms** — HLO cost-analysis FLOPs / bytes-accessed of the
  compiled train step (one compile per DISTINCT step program: knobs
  outside :data:`STEP_FIELDS` are zero-cost-off by contract — the
  ``hlo-*-off-identity`` rules — so the whole data-plane sub-lattice
  shares one executable via ``compile_cache.aot_get``; stage-2's
  contracts gate re-verifies the assumption per shipped candidate);
- **DP-sync term** — the PR-2 wire-byte model
  (:func:`crosscoder_tpu.parallel.comm_model.wire_bytes`) over the
  compiled step's collectives at the candidate mesh width;
- **data-plane terms** — the docs/SCALING.md refill and harvest cost
  models ("Zero-bubble refill", "Harvest cost model", "Fleet
  amortization") for ``refill_frac`` / ``refill_overlap`` /
  ``refill_dispatch_batch`` / ``prefetch`` / ``quant_buffer``.

Absolute accuracy is irrelevant — only the RANKING matters (stage 2
measures the survivors) — but the constants match the comm_model /
fleet-policy prediction plane so every modeled number in the repo is
comparable.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import sys
from typing import Any

# modeled accelerator constants, shared with the prediction plane:
# v5e public numbers (parallel/comm_model.py, resilience/fleet.py)
PEAK_FLOPS = 197e12
HBM_GBPS = 819.0
# measured host cost of one harvest-quantum Python dispatch
# (docs/SCALING.md "Zero-bubble refill": ~6-8 ms trace+dispatch+donation)
HOST_DISPATCH_MS = 7.0
# reference harvest device cost per model-batch at the reference shape
# (docs/SCALING.md "Measured collective volumes": ~85 ms/model-batch)
HARVEST_REF_MS = 85.0
_REF_BATCH = 4096
# harvest quanta dispatched per serve at the bench-default segmentation
_QUANTA_PER_SERVE = 4
# fraction of the batched dispatcher's host cost that still contends
# with the serve path when offloaded (refill_overlap=on dispatcher thread)
_OFF_CRITICAL = 0.1

# Config fields that change the COMPILED STEP program. Everything else
# is host/data-plane and shares the step executable (the zero-cost-off
# contract); candidates are projected onto this set to key the AOT memo.
STEP_FIELDS = frozenset({
    "activation", "topk_k", "factored_decode", "sparse_bwd", "quant_grads",
    "quant_block", "batch_size", "dict_size", "d_in", "n_models",
    "hook_points", "enc_dtype", "master_dtype", "l1_coeff", "l0_coeff",
    "aux_k", "aux_every", "remat", "grad_clip", "shard_sources",
    "data_axis_size", "model_axis_size", "seed",
})

OBJECTIVES = ("train", "serve", "fleet")


@dataclasses.dataclass
class Candidate:
    """One lattice point: the knob assignment plus its validated config.

    ``base_sig`` identifies the base config the lattice was swept from
    (everything NOT on a knob axis); two candidates share a pricing
    compile only when both the base and their step-relevant knobs agree.
    """

    knobs: dict[str, Any]
    cfg: Any
    base_sig: str = ""
    predicted: dict[str, Any] = dataclasses.field(default_factory=dict)
    score: float | None = None

    @property
    def label(self) -> str:
        return ",".join(f"{k}={self.knobs[k]}" for k in sorted(self.knobs))


def default_axes(cfg: Any, objective: str = "train") -> dict[str, tuple]:
    """The stock knob axes per objective — the data-plane and ladder
    knobs every deployment scenario was hand-pinning. Values that the
    base config cannot validate are pruned at enumeration, so axes may
    be generous."""
    if objective == "train":
        return {
            "refill_overlap": ("off", "on"),
            "refill_dispatch_batch": (4, 8),
            "refill_frac": (0.25, 0.5),
            "prefetch": (False, True),
            "quant_buffer": (False, True),
        }
    if objective == "serve":
        return {
            "serve_max_batch": (8, 16, 32),
            "serve_max_wait_ms": (1.0, 2.0, 5.0),
            "page_size": tuple(p for p in (16, 32, 64)
                               if p <= cfg.seq_len and cfg.seq_len % p == 0)
                         or (cfg.page_size,),
        }
    if objective == "fleet":
        return {
            "fleet_max_buckets": (2, 4, 8),
            "refill_frac": (0.25, 0.5),
            "prefetch": (False, True),
        }
    raise ValueError(f"objective must be one of {OBJECTIVES}, "
                     f"got {objective!r}")


def enumerate_lattice(
    base_cfg: Any, axes: dict[str, tuple]
) -> tuple[list[Candidate], int]:
    """Cartesian product of ``axes`` over ``base_cfg``, keeping exactly
    the points ``CrossCoderConfig`` validation accepts. Returns
    ``(candidates, n_pruned_invalid)``. Deterministic: axes iterate in
    sorted-name order, values in the given order."""
    names = sorted(axes)
    base_dict = {k: v for k, v in base_cfg.to_dict().items()
                 if k not in axes}
    base_sig = hashlib.sha256(
        json.dumps(base_dict, sort_keys=True, default=str).encode()
    ).hexdigest()[:16]
    out: list[Candidate] = []
    pruned = 0
    for values in itertools.product(*(axes[n] for n in names)):
        knobs = dict(zip(names, values))
        try:
            cfg = base_cfg.replace(**knobs)
        except (ValueError, TypeError):
            pruned += 1
            continue
        out.append(Candidate(knobs=knobs, cfg=cfg, base_sig=base_sig))
    return out, pruned


# ---------------------------------------------------------------------------
# static pricing
# ---------------------------------------------------------------------------


def _step_signature(cand: Candidate) -> str:
    """The pricing-compile share key: the base config's identity plus the
    candidate's step-relevant knob values. Knobs outside
    :data:`STEP_FIELDS` are data-plane (zero-cost-off), so candidates
    differing only in those share one compiled step."""
    step_knobs = {k: v for k, v in sorted(cand.knobs.items())
                  if k in STEP_FIELDS}
    return cand.base_sig + "|" + json.dumps(step_knobs, sort_keys=True,
                                            default=str)


def _step_cost(cand: Candidate, n_devices: int) -> dict[str, float]:
    """FLOPs / bytes-accessed / wire-bytes of the candidate's step
    program, one compile per distinct :func:`_step_signature` via
    ``aot_get`` (so a 32-point data-plane lattice costs ONE compile)."""
    import jax

    from crosscoder_tpu.parallel import comm_model
    from crosscoder_tpu.parallel import mesh as mesh_lib
    from crosscoder_tpu.utils import compile_cache

    cfg = cand.cfg
    key = ("tune_step", _step_signature(cand))

    def build():
        mesh = mesh_lib.make_mesh(devices=jax.devices()[:1])
        return comm_model._compile_train_step(cfg, mesh)

    # disk-first (cfg.compile_cache_dir): a previously priced signature
    # answers from the persisted cost sidecar without compiling — or
    # even deserializing — anything
    cost = compile_cache.cost_of(key)
    comm = compile_cache.collectives_of(key)
    if cost is None or comm is None:
        compiled = compile_cache.aot_get(key, build)
        cost = (cost or compile_cache.cost_of(key)
                or compile_cache.record_cost(key, compiled))
        if comm is None:
            comm = comm_model.collective_bytes(compiled.as_text())
    n_model = max(1, int(cfg.model_axis_size))
    profile = comm_model.CommProfile("tune_step", n_devices, n_model, comm)
    n_data = max(1, n_devices // n_model)
    return {
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes_accessed", 0.0),
        "wire_bytes": comm_model.wire_bytes(profile, axis_size=n_data),
    }


def _data_plane_ms(cfg: Any, device_ms: float) -> dict[str, float]:
    """The docs/SCALING.md refill cost model, per step.

    - harvest: serves per harvested row is ``0.5/refill_frac`` (the
      reference trigger fires at half-buffer), so the per-serve harvest
      share scales as ``2*refill_frac``;
    - host dispatch: a synchronous loop pays every quantum's host cost on
      the serve path; the overlap engine batches ``refill_dispatch_batch``
      quanta per dispatch and pumps them off-thread, leaving only
      residual contention plus whatever device time the step can't hide;
    - serve gather: the batch fetch, hidden entirely by ``prefetch``;
      ``quant_buffer`` reads ~0.51x the store bytes.
    """
    batch_scale = cfg.batch_size / _REF_BATCH
    harvest_dev_ms = HARVEST_REF_MS * batch_scale * (2.0 * cfg.refill_frac)
    q = _QUANTA_PER_SERVE
    gather_bytes = (cfg.batch_size * cfg.n_sources * cfg.d_in
                    * (1.04 if cfg.quant_buffer else 2.0))
    gather_ms = 1e3 * gather_bytes / (HBM_GBPS * 1e9)
    if cfg.refill_overlap == "on":
        k = max(1, int(cfg.refill_dispatch_batch))
        host_ms = q * HOST_DISPATCH_MS / k * _OFF_CRITICAL
        bubble_ms = max(0.0, harvest_dev_ms - device_ms)
    else:
        host_ms = q * HOST_DISPATCH_MS
        bubble_ms = harvest_dev_ms
    fetch_ms = 0.0 if cfg.prefetch else gather_ms
    return {
        "harvest_ms": harvest_dev_ms,
        "refill_host_ms": host_ms,
        "refill_bubble_ms": bubble_ms,
        "fetch_ms": fetch_ms,
    }


def price_candidate(
    cand: Candidate, objective: str = "train", n_devices: int = 1
) -> dict[str, Any]:
    """Stage-1 analytical price of one candidate for ``objective``.
    Fills ``cand.predicted`` / ``cand.score`` and returns the breakdown;
    higher score is better for every objective (latency objectives score
    the negated prediction)."""
    cfg = cand.cfg
    step = _step_cost(cand, n_devices)
    compute_ms = 1e3 * step["flops"] / PEAK_FLOPS
    hbm_ms = 1e3 * step["bytes_accessed"] / (HBM_GBPS * 1e9)
    device_ms = max(compute_ms, hbm_ms)
    wire_ms = 1e3 * step["wire_bytes"] / (HBM_GBPS * 1e9)
    plane = _data_plane_ms(cfg, device_ms)
    total_ms = (device_ms + wire_ms + plane["refill_host_ms"]
                + plane["refill_bubble_ms"] + plane["fetch_ms"])
    pred: dict[str, Any] = {
        "device_ms": device_ms, "wire_ms": wire_ms,
        "step_total_ms": total_ms, **step, **plane,
    }
    if objective == "train":
        score = cfg.batch_size * 1e3 / (total_ms * max(1, n_devices))
        pred["acts_per_sec_chip"] = score
    elif objective == "serve":
        b = int(cfg.serve_max_batch)
        nd = cfg.n_sources * cfg.d_in
        encode_ms = 1e3 * (2.0 * b * nd * cfg.dict_size) / PEAK_FLOPS
        # page granularity: a request pads its tail to a whole KV page
        page_waste = cfg.page_size / (2.0 * cfg.seq_len)
        prefill_ms = (HARVEST_REF_MS * (b / _REF_BATCH)
                      * (1.0 + page_waste))
        p99_ms = cfg.serve_max_wait_ms + prefill_ms + encode_ms
        pred.update(encode_ms=encode_ms, prefill_ms=prefill_ms,
                    p99_ms=p99_ms)
        score = -p99_ms
    elif objective == "fleet":
        n_tenants = max(1, len([t for t in cfg.fleet_tenants.split(";")
                                if t.strip()]) or 1)
        buckets = min(n_tenants, max(1, int(cfg.fleet_max_buckets)))
        round_ms = (plane["harvest_ms"] + plane["refill_host_ms"]
                    + buckets * (device_ms + wire_ms))
        score = n_tenants * cfg.batch_size * 1e3 / (
            round_ms * max(1, n_devices))
        pred.update(round_ms=round_ms, n_buckets=buckets,
                    agg_acts_per_sec_chip=score)
    else:
        raise ValueError(f"objective must be one of {OBJECTIVES}, "
                         f"got {objective!r}")
    pred["score"] = score
    cand.predicted = pred
    cand.score = score
    return pred


def rank_candidates(
    candidates: list[Candidate], objective: str = "train",
    n_devices: int = 1, seed: int = 0,
) -> list[Candidate]:
    """Price every candidate and return them best-first. Deterministic
    under a fixed seed: exact score ties break on a seeded hash of the
    knob assignment (stable across processes — never dict order). A
    candidate whose pricing compile fails is dropped with a stderr note,
    not a crash: pricing runs over arbitrary user axes."""
    priced: list[Candidate] = []
    for cand in candidates:
        try:
            price_candidate(cand, objective, n_devices)
            priced.append(cand)
        except Exception as e:  # noqa: BLE001 — user-supplied lattice
            print(f"[crosscoder_tpu] tune: pricing {cand.label} failed "
                  f"({type(e).__name__}: {e})"[:300],
                  file=sys.stderr, flush=True)

    def tie(c: Candidate) -> str:
        return hashlib.sha256(
            f"{seed}:{json.dumps(c.knobs, sort_keys=True, default=str)}"
            .encode()).hexdigest()

    priced.sort(key=lambda c: (-c.score, tie(c)))
    return priced
