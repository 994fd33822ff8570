"""Paired-activation replay buffer: harvest, calibrate, shuffle, serve.

Re-implements the reference ``Buffer`` (reference ``buffer.py:7-125``) with a
TPU-native split of responsibilities:

- **Harvest on device**: all models' residual streams at the hook point(s)
  come from ONE jitted :func:`crosscoder_tpu.models.lm.run_with_cache_multi`
  dispatch per chunk, truncated at the highest hooked layer (replacing the
  reference's per-model full-depth TransformerLens ``run_with_cache``,
  reference ``buffer.py:81-89``), batch-shardable over the mesh ``data``
  axis.
- **Buffer + shuffle on host**: the replay store is host RAM (bf16 numpy),
  not HBM — the reference burns ~4.8 GB of GPU memory on it (reference
  ``buffer.py:18-22``). Instead of physically permuting 4.8 GB every refresh
  (reference ``buffer.py:111-113``'s on-GPU ``randperm`` gather), we keep
  the store in harvest order and serve batches through a shuffled *index*
  permutation — the same without-replacement sampling distribution, zero
  large copies; only the 36 MB batch gather crosses host→device per step.

Behavioral parity with the reference (each a deliberate keep, SURVEY.md §2
"behavioral quirks"):

- sizes: ``buffer_size = batch_size·buffer_mult`` rounded DOWN to a multiple
  of ``seq_len−1`` (BOS rows are dropped; reference ``buffer.py:15-17,93``);
- first ``refresh()`` fills the whole buffer, later ones refill a
  ``cfg.refill_frac`` fraction (default 0.5 — the reference's half-refill,
  ``buffer.py:70-74``; smaller fractions re-serve survivors more, trading
  data freshness for harvest FLOPs);
- ``next()`` triggers a refresh once the read pointer passes
  ``buffer_size//2 − batch_size`` (reference ``buffer.py:121``);
- per-source norm calibration ``sqrt(d_in)/mean_token_norm`` over
  ``norm_calib_batches × model_batch_size`` sequences (reference
  ``buffer.py:44-63``), applied multiplicatively in ``next()`` (reference
  ``buffer.py:123-124``); calibration reads the same leading tokens the
  first refresh consumes (reference ``buffer.py:26,51``);
- ``next()`` returns fp32 rows ``[batch, n_sources, d_in]``.

Additions the reference lacks: multi-source harvest (N models × L hook
points in one pass — the source axis generalization, SURVEY components
N4/N8), deterministic seeded shuffles, and ``state_dict``/``load_state_dict``
so training can resume mid-stream (the reference cannot resume at all,
SURVEY.md §5 checkpoint/resume).
"""

from __future__ import annotations

import functools
import sys
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from crosscoder_tpu import native, obs
from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.models import lm
from crosscoder_tpu.obs import trace
from crosscoder_tpu.parallel import multihost
from crosscoder_tpu.utils import pipeline

_BF16 = np.dtype(jnp.bfloat16.dtype)

# Harvest dispatch/drain and the serve gather run under
# pipeline.sharded_program_guard(): when two buffers live in one process
# (paired-trainer tests, A/B sweeps) with prefetching trainers, producer
# threads and the main thread would otherwise execute sharded programs
# concurrently on the same device set, which can deadlock XLA:CPU (see
# the guard's docstring). The guard is process-wide and a no-op off-CPU;
# producer threads only exist in single-process mode (trainer disables
# prefetch on multi-process meshes), so it cannot cross-host desync, and
# buffers never wait on each other, so lock ordering is trivial.


def _on_mesh(params: lm.LMParams, mesh) -> lm.LMParams:
    """Commit one model's weights to the harvest mesh, once. A leaf left
    uncommitted on the default device (``lm.from_hf`` without shardings,
    host arrays after :meth:`prepare_reshard`) is otherwise re-replicated
    across the mesh device-to-device by EVERY harvest dispatch. Leaves
    already laid out over this mesh's devices (``lm.tp_shardings``) keep
    their layout."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = set(mesh.devices.flat)
    replicated = NamedSharding(mesh, P())

    def place(a):
        if (isinstance(a, jax.Array) and a.committed
                and set(a.sharding.device_set) == devices):
            return a
        return multihost.put_global(a, replicated)

    return jax.tree_util.tree_map(place, params)


class _SingleDispatchJob:
    """Adapter giving an already-dispatched harvest future the
    :class:`crosscoder_tpu.models.lm.SegmentedHarvest` step protocol (used
    where segmentation doesn't apply, e.g. the seq-parallel harvest)."""

    n_steps = 1

    def __init__(self, result) -> None:
        self._result = result

    def step(self) -> bool:
        return False

    def step_many(self, quanta: int) -> tuple[int, bool]:
        # already dispatched in full: one quantum of the pacing budget
        return 1, False

    def inflight(self):
        return [self._result]

    def result(self):
        return self._result


class PairedActivationBuffer:
    """Serves shuffled paired activations for crosscoder training.

    Parameters
    ----------
    cfg: framework config (sizes, hook points, calibration knobs).
    lm_cfg: architecture of the harvested models.
    model_params: one LM param pytree per model (reference: Gemma-2-2B base
        and IT, ``train.py:45-55``). ``len(model_params)`` must equal
        ``cfg.n_models``.
    tokens: ``[n_seqs, seq_len]`` int array of pretokenized sequences (the
        reference's global ``all_tokens``, ``utils.py:180-196``).
    batch_sharding: optional ``NamedSharding`` for the harvest forward's
        token batches (mesh ``data`` axis; component N5).
    """

    # harvest chunks kept in flight during refresh/calibration: device
    # compute overlaps host fetch+scatter (1 = fully serial, the
    # reference's behavior); see crosscoder_tpu.utils.pipeline
    PIPELINE_DEPTH = pipeline.DEFAULT_DEPTH

    # the host store funnels every harvest chunk through one process's RAM
    # (device_get raises on cross-process-sharded arrays); the device/mesh
    # subclasses keep rows on device and override this
    _MULTIPROCESS_OK = False

    # whether the overlap engine may offload its dispatch pump to a
    # dedicated thread: the host store's drains touch only host memory in
    # rows disjoint from everything the serve path reads, so the thread is
    # safe; the device stores rebind a DONATED store array per scatter,
    # which would race the serve gather's read of that binding on async
    # backends — they pump inline instead (still batched)
    _DISPATCH_THREAD_OK = True

    def _pipelined(self, produced, drain) -> None:
        pipeline.drive(produced, drain, depth=self.PIPELINE_DEPTH)

    def __init__(
        self,
        cfg: CrossCoderConfig,
        lm_cfg: lm.LMConfig,
        model_params: Sequence[lm.LMParams],
        tokens: np.ndarray | jax.Array,
        batch_sharding: Any | None = None,
        lazy: bool = False,
        chaos: Any | None = None,
    ) -> None:
        if len(model_params) != cfg.n_models:
            raise ValueError(f"got {len(model_params)} param sets for n_models={cfg.n_models}")
        if not self._MULTIPROCESS_OK and jax.process_count() > 1:
            # fail at CONSTRUCTION, before model loads / calibration burn
            # minutes of device time, not at the first harvest drain
            raise ValueError(
                "buffer_device='host' cannot run on a multi-process mesh "
                "(chunks funnel through one process's RAM); use "
                "buffer_device='hbm' — the mesh-sharded store"
            )
        self.cfg = cfg
        self.lm_cfg = lm_cfg
        self.model_params = list(model_params)
        # fault-injection hook (resilience/chaos.py): fires at each harvest
        # chunk's dispatch; None (default, all production configs) is never
        # consulted beyond an is-None check
        self.chaos = chaos
        self.tokens = np.asarray(tokens)
        if self.tokens.ndim != 2 or self.tokens.shape[1] != cfg.seq_len:
            raise ValueError(f"tokens must be [n_seqs, {cfg.seq_len}], got {self.tokens.shape}")
        self.hook_points = cfg.resolved_hook_points()
        self.batch_sharding = batch_sharding
        if batch_sharding is not None:
            self.model_params = [
                _on_mesh(p, batch_sharding.mesh) for p in self.model_params
            ]
        # sequence-parallel harvest (component N5 made reachable): shard the
        # harvest forward's SEQUENCE axis over the mesh data axis — exact
        # ring attention (parallel/ring_attention.py) — for contexts whose
        # score matrix won't fit one chip. The replay/serve side is
        # untouched: rows are rows regardless of how the forward was sharded.
        self._seq_mesh = None
        if cfg.seq_shards > 1:
            if batch_sharding is None:
                raise ValueError(
                    "seq_shards needs a mesh: pass batch_sharding (its mesh's "
                    "'data' axis is the sequence-shard axis)"
                )
            mesh_axis = int(batch_sharding.mesh.shape.get("data", 1))
            if mesh_axis != cfg.seq_shards:
                raise ValueError(
                    f"seq_shards {cfg.seq_shards} != mesh data axis {mesh_axis}"
                )
            self._seq_mesh = batch_sharding.mesh

        rows_per_seq = cfg.seq_len - 1                      # BOS dropped
        # reference buffer.py:15-17: round the row budget down to whole seqs
        self.buffer_batches = cfg.batch_size * cfg.buffer_mult // rows_per_seq
        self.buffer_size = self.buffer_batches * rows_per_seq
        if self.buffer_size < 2 * cfg.batch_size:
            raise ValueError(
                f"buffer_size {self.buffer_size} < 2×batch_size; raise buffer_mult"
            )

        # every harvest forward runs at this fixed sequence count: a multiple
        # of the mesh data-axis size (sharding divisibility) >= the requested
        # model_batch_size — one compile shape, ragged tails padded. Under
        # seq_shards the data axis carries the SEQUENCE, so the batch axis
        # has no divisibility constraint. Computed BEFORE _alloc_store so
        # store implementations can validate harvest-chunk divisibility at
        # construction (MeshPairedActivationBuffer does).
        data_axis = 1
        if batch_sharding is not None and self._seq_mesh is None:
            data_axis = int(batch_sharding.mesh.shape.get("data", 1))
        self._chunk_seqs = -(-cfg.model_batch_size // data_axis) * data_axis
        # paged harvest runtime (cfg.harvest_runtime="paged";
        # models/lm.run_with_cache_multi_paged + data/paging.py): mixed-
        # length chunks pack into a dense token plane before the forward,
        # so harvest matmul cost tracks REAL tokens. The emitted chunk
        # comes back in the padded [C, S, n, d] layout with pad positions
        # zeroed — every drain/scatter path downstream is untouched, and
        # on the all-full-length production corpus the stream is BIT-
        # identical to the padded path (tests/test_paging.py). With the
        # default "padded" runtime none of this code is reachable.
        self._paged = cfg.harvest_runtime == "paged"
        self._plane_multiple = data_axis
        self._paged_valid_tokens = 0    # padding-efficiency telemetry
        self._paged_total_tokens = 0

        # zero-bubble refill (cfg.refill_overlap="on"; docs/SCALING.md
        # "Zero-bubble refill"): steady-state cycles harvest into SPARE
        # physical rows while the live rows keep serving, and a logical→
        # physical row map swaps at the cycle boundary — pure index
        # bookkeeping, no data movement. _spare_rows equals the steady-
        # state refill target, so one shadow cycle always fits; full
        # fills (first fill, restore) exceed it and take the baseline
        # in-place path. Store memory grows ×(1 + refill_frac).
        self._overlap = cfg.refill_overlap == "on"
        self._spare_rows = (
            self._refill_batches() * rows_per_seq if self._overlap else 0
        )
        self._store_rows = self.buffer_size + self._spare_rows
        self._row_map = np.arange(self.buffer_size)
        self._free_rows = self.buffer_size + np.arange(self._spare_rows)
        # batched/offloaded dispatch: a dedicated thread spends the
        # pacing credit so the per-dispatch host cost never sits on
        # the serve path. Single-process only — the thread's timing is
        # host-local, so on a multi-process mesh the same pump runs
        # inline in _advance_cycle (count-based, SPMD-consistent).
        self._dispatcher = None
        if (self._overlap and self._DISPATCH_THREAD_OK
                and jax.process_count() == 1):
            self._dispatcher = pipeline.QuantumDispatcher(self._pump_locked)

        self._alloc_store()
        self._perm = np.arange(self.buffer_size)
        self._rng = np.random.default_rng(cfg.seed)
        self.pointer = 0            # read position in the permutation
        self.token_pointer = 0      # next unharvested sequence (mod corpus)
        self._global_seq = 0        # monotone count of harvested sequences
        # per-row provenance: which global sequence produced each store row —
        # lets save/resume rewind to the OLDEST unserved row's tokens
        self._src_global = np.zeros(self.buffer_size, dtype=np.int64)
        self.first = True
        self._filled = False
        # multi-consumer fan-out (fleet serving; train/fleet.py): one real
        # gather per stream position, cached and handed to every attached
        # consumer whose cursor sits at that position. _serve_seq counts
        # REAL serves (solo next()/next_raw() calls advance it too, so a
        # consumer attached mid-stream starts at the true next position).
        self._serve_seq = 0
        self._consumers: dict[str, int] = {}
        self._fanout_batch: np.ndarray | None = None
        self._fanout_seq = -1

        if not lazy:
            # lazy=True defers calibration+fill to load_state_dict() so a
            # resumed run doesn't harvest the whole buffer twice
            with trace.span("calibrate"):
                self.normalisation_factor = self._estimate_norm_scaling_factors()
            self.refresh()

    def _alloc_store(self) -> None:
        # _store_rows = buffer_size + the overlap engine's spare region
        # (equal to buffer_size with refill_overlap off)
        self._store = np.empty(
            (self._store_rows, self.cfg.n_sources, self.cfg.d_in), dtype=_BF16
        )

    def store_nbytes(self) -> int:
        """Bytes the replay store occupies (host RAM here; HBM for the
        device subclasses) — the accounting the quantized-plane HBM
        budget asserts against."""
        return self._store.nbytes

    def _refill_batches(self) -> int:
        """Sequences harvested per steady-state cycle. refill_frac 0.5 is
        the reference's half-refill (buffer.py:70-74); smaller fractions
        re-serve survivors more (~0.5/refill_frac serves per harvested row)
        and cut harvest FLOPs proportionally — the serve trigger stays at
        the reference's half-buffer point either way."""
        return max(1, int(self.buffer_batches * self.cfg.refill_frac))

    # ------------------------------------------------------------------
    # harvest

    def _pad_chunk(self, token_batch: np.ndarray) -> tuple[np.ndarray, int]:
        """Pad a ragged chunk to the fixed harvest shape: keeps dim 0
        divisible by the mesh data axis and avoids per-shape recompiles."""
        n = token_batch.shape[0]
        if n != self._chunk_seqs:
            assert n < self._chunk_seqs, (n, self._chunk_seqs)
            pad = np.broadcast_to(token_batch[:1], (self._chunk_seqs - n, *token_batch.shape[1:]))
            token_batch = np.concatenate([token_batch, pad])
        return token_batch, n

    def _harvest_dev_paged(self, padded_tokens: np.ndarray) -> jax.Array:
        """Paged-runtime harvest of one chunk: ragged lengths from
        trailing-pad detection, host-side packing, per-document ragged
        attention — returns the same padded-layout ``[C, S, n, d]`` bf16
        chunk as the dense path. ``pad_mode="wrap"``: positions past a
        document's length are filled by cycling its own post-BOS rows, so
        every row the fixed-rows-per-sequence drain ingests is a REAL
        activation (short documents' tokens get re-served proportionally
        more — the packing analogue of the survivor re-serves
        ``refill_frac`` already makes) rather than a zero vector."""
        from crosscoder_tpu.data import tokens as tokens_mod

        lengths = tokens_mod.valid_lengths(padded_tokens)
        self._paged_valid_tokens += int(lengths.sum())
        self._paged_total_tokens += int(padded_tokens.size)
        return lm.run_with_cache_multi_paged(
            self.model_params, padded_tokens, lengths, self.lm_cfg,
            self.hook_points, page_size=self.cfg.page_size,
            row_multiple=self._plane_multiple,
            batch_sharding=self.batch_sharding,
            pad_mode="wrap", out_dtype=jnp.bfloat16,
        )

    def padding_efficiency(self) -> float | None:
        """Real-token fraction of everything harvested so far (paged
        runtime only; None under the padded runtime — it has no ragged
        accounting). Logged by the trainer as
        ``harvest/padding_efficiency``."""
        if not self._paged or self._paged_total_tokens == 0:
            return None
        return self._paged_valid_tokens / self._paged_total_tokens

    def _harvest_dev(self, padded_tokens: np.ndarray) -> jax.Array:
        """All sources' hook activations for one fixed-shape token chunk,
        DEVICE-resident ``[C, S, n_sources, d_in]`` bf16 (source axis
        model-major, matching ``n_sources = n_models × n_hooked_layers``).

        No host sync: the result is a future, so callers can pipeline
        several chunks' forwards against host-side fetch/scatter work.
        """
        if self._paged:
            return self._harvest_dev_paged(padded_tokens)
        tok = jnp.asarray(padded_tokens)
        if self._seq_mesh is not None:
            # sequence-sharded forwards (ring attention over the data axis),
            # all models in ONE compiled dispatch; capture comes back
            # globally stitched, same [C, S, n, d] shape and model-major
            # source order as the dense path
            stacked = lm.run_with_cache_multi_seq_parallel(
                self.model_params, tok, self.lm_cfg, self.hook_points,
                self._seq_mesh,
            )
        else:
            if self.batch_sharding is not None:
                tok = multihost.put_global(tok, self.batch_sharding)
            stacked = lm.run_with_cache_multi(
                self.model_params, tok, self.lm_cfg, self.hook_points
            )
        return stacked.astype(jnp.bfloat16)

    def _harvest(self, token_batch: np.ndarray) -> np.ndarray:
        """Blocking harvest of one (possibly ragged) chunk → host array."""
        padded, n = self._pad_chunk(token_batch)
        return np.asarray(jax.device_get(self._harvest_dev(padded)))[:n]

    def _estimate_norm_scaling_factors(self) -> np.ndarray:
        """Per-source ``sqrt(d_in) / mean_token_norm`` (reference
        ``buffer.py:44-63``; adapted there from SAELens). Means include every
        position, BOS included, as the reference's do.

        TPU-native shape: the per-chunk norm sums reduce ON DEVICE to a
        ``[n_sources]`` vector and accumulate there across chunks — one
        scalar-sized fetch at the very end instead of shipping every
        ``[B, S, n, d]`` chunk to host (the reference pulls all 800 forwards'
        activations through host memory). Under a sharded harvest the
        reduction is a psum-mean — XLA inserts the collective from the
        sharding (SURVEY component N1)."""
        cfg = self.cfg
        n_seqs = cfg.norm_calib_batches * cfg.model_batch_size
        if n_seqs > self.tokens.shape[0]:
            n_seqs = self.tokens.shape[0]

        @jax.jit
        def chunk_norm_sums(acts: jax.Array, n_valid: jax.Array) -> jax.Array:
            norms = jnp.linalg.norm(acts.astype(jnp.float32), axis=-1)  # [C,S,n]
            mask = (jnp.arange(acts.shape[0]) < n_valid)[:, None, None]
            return jnp.sum(norms * mask, axis=(0, 1))                   # [n]

        # same bounded pipeline as refresh(): a few chunk forwards in
        # flight, each chunk's [n_sources] partial sum fetched with lag and
        # accumulated host-side in float64 (unbounded enqueue would fill
        # HBM with queued activation intermediates)
        sums = np.zeros((cfg.n_sources,), np.float64)
        count = 0

        def produced():
            nonlocal count
            for start in range(0, n_seqs, self._chunk_seqs):
                chunk = self.tokens[start: start + self._chunk_seqs][:n_seqs - start]
                padded, n = self._pad_chunk(chunk)
                count += n * chunk.shape[1]
                yield chunk_norm_sums(self._harvest_dev(padded), np.int32(n))

        def drain(part) -> None:
            nonlocal sums
            sums += np.asarray(jax.device_get(part), np.float64)

        self._pipelined(produced(), drain)
        if cfg.obs == "on" and self.lm_cfg.sparse:
            self._gauge_expert_load()
        if cfg.obs == "on" and self.lm_cfg.n_streams > 1:
            self._gauge_stream_maps()
        mean_norm = sums / max(count, 1)
        return (np.sqrt(cfg.d_in) / mean_norm).astype(np.float32)

    def _gauge_expert_load(self) -> None:
        """``harvest/moe_load_max_over_mean``: how unevenly the first model's
        router spreads one calibration chunk over its experts (rows at the
        busiest expert over the mean, worst layer), and
        ``harvest/moe_local_row_share``: the share of its routed rows that
        go to the experts this chip holds (mean over the sparse layers; 1
        where it holds them all). Read ONCE, here, where calibration fetches
        from the device anyway: the loop gains no sync, and with ``obs`` off
        nothing runs."""
        from crosscoder_tpu.ops import moe

        cfg = self.lm_cfg
        depth = max(lm.hooked_depth(cfg, self.hook_points), 1)
        sparse = [i for i in range(depth) if cfg.mlp_types[i] == lm.SPARSE]
        if not sparse:
            return
        padded, _ = self._pad_chunk(self.tokens[: self._chunk_seqs])
        counts = jax.device_get(lm.expert_load(
            self.model_params[0], jnp.asarray(padded), cfg, depth))[sparse]
        obs.gauge("harvest/moe_load_max_over_mean", moe.load_max_over_mean(counts))
        obs.gauge("harvest/moe_local_row_share",
                  moe.local_row_share(counts, cfg.first_expert, cfg.n_held))

    def _gauge_stream_maps(self) -> None:
        """``harvest/mhc_col_err``: how far the first model's mixing matrices
        are from doubly stochastic over one calibration chunk (``max
        |colsum(M) − 1|`` over tokens, sublayers and the hooked layers) —
        Sinkhorn's remainder, by which the streams' mean is the hooked
        residual stream. Read ONCE, beside the load gauge, only with ``obs``
        on: the loop gains no sync."""
        depth = max(lm.hooked_depth(self.lm_cfg, self.hook_points), 1)
        padded, _ = self._pad_chunk(self.tokens[: self._chunk_seqs])
        errs = jax.device_get(lm.mhc_col_err(
            self.model_params[0], jnp.asarray(padded), self.lm_cfg, depth))
        obs.gauge("harvest/mhc_col_err", float(np.max(errs)))

    def refresh(self) -> None:
        """Synchronous refill: first fill, resume, and tests.

        First call fills the whole buffer; later calls refill
        ``cfg.refill_frac`` of it (0.5 = the reference's half-refill,
        reference ``buffer.py:70-74``). Steady-state training does NOT come through
        here — the serve path refills *incrementally*, interleaving harvest
        chunks between train steps (see :meth:`_advance_cycle`), so the
        reference's multi-second stall every ~63 steps (reference
        ``buffer.py:121-122``) becomes a sub-batch-sized bubble.
        """
        self._quiesce_dispatch()
        first, self.first = self.first, False
        if not first:
            self._begin_cycle(self._refill_batches())
            self._finish_cycle()
            return
        # span site (docs/OBSERVABILITY.md): the synchronous whole-buffer
        # fill — the largest part of set-up the program can shorten
        with trace.span("first_fill", batches=self.buffer_batches):
            self._begin_cycle(self.buffer_batches)
            self._finish_cycle()

    # -- incremental refill cycle ---------------------------------------
    #
    # One cycle = one reference refresh(): harvest `_cyc_batches` sequences,
    # overwrite the permutation region `_perm[:target]`, re-shuffle, reset
    # the read pointer. The reference runs the whole cycle as one blocking
    # stall at the trigger point; here chunks are dispatched as the serve
    # pointer frees their target positions, so the device interleaves
    # harvest forwards with train steps and the trigger point only has to
    # drain the (typically already-finished) last chunks.
    #
    # Write-safety invariant: a chunk's rows may land only on positions the
    # current fill can no longer serve — either already-served slots
    # (serve-order index < pointer) or the *statically unserved tail*: the
    # trigger fires once pointer > buffer//2 − batch, i.e. after exactly
    # m = floor((buffer//2 − batch)/batch) + 1 serves, so serve-order
    # positions [m·batch, target) are provably never served this fill (the
    # reference overwrites this same tail unseen, reference buffer.py:98-121).
    # Writes go tail-first (rotation by `_cyc_rot`), then follow the pointer
    # through the served prefix: a chunk at write offset w of r rows is safe
    # once  w + r ≤ pointer + tail.
    #
    # The invariant constrains the WRITE (the drain's scatter), not the
    # harvest forward — a dispatched chunk touches no store row until it is
    # drained. So dispatch runs AHEAD of the budget (bounded by
    # PIPELINE_DEPTH, paced at ~one chunk per serve so forwards spread
    # evenly through the device queue instead of clumping) and only the
    # drain is budget-gated. Without the lead, the cycle's last chunk can
    # only be DISPATCHED at the trigger serve — refill 0.5's budget frees
    # its positions exactly then — queuing a full LM forward inside the
    # trigger step (the measured 111 ms refresh bubble, BENCH_r04 e2e;
    # the stall being amortized is the reference's blocking refresh,
    # reference buffer.py:121-122). With it, the trigger point finds every
    # chunk harvested and only scatters + reshuffles.

    def _begin_cycle(self, num_batches: int | None = None) -> None:
        rows_per_seq = self.cfg.seq_len - 1
        # A forced refresh() mid-cycle abandons the whole unfinished cycle.
        # NOTHING dispatched this cycle has been served yet (chunks land only
        # on already-served or never-served-this-fill slots, and become
        # servable only after _finish_cycle's reshuffle), so rewind the token
        # stream over every dispatched sequence — in-flight AND drained —
        # or those sequences would be harvested, overwritten, and never seen.
        # A completed cycle zeroes _cyc_seq_done before calling here.
        dropped = getattr(self, "_cyc_seq_done", 0)
        if dropped:
            self.token_pointer = (self.token_pointer - dropped) % self.tokens.shape[0]
            self._global_seq -= dropped
            self._cyc_inflight = []
            self._cyc_job = None
        if num_batches is None:
            num_batches = self._refill_batches()
        b = self.cfg.batch_size
        trigger = self.buffer_size // 2 - b
        served_at_finish = (trigger // b + 1) * b
        self._cyc_batches = num_batches
        self._cyc_target = num_batches * rows_per_seq
        # the tail rotation only applies to a cycle consumed incrementally
        # (steady-state half refill); a full fill is synchronous and must
        # keep the linear write order (store stays in harvest order)
        if self._cyc_target > self.buffer_size // 2:
            self._cyc_tail = 0
        else:
            self._cyc_tail = max(0, self._cyc_target - served_at_finish)
        self._cyc_rot = served_at_finish if self._cyc_tail else 0
        self._cyc_seq_done = 0          # sequences dispatched so far
        self._cyc_write = 0             # rows dispatched so far
        self._cyc_drained = 0           # rows landed in the store
        self._cyc_inflight: list[tuple] = []
        self._cyc_job: tuple | None = None   # (job, n, seq_globals, woff) mid-dispatch
        # dispatch pacing: spread the cycle's harvest quanta evenly over the
        # serves before the trigger, so every train step queues the same
        # slice of harvest device-time (the refresh-bubble fix; see the
        # invariant notes above)
        n_chunks = -(-num_batches // self._chunk_seqs)
        serves = max(1, trigger // b + 1)
        self._cyc_segs_per_serve = -(-n_chunks * self._segs_per_chunk() // serves)
        # shadow cycle (overlap engine): the cycle's rows land in spare
        # physical rows instead of in-place, so drains need no write-
        # safety gate and the swap at _finish_cycle is pure bookkeeping.
        # Only steady-state cycles fit the spare region; full fills keep
        # the baseline in-place path (and its linear write order).
        self._cyc_shadow = self._overlap and self._cyc_target <= self._spare_rows
        self._cyc_phys = (
            self._free_rows[: self._cyc_target] if self._cyc_shadow else None
        )
        # deferred provenance (see _record_src): applied at the swap
        self._cyc_src = (
            np.empty(self._cyc_target, np.int64) if self._cyc_shadow else None
        )

    def _segs_per_chunk(self) -> int:
        """Dispatch quanta one harvest chunk costs (pacing denominator)."""
        if self._seq_mesh is not None or self._paged:
            # seq-parallel and paged harvests stay one dispatch each (the
            # paged plane is one fused jit; its cost already shrank by the
            # packing factor, which is the bubble the segmentation fights)
            return 1
        return lm.SegmentedHarvest.count(
            self.lm_cfg, self.hook_points, len(self.model_params)
        )

    def _harvest_job(self, padded_tokens: np.ndarray):
        """A segment-steppable harvest job for one fixed-shape chunk (the
        incremental-refill counterpart of :meth:`_harvest_dev`)."""
        if self.chaos is not None:
            self.chaos.on_harvest()    # injected stall/failure (tests only)
        if self._seq_mesh is not None or self._paged:
            return _SingleDispatchJob(self._harvest_dev(padded_tokens))
        if self.batch_sharding is not None:
            tok = multihost.put_global(padded_tokens, self.batch_sharding)
        else:
            tok = jnp.asarray(padded_tokens)
        return lm.SegmentedHarvest(
            self.model_params, tok, self.lm_cfg, self.hook_points,
            out_dtype=jnp.bfloat16,
        )

    def _cyc_logical(self, woff: int, n_rows: int) -> np.ndarray:
        """LOGICAL store rows for cycle write offsets [woff, woff+n_rows):
        serve-order index = rot + j for the tail writes, j − tail after."""
        j = np.arange(woff, woff + n_rows)
        order = np.where(j < self._cyc_tail, self._cyc_rot + j, j - self._cyc_tail)
        return self._perm[order]

    def _cyc_positions(self, woff: int, n_rows: int) -> np.ndarray:
        """PHYSICAL rows the drain scatters to: a shadow cycle's reserved
        spare rows; otherwise the live physical rows of the logical
        targets (``_row_map`` is the identity with overlap off)."""
        if self._cyc_shadow:
            return self._cyc_phys[woff: woff + n_rows]
        logical = self._cyc_logical(woff, n_rows)
        return self._row_map[logical] if self._overlap else logical

    def _record_src(self, woff: int, n_rows: int,
                    seq_globals: np.ndarray) -> None:
        """Per-row provenance for one drained chunk. A shadow cycle defers
        it to the swap (``_finish_cycle``): its data only becomes the
        logical content there, so an abandoned shadow cycle must leave
        ``_src_global`` — and the suffix-min resume snapshot derived from
        it — untouched."""
        src = np.repeat(seq_globals, self.cfg.seq_len - 1)
        if self._cyc_shadow:
            self._cyc_src[woff: woff + n_rows] = src
        else:
            self._src_global[self._cyc_logical(woff, n_rows)] = src

    def _create_job(self) -> tuple:
        """Open the next chunk's harvest job (dispatches nothing yet) and
        account its sequences as dispatched — the token stream advances at
        job creation, so the abandon-rewind in ``_begin_cycle`` covers jobs
        mid-dispatch exactly like landed chunks."""
        rows_per_seq = self.cfg.seq_len - 1
        n_seqs = min(self._chunk_seqs, self._cyc_batches - self._cyc_seq_done)
        seq_globals = self._global_seq + np.arange(n_seqs)
        padded, n = self._pad_chunk(self._take_tokens(n_seqs))
        entry = (self._harvest_job(padded), n, seq_globals, self._cyc_write)
        self._cyc_seq_done += n_seqs
        self._cyc_write += n_seqs * rows_per_seq
        return entry

    def _step_job(self) -> bool:
        """Advance the harvest pipeline by ONE dispatch quantum: open a new
        job if none is active (depth-bounded), else step the active one;
        completed jobs move to the drain queue. Returns False when the
        cycle has nothing left to dispatch right now."""
        if self._cyc_job is None:
            if (self._cyc_seq_done >= self._cyc_batches
                    or len(self._cyc_inflight) + 1 > self.PIPELINE_DEPTH):
                return False
            self._cyc_job = self._create_job()
        job, n, seq_globals, woff = self._cyc_job
        alive = job.step()
        # the dispatched quantum must finish inside the program guard on
        # XLA:CPU (dispatch is async; see pipeline.sharded_program_guard)
        pipeline.finish_on_cpu(job.inflight())
        if not alive:
            self._cyc_inflight.append((job.result(), n, seq_globals, woff))
            self._cyc_job = None
        return True

    def _drain_one(self) -> None:
        cfg = self.cfg
        acts_dev, n, seq_globals, woff = self._cyc_inflight.pop(0)
        acts = np.asarray(jax.device_get(acts_dev))[:n]
        acts = acts[:, 1:]                              # drop BOS (buffer.py:93)
        rows = acts.reshape(-1, cfg.n_sources, cfg.d_in)
        positions = self._cyc_positions(woff, rows.shape[0])
        native.scatter_rows(self._store, positions, rows)
        self._record_src(woff, rows.shape[0], seq_globals)
        self._cyc_drained += rows.shape[0]

    def _head_drainable(self) -> bool:
        """Write-safety check for the OLDEST in-flight chunk: its store
        positions are freed once the serve pointer (plus the static tail)
        covers its write extent. A shadow cycle writes only spare rows —
        nothing to protect — so it keeps just a one-chunk drain lag
        (device compute overlaps the fetch/scatter of the previous chunk;
        count-based, so every process decides identically)."""
        if not self._cyc_inflight:
            return False
        if self._cyc_shadow:
            return len(self._cyc_inflight) > 1
        _, n, _, woff = self._cyc_inflight[0]
        return woff + n * (self.cfg.seq_len - 1) <= self.pointer + self._cyc_tail

    def _dispatch_quanta(self, quanta: int) -> int:
        """Spend up to ``quanta`` dispatch credit on the harvest pipeline
        as ONE batched sub-scan program (``cfg.refill_dispatch_batch``
        quanta fused per Python dispatch — the sequential scan carry makes
        a k-wide sub-scan bitwise identical to k narrow ones, so only the
        per-dispatch host cost divides). Returns the credit actually
        spent; 0 when nothing is dispatchable right now (cycle fully
        dispatched, or the in-flight window is full)."""
        if self._cyc_job is None:
            if (self._cyc_seq_done >= self._cyc_batches
                    or len(self._cyc_inflight) + 1 > self.PIPELINE_DEPTH):
                return 0
            self._cyc_job = self._create_job()
        job, n, seq_globals, woff = self._cyc_job
        used, alive = job.step_many(
            min(quanta, max(1, self.cfg.refill_dispatch_batch))
        )
        pipeline.finish_on_cpu(job.inflight())
        if not alive:
            self._cyc_inflight.append((job.result(), n, seq_globals, woff))
            self._cyc_job = None
        return max(used, 1)

    def _overlap_pump(self, credit: int) -> None:
        """Shadow-cycle refill progress: spend ``credit`` dispatch quanta
        (batched) and land every finished chunk past the count-based
        drain lag. The caller holds the program guard (the dispatcher
        thread enters through :meth:`_pump_locked`)."""
        # span site (docs/OBSERVABILITY.md): one credit grant's dispatch +
        # drain work — on the refill-dispatch thread when offloaded, on
        # the serve thread when pumped inline (multi-process)
        with trace.span("refill_dispatch", credit=credit):
            with trace.span("harvest_dispatch", credit=credit):
                while credit > 0:
                    used = self._dispatch_quanta(credit)
                    if used == 0:
                        break
                    credit -= used
            while self._head_drainable():
                with trace.span("harvest"):
                    self._drain_one()

    def _pump_locked(self, credit: int) -> None:
        with pipeline.sharded_program_guard():
            self._overlap_pump(credit)

    def _quiesce_dispatch(self) -> None:
        """Wait out any offloaded refill work before mutating cycle state
        under the dispatcher's feet (forced refresh, restore); re-raises
        any harvest error the dispatcher thread hit."""
        if getattr(self, "_dispatcher", None) is not None:
            self._dispatcher.drain()

    def close(self) -> None:
        """Stop the refill dispatcher thread (a no-op with overlap off or
        on a device store). Idempotent; swallows in-flight work — callers
        tear the buffer down after this."""
        if getattr(self, "_dispatcher", None) is not None:
            self._dispatcher.close()
            self._dispatcher = None

    def _advance_cycle(self) -> None:
        """One serve's worth of refill progress: dispatch the paced number
        of harvest quanta (``_cyc_segs_per_serve`` — the cycle's total
        dispatch budget spread evenly over its serves, so every train step
        queues the same slice of harvest device-time) and land every chunk
        whose target positions the serve pointer has freed.

        All decisions derive from host-replicated state (pointer, write
        offsets, depth, the credit counter), so every process of a
        multi-process mesh makes identical dispatch/drain choices — the
        SPMD rendezvous-order requirement that ruled out the old
        is_ready() opportunistic drain. The overlap engine keeps this:
        the shadow path's dispatch/drain schedule is the same count-based
        function of the credit stream; only WHICH thread runs it moves
        (the dispatcher thread exists in single-process mode only).
        """
        if self._cyc_shadow:
            credit = self._cyc_segs_per_serve
            if self._dispatcher is not None:
                self._dispatcher.submit(credit)
            else:
                with pipeline.sharded_program_guard():
                    self._overlap_pump(credit)
            return
        with pipeline.sharded_program_guard():
            credit = self._cyc_segs_per_serve
            # span site: this serve's paced harvest dispatches — where the
            # producer parks when the device's launch queue pushes back
            with trace.span("harvest_dispatch", credit=credit):
                while credit > 0 and self._step_job():
                    credit -= 1
            while self._head_drainable():
                # span site (docs/OBSERVABILITY.md): one harvest chunk
                # landing (device fetch + store scatter) — a no-op unless
                # a tracer is installed (cfg.obs="on")
                with trace.span("harvest"):
                    self._drain_one()

    def _finish_cycle(self) -> None:
        """Complete the cycle: dispatch the remainder (none in steady
        state — the paced dispatches have already finished), land
        everything, re-shuffle, reset the read pointer.

        The ``refill`` span here brackets the serve-trigger completion —
        the residual refill bubble the incremental dispatches exist to
        amortize, now directly visible per cycle in the trace."""
        if self._cyc_shadow and self._dispatcher is not None:
            # quiesce BEFORE taking the guard: the dispatcher thread takes
            # the guard inside its pump, and the serve thread never holds
            # it here, so there is no lock-ordering cycle
            self._dispatcher.drain()
        with trace.span("refill", target_rows=self._cyc_target), \
                pipeline.sharded_program_guard():
            while (self._cyc_seq_done < self._cyc_batches
                   or self._cyc_job is not None):
                with trace.span("harvest_dispatch"):
                    advanced = (self._dispatch_quanta(1 << 30) if self._cyc_shadow
                                else self._step_job())
                if not advanced:            # depth window full: free a slot
                    with trace.span("harvest"):
                        self._drain_one()
            while self._cyc_inflight:
                with trace.span("harvest"):
                    self._drain_one()
        assert self._cyc_drained == self._cyc_write == self._cyc_target
        if self._cyc_shadow:
            # THE SWAP: the shadow rows become the logical content and the
            # displaced live rows become the next cycle's spare region —
            # pure index bookkeeping, no row bytes move. Logical row
            # _perm[order(j)] now maps to the physical row holding cycle
            # row j, exactly the row the baseline in-place path would have
            # written there: the served stream is byte-identical.
            logical = self._cyc_logical(0, self._cyc_target)
            old_phys = self._row_map[logical].copy()
            self._row_map[logical] = self._cyc_phys
            self._free_rows = np.concatenate(
                [old_phys, self._free_rows[self._cyc_target:]]
            )
            self._src_global[logical] = self._cyc_src
        self._cyc_seq_done = 0      # cycle consumed: nothing left to abandon
        self._perm = self._rng.permutation(self.buffer_size)
        self.pointer = 0
        self._filled = True
        # suffix-min of source provenance in serve order: makes the per-step
        # stream snapshot (state_dict) O(1) instead of an O(buffer_size)
        # min over the unserved tail on the hot serve path. Mid-cycle
        # incremental writes never touch the unserved survivor region (the
        # write-safety invariant above), so this stays valid between fills;
        # tail writes can only make it conservative (older), which is the
        # safe direction for resume.
        self._suffix_min_src = np.minimum.accumulate(
            self._src_global[self._perm][::-1]
        )[::-1]
        self._begin_cycle()

    def _take_tokens(self, n: int) -> np.ndarray:
        """Next ``n`` sequences, wrapping at the end of the corpus (the
        reference would IndexError past 400M tokens; the wrap makes long
        runs and small test corpora safe)."""
        total = self.tokens.shape[0]
        idx = (self.token_pointer + np.arange(n)) % total
        self.token_pointer = (self.token_pointer + n) % total
        self._global_seq += n
        return self.tokens[idx]

    # ------------------------------------------------------------------
    # serving

    def _next_idx(self) -> np.ndarray:
        cfg = self.cfg
        if not self._filled:
            raise RuntimeError(
                "buffer was built lazy and never filled; call load_state_dict "
                "(resume) or refresh() first"
            )
        idx = self._perm[self.pointer: self.pointer + cfg.batch_size]
        self.pointer += cfg.batch_size
        if self._overlap:
            idx = self._row_map[idx]    # logical → physical (identity off)
        return idx

    def next(self) -> np.ndarray:
        """One training batch ``[batch_size, n_sources, d_in]`` fp32, norm
        factors applied (reference ``buffer.py:115-125``). Gather, upcast,
        and scale run as one fused native pass when the C++ kernels are
        available (:mod:`crosscoder_tpu.native`)."""
        with trace.span("serve_gather"):
            idx = self._next_idx()
            out = native.gather_scale_f32(self._store, idx, self.normalisation_factor)
        self._after_serve()
        return out

    def next_raw(self) -> np.ndarray:
        """One training batch as RAW bf16 rows ``[batch, n_sources, d_in]`` —
        no upcast, no norm factors (they are in :attr:`normalisation_factor`).

        The fast path for TPU training: half the host bytes and
        host→device transfer of :meth:`next`; the trainer applies
        ``x.astype(f32) * normalisation_factor`` inside the compiled step,
        which is numerically identical to the reference's host-side
        ``acts.float() * factor`` (reference ``buffer.py:123-124``).
        """
        with trace.span("serve_gather"):
            idx = self._next_idx()
            out = native.gather_rows(self._store, idx)
        self._after_serve()
        return out

    def _after_serve(self) -> None:
        """Post-serve bookkeeping: interleave refill work, and complete the
        cycle at the reference's trigger point (reference ``buffer.py:121``)
        — by which time the incremental dispatches have already landed
        nearly all of it."""
        self._serve_seq += 1
        self._advance_cycle()
        if self.pointer > self.buffer_size // 2 - self.cfg.batch_size:
            self._finish_cycle()

    # ------------------------------------------------------------------
    # multi-consumer fan-out (fleet serving; train/fleet.py)

    def attach_consumer(self, name: str) -> int:
        """Register a fan-out consumer at the CURRENT stream position and
        return that position. Each consumer gets a deterministic cursor
        into the one shared serve stream: the sequence of batches it is
        handed from here on is bitwise the sequence a solo run of this
        buffer (same cfg.seed) would serve from the same position — the
        fleet's per-tenant determinism contract."""
        if name in self._consumers:
            raise ValueError(f"consumer {name!r} already attached")
        self._consumers[name] = self._serve_seq
        return self._serve_seq

    def detach_consumer(self, name: str) -> None:
        """Retire a consumer; its cursor is dropped (any cached batch stays
        for the remaining consumers at that position)."""
        self._consumers.pop(name, None)

    def consumer_cursor(self, name: str) -> int:
        return self._consumers[name]

    def next_raw_for(self, name: str) -> np.ndarray:
        """Serve the batch at ``name``'s cursor, advancing the cursor.

        ONE real gather per stream position no matter how many consumers:
        the first consumer to reach a position pays :meth:`next_raw` (one
        ``native.gather_rows`` + the refill bookkeeping); every other
        consumer at the same position is handed the cached array. The
        scheduler steps tenants in lockstep rounds, so the cache never
        needs more than one position of depth — a cursor that is neither
        at the cached position nor at the stream head indicates a broken
        lockstep and raises rather than silently re-gathering."""
        cur = self._consumers[name]
        if cur == self._fanout_seq:
            batch = self._fanout_batch
        elif cur == self._serve_seq:
            batch = self.next_raw()
            self._fanout_seq = cur
            self._fanout_batch = batch
        else:
            raise RuntimeError(
                f"fan-out consumer {name!r} at position {cur} is out of "
                f"lockstep (cached={self._fanout_seq}, "
                f"head={self._serve_seq}): consumers must drain each "
                f"stream position together"
            )
        self._consumers[name] = cur + 1
        return batch

    # ------------------------------------------------------------------
    # resume support (no reference counterpart)

    def state_dict(self) -> dict[str, Any]:
        """Stream-resume state. The ~5 GB store is NOT saved; on restore the
        buffer re-fills starting from the OLDEST unserved row's source
        sequence (per-row provenance in ``_src_global``), so no token's
        activations are dropped unseen by a save/resume cycle — tokens
        between that oldest straggler and the save point are re-harvested
        (and some re-served), the safe direction for training data. A save
        before the first fill (crash during startup) records a from-scratch
        state."""
        if not self._filled:
            return {"token_pointer": 0, "rng_state": self._rng.bit_generator.state,
                    "normalisation_factor": None}
        oldest = (
            int(self._suffix_min_src[self.pointer])
            if self.pointer < self.buffer_size
            else self._global_seq
        )
        return {
            "token_pointer": oldest % self.tokens.shape[0],
            "rng_state": self._rng.bit_generator.state,
            "normalisation_factor": self.normalisation_factor.tolist(),
        }

    def load_state_dict(self, state: dict[str, Any]) -> None:
        # the restored stream position supersedes any live cycle: drop its
        # chunks WITHOUT the abandon-rewind (that would shift the restored
        # pointer by sequences belonging to the pre-restore stream)
        self._quiesce_dispatch()
        self._cyc_inflight = []
        self._cyc_job = None
        self._cyc_seq_done = 0
        # the restored stream position is the new head: any cached fan-out
        # batch belongs to the superseded stream, and every attached
        # consumer re-aligns to the restore point (the fleet restores all
        # tenants from the same boundary save, so their cursors agree)
        self._fanout_batch = None
        self._fanout_seq = -1
        for _name in self._consumers:
            self._consumers[_name] = self._serve_seq
        # restore must be independent of pre-restore buffer history: reset
        # the permutation so the refill lands rows in harvest order, exactly
        # as a freshly-constructed buffer's restore does (determinism A2) —
        # and, under the overlap engine, reset the row map/spare region the
        # same way (the restore's full fill writes logical == physical)
        self._perm = np.arange(self.buffer_size)
        if self._overlap:
            self._row_map = np.arange(self.buffer_size)
            self._free_rows = self.buffer_size + np.arange(self._spare_rows)
        self.token_pointer = int(state["token_pointer"])
        self._global_seq = self.token_pointer
        self._rng.bit_generator.state = state["rng_state"]
        if state.get("normalisation_factor") is None:
            self.first = True
            self._filled = False
            self.ensure_filled()        # calibrate + fill from scratch
            return
        self.normalisation_factor = np.asarray(state["normalisation_factor"], np.float32)
        self.first = True
        self.refresh()

    def ensure_filled(self) -> None:
        """Calibrate + fill a lazy buffer that a resume could not restore
        (checkpoint without buffer state) — the from-scratch fallback, run
        once, instead of crashing at the first ``next()``."""
        if not self._filled:
            self.normalisation_factor = self._estimate_norm_scaling_factors()
            self.refresh()

    # ------------------------------------------------------------------
    # elastic re-mesh support (resilience/elastic.py; docs/resilience.md)

    def prepare_reshard(self) -> None:
        """Quiesce in-flight refill work and park every device-resident
        piece this buffer OWNS (the LM parameters) to host memory, ahead
        of a backend teardown — an elastic shrink OR grow invalidates all
        live device buffers either way. Must run BEFORE
        ``multihost.shrink_to_local()`` / ``multihost.grow_to()``;
        :meth:`reshard` rebuilds the device side on the new mesh. Both
        calls are direction-agnostic and re-entrant per cycle, so a full
        grow/shrink/grow sequence is just the pair applied once per
        membership change (``reshard`` re-materializes the parked params
        with ``jnp.asarray``, which a later ``prepare_reshard`` parks
        again). The store itself is NOT parked: it re-fills from the
        provenance stream, which is the existing save/restore contract
        and cheaper than dragging the multi-GB store through host RAM —
        and it is what makes the post-cycle batch stream deterministic:
        the stream position, not the store bytes, is the state."""
        try:
            self._quiesce_dispatch()
        except Exception as e:
            # a dispatcher that died with the torn collective must not
            # block the teardown — its work is discarded below anyway
            print(f"[crosscoder_tpu] reshard: dispatcher drain failed "
                  f"({type(e).__name__}: {e})"[:300], flush=True,
                  file=sys.stderr)
        self.close()
        # in-flight harvest chunks hold device arrays that die with the
        # backend; the post-reshard stream restore supersedes the cycle
        self._cyc_inflight = []
        self._cyc_job = None
        self.model_params = [
            jax.tree_util.tree_map(lambda a: np.asarray(jax.device_get(a)), p)
            for p in self.model_params
        ]

    def reshard(self, batch_sharding: Any | None, refill: bool = True) -> None:
        """Re-derive every mesh-coupled piece of the buffer for a new
        ``batch_sharding``: harvest chunk rounding, the store allocation
        (sharded over the new mesh's data axis for the device stores), the
        dispatcher thread (re-created when the new world qualifies), and
        the LM params' device residency. By default the store then
        re-fills from the live stream snapshot, so the served batch
        sequence continues exactly as a fresh buffer restored from
        :meth:`state_dict` would (determinism A2). ``refill=False`` leaves
        the buffer empty for the caller's own ``load_state_dict`` — the
        elastic restore path, which replays the CHECKPOINT's buffer
        snapshot rather than the live one."""
        if self.cfg.seq_shards > 1:
            raise ValueError(
                "reshard with seq_shards > 1 is unsupported (the mesh data "
                "axis carries the sequence there, not the batch)"
            )
        snap = self.state_dict() if refill else None
        self.batch_sharding = batch_sharding
        data_axis = 1
        if batch_sharding is not None:
            data_axis = int(batch_sharding.mesh.shape.get("data", 1))
        self._chunk_seqs = -(-self.cfg.model_batch_size // data_axis) * data_axis
        self._plane_multiple = data_axis
        # re-materialize the LM params on the current backend (host numpy
        # after prepare_reshard), committed to the new mesh
        self.model_params = [
            jax.tree_util.tree_map(jnp.asarray, p) if batch_sharding is None
            else _on_mesh(p, batch_sharding.mesh)
            for p in self.model_params
        ]
        self._cyc_inflight = []
        self._cyc_job = None
        self._cyc_seq_done = 0
        self._perm = np.arange(self.buffer_size)
        self._row_map = np.arange(self.buffer_size)
        self._free_rows = self.buffer_size + np.arange(self._spare_rows)
        self.pointer = 0
        self._src_global = np.zeros(self.buffer_size, dtype=np.int64)
        self.first = True
        self._filled = False
        self._fanout_batch = None       # cached batch died with the old store
        self._fanout_seq = -1
        self._alloc_store()
        if (self._overlap and self._DISPATCH_THREAD_OK
                and self._dispatcher is None and jax.process_count() == 1):
            self._dispatcher = pipeline.QuantumDispatcher(self._pump_locked)
        if refill:
            self.load_state_dict(snap)


def make_buffer(cfg: CrossCoderConfig, lm_cfg, model_params, tokens,
                **kwargs) -> "PairedActivationBuffer":
    """Construct the replay buffer per ``cfg.buffer_device`` (the single
    selection point — host RAM vs HBM store, same semantics). An HBM store
    on a multi-chip mesh shards over the ``data`` axis
    (:class:`MeshPairedActivationBuffer`). ``cfg.quant_buffer`` swaps in
    the block-scaled int8 storage subclass of the same placement — the
    bf16 classes are never touched when quantization is off (the zero-cost
    guarantee tests/test_quant.py asserts)."""
    cls: type[PairedActivationBuffer] = PairedActivationBuffer
    if cfg.buffer_device == "hbm":
        bs = kwargs.get("batch_sharding")
        if bs is not None and int(bs.mesh.shape.get("data", 1)) > 1:
            cls = (QuantMeshPairedActivationBuffer if cfg.quant_buffer
                   else MeshPairedActivationBuffer)
        else:
            cls = (QuantDevicePairedActivationBuffer if cfg.quant_buffer
                   else DevicePairedActivationBuffer)
    elif cfg.quant_buffer:
        cls = QuantPairedActivationBuffer
    # the job's telemetry plane is born here when the buffer is built first
    # (every entry point does): calibration and the first fill run inside
    # the constructor, and the Trainer adopts — and closes — the same plane
    obs.acquire(cfg)
    return cls(cfg, lm_cfg, model_params, tokens, **kwargs)


# ---------------------------------------------------------------------------
# HBM-resident variant


@jax.jit
@jax.named_scope("store/gather")
def _dev_gather(store: jax.Array, idx: jax.Array) -> jax.Array:
    return store[idx]


@functools.partial(jax.jit, donate_argnums=0)
@jax.named_scope("store/scatter")
def _dev_scatter(store: jax.Array, positions: jax.Array, acts: jax.Array) -> jax.Array:
    """In-place (donated) row scatter of one harvest chunk.

    ``acts`` is the PADDED device chunk ``[C, S, n, d]``; BOS dropped and
    flattened here so the bytes never leave the device. ``positions`` is
    padded to the fixed chunk size with UNIQUE out-of-range indices that
    ``mode="drop"`` discards (duplicate pad indices would make
    ``unique_indices=True`` a lie — undefined behavior in XLA scatter), so
    ragged tails reuse the same compiled program.
    """
    rows = acts[:, 1:].reshape(-1, acts.shape[2], acts.shape[3])
    return store.at[positions].set(rows.astype(store.dtype), mode="drop",
                                   unique_indices=True)


class DevicePairedActivationBuffer(PairedActivationBuffer):
    """The replay store in device HBM instead of host RAM.

    Rows never funnel through host RAM, so multi-process meshes are fine
    (make_buffer picks the mesh-sharded subclass there; _MULTIPROCESS_OK).

    Same serve/refill semantics, cycle accounting, and resume state as the
    host-RAM parent (all that logic is inherited; only the storage ops
    differ): harvested activations are scattered into an HBM-resident
    ``[buffer_size, n_sources, d_in]`` bf16 array by a donated in-place
    jit (ragged-chunk padding targets unique dropped indices), and batches
    are served
    as device-resident gathers. NOTHING row-sized crosses host↔device —
    only token chunks (~16 KB) up and scalar metrics down.

    When to use which (``cfg.buffer_device``):

    - ``host`` (default): buffer bigger than HBM headroom, multi-host
      training, or analysis workflows that read the store. Costs one
      batch-sized host→device upload per step (overlapped by prefetch) and
      one chunk-sized fetch per harvest chunk.
    - ``hbm``: training where the buffer fits device memory — the
      reference's own placement (its 4.8 GB buffer lives in GPU HBM,
      reference ``buffer.py:18-22``), minus its full-buffer ``randperm``
      copies (index-permutation serving needs none). On a multi-chip mesh
      ``make_buffer`` picks :class:`MeshPairedActivationBuffer`, which
      shards this store over the ``data`` axis.
    """

    _MULTIPROCESS_OK = True
    _DISPATCH_THREAD_OK = False     # donated-scatter rebind vs serve gather

    def _alloc_store(self) -> None:
        cfg = self.cfg
        self._store_dev = jnp.zeros(
            (self._store_rows, cfg.n_sources, cfg.d_in), dtype=jnp.bfloat16
        )

    @property
    def _store(self) -> np.ndarray:
        """LOGICAL host view (tests/analysis only — fetches the whole
        store; the row map resolves overlap-mode physical placement)."""
        return np.asarray(jax.device_get(self._store_dev))[self._row_map]

    def store_nbytes(self) -> int:
        return self._store_dev.nbytes

    # storage hooks the mesh-sharded subclass overrides -----------------

    def _pad_limit(self) -> int:
        """First index guaranteed out of range of the device store — pad
        scatter positions start here so they are always dropped."""
        return self._store_rows

    def _scatter_chunk(self, positions: np.ndarray, acts_dev: jax.Array) -> None:
        self._store_dev = _dev_scatter(
            self._store_dev, jnp.asarray(positions, jnp.int32), acts_dev
        )

    def _gather_rows(self, idx: np.ndarray) -> jax.Array:
        return _dev_gather(self._store_dev, jnp.asarray(idx, jnp.int32))

    # -------------------------------------------------------------------

    def _drain_one(self) -> None:
        cfg = self.cfg
        rows_per_seq = cfg.seq_len - 1
        acts_dev, n, seq_globals, woff = self._cyc_inflight.pop(0)
        positions = self._cyc_positions(woff, n * rows_per_seq)
        pad_rows = (self._chunk_seqs - n) * rows_per_seq
        if pad_rows:
            # unique out-of-range pad indices, dropped by the scatter
            positions = np.concatenate([
                positions,
                self._pad_limit() + np.arange(pad_rows, dtype=positions.dtype),
            ])
        self._scatter_chunk(positions, acts_dev)
        # the scatter program (mesh variant: all_gather + sharded write)
        # must finish inside the program guard on XLA:CPU
        pipeline.finish_on_cpu([
            a for a in (getattr(self, "_store_dev", None),
                        getattr(self, "_store_q", None),
                        getattr(self, "_store_scale", None))
            if a is not None
        ])
        self._record_src(woff, n * rows_per_seq, seq_globals)
        self._cyc_drained += n * rows_per_seq

    def next(self) -> jax.Array:
        """fp32 normalized batch, DEVICE-resident."""
        # the serve gather is a sharded program too (mesh variant:
        # psum_scatter) — same XLA:CPU concurrency guard as the refill
        with trace.span("serve_gather"), pipeline.sharded_program_guard():
            out = self._gather_rows(self._next_idx())
            out = out.astype(jnp.float32) * jnp.asarray(
                self.normalisation_factor
            )[None, :, None]
            pipeline.finish_on_cpu(out)
        self._after_serve()
        return out

    def next_raw(self) -> jax.Array:
        """Raw bf16 batch, DEVICE-resident (the trainer's fast path — the
        step applies the norm factors on device)."""
        with trace.span("serve_gather"), pipeline.sharded_program_guard():
            out = self._gather_rows(self._next_idx())
            pipeline.finish_on_cpu(out)
        self._after_serve()
        return out


# ---------------------------------------------------------------------------
# Mesh-sharded HBM variant


@functools.lru_cache(maxsize=8)
def _mesh_store_ops(mesh, rows_local: int, acts_sharded: bool):
    """Compiled scatter/gather for a store sharded over the mesh ``data``
    axis on its row dimension (shard d owns rows [d·rows_local, (d+1)·…)).

    - *scatter*: every device sees the full position list (replicated) and —
      after an ``all_gather`` of the harvest chunk's rows when the harvest
      was batch-sharded — applies exactly the updates that land in its own
      shard, via local indices with ``mode="drop"`` discarding the rest.
      One chunk's rows (~38 MB at Gemma-2-2B shapes) ride ICI per refill
      chunk; nothing goes through host.
    - *gather* (the serve path): each device gathers its local hits, zeroes
      the misses, and a ``psum_scatter`` over the batch axis leaves every
      device holding exactly its batch shard, fully summed — the output IS
      the train step's ``P('data', None, None)`` batch sharding, so serving
      moves only (n_dev−1)/n_dev of one batch over ICI and nothing else.

    Contributions are disjoint across devices (each global row lives in
    exactly one shard), so the bf16 psum adds zeros — exact.
    """
    from jax.sharding import PartitionSpec as P

    acts_spec = P("data", None, None, None) if acts_sharded else P()

    @jax.named_scope("store/scatter")
    def scatter(store, positions, acts):
        rows = acts[:, 1:].reshape(-1, acts.shape[2], acts.shape[3])
        if acts_sharded:
            rows = jax.lax.all_gather(rows, "data", axis=0, tiled=True)
        my = jax.lax.axis_index("data")
        local = positions - my * rows_local
        # out-of-shard rows must be DROPPED, but jnp indexing wraps
        # negative indices numpy-style before the OOB mode applies — remap
        # them to UNIQUE indices past the shard end (unique because
        # unique_indices=True + duplicate OOB indices is undefined)
        oob = rows_local + jnp.arange(local.shape[0], dtype=local.dtype)
        in_shard = (local >= 0) & (local < rows_local)
        local = jnp.where(in_shard, local, oob)
        return store.at[local].set(
            rows.astype(store.dtype), mode="drop", unique_indices=True
        )

    @jax.named_scope("store/gather")
    def gather(store, idx):
        my = jax.lax.axis_index("data")
        li = idx - my * rows_local
        inb = (li >= 0) & (li < rows_local)
        rows = store[jnp.clip(li, 0, rows_local - 1)]
        contrib = jnp.where(inb[:, None, None], rows, jnp.zeros_like(rows))
        return jax.lax.psum_scatter(contrib, "data", scatter_dimension=0,
                                    tiled=True)

    scatter_jit = jax.jit(
        jax.shard_map(scatter, mesh=mesh,
                      in_specs=(P("data", None, None), P(), acts_spec),
                      out_specs=P("data", None, None)),
        donate_argnums=0,
    )
    gather_jit = jax.jit(
        jax.shard_map(gather, mesh=mesh,
                      in_specs=(P("data", None, None), P()),
                      out_specs=P("data", None, None)),
    )
    return scatter_jit, gather_jit


class MeshPairedActivationBuffer(DevicePairedActivationBuffer):
    """HBM replay store **sharded over the mesh ``data`` axis** (round-3;
    VERDICT round-2 missing #3: every multi-chip config silently fell back
    to the one-process host path — the scaling story had no data plane).

    Serve/refill/resume semantics are byte-identical to the host store:
    the same permutation, cycle accounting, and provenance bookkeeping run
    on host (inherited); only the row bytes move differently — they stay
    distributed, each row resident on exactly one device, with the serve
    gather emitting batches already in the train step's batch sharding
    (see :func:`_mesh_store_ops`). Rows are padded up to a multiple of the
    shard count; pad rows are never referenced by the serve permutation.
    """

    def _mesh_setup(self):
        """Shared geometry validation + row-shard accounting for the mesh
        store (used by both the bf16 allocation below and the quantized
        subclass's): returns ``(mesh, acts_sharded)`` and sets
        ``_rows_local``/``_store_size``/``_acts_sharding``."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = self.cfg
        if self.batch_sharding is None:
            raise ValueError("MeshPairedActivationBuffer needs batch_sharding")
        mesh = self.batch_sharding.mesh
        n_shards = int(mesh.shape.get("data", 1))
        if cfg.batch_size % n_shards:
            raise ValueError(
                f"batch_size {cfg.batch_size} must divide by the mesh data "
                f"axis {n_shards} for the sharded-store serve path"
            )
        # batch-sharded harvest chunks ride an all_gather(tiled=True) over
        # the data axis in the scatter — their row dim must divide by it.
        # The base class's _chunk_seqs round-up guarantees this; validate
        # here so any misconfiguration (or a change to that padding) fails
        # at construction like the other guards, not as a shard_map spec
        # error at the first drain.
        if self._seq_mesh is None and self._chunk_seqs % n_shards:
            raise ValueError(
                f"harvest chunk of {self._chunk_seqs} seqs must divide by "
                f"the mesh data axis {n_shards} for the batch-sharded "
                f"scatter (model_batch_size={cfg.model_batch_size})"
            )
        self._rows_local = -(-self._store_rows // n_shards)
        self._store_size = self._rows_local * n_shards
        # under seq-parallel harvest the data axis carries the sequence, so
        # chunks arrive without a batch sharding — use the replicated-acts
        # scatter variant there
        acts_sharded = self._seq_mesh is None
        self._acts_sharding = NamedSharding(
            mesh,
            P("data", None, None, None) if acts_sharded else P(),
        )
        return mesh, acts_sharded

    def _alloc_store(self) -> None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = self.cfg
        mesh, acts_sharded = self._mesh_setup()
        sharding = NamedSharding(mesh, P("data", None, None))
        self._store_dev = jax.jit(
            functools.partial(
                jnp.zeros,
                (self._store_size, cfg.n_sources, cfg.d_in),
                jnp.bfloat16,
            ),
            out_shardings=sharding,
        )()
        self._scatter, self._gather = _mesh_store_ops(
            mesh, self._rows_local, acts_sharded
        )

    @property
    def _store(self) -> np.ndarray:
        """LOGICAL host view (tests/analysis only — fetches the whole
        store)."""
        return np.asarray(jax.device_get(self._store_dev))[self._row_map]

    def _pad_limit(self) -> int:
        # pad indices must clear the PADDED store so no shard keeps them
        return self._store_size

    # positions / idx go in as HOST arrays: jit then uploads them straight to
    # every device of the mesh, where a jnp.asarray would land them on the
    # default device first and re-replicate them device-to-device per call

    def _scatter_chunk(self, positions: np.ndarray, acts_dev: jax.Array) -> None:
        acts_dev = jax.device_put(acts_dev, self._acts_sharding)
        self._store_dev = self._scatter(
            self._store_dev, np.asarray(positions, np.int32), acts_dev
        )

    def _gather_rows(self, idx: np.ndarray) -> jax.Array:
        """Serve gather; the result comes back in the step's batch
        sharding (``P('data', None, None)``)."""
        return self._gather(self._store_dev, np.asarray(idx, np.int32))


# ---------------------------------------------------------------------------
# Block-scaled int8 storage variants (cfg.quant_buffer; ops/quant.py,
# docs/SCALING.md "Quantized data plane").
#
# Same serve/refill/resume semantics as their bf16 parents — the cycle
# accounting, permutation, and provenance bookkeeping are all inherited
# untouched; only the ROW BYTES change representation:
#
# - chunks are quantized AT HARVEST TIME, on device, before any row leaves
#   the chip: the host store's device→host chunk fetch, the device store's
#   scatter writes, and the mesh store's all_gather refill shards all move
#   int8 + f32 per-block scales (~0.51x the bf16 bytes at quant_block=256);
# - the serve path dequantizes inside the same fused gather (one jit for
#   the device stores, one numpy pass for the host store), so next_raw
#   still hands the trainer bf16 rows and next() fp32 — the trainer cannot
#   tell the stores apart;
# - quantization is deterministic, so host and device quantized stores
#   serve BIT-IDENTICAL rows from the same harvest chunks (asserted in
#   tests/test_quant.py).
#
# These classes exist only behind cfg.quant_buffer in make_buffer: with the
# flag off, none of their code (or int8 allocation) is reachable — the bf16
# classes above are byte-for-byte the pre-quantization data plane.


def _quant_module():
    from crosscoder_tpu.ops import quant

    return quant


@functools.partial(jax.jit, static_argnums=(1,))
def _quant_chunk(acts: jax.Array, block: int) -> tuple[jax.Array, jax.Array]:
    """Quantize one padded harvest chunk ``[C, S, n, d]`` on device (the
    host store's pre-fetch shrink: the chunk crosses PCIe at ~0.51x)."""
    from crosscoder_tpu.ops import quant

    return quant.quantize_rows(acts, block)


@functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(0, 1))
@jax.named_scope("store/scatter")
def _dev_scatter_quant(
    store_q: jax.Array, store_s: jax.Array, positions: jax.Array,
    acts: jax.Array, block: int,
) -> tuple[jax.Array, jax.Array]:
    """Quantize-then-scatter of one harvest chunk into the int8 store
    (the donated in-place analogue of ``_dev_scatter``; same padded
    unique-dropped-index contract)."""
    from crosscoder_tpu.ops import quant

    rows = acts[:, 1:].reshape(-1, acts.shape[2], acts.shape[3])
    q, s = quant.quantize_rows(rows, block)
    store_q = store_q.at[positions].set(q, mode="drop", unique_indices=True)
    store_s = store_s.at[positions].set(s, mode="drop", unique_indices=True)
    return store_q, store_s


@jax.jit
@jax.named_scope("store/gather")
def _dev_gather_dequant(
    store_q: jax.Array, store_s: jax.Array, idx: jax.Array
) -> jax.Array:
    """Fused gather + dequantize serve: int8 rows + scales gathered by
    index, expanded to bf16 in the same compiled program (XLA fuses the
    dequant into the gather's consumers — no int8 batch ever lands as a
    separate HBM intermediate)."""
    from crosscoder_tpu.ops import quant

    return quant.dequantize_blocks(store_q[idx], store_s[idx], jnp.bfloat16)


class QuantPairedActivationBuffer(PairedActivationBuffer):
    """Host-RAM replay store in block-scaled int8 + f32 scales."""

    def _alloc_store(self) -> None:
        cfg = self.cfg
        quant = _quant_module()
        nb = quant.n_blocks(cfg.d_in, cfg.quant_block)
        self._store_q = np.zeros(
            (self._store_rows, cfg.n_sources, cfg.d_in), np.int8
        )
        self._store_scale = np.zeros(
            (self._store_rows, cfg.n_sources, nb), np.float32
        )

    @property
    def _store(self) -> np.ndarray:
        """Dequantized LOGICAL bf16 view (tests/analysis only —
        materializes the whole store)."""
        return _quant_module().dequantize_np(
            self._store_q[self._row_map], self._store_scale[self._row_map],
            _BF16,
        )

    def store_nbytes(self) -> int:
        return self._store_q.nbytes + self._store_scale.nbytes

    def _drain_one(self) -> None:
        cfg = self.cfg
        acts_dev, n, seq_globals, woff = self._cyc_inflight.pop(0)
        # quantize ON DEVICE, then fetch int8+scales: the chunk's
        # device→host bytes drop ~2x before they touch the link
        q_dev, s_dev = _quant_chunk(acts_dev, cfg.quant_block)
        q = np.asarray(jax.device_get(q_dev))[:n, 1:]     # drop BOS
        s = np.asarray(jax.device_get(s_dev))[:n, 1:]
        rows_q = q.reshape(-1, cfg.n_sources, cfg.d_in)
        rows_s = s.reshape(-1, cfg.n_sources, s.shape[-1])
        positions = self._cyc_positions(woff, rows_q.shape[0])
        self._store_q[positions] = rows_q
        self._store_scale[positions] = rows_s
        self._record_src(woff, rows_q.shape[0], seq_globals)
        self._cyc_drained += rows_q.shape[0]

    def _gather_dequant(self, idx: np.ndarray, dtype) -> np.ndarray:
        return _quant_module().dequantize_np(
            self._store_q[idx], self._store_scale[idx], dtype
        )

    def next(self) -> np.ndarray:
        with trace.span("serve_gather"):
            idx = self._next_idx()
            out = self._gather_dequant(idx, np.float32)
            out *= self.normalisation_factor[None, :, None]
        self._after_serve()
        return out

    def next_raw(self) -> np.ndarray:
        with trace.span("serve_gather"):
            idx = self._next_idx()
            out = self._gather_dequant(idx, _BF16)
        self._after_serve()
        return out


class QuantDevicePairedActivationBuffer(DevicePairedActivationBuffer):
    """HBM replay store in block-scaled int8 + f32 scales (single-device).

    Serve is the fused gather+dequant jit (``_dev_gather_dequant``);
    refill quantizes inside the donated scatter. HBM for the store is
    ``(1 + 4/quant_block)/2`` of the bf16 parent's — the budget headroom
    that funds a ~2x buffer_mult (or dictionary) at equal HBM.
    """

    def _alloc_store(self) -> None:
        cfg = self.cfg
        quant = _quant_module()
        nb = quant.n_blocks(cfg.d_in, cfg.quant_block)
        self._store_q = jnp.zeros(
            (self._store_rows, cfg.n_sources, cfg.d_in), jnp.int8
        )
        self._store_scale = jnp.zeros(
            (self._store_rows, cfg.n_sources, nb), jnp.float32
        )

    @property
    def _store(self) -> np.ndarray:
        """Dequantized LOGICAL host view (tests/analysis only)."""
        return _quant_module().dequantize_np(
            np.asarray(jax.device_get(self._store_q))[self._row_map],
            np.asarray(jax.device_get(self._store_scale))[self._row_map],
            _BF16,
        )

    def store_nbytes(self) -> int:
        return self._store_q.nbytes + self._store_scale.nbytes

    def _scatter_chunk(self, positions: np.ndarray, acts_dev: jax.Array) -> None:
        self._store_q, self._store_scale = _dev_scatter_quant(
            self._store_q, self._store_scale,
            jnp.asarray(positions, jnp.int32), acts_dev, self.cfg.quant_block,
        )

    def _gather_rows(self, idx: np.ndarray) -> jax.Array:
        return _dev_gather_dequant(
            self._store_q, self._store_scale, jnp.asarray(idx, jnp.int32)
        )


@functools.lru_cache(maxsize=8)
def _mesh_store_ops_quant(mesh, rows_local: int, acts_sharded: bool, block: int):
    """Quantized variants of :func:`_mesh_store_ops`, same sharded-store
    contract with the row bytes in int8 + scales:

    - *scatter*: rows quantize BEFORE the cross-device all_gather, so the
      refill shards riding ICI are ~0.51x the bf16 bytes;
    - *gather* (serve): the disjoint-contribution psum_scatter runs on the
      int8 payload and the f32 scales separately (summing exact zeros is
      exact in any dtype), then dequantizes LOCALLY on each device's batch
      shard — serve ICI traffic halves and the output is the same bf16
      batch in the step's ``P('data', None, None)`` sharding.
    """
    from crosscoder_tpu.ops import quant
    from jax.sharding import PartitionSpec as P

    acts_spec = P("data", None, None, None) if acts_sharded else P()

    @jax.named_scope("store/scatter")
    def scatter(store_q, store_s, positions, acts):
        rows = acts[:, 1:].reshape(-1, acts.shape[2], acts.shape[3])
        q, s = quant.quantize_rows(rows, block)
        if acts_sharded:
            q = jax.lax.all_gather(q, "data", axis=0, tiled=True)
            s = jax.lax.all_gather(s, "data", axis=0, tiled=True)
        my = jax.lax.axis_index("data")
        local = positions - my * rows_local
        oob = rows_local + jnp.arange(local.shape[0], dtype=local.dtype)
        in_shard = (local >= 0) & (local < rows_local)
        local = jnp.where(in_shard, local, oob)
        store_q = store_q.at[local].set(q, mode="drop", unique_indices=True)
        store_s = store_s.at[local].set(s, mode="drop", unique_indices=True)
        return store_q, store_s

    @jax.named_scope("store/gather")
    def gather(store_q, store_s, idx):
        my = jax.lax.axis_index("data")
        li = idx - my * rows_local
        inb = (li >= 0) & (li < rows_local)
        qrows = store_q[jnp.clip(li, 0, rows_local - 1)]
        srows = store_s[jnp.clip(li, 0, rows_local - 1)]
        qc = jnp.where(inb[:, None, None], qrows, jnp.zeros_like(qrows))
        sc = jnp.where(inb[:, None, None], srows, jnp.zeros_like(srows))
        qb = jax.lax.psum_scatter(qc, "data", scatter_dimension=0, tiled=True)
        sb = jax.lax.psum_scatter(sc, "data", scatter_dimension=0, tiled=True)
        return quant.dequantize_blocks(qb, sb, jnp.bfloat16)

    store_spec = P("data", None, None)
    scatter_jit = jax.jit(
        jax.shard_map(scatter, mesh=mesh,
                      in_specs=(store_spec, store_spec, P(), acts_spec),
                      out_specs=(store_spec, store_spec)),
        donate_argnums=(0, 1),
    )
    gather_jit = jax.jit(
        jax.shard_map(gather, mesh=mesh,
                      in_specs=(store_spec, store_spec, P()),
                      out_specs=store_spec),
    )
    return scatter_jit, gather_jit


class QuantMeshPairedActivationBuffer(MeshPairedActivationBuffer):
    """Mesh-sharded HBM replay store in block-scaled int8 + f32 scales."""

    def _alloc_store(self) -> None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = self.cfg
        quant = _quant_module()
        nb = quant.n_blocks(cfg.d_in, cfg.quant_block)
        mesh, acts_sharded = self._mesh_setup()
        sharding = NamedSharding(mesh, P("data", None, None))
        self._store_q = jax.jit(
            functools.partial(
                jnp.zeros, (self._store_size, cfg.n_sources, cfg.d_in),
                jnp.int8,
            ),
            out_shardings=sharding,
        )()
        self._store_scale = jax.jit(
            functools.partial(
                jnp.zeros, (self._store_size, cfg.n_sources, nb),
                jnp.float32,
            ),
            out_shardings=sharding,
        )()
        self._scatter, self._gather = _mesh_store_ops_quant(
            mesh, self._rows_local, acts_sharded, cfg.quant_block
        )

    @property
    def _store(self) -> np.ndarray:
        """Dequantized LOGICAL host view (tests/analysis only)."""
        return _quant_module().dequantize_np(
            np.asarray(jax.device_get(self._store_q))[self._row_map],
            np.asarray(jax.device_get(self._store_scale))[self._row_map],
            _BF16,
        )

    def store_nbytes(self) -> int:
        return self._store_q.nbytes + self._store_scale.nbytes

    def _scatter_chunk(self, positions: np.ndarray, acts_dev: jax.Array) -> None:
        acts_dev = jax.device_put(acts_dev, self._acts_sharding)
        self._store_q, self._store_scale = self._scatter(
            self._store_q, self._store_scale,
            np.asarray(positions, np.int32), acts_dev,
        )

    def _gather_rows(self, idx: np.ndarray) -> jax.Array:
        return self._gather(
            self._store_q, self._store_scale, np.asarray(idx, np.int32)
        )
