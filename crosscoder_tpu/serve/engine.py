"""Online model-diffing inference engine (``cfg.serve``; docs/SERVING.md).

The request loop that turns a trained crosscoder into a service:

1. **admit** — ``submit()`` places token streams on a BOUNDED queue
   (``cfg.serve_queue``); each request's KV pages are allocated from a
   fixed :class:`~crosscoder_tpu.data.paging.PageTable` pool at submit,
   so page exhaustion and queue overflow both shed (429-style,
   ``serve/shed_total``) instead of growing host state unboundedly.
   ``cfg.serve_shed_ms`` additionally evicts queued requests that have
   waited past their deadline — an overloaded engine degrades, it does
   not stall every request behind an unbounded backlog.
2. **batch** — ``step()`` drains the queue into a
   :class:`~crosscoder_tpu.data.paging.ContinuousBatcher` plane and
   flushes on batch-full OR the ``cfg.serve_max_wait_ms`` slot deadline
   (deadline-aware micro-batching). The flushed plane is padded to the
   nearest power-of-two bucket ≤ ``cfg.serve_max_batch``, so every
   steady-state dispatch hits one of ≤ 8 AOT-prewarmed executables
   (:func:`crosscoder_tpu.utils.compile_cache.aot_get`) — no request
   ever eats a compile (``warmup()`` builds the ladder; the engine
   counts cache misses to prove it).
3. **prefill** — the bucket runs through the paged harvest forward
   (:func:`crosscoder_tpu.models.lm.paged_capture_aot`): mixed lengths
   packed by ``pack_chunk``, per-document ragged attention, captures
   bitwise-equal to the padded path at valid positions.
4. **encode** — :func:`crosscoder_tpu.serve.step.encode_topk_diff`:
   fused encoder→TopK on the captured activations + decoder-norm diff
   scores; only three ``[B, k]`` arrays leave the device.
5. **extend** — a live request (``submit(..., keep=True)``) appends
   follow-up tokens via :meth:`PageTable.extend`: the prefix keeps its
   pages (never re-allocated, never re-admitted through the prefill
   queue — the extend ticket jumps to the queue front) and the served
   result is bitwise-equal to re-prefilling from scratch
   (tests/test_serve.py pins both properties).

Per-request telemetry: ``queue_wait``/``prefill``/``extend``/``encode``
feed ``serve/*_ms`` histograms (p50/p99/max via
:meth:`MetricsRegistry.observe`) plus shed/request counters — the
honest-tail-latency surface the bench's SLO gate reads.
"""

from __future__ import annotations

import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from crosscoder_tpu.data.paging import ContinuousBatcher, PageTable, pack_chunk
from crosscoder_tpu.obs import trace
from crosscoder_tpu.obs.registry import MetricsRegistry
from crosscoder_tpu.serve import step as serve_step

__all__ = ["InferenceEngine", "ServeResult", "Shed"]


class Shed(RuntimeError):
    """429-style admission reject: queue full, deadline passed, or page
    pool exhausted. Counted in ``serve/shed_total``; the client retries
    with backoff or routes to a peer replica."""


@dataclass
class ServeResult:
    """One served request: top-k latent activations + model-diff scores
    (``diff[j]`` ≈ 0 → latent ``idx[j]`` is model-0-only, ≈ 0.5 shared,
    ≈ 1 model-1-only) and the request's latency breakdown."""

    request_id: int
    vals: np.ndarray                # [k] f32/bf16 latent activations
    idx: np.ndarray                 # [k] i32 latent indices
    diff: np.ndarray                # [k] f32 relative decoder norms
    bucket: int                     # compiled batch bucket served under
    queue_wait_ms: float
    prefill_ms: float
    encode_ms: float
    extended: bool = False          # served off an extend ticket


@dataclass
class _Pending:
    rid: int
    tokens: np.ndarray
    t: float                        # enqueue time (engine clock)
    keep: bool = False
    extend: bool = False


@dataclass
class _Live:
    tokens: np.ndarray = field(repr=False, default=None)


def batch_buckets(max_batch: int) -> tuple[int, ...]:
    """The AOT bucket ladder: powers of two ``1..max_batch`` (≤ 8
    buckets — cfg validation caps ``serve_max_batch`` at 128)."""
    out, b = [], 1
    while b <= max_batch:
        out.append(b)
        b *= 2
    return tuple(out)


def bucket_of(n: int, max_batch: int) -> int:
    """Smallest ladder bucket covering ``n`` requests."""
    b = 1
    while b < n:
        b *= 2
    return min(b, max_batch)


class InferenceEngine:
    def __init__(
        self,
        cfg,
        lm_cfg,
        lm_params_seq,
        cc_params,
        *,
        hook_points=None,
        norm_factors=None,
        registry: MetricsRegistry | None = None,
        clock=time.monotonic,
    ) -> None:
        if cfg.serve != "on":
            raise ValueError(
                "InferenceEngine requires cfg.serve='on' (the serve plane "
                "is off by default and zero-cost off — "
                "hlo-serve-off-identity)"
            )
        if getattr(cfg, "tuned", ""):
            # pin the serve-plane knobs from the tuned artifact
            # (docs/TUNING.md): idempotent when from_cli already applied
            # it; also covers engines constructed programmatically
            from crosscoder_tpu.tune.artifact import apply_tuned

            cfg = apply_tuned(cfg)
        self.cfg = cfg
        self.lm_cfg = lm_cfg
        self._lm_params = tuple(lm_params_seq)
        self._cc_params = cc_params
        self._hooks = tuple(
            hook_points if hook_points is not None
            else cfg.resolved_hook_points()
        )
        n_sources = len(self._lm_params) * len(self._hooks)
        self._pair = serve_step.diff_pair(n_sources, len(self._lm_params))
        norm = (np.ones(n_sources, np.float32) if norm_factors is None
                else np.asarray(norm_factors, np.float32))
        if norm.shape != (n_sources,):
            raise ValueError(
                f"norm_factors must be [{n_sources}] (one per source), "
                f"got {norm.shape}"
            )
        self._norm = norm
        self.registry = registry if registry is not None else MetricsRegistry()
        self._clock = clock
        self.buckets = batch_buckets(cfg.serve_max_batch)
        pages_per_seq = -(-cfg.seq_len // cfg.page_size)
        self._pages = PageTable(
            (cfg.serve_queue + cfg.serve_max_batch) * pages_per_seq,
            cfg.page_size,
        )
        self._batcher = ContinuousBatcher(
            cfg.seq_len, n_rows=cfg.serve_max_batch,
            max_wait_s=cfg.serve_max_wait_ms / 1e3,
        )
        self._queue: deque[_Pending] = deque()
        self._batch: list[_Pending] = []
        self._live: dict[int, _Live] = {}
        self._shed_ids: set[int] = set()
        self._next_id = 0
        self._compiles = 0
        self._warm_compiles = 0
        # compile accounting is touched from warmup's worker threads
        self._compile_lock = threading.Lock()
        self._warm_tl = threading.local()
        # persistent AOT tier (cfg.compile_cache_dir; docs/SCALING.md
        # "Persistent compile cache"): a fresh replica's warmup
        # deserializes the bucket ladder instead of compiling it
        from crosscoder_tpu.utils import compile_cache

        compile_cache.configure(cfg, registry=self.registry)
        # params are fixed per engine; their shape/dtype signature keys
        # the encode executables alongside the batch bucket
        self._cc_sig = tuple(sorted(
            (k, tuple(v.shape), str(v.dtype)) for k, v in cc_params.items()
        ))

    # -- admission -------------------------------------------------------

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    @property
    def compiles(self) -> int:
        """Executables built by this engine (prefill + encode, all
        buckets). Frozen into the warmup baseline by :meth:`warmup`."""
        return self._compiles

    @property
    def compiles_after_warmup(self) -> int:
        return self._compiles - self._warm_compiles

    def was_shed(self, rid: int) -> bool:
        return rid in self._shed_ids

    def _on_build(self, key) -> None:
        with self._compile_lock:
            self._compiles += 1
            n = getattr(self._warm_tl, "n", None)
            if n is not None:     # inside a warmup worker: per-bucket tally
                self._warm_tl.n = n + 1
        self.registry.count("serve/compiles")

    def _shed(self, rid: int | None, reason: str):
        self.registry.count("serve/shed_total")
        if rid is not None:
            self._shed_ids.add(rid)
        raise Shed(reason)

    def _evict_stale(self, now: float) -> None:
        """Max-queue-wait eviction (``cfg.serve_shed_ms``): drop queued
        requests whose deadline passed — they would be served too late to
        matter, and they hold pages newer requests need."""
        if self.cfg.serve_shed_ms <= 0:
            return
        limit = self.cfg.serve_shed_ms / 1e3
        kept: deque[_Pending] = deque()
        for p in self._queue:
            if not p.extend and now - p.t >= limit:
                self.registry.count("serve/shed_total")
                self._shed_ids.add(p.rid)
                self._drop_request(p)
            else:
                kept.append(p)
        self._queue = kept

    def _drop_request(self, p: _Pending) -> None:
        self._pages.free(p.rid)
        self._live.pop(p.rid, None)

    def submit(self, tokens, *, keep: bool = False,
               now: float | None = None) -> int:
        """Enqueue one request (1-D int32 token stream). Returns the
        request id; raises :class:`Shed` on overload. ``keep=True`` keeps
        the sequence resident after serving (pages held) so
        :meth:`extend` can append follow-up tokens."""
        now = self._clock() if now is None else now
        tokens = np.asarray(tokens, np.int32).ravel()
        ln = tokens.shape[0]
        if not 1 <= ln <= self.cfg.seq_len:
            raise ValueError(
                f"request length {ln} outside [1, {self.cfg.seq_len}]"
            )
        self._evict_stale(now)
        if len(self._queue) >= self.cfg.serve_queue:
            self._shed(None, f"queue full ({self.cfg.serve_queue})")
        rid = self._next_id
        self._next_id += 1
        if self._pages.alloc(rid, ln) is None:
            self._shed(rid, "page pool exhausted")
        if keep:
            self._live[rid] = _Live(tokens=tokens.copy())
        self._queue.append(_Pending(rid, tokens, now, keep=keep))
        return rid

    def extend(self, rid: int, extra_tokens,
               now: float | None = None) -> None:
        """Append follow-up tokens to a live (``keep=True``) request and
        re-enqueue it at the FRONT of the queue: the prefix's pages are
        kept (:meth:`PageTable.extend` grants only the delta) and the
        request never re-enters the prefill admission path."""
        now = self._clock() if now is None else now
        live = self._live.get(rid)
        if live is None:
            raise KeyError(
                f"request {rid} is not live (submit with keep=True, and "
                f"before release())"
            )
        with trace.span("extend", request=rid):
            extra = np.asarray(extra_tokens, np.int32).ravel()
            total = live.tokens.shape[0] + extra.shape[0]
            if total > self.cfg.seq_len:
                raise ValueError(
                    f"extended length {total} exceeds seq_len "
                    f"{self.cfg.seq_len}"
                )
            if self._pages.extend(rid, total) is None:
                self._shed(rid, "page pool exhausted on extend")
            live.tokens = np.concatenate([live.tokens, extra])
            self._queue.appendleft(
                _Pending(rid, live.tokens, now, keep=True, extend=True)
            )
        self.registry.count("serve/extends_total")

    def release(self, rid: int) -> None:
        """Retire a live request: pages return to the pool."""
        self._live.pop(rid)
        self._pages.free(rid)

    def drain_queue(self) -> list[tuple[int, np.ndarray]]:
        """Hand every queued (unserved) request back to the caller — the
        replica preemption path (serve/replica.py): the drained requests
        are re-submitted on a peer instead of dropped. Local pages are
        freed; live state is dropped."""
        out = []
        while self._queue:
            p = self._queue.popleft()
            out.append((p.rid, p.tokens))
            self._drop_request(p)
            self.registry.count("serve/drained_total")
        return out

    def pages_of(self, rid: int) -> list[int]:
        return self._pages.pages_of(rid)

    # -- the request loop ------------------------------------------------

    def step(self, now: float | None = None,
             force: bool = False) -> list[ServeResult]:
        """Admit queued requests and flush one micro-batch when it is
        due: batch-full, the oldest admitted request past
        ``serve_max_wait_ms``, or ``force=True``. Returns the served
        results (empty while the batch is still filling)."""
        now = self._clock() if now is None else now
        self._evict_stale(now)
        while self._queue and len(self._batch) < self.cfg.serve_max_batch:
            p = self._queue[0]
            if p.rid in self._shed_ids:
                self._queue.popleft()
                continue
            if not self._batcher.admit(p.tokens, now=p.t):
                break
            self._batch.append(p)
            self._queue.popleft()
        if not self._batch:
            return []
        full = len(self._batch) >= self.cfg.serve_max_batch
        if not (full or self._batcher.due(now) or force):
            return []
        return self._flush(now)

    def _flush(self, now: float) -> list[ServeResult]:
        n = len(self._batch)
        b = bucket_of(n, self.cfg.serve_max_batch)
        for _ in range(b - n):        # bucket padding: length-1 pad docs
            self._batcher.admit(np.zeros(1, np.int32), now=now)
        chunk = self._batcher.flush(n_rows=b)
        vals, idx, diff, prefill_ms, encode_ms = self._run_chunk(chunk, b)
        results = []
        for i, p in enumerate(self._batch):
            qw_ms = max(0.0, (now - p.t) * 1e3)
            self.registry.observe("serve/queue_wait_ms", qw_ms)
            self.registry.count("serve/requests_total")
            if not p.keep:
                self._pages.free(p.rid)
            results.append(ServeResult(
                request_id=p.rid, vals=vals[i], idx=idx[i], diff=diff[i],
                bucket=b, queue_wait_ms=qw_ms, prefill_ms=prefill_ms,
                encode_ms=encode_ms, extended=p.extend,
            ))
        trace.instant("queue_wait", docs=n,
                      max_ms=round(max(r.queue_wait_ms for r in results), 3))
        self._batch = []
        return results

    def _run_chunk(self, chunk, b: int):
        """Prefill + encode one bucket-shaped chunk; returns host-side
        ``(vals, idx, diff)`` (the only device→host transfer, ``[b, k]``
        each) plus the two stage wall times."""
        import jax

        from crosscoder_tpu.models import crosscoder, lm
        from crosscoder_tpu.utils import compile_cache

        cfg = self.cfg
        t0 = time.perf_counter()
        # (the scope names the prefill's ops in a device trace; it takes
        # effect when the bucket's program is traced, i.e. on a build)
        with trace.span("prefill", bucket=b), jax.named_scope("serve/prefill"):
            caps = lm.paged_capture_aot(
                self._lm_params, chunk, self.lm_cfg, self._hooks,
                page_size=cfg.page_size, pad_mode="zero",
                on_build=self._on_build,
            )
        t1 = time.perf_counter()
        with trace.span("encode", bucket=b):
            import jax.numpy as jnp

            lengths = jnp.asarray(chunk.lengths)
            norm = jnp.asarray(self._norm)
            fused = crosscoder.use_fused_encoder(cfg, b)
            statics = dict(enc_dtype=cfg.enc_dtype, k=cfg.topk_k,
                           fused=fused, pair=self._pair)
            key = ("serve_encode", b, tuple(caps.shape), str(caps.dtype),
                   self._cc_sig, tuple(sorted(statics.items())))

            def lower():
                return serve_step.encode_topk_diff.lower(
                    self._cc_params, caps, lengths, norm, **statics
                )

            compiled = compile_cache.aot_get(
                key, lambda: lower().compile(),
                on_build=self._on_build, lower=lower,
                topology=f"devices={jax.device_count()}",
            )
            out = compiled(self._cc_params, caps, lengths, norm)
            vals, idx, diff = (np.asarray(jax.device_get(t)) for t in out)
        t2 = time.perf_counter()
        prefill_ms, encode_ms = (t1 - t0) * 1e3, (t2 - t1) * 1e3
        self.registry.observe("serve/prefill_ms", prefill_ms)
        self.registry.observe("serve/encode_ms", encode_ms)
        return vals, idx, diff, prefill_ms, encode_ms

    def warmup(self) -> int:
        """Build — or deserialize from the persistent tier
        (``cfg.compile_cache_dir``) — every bucket's prefill + encode
        executable ahead of traffic (full-length synthetic chunks — the
        exact steady-state shapes). Buckets warm CONCURRENTLY: disk
        loads and residual compiles overlap across a small thread pool,
        so warmup wall is bounded by the slowest bucket, not the ladder
        sum (jax dispatch and the AOT memo are both thread-safe; equal
        keys coalesce onto one build). The readiness log stays in
        deterministic ladder order regardless of completion order.
        Freezes the compile baseline: after this,
        :attr:`compiles_after_warmup` must stay 0 (asserted by the bench
        serve leg and scripts/serve_smoke.sh)."""
        from concurrent.futures import ThreadPoolExecutor

        S = self.cfg.seq_len

        def _warm_one(b: int) -> tuple[float, int]:
            self._warm_tl.n = 0
            t0 = time.perf_counter()
            chunk = pack_chunk(np.ones((b, S), np.int32),
                               np.full(b, S, np.int64), n_rows=b)
            self._run_chunk(chunk, b)
            return (time.perf_counter() - t0) * 1e3, self._warm_tl.n

        with ThreadPoolExecutor(
                max_workers=min(8, len(self.buckets)),
                thread_name_prefix="serve-warmup") as pool:
            timings = list(pool.map(_warm_one, self.buckets))
        for b, (ms, n) in zip(self.buckets, timings):
            print(f"[crosscoder_tpu] serve: warm bucket={b} "
                  f"({ms:.0f} ms, {n} compile(s))",
                  file=sys.stderr, flush=True)
        self._warm_compiles = self._compiles
        return self._warm_compiles

    def stats(self) -> dict:
        """Registry snapshot (histogram percentiles included) + compile
        accounting — the serve smoke/bench report surface."""
        out = dict(self.registry.snapshot())
        out["serve_compiles_total"] = self._compiles
        out["serve_compiles_after_warmup"] = self.compiles_after_warmup
        return out
