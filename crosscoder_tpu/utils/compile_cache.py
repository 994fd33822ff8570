"""Persistent XLA compilation cache + compile-event observability.

Compiling the step, harvest and serve programs dominates a cold start;
the persistent cache turns restarts, resumes, and repeated bench/eval
runs into warm starts. Library code never sets this — only executables
opt in, so embedding applications keep control.

Two distinct persistence layers live here:

- :func:`enable` points JAX's own persistent *compilation* cache (HLO →
  binary, keyed internally by XLA) at a directory — compiles are still
  paid, just faster.
- The **AOT disk tier** (``cfg.compile_cache_dir``; docs/SCALING.md
  "Persistent compile cache") serializes whole compiled executables via
  ``jax.experimental.serialize_executable`` so a warm process *skips the
  compile entirely*: :func:`aot_get` and :func:`observed` check the disk
  tier before building, and a fresh serve replica / re-meshed trainer /
  tune run deserializes in milliseconds what a cold one compiled in
  seconds. Off by default (``compile_cache_dir=""``) the tier costs
  nothing and the compiled programs are byte-identical to a build
  without it (tests/test_compile_cache_disk.py pins step-HLO identity).
  The cache may only ever make things faster — corrupt, stale, or
  fingerprint-mismatched entries fall back to a live compile, never an
  error.

:func:`observed` is the telemetry side (``cfg.obs``;
docs/OBSERVABILITY.md): a jitted step variant wrapped by it AOT-compiles
on its first call under a ``compile`` span (``source=disk|build``), and
the event — variant key, compile wall time and the compiled program's
collective accounting — is reported through the observability registry
(its HLO cost-analysis FLOPs/bytes are kept per key, :func:`record_cost`). With observability off and
the disk tier off nothing here wraps anything: the jitted functions are
called exactly as before, so the off path is untouched.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
import sys
import threading
import time
from collections import OrderedDict
from pathlib import Path
from typing import Any, Callable, Mapping

from crosscoder_tpu.obs import trace

DISK_FORMAT = 1

# one process compiles, peers deserialize: a loser of the claim race waits
# at most this long for the leader's entry before compiling live anyway
_CLAIM_WAIT_S = float(os.environ.get("CROSSCODER_COMPILE_CACHE_WAIT_S", "120"))
# a claim older than this is a dead leader; stealable
_CLAIM_TTL_S = float(os.environ.get("CROSSCODER_COMPILE_CACHE_CLAIM_TTL_S",
                                    "600"))


def variant_key(metrics: bool, aux: bool, refresh: bool, *,
                tenant: str = "") -> str:
    """Canonical compile-event key for one train-step variant.

    ``(metrics, aux, refresh)`` is the Trainer's compiled-variant cache
    tuple. The text keeps ``enc=dense``, the one encoder a step has, so
    compile logs and the disk tier's keys read as they always have.
    ``tenant`` is the
    fleet scheduler's compile-bucket tag (train/fleet.py): a stacked
    cohort or a heterogeneous tenant signature appends its bucket name
    so per-tenant compile events stay distinguishable; solo-trainer
    keys (``tenant=""``) are byte-stable with the pre-fleet format.
    Every writer of a step-variant key goes through here — the single
    place the key format lives.
    """
    tag = f", tenant={tenant}" if tenant else ""
    return (f"train_step(metrics={metrics}, aux={aux}, "
            f"refresh={refresh}, enc=dense{tag})")


def enable() -> str | None:
    """Turn on JAX's persistent compilation cache; returns its directory
    (``None`` when it is off).

    ``JAX_COMPILATION_CACHE_DIR`` is JAX's own variable: when it is set,
    JAX has already read it and this function sets no directory (an empty
    value leaves the cache off). Unset, the cache goes to the fixed
    ``<checkout>/.jax_cache`` — the path is part of the cache key, so it
    never carries a temp name, pid or time. Source file names in what is
    lowered from here on are made relative to the checkout, so that the
    same program lowered from a checkout at another path has the same key
    (tests/test_compile_cache.py). Safe to call before or after backend
    init, but before the first trace.

    The key also takes the digest of the registered scope names
    (``obs/scopes.py``), through JAX's own hook for an addition to it: JAX
    strips op metadata from a program before hashing it, so a tree that
    adds or renames a ``jax.named_scope`` would otherwise be served the
    older tree's executable, and a profile window would show the older
    tree's names. Only the names reach the key — no file name, no line —
    so a change of the table, and nothing else of the metadata, compiles
    each program once more.
    """
    import jax

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_dir is None:
        cache_dir = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # source locations relative to the checkout: a Pallas call's payload
    # carries the MLIR locations of its kernel's equations (file names of
    # the traceback's user frames), the payload is in the HLO the cache key
    # hashes, and so a program that holds a kernel missed from every
    # checkout at another path until the root was stripped
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex", re.escape(root + os.sep))
    # cache EVERYTHING: the analysis entry points' first call is dominated
    # by many sub-second compiles (decoder norms, cosines, logit lens)
    # that a 1.0 s threshold would silently re-pay in every process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _key_scope_names()
    return cache_dir or None


def _key_scope_names() -> None:
    """``scopes:<digest>`` into every persistent-cache key from here on
    (``jax._src.cache_key.custom_hook``: "any addition to the cache key",
    read by ``get()`` at each call). The directory stays the one JAX was
    given: a cache placed by ``JAX_COMPILATION_CACHE_DIR`` is not moved."""
    from crosscoder_tpu.obs import scopes

    addition = f"scopes:{scopes.digest()}"
    try:
        from jax._src import cache_key

        cache_key.custom_hook = lambda: addition
    except Exception as e:      # the hook moved: names may be an older tree's
        print(f"[crosscoder_tpu] compile cache: scope names not in the key "
              f"({e!r}); perf/device/scoped_share will say if they are stale",
              file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# in-memory memo: bounded LRU + lock


# guards every module-level table below; RLock because record_cost /
# cost_of / the eviction settle re-enter from under it
_LOCK = threading.RLock()

# in-flight builds: key → _Inflight; concurrent same-key callers get
# exactly one build (the serve warmup hammers this from a thread pool)
_INFLIGHT: dict[Any, "_Inflight"] = {}

# bounded LRU of AOT executables (insertion order = recency; hits
# move_to_end). 256 covers every ladder in the repo (8 serve buckets ×
# 2 stages, ≤ 8 step variants, a 32-point tune lattice) with wide margin;
# the bound exists so a pathological caller cannot leak executables.
_AOT_CACHE: "OrderedDict[Any, Any]" = OrderedDict()
_AOT_CACHE_CAP = 256

# key → {"flops": float, "bytes_accessed": float} for every executable
# that passed through here; the autotuner's stage-1 pricing and the
# report tooling query it via cost_of() instead of re-pulling
# cost_analysis() ad hoc
_COST_CACHE: dict[Any, dict[str, float]] = {}

# key → executable whose cost analysis has not been pulled yet: aot_get
# stashes here instead of paying cost_analysis() on the hot compile path
# (it is not free on large programs), and cost_of() settles on demand
_COST_PENDING: dict[Any, Any] = {}

# key → per-collective wire-byte dict parsed from the program's HLO,
# loaded from a disk-tier cost sidecar so tune's stage-1 pricing answers
# without compiling (or even deserializing) anything
_COLLECTIVES: dict[Any, dict[str, float]] = {}


class _Inflight:
    """One in-progress build: the owner resolves, waiters block on it."""

    __slots__ = ("event", "value", "error")

    def __init__(self) -> None:
        self.event = threading.Event()
        self.value: Any = None
        self.error: BaseException | None = None

    def wait(self) -> Any:
        self.event.wait()
        if self.error is not None:
            raise self.error
        return self.value


def _evict_memo_locked() -> None:
    """Drop least-recently-used executables past the cap (_LOCK held).
    A pending cost analysis settles before its executable is dropped so
    cost_of() keeps answering for evicted keys."""
    while len(_AOT_CACHE) > _AOT_CACHE_CAP:
        k, _ = _AOT_CACHE.popitem(last=False)
        exe = _COST_PENDING.pop(k, None)
        if exe is not None and k not in _COST_CACHE:
            _COST_CACHE[k] = extract_cost(exe)


def extract_cost(compiled: Any) -> dict[str, float]:
    """FLOPs / bytes-accessed of a compiled executable, normalized.

    The single place the repo reads ``compiled.cost_analysis()`` — older
    jax returns a list-wrapped dict, newer a bare dict, and either may
    omit keys; callers (obs compile events, the fleet policy's analytic
    ranking, bench's HBM-traffic numbers, the tune lattice) get a plain
    ``{"flops", "bytes_accessed"}`` dict with 0.0 for anything missing.
    Never raises: an executable without cost analysis prices as zeros.
    """
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):    # older jax returns [dict]
            cost = cost[0] if cost else {}
        return {
            "flops": float(cost.get("flops", 0.0) or 0.0),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0) or 0.0),
        }
    except Exception:
        return {"flops": 0.0, "bytes_accessed": 0.0}


def record_cost(key: Any, compiled: Any) -> dict[str, float]:
    """Extract + memoize the cost analysis of ``compiled`` under ``key``
    (tuple AOT keys and string variant keys share one table)."""
    cost = extract_cost(compiled)
    with _LOCK:
        _COST_PENDING.pop(key, None)
        _COST_CACHE[key] = cost
    return cost


def cost_of(key: Any) -> dict[str, float] | None:
    """The memoized HLO cost analysis for a previously compiled variant,
    or ``None`` if nothing under ``key`` has compiled in this process
    AND the disk tier has no cost sidecar for it. Executables stashed
    lazily by :func:`aot_get` settle here on first query (under the
    module lock — safe against the background prewarm threads)."""
    with _LOCK:
        got = _COST_CACHE.get(key)
        if got is None and key in _COST_PENDING:
            got = record_cost(key, _COST_PENDING.pop(key))
    if got is None and _DISK is not None:
        dk = disk_key(key)
        side = _DISK.cost(dk) if dk is not None else None
        if side is not None:
            got = {"flops": float(side.get("flops", 0.0) or 0.0),
                   "bytes_accessed":
                       float(side.get("bytes_accessed", 0.0) or 0.0)}
            with _LOCK:
                _COST_CACHE[key] = got
                if isinstance(side.get("collectives"), dict):
                    _COLLECTIVES[key] = side["collectives"]
    return got


def collectives_of(key: Any) -> dict[str, float] | None:
    """Per-collective wire bytes for ``key`` if a disk-tier cost sidecar
    carried them (stored at build time from the program's HLO text) —
    lets tune's stage-1 pricing skip the HLO parse on warm runs. ``None``
    when unknown; callers fall back to parsing ``compiled.as_text()``."""
    with _LOCK:
        got = _COLLECTIVES.get(key)
    if got is None and _DISK is not None:
        dk = disk_key(key)
        side = _DISK.cost(dk) if dk is not None else None
        if side is not None and isinstance(side.get("collectives"), dict):
            got = side["collectives"]
            with _LOCK:
                _COLLECTIVES[key] = got
    return got


# ---------------------------------------------------------------------------
# disk-tier keying


class _Uncacheable(TypeError):
    """A key component with no stable canonical form (callable, live
    array, ...) — the entry stays memo-only, never wrongly shared."""


def _canon(o: Any) -> str:
    """Deterministic canonical string of a cache-key component.

    Covers everything the repo actually keys on: primitives, nested
    tuples/lists/dicts/sets, config dataclasses (LMConfig,
    CrossCoderConfig), and jax shardings (mesh axis topology + spec —
    never device ids, which differ across processes). Anything else
    raises :class:`_Uncacheable` and the executable stays memo-only —
    an unkeyable entry must never be persisted under a lossy key.
    """
    if o is None or isinstance(o, (bool, int, float, str, bytes)):
        return repr(o)
    if isinstance(o, (tuple, list)):
        return "(" + ",".join(_canon(x) for x in o) + ")"
    if isinstance(o, dict):
        items = sorted(o.items(), key=lambda kv: repr(kv[0]))
        return "{" + ",".join(f"{_canon(k)}:{_canon(v)}" for k, v in items) + "}"
    if isinstance(o, (set, frozenset)):
        return "s{" + ",".join(sorted(_canon(x) for x in o)) + "}"
    if dataclasses.is_dataclass(o) and not isinstance(o, type):
        return type(o).__name__ + _canon(dataclasses.asdict(o))
    mesh = getattr(o, "mesh", None)
    spec = getattr(o, "spec", None)
    if mesh is not None and spec is not None:      # NamedSharding-like
        return f"sharding({sorted(mesh.shape.items())},{spec})"
    raise _Uncacheable(f"no canonical form for {type(o).__name__}")


def backend_fingerprint() -> str:
    """The compile-environment identity a persisted executable is only
    valid under: jax/jaxlib versions, backend platform, device kind, and
    the digest of the registered scope names (``obs/scopes.py``: an
    executable carries the names it was compiled with).
    Part of every disk key AND stored in every entry — a version bump or
    hardware change makes old entries unreachable (key changes) and
    unloadable (stored fingerprint check), so stale binaries can never
    run. Deliberately NOT topology (device/process counts): topology is
    its own key component (the caller's mesh scope / aval signature), so
    the remesh prewarm can store entries for a topology this process
    does not have yet. Recomputed per call — a backend reset can change
    the answer mid-process."""
    import jax

    from crosscoder_tpu.obs import scopes

    names = f"scopes={scopes.digest()}"     # an executable carries its names
    try:
        devs = jax.devices()
        kind = devs[0].device_kind if devs else "none"
        return (f"jax={jax.__version__},jaxlib={_jaxlib_version()},"
                f"backend={jax.default_backend()},device={kind},{names}")
    except Exception:
        return f"jax={jax.__version__},backend=unknown,{names}"


def _jaxlib_version() -> str:
    try:
        import jaxlib

        return getattr(jaxlib, "__version__", "?")
    except Exception:
        return "?"


def disk_key(key: Any) -> str | None:
    """Content digest a memo key persists under: sha256 of the canonical
    key string + the backend fingerprint + the disk format version.
    ``None`` when any component has no canonical form — such entries
    stay in-memory only (correct, just not persistent)."""
    try:
        canon = _canon(key)
    except _Uncacheable:
        return None
    blob = f"v{DISK_FORMAT}\x1f{backend_fingerprint()}\x1f{canon}"
    return hashlib.sha256(blob.encode()).hexdigest()


def step_knob_projection(cfg_dict: Mapping[str, Any]) -> dict[str, Any]:
    """The step-program-relevant knob slice of a config dict — exactly
    ``tune.lattice.STEP_FIELDS``, the single source of truth for "which
    knobs change the compiled step". The trainer's disk scope hashes
    this projection, so two configs that differ only in data-plane knobs
    (refill_frac, log cadence, paths) share one disk entry while any
    step-shaping knob forks the key. The ``cache-key-completeness``
    contracts rule mechanically asserts every STEP_FIELDS knob feeds
    :func:`step_digest`."""
    from crosscoder_tpu.tune.lattice import STEP_FIELDS

    return {k: cfg_dict.get(k) for k in sorted(STEP_FIELDS)}


def step_digest(cfg_dict: Mapping[str, Any]) -> str:
    """Hash of :func:`step_knob_projection` — the step-knob component of
    a trainer disk key."""
    proj = step_knob_projection(cfg_dict)
    blob = json.dumps(proj, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# disk tier


def _atomic_write(path: Path, data: bytes) -> None:
    """tmp + rename: readers never observe a torn entry (the
    serve/replica.py board discipline)."""
    tmp = path.with_suffix(path.suffix + f".tmp-{os.getpid()}")
    tmp.write_bytes(data)
    os.replace(tmp, path)


class DiskCache:
    """One directory of serialized AOT executables + cost sidecars.

    Layout (under ``<root>/v{DISK_FORMAT}/``):

    - ``<digest>.exec`` — pickle of ``{format, fingerprint, hlo_sha,
      payload, in_tree, out_tree, device_ids}`` (``jax.experimental
      .serialize_executable`` triple plus validity metadata and the ids
      of the devices the program was compiled for)
    - ``<digest>.cost.json`` — normalized HLO cost analysis
      (+ per-collective wire bytes when the HLO parse succeeds), so
      ``cost_of`` answers from disk without deserializing anything
    - ``<digest>.claim`` — compile-leader marker (claim-by-rename;
      exactly one winner, peers deserialize the winner's entry)
    - ``manifest.json`` — versioned advisory index
      ``{version, entries: {digest: {bytes, variant, topology, created,
      last_used}}}`` for the report tooling; eviction trusts the actual
      files, so a lost manifest update can never strand bytes

    Every failure mode — corrupt pickle, stale fingerprint, strict-mode
    HLO mismatch, unserializable executable, full disk — degrades to a
    live compile (a miss), never an error: the cache may only make
    things faster.
    """

    def __init__(self, root: str | os.PathLike, *,
                 max_bytes: int = 1 << 30, registry: Any = None) -> None:
        self.root = Path(root) / f"v{DISK_FORMAT}"
        self.root.mkdir(parents=True, exist_ok=True)
        self.max_bytes = int(max_bytes)
        self.registry = registry
        self.stats = {"disk_hit": 0, "disk_miss": 0, "evictions": 0}
        self._lock = threading.Lock()

    # -- counters --------------------------------------------------------

    def _count(self, what: str) -> None:
        with self._lock:
            self.stats[what] += 1
        if self.registry is not None:
            try:
                self.registry.count(f"compile/{what}")
            except Exception:
                pass

    # -- manifest (advisory; atomic read-modify-write) -------------------

    @property
    def manifest_path(self) -> Path:
        return self.root / "manifest.json"

    def manifest(self) -> dict:
        try:
            man = json.loads(self.manifest_path.read_text())
            if not isinstance(man, dict) or not isinstance(
                    man.get("entries"), dict):
                raise ValueError("ill-typed manifest")
            return man
        except (OSError, ValueError):
            # absent / torn / corrupt: advisory data, start fresh
            return {"version": DISK_FORMAT, "entries": {}}

    def _update_manifest(self, fn: Callable[[dict], None]) -> None:
        with self._lock:
            man = self.manifest()
            try:
                fn(man)
                _atomic_write(self.manifest_path,
                              json.dumps(man, sort_keys=True).encode())
            except OSError:
                pass        # manifest is advisory; the files are the truth

    # -- entries ---------------------------------------------------------

    def _exec_path(self, digest: str) -> Path:
        return self.root / f"{digest}.exec"

    def _cost_path(self, digest: str) -> Path:
        return self.root / f"{digest}.cost.json"

    def _claim_path(self, digest: str) -> Path:
        return self.root / f"{digest}.claim"

    def has(self, digest: str) -> bool:
        """Entry presence without deserializing (prewarm dedup check)."""
        return self._exec_path(digest).exists()

    def _discard(self, digest: str) -> None:
        for p in (self._exec_path(digest), self._cost_path(digest)):
            try:
                p.unlink()
            except OSError:
                pass
        self._update_manifest(lambda m: m["entries"].pop(digest, None))

    def load(self, digest: str, *, lower: Callable[[], Any] | None = None,
             verify: str = "off") -> Any | None:
        """Deserialize the entry under ``digest``, or ``None`` (a miss).

        Validity gates, each a silent fall-back to live compile:
        format/fingerprint mismatch (stale jaxlib, different topology),
        corrupt pickle or failed deserialize (entry discarded), and —
        ``verify="strict"`` — a re-lowering check that the stored
        program's HLO hash matches what ``lower()`` produces live now
        (unverifiable entries miss too, strict means strict).
        """
        path = self._exec_path(digest)
        try:
            blob = path.read_bytes()
        except OSError:
            self._count("disk_miss")
            return None
        try:
            import pickle

            rec = pickle.loads(blob)
            if (not isinstance(rec, dict)
                    or rec.get("format") != DISK_FORMAT
                    or rec.get("fingerprint") != backend_fingerprint()):
                self._count("disk_miss")
                return None
            if verify == "strict":
                stored = rec.get("hlo_sha")
                if stored is None or lower is None:
                    self._count("disk_miss")
                    return None
                live = hashlib.sha256(
                    lower().as_text().encode()).hexdigest()
                if live != stored:
                    print(f"[crosscoder_tpu] compile cache: strict verify "
                          f"REJECTED {digest[:12]} (stored HLO != live "
                          f"lowering); recompiling",
                          file=sys.stderr, flush=True)
                    self._discard(digest)
                    self._count("disk_miss")
                    return None
            import jax
            from jax.experimental.serialize_executable import \
                deserialize_and_load

            # load onto exactly the devices the program was compiled for:
            # the loader's default is EVERY backend device, which hands
            # back an executable that raises at call time when the
            # program spans fewer. Ids this process does not have (an
            # entry stored for another topology) are a plain miss.
            by_id = {d.id: d for d in jax.devices()}
            ids = rec.get("device_ids")
            if not ids or any(i not in by_id for i in ids):
                self._count("disk_miss")
                return None
            exe = deserialize_and_load(
                rec["payload"], rec["in_tree"], rec["out_tree"],
                execution_devices=[by_id[i] for i in ids])
        except Exception:
            # corrupt / undeserializable on this backend: drop it so the
            # next process pays the read even less
            self._discard(digest)
            self._count("disk_miss")
            return None
        self._count("disk_hit")
        now = time.time()
        try:
            os.utime(path, (now, now))      # LRU recency = file mtime
        except OSError:
            pass
        self._update_manifest(
            lambda m: m["entries"].get(digest, {}).__setitem__(
                "last_used", now)
            if digest in m["entries"] else None)
        return exe

    def store(self, digest: str, compiled: Any, *, variant: str = "",
              topology: str = "",
              lower: Callable[[], Any] | None = None) -> bool:
        """Serialize ``compiled`` under ``digest`` + write its cost
        sidecar; returns False (and persists nothing) when the
        executable does not round-trip through
        ``serialize_executable``."""
        try:
            import pickle

            from jax.experimental.serialize_executable import serialize

            payload, in_tree, out_tree = serialize(compiled)
            hlo_sha = None
            try:
                text = (lower().as_text() if lower is not None
                        else compiled.as_text())
                hlo_sha = hashlib.sha256(text.encode()).hexdigest()
            except Exception:
                text = None
            rec = {"format": DISK_FORMAT,
                   "fingerprint": backend_fingerprint(),
                   "hlo_sha": hlo_sha, "payload": payload,
                   "in_tree": in_tree, "out_tree": out_tree,
                   "device_ids": [
                       d.id for d in
                       compiled.runtime_executable().local_devices()]}
            blob = pickle.dumps(rec)
            _atomic_write(self._exec_path(digest), blob)
        except Exception as e:
            print(f"[crosscoder_tpu] compile cache: store of "
                  f"{variant or digest[:12]} skipped "
                  f"({type(e).__name__}: {e})"[:300],
                  file=sys.stderr, flush=True)
            return False
        side: dict[str, Any] = extract_cost(compiled)
        try:
            from crosscoder_tpu.parallel import comm_model

            hlo = text if text is not None else compiled.as_text()
            side["collectives"] = comm_model.collective_bytes(hlo)
        except Exception:
            pass
        try:
            _atomic_write(self._cost_path(digest),
                          json.dumps(side, sort_keys=True).encode())
        except (OSError, TypeError, ValueError):
            pass
        now = time.time()

        def _add(man: dict) -> None:
            man["entries"][digest] = {
                "bytes": len(blob), "variant": str(variant)[:120],
                "topology": str(topology)[:120],
                "created": now, "last_used": now,
            }
        self._update_manifest(_add)
        self._evict()
        return True

    def cost(self, digest: str) -> dict[str, Any] | None:
        """The cost sidecar under ``digest`` (no executable touched)."""
        try:
            side = json.loads(self._cost_path(digest).read_text())
            return side if isinstance(side, dict) else None
        except (OSError, ValueError):
            return None

    # -- byte-capped LRU eviction ---------------------------------------

    def _evict(self) -> None:
        """Drop oldest-used entries until total bytes fit
        ``max_bytes``. Recency/size come from the actual ``.exec``
        files (mtime touched on every hit), not the advisory manifest —
        a lost manifest update can never strand bytes on disk."""
        try:
            entries = []
            total = 0
            for p in self.root.glob("*.exec"):
                try:
                    st = p.stat()
                except OSError:
                    continue
                entries.append((st.st_mtime, st.st_size, p))
                total += st.st_size
            entries.sort()
            for _, size, p in entries:
                if total <= self.max_bytes:
                    break
                self._discard(p.name[:-len(".exec")])
                total -= size
                self._count("evictions")
        except OSError:
            pass

    # -- compile-leader claim (exactly one process builds) ---------------

    def claim(self, digest: str) -> bool:
        """Try to become the compile leader for ``digest``: write a tmp
        marker and link it into place — the rename-style atomic create
        of the ReplicaBoard drain protocol, exactly one winner. A claim
        older than the TTL belongs to a dead leader and is stolen."""
        path = self._claim_path(digest)
        tmp = path.with_suffix(f".tmp-{os.getpid()}")
        try:
            tmp.write_text(str(os.getpid()))
            try:
                os.link(tmp, path)
                return True
            except FileExistsError:
                try:
                    if time.time() - path.stat().st_mtime > _CLAIM_TTL_S:
                        os.replace(tmp, path)   # steal the stale claim
                        tmp = None
                        return True
                except OSError:
                    pass
                return False
            except OSError:
                # filesystem without hardlinks: O_EXCL fallback
                try:
                    fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                    os.close(fd)
                    return True
                except OSError:
                    return False
        except OSError:
            return True     # can't coordinate — compile locally, don't wait
        finally:
            if tmp is not None:
                try:
                    tmp.unlink()
                except OSError:
                    pass

    def release(self, digest: str) -> None:
        try:
            self._claim_path(digest).unlink()
        except OSError:
            pass

    def wait_for(self, digest: str, *,
                 lower: Callable[[], Any] | None = None,
                 verify: str = "off",
                 timeout_s: float | None = None) -> Any | None:
        """Claim-race loser path: poll for the leader's entry. Returns
        the deserialized executable, or ``None`` when the leader died
        (claim gone, no entry) or the timeout lapsed — caller compiles
        live (and does NOT store, to avoid stomping the leader)."""
        deadline = time.monotonic() + (
            _CLAIM_WAIT_S if timeout_s is None else timeout_s)
        while time.monotonic() < deadline:
            if self._exec_path(digest).exists():
                return self.load(digest, lower=lower, verify=verify)
            if not self._claim_path(digest).exists():
                return None         # leader gone without publishing
            time.sleep(0.05)
        return None


# ---------------------------------------------------------------------------
# module-level disk-tier state


_DISK: DiskCache | None = None
_VERIFY = "off"


def configure(cfg: Any = None, *, cache_dir: str | None = None,
              max_bytes: int | None = None, verify: str | None = None,
              registry: Any = None) -> DiskCache | None:
    """Point the AOT disk tier at ``cfg.compile_cache_dir`` (or the
    explicit ``cache_dir``; ``$CROSSCODER_COMPILE_CACHE_DIR`` as the
    tooling fallback). Empty directory → tier off (``None``), the
    default — every aot_get/observed path then skips all disk logic.
    Idempotent per directory; re-configuring rebinds the registry and
    byte cap in place so hit/miss counters survive. Called by the
    Trainer, the serve engine, and the tune calibrator on construction.
    """
    global _DISK, _VERIFY
    if cache_dir is None:
        cache_dir = str(getattr(cfg, "compile_cache_dir", "") or "")
    cache_dir = cache_dir or os.environ.get(
        "CROSSCODER_COMPILE_CACHE_DIR", "")
    if verify is None:
        verify = str(getattr(cfg, "compile_cache_verify", "off") or "off")
    if max_bytes is None:
        max_bytes = int(getattr(cfg, "compile_cache_max_bytes", 1 << 30))
    with _LOCK:
        _VERIFY = verify
        if not cache_dir:
            _DISK = None
            return None
        root = Path(cache_dir)
        if _DISK is not None and _DISK.root == root / f"v{DISK_FORMAT}":
            _DISK.max_bytes = int(max_bytes)
            if registry is not None:
                _DISK.registry = registry
            return _DISK
        try:
            _DISK = DiskCache(root, max_bytes=int(max_bytes),
                              registry=registry)
        except OSError as e:
            print(f"[crosscoder_tpu] compile cache: disk tier disabled "
                  f"({cache_dir!r} not usable: {e})",
                  file=sys.stderr, flush=True)
            _DISK = None
        return _DISK


def disk_enabled() -> bool:
    return _DISK is not None


def disk_cache() -> DiskCache | None:
    """The active disk tier, or ``None`` when off — the trainer's remesh
    prewarm stores target-topology entries through it directly."""
    return _DISK


def disk_entry_count() -> int:
    """Number of persisted executables in the active tier (0 when off)."""
    if _DISK is None:
        return 0
    try:
        return sum(1 for _ in _DISK.root.glob("*.exec"))
    except OSError:
        return 0


def _aval_sig(args: Any) -> tuple:
    """Shape/dtype/sharding signature of a call's argument tree — the
    part of an :func:`observed` disk key that the variant label and mesh
    scope do not already pin. Works on concrete arrays and
    ``ShapeDtypeStruct`` avals alike (the prewarm path keys abstractly,
    the live path concretely, and the two must collide)."""
    import jax

    sig = []
    for a in jax.tree_util.tree_leaves(args):
        shard = getattr(a, "sharding", None)
        try:
            s = _canon(shard) if shard is not None else ""
        except _Uncacheable:
            s = ""
        sig.append((tuple(getattr(a, "shape", ())),
                    str(getattr(a, "dtype", "")), s))
    return tuple(sig)


def observed_digest(key: str, disk_scope: Any, example_args: Any) -> str | None:
    """The disk digest an :func:`observed` wrapper for ``(key,
    disk_scope)`` called with ``example_args`` resolves to. The remesh
    prewarm computes this with abstract avals for the TARGET mesh and
    stores under it, so the post-rebuild first step's lookup — same
    label, same scope, equivalent avals — hits the prewarmed entry."""
    return disk_key(("observed", key, disk_scope, _aval_sig(example_args)))


def disk_stats() -> dict[str, int]:
    """Hit/miss/eviction counters of the active disk tier (zeros when
    off) — the bench compile_cache leg and warm-start smoke read these."""
    if _DISK is None:
        return {"disk_hit": 0, "disk_miss": 0, "evictions": 0}
    with _DISK._lock:
        return dict(_DISK.stats)


def _settle_from_disk(key: Any, dk: str | None, exe: Any) -> None:
    """After a disk hit: prime the cost tables from the sidecar so
    cost_of()/collectives_of() answer without touching the executable;
    fall back to lazy settling when no sidecar survived."""
    side = _DISK.cost(dk) if (_DISK is not None and dk) else None
    with _LOCK:
        if key not in _COST_CACHE:
            if side is not None:
                _COST_CACHE[key] = {
                    "flops": float(side.get("flops", 0.0) or 0.0),
                    "bytes_accessed":
                        float(side.get("bytes_accessed", 0.0) or 0.0)}
                if isinstance(side.get("collectives"), dict):
                    _COLLECTIVES[key] = side["collectives"]
            else:
                _COST_PENDING[key] = exe


def _variant_hint(key: Any) -> str:
    """Human-readable manifest label for a memo key."""
    if isinstance(key, tuple) and key and isinstance(key[0], str):
        return key[0]
    return str(key)[:80]


def _disk_acquire(dk: str | None, build: Callable[[], Any], *,
                  lower: Callable[[], Any] | None = None,
                  variant: str = "", topology: str = "",
                  span: Callable[[str], Any] | None = None):
    """The disk-tier acquisition protocol shared by :func:`aot_get` and
    :class:`_ObservedJit`: load → (claim → build+store | wait → load) →
    live build. Returns ``(executable, source)`` with source
    ``"disk" | "build"``. ``span(source)`` (optional) wraps the
    expensive part so the ``compile`` span's source attribute is
    honest."""
    disk = _DISK

    def _run(src: str, fn: Callable[[], Any]) -> Any:
        if span is not None:
            with span(src):
                return fn()
        return fn()

    if disk is None or dk is None:
        return _run("build", build), "build"
    exe = _run("disk", lambda: disk.load(dk, lower=lower, verify=_VERIFY))
    if exe is not None:
        return exe, "disk"
    if disk.claim(dk):
        try:
            exe = _run("build", build)
            disk.store(dk, exe, variant=variant, topology=topology,
                       lower=lower)
            return exe, "build"
        finally:
            disk.release(dk)
    exe = disk.wait_for(dk, lower=lower, verify=_VERIFY)
    if exe is not None:
        return exe, "disk"
    return _run("build", build), "build"    # leader died: compile, no store


# ---------------------------------------------------------------------------
# AOT memo


def aot_get(key: Any, build: Callable[[], Any],
            on_build: Callable[[Any], None] | None = None, *,
            on_load: Callable[[Any], None] | None = None,
            lower: Callable[[], Any] | None = None,
            topology: str = "") -> Any:
    """Process-wide memo of AOT-compiled executables, with an optional
    persistent tier underneath (:func:`configure`).

    ``build()`` must return ``jit_fn.lower(*args).compile()`` for the
    variant ``key`` describes (shapes/dtypes/shardings/statics — the
    caller owns key completeness). Dispatching through the returned
    executable skips the jit call path's tracing/cache machinery — the
    host-cost half of the refill engine's batched dispatch
    (docs/SCALING.md "Zero-bubble refill") — and keeps the donation and
    shardings of the jit it was lowered from: the compiled program is
    byte-identical to what the implicit jit call would have run.

    Thread-safe: the memo is a bounded LRU under a lock, and concurrent
    callers of the same key coalesce onto ONE build (the others block on
    it) — the serve engine's concurrent warmup and the trainer's remesh
    prewarm both hammer this from worker threads.

    ``on_build(key)`` fires only when ``build()`` actually ran — a true
    compile, neither a memo hit nor a disk-tier deserialize. The serve
    engine counts misses through it to assert its
    zero-compiles-after-warmup SLO (docs/SERVING.md): a steady-state
    request that eats a compile is a bucket-ladder bug, not a latency
    outlier. ``on_load(key)`` fires on a disk-tier hit. ``lower()``
    (optional, returns the lowered-but-uncompiled program) enables the
    strict-mode re-verify of disk entries; ``topology`` labels the
    manifest row.
    """
    with _LOCK:
        if key in _AOT_CACHE:
            _AOT_CACHE.move_to_end(key)
            return _AOT_CACHE[key]
        fl = _INFLIGHT.get(key)
        owner = fl is None
        if owner:
            fl = _INFLIGHT[key] = _Inflight()
    if not owner:
        return fl.wait()
    try:
        dk = disk_key(key) if _DISK is not None else None
        exe, src = _disk_acquire(dk, build, lower=lower,
                                 variant=_variant_hint(key),
                                 topology=topology)
    except BaseException as e:
        fl.error = e
        with _LOCK:
            _INFLIGHT.pop(key, None)
        fl.event.set()
        raise
    with _LOCK:
        _AOT_CACHE[key] = exe
        if src == "build":
            _COST_PENDING[key] = exe      # cost_of() settles on demand
        _evict_memo_locked()
        _INFLIGHT.pop(key, None)
    fl.value = exe
    fl.event.set()
    if src == "disk":
        _settle_from_disk(key, dk, exe)
        if on_load is not None:
            on_load(key)
    elif on_build is not None:
        on_build(key)
    return exe


def contracts_check(key: str, lowered: Any) -> None:
    """``CROSSCODER_CONTRACTS`` runtime hook: re-run the textual HLO
    contracts (no-f64, no-host-transfer; ``hlo_rules.check_compiled_text``)
    against the program actually being compiled, not just the variants the
    offline sweep lowers. Off (unset/empty): a single env read, nothing
    imported. ``1``: findings print to stderr. ``strict``: findings raise.
    """
    mode = os.environ.get("CROSSCODER_CONTRACTS", "")
    if mode not in ("1", "strict"):
        return
    try:
        from crosscoder_tpu.analysis.contracts.hlo_rules import \
            check_compiled_text
        findings = check_compiled_text(key, lowered.as_text())
    except Exception as e:  # noqa: BLE001 — the hook must not break compiles
        print(f"[crosscoder_tpu] contracts: runtime check of {key} "
              f"unavailable ({type(e).__name__}: {e})",
              file=sys.stderr, flush=True)
        return
    for f in findings:
        print(f"[crosscoder_tpu] contracts: {f}", file=sys.stderr, flush=True)
    if findings and mode == "strict":
        raise RuntimeError(
            f"CROSSCODER_CONTRACTS=strict: {len(findings)} contract "
            f"violation(s) in compiled program {key!r} (see stderr)")


class _ObservedJit:
    """A jitted callable whose FIRST call resolves the executable —
    from the disk tier when an entry exists (``compile`` span with
    ``source=disk``), else an explicit lower+compile (``source=build``;
    timed, spanned, reported, and persisted when the tier is on); later
    calls hit the compiled executable directly. The build path compiles
    the exact program ``jax.jit`` would have compiled implicitly on that
    same call — same donation, same shardings, same HLO — it only makes
    the compile event *visible*.

    ``obs`` may be ``None`` (disk tier on, observability off): spans go
    through the process-global tracer hook (a no-op by default) and no
    compile event is reported, but the disk tier still serves/saves.
    Any failure in the AOT/report path degrades to calling the wrapped
    jit directly: observability must never be able to break training.
    """

    def __init__(self, jit_fn: Any, key: str, obs: Any, *,
                 disk_scope: Any = None) -> None:
        self._jit_fn = jit_fn
        self._key = key
        self._obs = obs
        self._disk_scope = disk_scope
        self._compiled: Any | None = None

    def __call__(self, *args: Any):
        if self._compiled is not None:
            return self._compiled(*args)
        obs, key = self._obs, self._key
        tracer = obs.tracer if obs is not None else trace
        t0 = time.perf_counter()
        dk = None
        if _DISK is not None and self._disk_scope is not None:
            dk = observed_digest(key, self._disk_scope, args)
        box: dict[str, Any] = {}

        def lower_live():
            if "lowered" not in box:
                box["lowered"] = self._jit_fn.lower(*args)
            return box["lowered"]

        def build():
            return lower_live().compile()

        try:
            exe, src = _disk_acquire(
                dk, build, lower=lower_live, variant=key,
                topology=str(self._disk_scope or ""),
                span=lambda s: tracer.span("compile", variant=key, source=s))
        except Exception as e:
            print(f"[crosscoder_tpu] obs: AOT compile of {key} failed "
                  f"({type(e).__name__}: {e}); falling back to implicit "
                  f"jit compilation (event unreported)",
                  file=sys.stderr, flush=True)
            self._compiled = self._jit_fn
            return self._compiled(*args)
        # outside the try: in strict mode a contract violation must fail
        # the step, not degrade to implicit compilation
        if "lowered" in box:
            contracts_check(key, box["lowered"])
        if src == "build" and obs is not None:
            obs.on_compile(key, exe, time.perf_counter() - t0)
        elif src == "build":
            with _LOCK:
                _COST_PENDING[key] = exe
        else:
            _settle_from_disk(key, dk, exe)
        self._compiled = exe
        return exe(*args)


def observed(jit_fn: Any, key: str, obs: Any, *,
             disk_scope: Any = None) -> _ObservedJit:
    """Wrap a jitted function for compile-event reporting under the
    observability plane (``obs`` is a
    :class:`crosscoder_tpu.obs.Observability`, or ``None`` when only
    the disk tier wants the wrap). ``disk_scope`` scopes the persistent
    key — the trainer passes ``(mesh topology, step-knob projection
    hash)`` so a remeshed or re-knobbed run can never collide with this
    one's entries."""
    return _ObservedJit(jit_fn, key, obs, disk_scope=disk_scope)
