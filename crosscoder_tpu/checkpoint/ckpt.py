"""Versioned checkpointing with full train-state resume.

Directory layout mirrors the reference's auto-versioned scheme so tooling
that walks reference checkpoints finds the same shape (reference
``crosscoder.py:132-158``): a ``checkpoints/version_N/`` directory per run
(N = 1 + max existing, scanned from disk), holding per-save artifacts
``{v}_cfg.json`` plus weights. Two deliberate upgrades over the reference:

- **Weights artifact** is ``{v}.npz`` (named arrays, fp32) instead of a
  pickled torch state_dict; :mod:`crosscoder_tpu.checkpoint.torch_compat`
  converts to/from the reference's ``.pt`` layout (same tensor names and
  axis order) for interop with its published HF checkpoints.
- **Full resume**: ``{v}_train_state.npz`` carries every optimizer leaf +
  step counter, and ``{v}_meta.json`` the data-pipeline state. The reference
  saves weights only — "training cannot resume" (SURVEY.md §5); here
  ``Checkpointer.restore`` rebuilds the exact TrainState.

Restore rebuilds the pytree by flattening a freshly-initialized state with
the same cfg/optimizer and pairing leaves BY PYTREE PATH (keys like
``.params['W_enc']`` in the npz) — no pickled treedefs, so checkpoints stay
readable across refactors, and a changed/reordered optimizer chain fails
loudly on a missing path instead of silently loading moments into the
wrong slots. Old positional (``leaf_i``) saves still load.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys
import threading
from pathlib import Path
from typing import Any

import jax
import numpy as np

from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.obs import trace


def _sha256_file(path: Path) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _fsync_dir(path: Path) -> None:
    """fsync a directory so a just-completed ``os.replace`` rename is
    durable (file-content fsync alone does not persist the directory
    entry). Each artifact's rename is synced before the next begins, so
    the meta marker's durability implies its predecessors' — a power loss
    can never leave meta on disk without the weights it vouches for."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _atomic_savez(path: Path, arrays: dict[str, np.ndarray]) -> str:
    """npz write that becomes visible all-or-nothing: stream into a
    ``.tmp`` sibling, fsync, ``os.replace`` (atomic on POSIX), fsync the
    directory. A process killed mid-write leaves only the tmp file, which
    every reader path (``latest_save``/``restore``) ignores; the fsyncs
    extend the guarantee to power loss, and cost nothing on the critical
    path now that writes ride the background thread.

    Returns the artifact's SHA-256 (hashed from the tmp file before the
    rename — np.savez's zip writer seeks back to patch headers, so a
    write-through tee hash would record stale header bytes). The meta
    marker records these digests; verified restore checks them."""
    tmp = path.with_name(path.name + ".tmp")
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
        f.flush()
        os.fsync(f.fileno())
    digest = _sha256_file(tmp)
    os.replace(tmp, path)
    _fsync_dir(path.parent)
    return digest


def _atomic_write_text(path: Path, text: str) -> str:
    """Atomic sibling of :func:`_atomic_savez` for the JSON artifacts — the
    meta file is the save's completion marker, so it especially must never
    exist half-written (or durable ahead of the files it marks). Returns
    the text's SHA-256."""
    tmp = path.with_name(path.name + ".tmp")
    with open(tmp, "w") as f:
        f.write(text)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _fsync_dir(path.parent)
    return hashlib.sha256(text.encode()).hexdigest()


class Checkpointer:
    def __init__(
        self,
        base_dir: str | Path | None = None,
        cfg: CrossCoderConfig | None = None,
        chaos: Any | None = None,
        counters: Any | None = None,
        tenant: str | None = None,
    ) -> None:
        if base_dir is None:
            base_dir = cfg.checkpoint_dir if cfg is not None else "./checkpoints"
        if tenant is not None:
            # fleet namespacing (train/fleet.py): each tenant's saves live
            # under <base>/tenants/<name>/ with their OWN version_* dirs,
            # so keep-last-k retention (`_prune_saves`, scoped to one
            # version dir) counts and prunes PER TENANT — a 4-tenant fleet
            # with keep_saves=3 keeps 3 saves per tenant, never reaping a
            # sibling's. A shared flat dir would interleave all tenants'
            # monotone save numbers and retention would reap globally.
            if not tenant or "/" in tenant or tenant in (".", ".."):
                raise ValueError(f"invalid tenant name {tenant!r}")
            base_dir = Path(base_dir) / "tenants" / tenant
        self.tenant = tenant
        self.base_dir = Path(base_dir)
        self.save_dir: Path | None = None
        self.save_version = 0
        # fault-injection hook (resilience/chaos.py): corrupts a just-
        # written save's artifacts when the chaos plan says so; None (the
        # default and every production path) is never called
        self.chaos = chaos
        # resilience/* metric channel (utils.logging.ResilienceCounters);
        # restore bumps corrupt_artifact_skips when a save fails checksum
        # verification. The Trainer shares its own instance in here.
        self.counters = counters
        # background-write state (save(background=True)): one writer thread
        # at a time; wait() joins it and re-raises any write failure
        self._writer: threading.Thread | None = None
        self._writer_error: BaseException | None = None

    def _bump(self, name: str, n: int = 1) -> None:
        if self.counters is not None:
            self.counters.bump(name, n)

    def wait(self, raise_error: bool = True) -> None:
        """Block until any in-flight background write has finished; raises
        the write's exception here if it failed (``raise_error=False``
        joins only — ``save`` uses it so a failure surfaces AFTER the
        collective state fetch, keeping collective entry symmetric across
        hosts)."""
        if self._writer is not None:
            self._writer.join()
            self._writer = None
        if raise_error and self._writer_error is not None:
            err, self._writer_error = self._writer_error, None
            raise err

    # --- directory management (reference crosscoder.py:132-145 semantics) ---
    def _create_save_dir(self) -> None:
        self.base_dir.mkdir(parents=True, exist_ok=True)
        versions = [
            int(p.name.split("_")[1])
            for p in self.base_dir.iterdir()
            if p.is_dir() and p.name.startswith("version_") and p.name.split("_")[1].isdigit()
        ]
        next_v = 1 + max(versions) if versions else 0
        self.save_dir = self.base_dir / f"version_{next_v}"
        self.save_dir.mkdir(parents=True)

    @staticmethod
    def _fetch_global(leaf: Any) -> np.ndarray:
        """Leaf → host numpy the caller OWNS, safe on a multi-host mesh.

        ``np.asarray`` on a sharded ``jax.Array`` whose shards live on
        other processes' devices raises (the leaf is not fully
        addressable); those leaves are assembled with a
        ``process_allgather`` — a COLLECTIVE, so every process must reach
        this call (``Trainer.save`` runs save on all processes and gates
        only the file writes). Single-process arrays take the cheap path.

        The ownership copy is load-bearing for background saves: on the
        CPU backend ``np.asarray(jax.Array)`` can be a ZERO-COPY view of
        the device buffer, and the train step DONATES its state — XLA
        reuses that memory for later steps, so a background writer
        serializing the view records a LATER step's bytes under this
        save's meta (observed live: ``train_state`` at step 10 under
        ``meta["step"] == 5``, with a NaN step in between — a silently
        poisoned checkpoint that the divergence guard's finite-params
        fallback caught). Device→host copies (TPU) already own their
        data, so the guard costs nothing there.
        """
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(leaf, tiled=True))
        out = np.asarray(leaf)
        if isinstance(out, np.ndarray) and not out.flags.owndata:
            out = out.copy()
        return out

    @classmethod
    def _flatten(cls, tree: Any) -> dict[str, np.ndarray]:
        # leaves are keyed by their PYTREE PATH, not position: a reordering
        # of optax's internal state fields then fails loudly on restore
        # (path mismatch) instead of silently loading moments into params
        paths = jax.tree_util.tree_flatten_with_path(tree)[0]
        return {jax.tree_util.keystr(p): cls._fetch_global(leaf) for p, leaf in paths}

    # --- save ---------------------------------------------------------------
    def save(
        self,
        state: Any,
        cfg: CrossCoderConfig,
        buffer: Any | None = None,
        background: bool = False,
    ) -> Path | None:
        """Write one versioned save; returns the weights path, or ``None``
        on a non-primary process (which never touches the filesystem, so
        there is no real path to hand back).

        EVERY process must call this on a multi-host mesh (the state fetch
        is collective); only process 0 touches the filesystem.

        ``background=True`` overlaps the file write with training: the
        device→host fetch (the part that must see a consistent state)
        stays synchronous, then a single writer thread streams the ~GBs to
        disk while the step loop resumes — at production shape (dict 2^16,
        fp32 masters) the write is most of the save, so periodic saves
        stop stalling steps and the SIGTERM preemption window shrinks to
        the fetch. Writes are serialized (a new save waits for the
        previous write) and atomic (tmp + ``os.replace``, meta last, so a
        kill mid-write never leaves a torn save that ``restore`` could
        read). Call :meth:`wait` (Trainer.close does) before process exit.

        Telemetry (docs/OBSERVABILITY.md; no-ops without a tracer): the
        ``save`` span brackets the loop-blocking portion (previous-write
        join + collective fetch), ``save_write`` the file write — on the
        writer thread for background saves, so the trace shows exactly how
        much of each save overlapped training.
        """
        with trace.span("save", version=self.save_version,
                        background=background):
            return self._save_impl(state, cfg, buffer, background)

    def _save_impl(
        self,
        state: Any,
        cfg: CrossCoderConfig,
        buffer: Any | None,
        background: bool,
    ) -> Path | None:
        # collective fetches first, identical order on all processes; each
        # leaf crosses the network ONCE — the weights artifact reuses the
        # same fetched arrays via an identity cache (no reliance on how
        # keystr renders the params field, which is not a stable API)
        fetched: dict[int, np.ndarray] = {}

        def fetch(leaf):
            out = fetched.get(id(leaf))
            if out is None:
                out = self._fetch_global(leaf)
                fetched[id(leaf)] = out
            return out

        # serialize with any in-flight background write BEFORE fetching —
        # but do NOT raise a previous write failure yet: the fetch below is
        # a COLLECTIVE on a multi-host mesh, and only the writing process
        # carries the error; raising before the fetch would leave every
        # other host parked in process_allgather (asymmetric entry)
        self.wait(raise_error=False)

        pathed = jax.tree_util.tree_flatten_with_path(state)[0]
        flat_state = {jax.tree_util.keystr(p): fetch(leaf) for p, leaf in pathed}
        weights = {k: fetch(x).astype(np.float32) for k, x in state.params.items()}
        # collectives done — a stashed write failure can surface safely now
        if self._writer_error is not None:
            err, self._writer_error = self._writer_error, None
            raise err
        primary = jax.process_index() == 0
        if self.save_dir is None and primary:
            self._create_save_dir()
        v = self.save_version
        meta = {
            "step": int(state.step),
            "save_version": v,
            "format": "crosscoder_tpu/v1",
        }
        if buffer is not None and hasattr(buffer, "state_dict"):
            meta["buffer"] = buffer.state_dict()
        if primary:
            save_dir = self.save_dir

            def write() -> None:
                # per-artifact SHA-256, recorded in the meta marker so
                # restore can prove the bytes it reads are the bytes that
                # were written (bit-rot / partial-page corruption slips
                # past the presence-only torn-save check). The save_write
                # span lands on whichever thread runs the write — the
                # writer thread for background saves, so the trace shows
                # the write overlapping subsequent steps.
                with trace.span("save_write", version=v):
                    sums = {
                        f"{v}.npz": _atomic_savez(save_dir / f"{v}.npz", weights),
                        f"{v}_cfg.json": _atomic_write_text(
                            save_dir / f"{v}_cfg.json", cfg.to_json_str()
                        ),
                        f"{v}_train_state.npz": _atomic_savez(
                            save_dir / f"{v}_train_state.npz", flat_state
                        ),
                    }
                    meta["checksums"] = sums
                    # meta LAST: its presence marks the save complete —
                    # latest_save keys off it, so a torn save is unreadable
                    _atomic_write_text(
                        save_dir / f"{v}_meta.json", json.dumps(meta, indent=2)
                    )
                    self._prune_saves(save_dir, cfg.keep_saves)
                    if self.chaos is not None:
                        self.chaos.corrupt_save(save_dir, v)
                    print(f"Saved as version {v} in {save_dir}", file=sys.stderr)

            if background:
                def guarded() -> None:
                    try:
                        write()
                    except BaseException as e:  # surfaced by the next wait()
                        self._writer_error = e

                self._writer = threading.Thread(
                    target=guarded, name="ckpt-writer", daemon=False
                )
                self._writer.start()
            else:
                write()
        self.save_version += 1
        if self.save_dir is None:
            return None
        return self.save_dir / f"{v}.npz"

    @classmethod
    def _prune_saves(cls, save_dir: Path, keep: int) -> None:
        """Keep-last-k retention: delete all but the newest ``keep``
        COMPLETE saves of this version dir (``keep <= 0`` = unbounded,
        the pre-retention behavior). Runs on the writer, after the new
        save's meta lands — the newly-written save always survives. The
        meta marker is unlinked FIRST so a crash mid-prune leaves a torn
        (invisible) save, never a meta vouching for deleted artifacts."""
        if keep <= 0:
            return
        for old in cls.complete_saves(save_dir)[:-keep]:
            for name in (f"{old}_meta.json", f"{old}.npz",
                         f"{old}_train_state.npz", f"{old}_cfg.json"):
                (save_dir / name).unlink(missing_ok=True)

    def discard_saves_after(self, version_dir: str | Path, v: int) -> None:
        """Branch truncation for rollback: delete every complete save
        NEWER than ``v`` in this version dir. After a divergence rollback
        the run continues from ``v`` on a new trajectory; the stale newer
        saves (possibly carrying the poisoned state the rollback escaped)
        must not be what a later auto-resume picks. Meta is unlinked first
        (same torn-not-corrupt ordering as retention pruning); only the
        writing process touches the filesystem."""
        if jax.process_index() != 0:
            return
        vdir = Path(version_dir)
        for s in self.complete_saves(vdir):
            if s > v:
                for name in (f"{s}_meta.json", f"{s}.npz",
                             f"{s}_train_state.npz", f"{s}_cfg.json"):
                    (vdir / name).unlink(missing_ok=True)

    # --- load/restore -------------------------------------------------------
    @staticmethod
    def _version_dirs(base_dir: str | Path) -> list[Path]:
        base = Path(base_dir)
        return [
            p for _, p in sorted(
                (int(p.name.split("_")[1]), p)
                for p in base.iterdir()
                if p.is_dir() and p.name.startswith("version_")
                and p.name.split("_")[1].isdigit()
            )
        ]

    @classmethod
    def latest_version_dir(cls, base_dir: str | Path) -> Path:
        versions = cls._version_dirs(base_dir)
        if not versions:
            raise FileNotFoundError(f"no version_* dirs under {base_dir}")
        return versions[-1]

    @staticmethod
    def complete_saves(version_dir: str | Path) -> list[int]:
        """Saves whose meta (written LAST, atomically) exists — the only
        ones ``restore`` will touch; a save torn mid-write has no meta."""
        return sorted(
            int(p.name.split("_")[0])
            for p in Path(version_dir).glob("*_meta.json")
            if p.name.split("_")[0].isdigit()
        )

    @classmethod
    def _latest_resumable_dir(cls, base_dir: str | Path) -> Path:
        """Newest version dir holding at least one COMPLETE save. A fresh
        run preempted during its very first save leaves a version dir with
        only torn artifacts — auto-resume must fall back to the previous
        run's dir, not crash on the torn one."""
        versions = cls._version_dirs(base_dir)
        for vdir in reversed(versions):
            if cls.complete_saves(vdir):
                return vdir
        raise FileNotFoundError(
            f"no version dir under {base_dir} holds a complete "
            "(meta-marked) save"
        )

    @classmethod
    def verify_save(cls, version_dir: str | Path, v: int) -> bool:
        """Integrity check of one complete save: every artifact the meta
        marker vouches for exists and matches its recorded SHA-256. Saves
        from before the checksum era (no ``checksums`` key) are trusted,
        as are hand-assembled weights-only dirs (no meta at all is handled
        by the caller — this method is only meaningful for meta-marked
        saves). An unreadable/undecodable meta counts as corrupt."""
        vdir = Path(version_dir)
        try:
            meta = json.loads((vdir / f"{v}_meta.json").read_text())
        except (OSError, json.JSONDecodeError, UnicodeDecodeError):
            return False
        sums = meta.get("checksums")
        if not sums:
            return True     # pre-checksum save: presence is all we have
        for name, want in sums.items():
            path = vdir / name
            if not path.exists() or _sha256_file(path) != want:
                return False
        return True

    def _select_verified(self, version_dir: str | Path | None) -> tuple[Path, int]:
        """Newest save that passes :meth:`verify_save`, searching the given
        version dir (or, when None, every version dir newest-first). Saves
        failing verification are skipped — counted in
        ``resilience/corrupt_artifact_skips`` — and the search falls back
        to the previous complete save, then to earlier version dirs; the
        keep-last-k retention policy (``cfg.keep_saves``) is what keeps
        this fallback chain non-empty without unbounded disk."""
        if version_dir is not None:
            dirs = [Path(version_dir)]
            if not self.complete_saves(dirs[0]):
                raise FileNotFoundError(
                    f"no complete (meta-marked) save under {dirs[0]}; "
                    "saves torn mid-write are not resumable"
                )
        else:
            dirs = [d for d in reversed(self._version_dirs(self.base_dir))
                    if self.complete_saves(d)]
            if not dirs:
                raise FileNotFoundError(
                    f"no version dir under {self.base_dir} holds a complete "
                    "(meta-marked) save"
                )
        for vdir in dirs:
            for v in reversed(self.complete_saves(vdir)):
                if self.verify_save(vdir, v):
                    return vdir, v
                self._bump("corrupt_artifact_skips")
                print(f"[crosscoder_tpu] checkpoint save {v} in {vdir} "
                      f"failed checksum verification; falling back to the "
                      f"previous intact save", flush=True, file=sys.stderr)
        raise FileNotFoundError(
            f"no complete save under {dirs} passed checksum verification"
        )

    @classmethod
    def latest_save(cls, version_dir: str | Path) -> int:
        # key off the meta file — it is written LAST (atomically), so its
        # presence proves the whole save landed; globbing *.npz would pick
        # a save whose train_state/meta a mid-save kill never wrote
        saves = cls.complete_saves(version_dir)
        if not saves:
            vdir = Path(version_dir)
            # hand-assembled WEIGHTS-ONLY dirs (converted foreign
            # checkpoints for the analysis path) carry npz + cfg but
            # neither meta nor train_state. Anything else meta-less is a
            # torn save — train_state present (killed before meta), or
            # weights without their cfg (killed before cfg; load_weights
            # needs the cfg, so no usable foreign dir lacks it).
            if list(vdir.glob("*_train_state.npz")):
                raise FileNotFoundError(
                    f"only torn (meta-less) saves under {version_dir}"
                )
            saves = [
                int(p.stem)
                for p in vdir.glob("*.npz")
                if p.stem.isdigit() and (vdir / f"{p.stem}_cfg.json").exists()
            ]
        if not saves:
            raise FileNotFoundError(f"no saves under {version_dir}")
        return max(saves)

    @classmethod
    def load_weights(
        cls, version_dir: str | Path, save: int | None = None
    ) -> tuple[dict[str, jax.Array], CrossCoderConfig]:
        """Load crosscoder weights + cfg (analysis path; mirrors reference
        ``CrossCoder.load``, crosscoder.py:207-217)."""
        vdir = Path(version_dir)
        v = cls.latest_save(vdir) if save is None else save
        cfg = CrossCoderConfig.from_json(vdir / f"{v}_cfg.json")
        with np.load(vdir / f"{v}.npz") as z:
            # the added zero forces XLA-owned buffers (see restore(): a
            # zero-copy alias of the npz's numpy memory must not leak
            # into device state that downstream code may donate)
            params = {
                k: (lambda a: a + jax.numpy.zeros((), a.dtype))(
                    jax.numpy.asarray(z[k])
                )
                for k in z.files
            }
        return params, cfg

    def restore(
        self, cfg: CrossCoderConfig, tx: Any, version_dir: str | Path | None = None, save: int | None = None,
        n_data: int | None = None,
    ) -> tuple[Any, dict]:
        """Rebuild the full TrainState (+ pipeline meta) for resume.

        Auto-selection (``save=None``) only ever touches COMPLETE saves —
        a save (or whole fresh-run dir) torn by a mid-write kill is
        skipped — and additionally VERIFIES each candidate's per-artifact
        checksums, falling back past corrupted saves (and whole version
        dirs) to the newest intact one. On a multi-process mesh the
        chosen save is agreed across hosts (allgather-min, so a host
        whose local filesystem view is ahead rolls back with the rest);
        an explicitly requested ``save`` is the caller's agreement and is
        verified but not negotiated — corruption there raises.

        RESTORE-WITH-RESPEC: ``n_data`` is the data-axis width of the mesh
        the state is being restored ONTO (default: cfg-derived). A
        checkpoint written under a different mesh restores fine — the
        TrainState is layout-free on disk and the caller re-derives
        shardings — except the quant_grads error-feedback residuals, whose
        SHAPE is a mesh property; those reset to zero when the layouts
        disagree (see ``_restore_impl``). This is the elastic re-mesh
        path's restore (docs/resilience.md) and also covers deliberate
        topology changes between runs (e.g. TP-only → DP×TP)."""
        with trace.span("restore"):
            return self._restore_impl(cfg, tx, version_dir, save, n_data)

    def _restore_impl(
        self, cfg: CrossCoderConfig, tx: Any,
        version_dir: str | Path | None, save: int | None,
        n_data: int | None = None,
    ) -> tuple[Any, dict]:
        from crosscoder_tpu.train.state import init_train_state

        self.wait()  # a background write from THIS instance must land first

        if save is None:
            vdir, v = self._select_verified(version_dir)
            if jax.process_count() > 1:
                # all processes must rebuild the SAME state: agree on the
                # minimum (version dir, save id) — ties to the most
                # conservative host, so a shared-FS lag or host-local
                # corruption pulls every process back together instead of
                # leaving hosts resuming from different steps. The dir is
                # negotiated FIRST (bare save ids are only comparable
                # within one dir); an explicitly passed version_dir is
                # already the callers' agreement and only the save id is
                # negotiated. The agreed save is re-verified locally — a
                # host that cannot produce those bytes must fail loudly,
                # not load unverified artifacts.
                from jax.experimental import multihost_utils

                def _agree_min(x: int) -> int:
                    return int(multihost_utils.process_allgather(
                        np.array([x], np.int32)
                    ).min())

                if version_dir is None:
                    vnum = int(vdir.name.split("_")[1])
                    agreed_dir = _agree_min(vnum)
                    if agreed_dir != vnum:
                        vdir = self.base_dir / f"version_{agreed_dir}"
                        # newest locally-verified save of the agreed dir
                        vdir, v = self._select_verified(vdir)
                agreed = _agree_min(v)
                if agreed != v:
                    print(f"[crosscoder_tpu] multihost restore agreement: "
                          f"local save {v} -> agreed save {agreed}", flush=True, file=sys.stderr)
                    v = agreed
                    if not self.verify_save(vdir, v):
                        raise ValueError(
                            f"multihost-agreed save {v} under {vdir} is "
                            "missing or fails checksum verification on this "
                            "host; refusing to load unverified state"
                        )
        else:
            vdir = Path(version_dir) if version_dir else self._latest_resumable_dir(self.base_dir)
            v = save
            if not self.verify_save(vdir, v):
                self._bump("corrupt_artifact_skips")
                raise ValueError(
                    f"checkpoint save {v} under {vdir} failed checksum "
                    "verification (corrupt or truncated artifact)"
                )
        # shapes, dtypes and paths only: a concrete template would be one
        # more full TrainState on the device while the checkpoint's lands
        template = jax.eval_shape(
            lambda key: init_train_state(key, cfg, tx, n_data=n_data),
            jax.random.key(cfg.seed))
        pathed, treedef = jax.tree_util.tree_flatten_with_path(template)
        with np.load(vdir / f"{v}_train_state.npz") as z:
            positional = all(k.startswith("leaf_") for k in z.files)
            # Respec across mesh layouts: the quant_grads error-feedback
            # residuals are the ONE state piece whose SHAPE is a mesh
            # property ([n_data, ...]; absent entirely when n_data == 1), so
            # a checkpoint from a different mesh may carry extra, missing,
            # or differently-shaped quant_ef leaves. Those RESET to the
            # template's zero init — error feedback is a compression
            # residual, and resetting costs one step of re-accumulated
            # quantization error, not correctness. Every other leaf stays
            # strict. Positional (leaf_i) layouts predate path keys and
            # cannot identify quant_ef leaves, so they keep the strict
            # contract.
            def _is_ef(key: str) -> bool:
                return not positional and "quant_ef" in key

            tkeys = [
                f"leaf_{i}" if positional else jax.tree_util.keystr(path)
                for i, (path, _) in enumerate(pathed)
            ]
            if (sum(1 for k in tkeys if not _is_ef(k))
                    != sum(1 for k in z.files if not _is_ef(k))):
                raise ValueError(
                    f"checkpoint has {len(z.files)} leaves but state expects {len(pathed)}; "
                    "optimizer chain or model shape changed since save"
                )
            dropped = [k for k in z.files if _is_ef(k) and k not in tkeys]
            respec_resets = list(dropped)
            loaded = []
            for key, (path, leaf) in zip(tkeys, pathed):
                if key not in z.files:
                    if _is_ef(key):
                        respec_resets.append(key)
                        loaded.append(jax.numpy.zeros(leaf.shape, leaf.dtype))
                        continue
                    raise ValueError(
                        f"checkpoint is missing state leaf {key!r}; optimizer "
                        "chain changed since save (leaves are path-keyed)"
                    )
                raw = z[key]
                want = np.dtype(leaf.dtype)
                # npz stores extension dtypes (bf16 and friends from
                # ml_dtypes) as raw void bytes; reinterpret against the
                # template's dtype — without this, bf16-master checkpoints
                # save fine but cannot restore ("No cast function available")
                if (raw.dtype.kind == "V" and raw.dtype != want
                        and raw.dtype.itemsize == want.itemsize):
                    raw = raw.view(want)
                if _is_ef(key) and raw.shape != leaf.shape:
                    respec_resets.append(key)
                    loaded.append(jax.numpy.zeros(leaf.shape, leaf.dtype))
                    continue
                arr = jax.numpy.asarray(raw, dtype=leaf.dtype)
                # force an XLA-OWNED buffer: on the CPU backend
                # jnp.asarray can ZERO-COPY the numpy buffer, and a state
                # whose leaves alias numpy memory is later DONATED by the
                # train step — observed as flaky segfaults / NaN'd state
                # when training resumes after a mid-run restore (the
                # compile cache perturbs allocator timing enough to
                # surface it). The added zero runs an actual program, so
                # the result lives in memory XLA allocated and may free.
                loaded.append(arr + jax.numpy.zeros((), arr.dtype))
            if respec_resets:
                print(f"[crosscoder_tpu] restore-with-respec: reset "
                      f"{len(respec_resets)} quant_ef leaf(s) to zero init "
                      f"(checkpoint mesh layout differs from target)",
                      flush=True, file=sys.stderr)
        for (path, b), a in zip(pathed, loaded):
            if a.shape != b.shape:
                raise ValueError(
                    f"leaf {jax.tree_util.keystr(path)}: checkpoint shape "
                    f"{a.shape} != expected {b.shape}"
                )
        state = jax.tree_util.tree_unflatten(treedef, loaded)
        meta = json.loads((vdir / f"{v}_meta.json").read_text())
        # continue versioning in the same dir, after the restored save
        self.save_dir = vdir
        self.save_version = v + 1
        return state, meta
