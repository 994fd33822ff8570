"""End-to-end training entry point (the reference's ``train.py:main``).

``python scripts/train.py --flags`` (or ``python -m crosscoder_tpu.train.main``)
wires the whole stack: config from CLI (the reference's CLI path is dead
code — ``run_training.sh:4`` forwards ``"$@"`` but ``train.py`` never
parses argv; here flags work) → model pair + tokens → paired-activation
buffer → mesh-sharded Trainer → versioned checkpoints.

Reference flow being reproduced (``train.py:43-62``):
load Gemma-2-2B base + IT → load token corpus → cfg with ``d_in`` injected
from the model → ``Trainer(cfg, ...).train()``. Plus what it lacks:
``--data-source synthetic`` trains the full skeleton with no LM in the loop
(SURVEY.md §7 "minimum end-to-end slice"), and ``--resume true`` continues
from the latest checkpoint (full TrainState + data stream).
"""

from __future__ import annotations

import sys
from typing import Any, Sequence

from crosscoder_tpu.checkpoint.ckpt import Checkpointer
from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.parallel import mesh as mesh_lib
from crosscoder_tpu.train.trainer import Trainer
from crosscoder_tpu.utils.logging import MetricsLogger


def build_buffer(
    cfg: CrossCoderConfig, mesh, chaos: Any | None = None
) -> tuple[Any, CrossCoderConfig]:
    """Data source per ``cfg.data_source``; returns (buffer, cfg) with
    ``d_in`` injected from the loaded model (reference train.py:38-40)."""
    if cfg.data_source == "synthetic":
        from crosscoder_tpu.data.synthetic import SyntheticActivationSource

        return SyntheticActivationSource(cfg), cfg

    from jax.sharding import NamedSharding, PartitionSpec as P

    from crosscoder_tpu.data.buffer import make_buffer
    from crosscoder_tpu.data.tokens import load_pile_lmsys_mixed_tokens
    from crosscoder_tpu.models import lm

    names: Sequence[str] = cfg.model_names or (
        f"google/{cfg.model_name}",
        f"google/{cfg.model_name}-it",   # base vs instruction-tuned pair (train.py:45-55)
    )
    if len(names) != cfg.n_models:
        raise ValueError(f"{len(names)} model names for n_models={cfg.n_models}")
    lm_cfg = lm.config_for(names[0])
    lm_shardings = None
    if cfg.shard_lm:
        if int(mesh.shape.get("model", 1)) < 2:
            raise ValueError(
                "--shard-lm true needs a model mesh axis >= 2 "
                "(--model-axis-size); a 1-wide axis shards nothing"
            )
        # leaves go straight into their tensor-parallel shards during
        # conversion — the full model never lands on one device
        lm_shardings = lm.tp_shardings(mesh, cfg=lm_cfg)
    params_list = [lm.from_hf(n, lm_cfg, shardings=lm_shardings)[0] for n in names]
    cfg = cfg.replace(d_in=lm_cfg.d_model)
    tokens = load_pile_lmsys_mixed_tokens(cfg)
    buffer = make_buffer(
        cfg, lm_cfg, params_list, tokens,
        batch_sharding=NamedSharding(mesh, P("data", None)),
        lazy=cfg.resume,   # resume restores calibration + refills once, in restore()
        chaos=chaos,       # harvest-level fault injection (None in production)
    )
    return buffer, cfg


def main(argv: list[str] | None = None) -> Any:
    from crosscoder_tpu.parallel import multihost
    from crosscoder_tpu.utils import compile_cache

    compile_cache.enable()   # warm restarts/resumes skip recompiles

    distributed = multihost.initialize()   # no-op single-process
    cfg = CrossCoderConfig.from_cli(argv)
    if cfg.tuned:
        # from_cli already applied the artifact's knobs (docs/TUNING.md);
        # announce WHICH artifact pinned this run's knobs so logs are
        # attributable to a search
        print(f"[crosscoder_tpu] tuned: running with pinned artifact "
              f"{cfg.tuned}", file=sys.stderr)
    mesh = mesh_lib.mesh_from_cfg(cfg)
    if distributed:
        print(f"[crosscoder_tpu] multihost: {multihost.process_info()}", file=sys.stderr)
    # fault injection (cfg.chaos / CROSSCODER_CHAOS env): None unless a
    # chaos spec was explicitly configured — production runs construct no
    # chaos objects and every hook site stays a no-op is-None check
    from crosscoder_tpu.resilience.chaos import Chaos

    chaos = Chaos.from_cfg_env(cfg)
    if chaos is not None:
        import os

        print(f"[crosscoder_tpu] CHAOS ENABLED: "
              f"{(cfg.chaos or os.environ.get('CROSSCODER_CHAOS', ''))!r}",
              flush=True, file=sys.stderr)
    buffer, cfg = build_buffer(cfg, mesh, chaos=chaos)
    if cfg.fleet == "on":
        # fleet mode: N tenants in lockstep off the one buffer; the
        # scheduler owns per-tenant checkpointers under
        # <checkpoint_dir>/tenants/<name>/ (docs/RUNBOOK.md §7)
        from crosscoder_tpu.obs.registry import MetricsRegistry
        from crosscoder_tpu.train.fleet import FleetScheduler

        fleet = FleetScheduler(
            cfg, buffer=buffer, mesh=mesh,
            logger=MetricsLogger(cfg) if multihost.is_primary() else None,
            registry=MetricsRegistry(),
        )
        try:
            if cfg.resume:
                restored = fleet.restore_all()
                print(f"[crosscoder_tpu] fleet resumed: {restored}",
                      file=sys.stderr)
            fleet.run()
        finally:
            fleet.quiesce()
            if hasattr(buffer, "close"):
                buffer.close()
        return fleet
    trainer = Trainer(
        cfg, buffer, mesh=mesh,
        # logging is a process-0 singleton; the checkpointer exists on every
        # process (restore must run SPMD on all hosts or params diverge) and
        # gates its writes on the primary itself
        logger=MetricsLogger(cfg) if multihost.is_primary() else None,
        checkpointer=Checkpointer(cfg=cfg, chaos=chaos),
        chaos=chaos,
    )
    try:
        if cfg.resume:
            meta = trainer.restore()
            print(f"[crosscoder_tpu] resumed at step {meta['step']}", file=sys.stderr)
        trainer.train()
    finally:
        # train() closes on its own exits, but a restore() failure — or an
        # exception before the loop ever starts — must still release the
        # worker threads (prefetch pool, the buffer's refill dispatcher)
        # and land background writes; close() is idempotent
        trainer.close()
    return trainer


if __name__ == "__main__":
    main()
