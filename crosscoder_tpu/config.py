"""Typed configuration for the TPU crosscoder framework.

The reference configures everything through a flat 24-key Python dict edited in
source (reference ``train.py:8-41``; its README says "I just set the cfg by
editing the code") and serializes that dict as JSON next to every checkpoint
(reference ``crosscoder.py:151-155``), making the cfg-JSON the de-facto schema.

Here the config is a typed dataclass that

- keeps the exact reference key names so published checkpoint cfg JSONs load
  unchanged (``seed`` ... ``hook_point``; see ``from_dict``),
- adds the TPU-native keys the reference lacks (``n_models`` generalized from
  the hardcoded 2 at reference ``crosscoder.py:32``; mesh axes; sparse-encode
  activation options for the Pallas kernels; multi-layer hook lists),
- round-trips unknown keys (``extras``) so foreign cfg JSONs survive
  load→save, and
- has a real CLI reflector (the reference ships one at ``utils.py:151-178``
  but never calls it, so ``run_training.sh``'s ``"$@"`` is silently dropped).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

from crosscoder_tpu.utils.dtypes import DTYPES


def _check_choice(field_name: str, value: Any,
                  choices: tuple[str, ...]) -> None:
    """Membership check for a string mode knob, with a difflib typo
    hint — every choice knob validates through here so the error shape
    lives in one place instead of a copy per knob."""
    if value in choices:
        return
    import difflib

    close = difflib.get_close_matches(str(value), choices, n=1)
    hint = f"; did you mean {close[0]!r}?" if close else ""
    raise ValueError(
        f"{field_name} must be {'|'.join(choices)}, got {value!r}{hint}"
    )

# dtype strings follow the reference's DTYPES table (reference crosscoder.py:12)
DTYPE_NAMES = tuple(DTYPES)

_ACTIVATIONS = ("relu", "topk", "jumprelu", "batchtopk")


@dataclass
class CrossCoderConfig:
    """Full training/analysis configuration.

    Field names and defaults mirror the reference dict (reference
    ``train.py:13-35``) so that parity runs and published cfg JSONs are
    drop-in; TPU-native additions are grouped at the bottom.
    """

    # --- reference keys (train.py:13-35), same names and defaults ---
    seed: int = 49
    batch_size: int = 4096          # activation rows per optimizer step
    buffer_mult: int = 128          # replay buffer = batch_size * buffer_mult rows
    lr: float = 5e-5
    num_tokens: int = 400_000_000   # total training token budget
    l1_coeff: float = 2.0           # weight on the decoder-norm-weighted L1
    beta1: float = 0.9
    beta2: float = 0.999
    dict_size: int = 2 ** 14        # crosscoder latent count (d_hidden)
    seq_len: int = 1024
    enc_dtype: str = "bf16"         # compute dtype of encode/decode
    model_name: str = "gemma-2-2b"
    site: str = "resid_pre"
    device: str = "tpu"             # kept for cfg-JSON compat; placement is mesh-driven
    model_batch_size: int = 4       # sequences per harvest forward
    log_every: int = 100
    save_every: int = 30000
    dec_init_norm: float = 0.08
    hook_point: str = "blocks.14.hook_resid_pre"
    wandb_project: str = ""
    wandb_entity: str = ""
    d_in: int = 2304                # residual stream width (gemma-2-2b d_model)

    # --- TPU-native extensions (no reference counterpart) ---
    n_models: int = 2               # reference hardcodes 2 (crosscoder.py:32)
    hook_points: tuple[str, ...] = ()   # multi-layer crosscoder: several hooks per model
    activation: str = "relu"        # relu | topk | jumprelu | batchtopk
    topk_k: int = 32                # k for (batch)topk activation. NB
                                    # batchtopk keeps ALL entries tied at
                                    # the global threshold, so its
                                    # effective L0 can exceed k·batch when
                                    # bf16 pre-acts tie there (topk proper
                                    # breaks ties by index and keeps
                                    # exactly k per row)
    factored_decode: str = "auto"   # topk + Pallas tier: decode FORWARD
                                    # through the k active rows (sparsify
                                    # kernel + the rows). "auto" is
                                    # resolved when a step is traced
                                    # (models.crosscoder.rows_live): on
                                    # where the rows are fetched by DMA
                                    # (ops/row_gather.py: a TPU backend
                                    # with ONE device, bf16 rows, no AuxK
                                    # consumer of the pre-acts on that
                                    # step, a supported shape), and
                                    # elsewhere from dict >= 2^17 (XLA's
                                    # take against the dense matmul: -8 ms
                                    # at 2^17, +6 ms at 2^16); "on"/"off"
                                    # force. Requires l1_coeff == 0 (see
                                    # models.crosscoder._factored_topk_forward)
    sparse_bwd: str = "auto"        # topk factored tier: replace the dense
                                    # backward matmuls (dW_dec, df, dW_enc)
                                    # with O(B·k) sums over rows fetched by
                                    # DMA (ops/row_gather.py; docs/SCALING.md
                                    # "Sparse backward plane"). "auto" = on
                                    # where the row kernels are live (the
                                    # factored tier then runs on them too:
                                    # all four k-sparse products, or none);
                                    # "on" forces
                                    # (also forces the factored tier; where
                                    # the kernels are not live it is the
                                    # XLA scatter: sound, slow, for CPU
                                    # parity and A/Bs); "off" never.
                                    # Requires l1_coeff == 0 (the factored
                                    # tier's soundness gate).
    jumprelu_theta: float = 0.001   # initial JumpReLU threshold
    jumprelu_bandwidth: float = 0.001  # STE bandwidth for the threshold gradient
    l0_coeff: float = 0.0           # jumprelu only: coefficient on the
                                    # rectangle-kernel-STE L0 penalty (the
                                    # JumpReLU paper's sparsity objective);
                                    # combine with l1_coeff=0 for pure-L0
                                    # training
    aux_k: int = 0                  # >0: AuxK dead-latent mitigation (the
                                    # standard TopK-SAE recipe, Gao et al.
                                    # 2024): an auxiliary loss reconstructs
                                    # the main reconstruction's residual
                                    # with the top aux_k DEAD latents
                                    # (steps_since_fired >= aux_dead_steps),
                                    # giving dead latents a gradient path
                                    # back to life. Typical: 2-16x topk_k.
    aux_k_coeff: float = 1.0 / 32.0  # weight on the (residual-normalized)
                                    # aux loss; 1/32 is the Gao et al.
                                    # default. Measured (ACT_QUALITY_r04):
                                    # at 10k steps the default holds eval
                                    # L2 but leaves dead fraction flat; a
                                    # concentrated setting (aux_k=2k,
                                    # coeff 0.25) cut dead latents
                                    # 85%->73% at slightly BETTER eval L2
                                    # — turn it up when revival matters.
    aux_dead_steps: int = 500       # a latent is "dead" after this many
                                    # consecutive steps without firing
                                    # (500 steps x batch 4096 ≈ 2M rows)
    aux_exact_rank: bool = False    # rank dead latents with exact top_k
                                    # instead of approx_max_k. Slow (the
                                    # exact [B,H] sort costs more than the
                                    # rest of the step at dict 2^15) —
                                    # engine-parity runs only, where the
                                    # torch oracle's exact ranking must
                                    # select identical aux latents
    aux_every: int = 1              # run the aux ranking+decode every Nth
                                    # step (fired-tracking stays per-step,
                                    # so deadness is always current). The
                                    # full aux path costs 2.2-2.7x a plain
                                    # TopK step (BENCH_r04 matrix); N
                                    # amortizes that to ~(N-1+2.7)/N — at
                                    # N=8, ~1.2x. 1 = the per-step Gao
                                    # et al. recipe. Quality under
                                    # amortization: artifacts/
                                    # ACT_QUALITY_r05.json.
    resample_every: int = 0         # >0: dead-latent RESAMPLING every Nth
                                    # step (Bricken et al. 2023's neuron
                                    # resampling, the alternative to AuxK):
                                    # dead latents' decoder rows re-init
                                    # from high-residual batch examples,
                                    # encoder rows aligned and downscaled,
                                    # b_enc zeroed, Adam moments reset.
                                    # Deadness = steps_since_fired >=
                                    # resample_dead_steps. Composes with
                                    # aux_k (either or both).
    resample_dead_steps: int = 0    # deadness threshold for resampling;
                                    # 0 = inherit aux_dead_steps
    resample_enc_scale: float = 0.2  # revived encoder norm as a fraction
                                    # of the mean ALIVE encoder norm.
                                    # 0.2 is the Bricken et al. SAE rule
                                    # (fire weakly, adapt gently) — but
                                    # under TopK a downscaled encoder can
                                    # never WIN the top-k selection race,
                                    # so revived latents cycle
                                    # resample→die→resample (measured:
                                    # ACT_QUALITY_r05 resample_30k, dead
                                    # 86% unchanged); 1.0 gives revived
                                    # latents full competitive scale
    batchtopk_threshold: float = 0.0   # >0: batchtopk EVAL mode — a fixed
                                    # global threshold (from
                                    # crosscoder.calibrate_batchtopk_threshold)
                                    # so per-example activations don't
                                    # depend on batch composition; 0 =
                                    # per-batch k·B-th threshold (training)
    data_axis_size: int = -1        # -1: all remaining devices on the data axis
    model_axis_size: int = 1        # tensor-parallel shards of the dict axis
    shard_sources: bool = False     # EP-style: shard the SOURCE axis
                                    # (n_models × n_hooked_layers) over the
                                    # 'model' mesh axis instead of the dict
                                    # axis — for many-model/many-layer diffs;
                                    # n_sources must divide by model_axis_size
    buffer_device: str = "host"     # replay store placement: host RAM (big
                                    # buffers, multi-host, analysis reads)
                                    # | "hbm": zero host↔device row traffic
                                    # — the reference's own placement
                                    # (buffer.py:18-22); on a multi-chip
                                    # mesh the store shards over the data
                                    # axis and serves batches pre-sharded
    shard_lm: bool = False          # tensor-parallel harvest: load/keep the
                                    # subject LMs' weights sharded over the
                                    # 'model' mesh axis (lm.tp_shardings) —
                                    # for pairs too big for one chip's HBM
                                    # (e.g. Gemma-2-9B, BASELINE config 3)
    seq_shards: int = 0             # >0: harvest forwards shard the SEQUENCE
                                    # axis over the mesh data axis (ring
                                    # attention), for contexts too long for
                                    # one chip; must equal the data-axis size
                                    # and divide seq_len. 0 = batch-sharded
                                    # harvest (default).
    harvest_runtime: str = "padded"  # LM-harvest forward runtime:
                                    # "padded" (default — every document
                                    # padded to seq_len, the reference's
                                    # layout, byte-identical to builds
                                    # without this knob) | "paged" — the
                                    # ragged/paged runtime (data/paging.py
                                    # + ops/paged_attention.py): mixed-
                                    # length documents pack into a dense
                                    # token plane (projections/MLP cost
                                    # proportional to REAL tokens), with
                                    # per-document ragged attention over
                                    # fixed-size KV pages. Bit-identical
                                    # hook activations to the padded path
                                    # at valid positions; pad positions
                                    # are emitted zeroed under an explicit
                                    # valid-length mask. docs/SCALING.md
                                    # "Harvest cost model".
    page_size: int = 64             # paged runtime: tokens per KV page
                                    # (the attention kernel's DMA/compute
                                    # quantum). Power of two dividing
                                    # seq_len; page-table overhead is
                                    # 4·seq_len/page_size bytes/sequence.
    grad_clip: float = 1.0          # reference hardcodes this (trainer.py:46)
    lr_decay_frac: float = 0.2      # linear lr decay over the last fraction (trainer.py:29-32)
    l1_warmup_frac: float = 0.05    # l1 warmup over the first fraction (trainer.py:36)
    norm_calib_batches: int = 100   # batches for norm calibration (buffer.py:45)
    refill_frac: float = 0.5        # buffer fraction re-harvested per refill
                                    # cycle. 0.5 = reference parity (1:1
                                    # harvest:serve, buffer.py:70-74). Lower
                                    # = each harvested row is served
                                    # ~0.5/refill_frac times — harvest is
                                    # ~2.4x the train step's FLOPs/row on
                                    # TPU, so 0.25 raises end-to-end
                                    # throughput ~1.4x at the cost of
                                    # fresher-data churn.
    checkpoint_dir: str = "./checkpoints"
    data_dir: str = "./data"
    dataset_name: str = "ckkissane/pile-lmsys-mix-1m-tokenized-gemma-2"
    log_backend: str = "auto"       # auto | wandb | jsonl | null
    profile_dir: str = ""           # non-empty: write jax.profiler traces here
    remat: bool = False             # jax.checkpoint the encode for memory;
                                    # the backward then re-runs it (incl.
                                    # the Pallas TopK kernel — measured
                                    # ~1.44x step time at topk dict 2^16
                                    # on v5e for roughly halved activation
                                    # memory)
    data_source: str = "gemma"      # gemma (paired-LM harvest) | synthetic
    model_names: tuple[str, ...] = ()  # HF ids to diff; default: (google/<model_name>, +"-it")
    resume: bool = False            # resume from the latest checkpoint version
    prefetch: bool = True           # overlap host batch gather with the device step
    refill_overlap: str = "off"     # off | on: zero-bubble refill engine
                                    # (docs/SCALING.md "Zero-bubble
                                    # refill"). "on" harvests refill
                                    # cycles into spare store rows while
                                    # the live rows serve (a logical→
                                    # physical row map swaps at cycle
                                    # boundaries — no data copy) and
                                    # batches/offloads the harvest
                                    # dispatch quanta; the served batch
                                    # stream stays byte-identical. Costs
                                    # ×(1 + refill_frac) store memory.
    refill_dispatch_batch: int = 4  # refill_overlap="on" only: harvest
                                    # dispatch quanta issued per Python
                                    # dispatch (one wide sub-scan program
                                    # instead of N narrow ones) — divides
                                    # the per-dispatch host cost by this
                                    # factor (not measured on a chip).
    stop_poll_every: int = 20       # multi-process only: steps between
                                    # allgathered stop-flag polls (the
                                    # SIGTERM coordinated stop). Each poll
                                    # is a host-blocking cross-host
                                    # collective, so per-step polling
                                    # would defeat async dispatch; 20
                                    # bounds the stop latency at ~20 steps
                                    # while costing <5% of steps a sync.
    # --- resilience (crosscoder_tpu/resilience; docs/resilience.md) ---
    guard_loss: bool = False        # divergence guard: at log_every
                                    # granularity (piggybacking the log
                                    # step's existing loss fetch — the
                                    # fast path gains NO host sync),
                                    # non-finite or spiking loss triggers
                                    # rollback to the last intact save +
                                    # skip of the poisoned data window
    loss_spike_factor: float = 10.0  # loss > factor × last healthy logged
                                    # loss counts as divergence
    max_rollbacks: int = 3          # rollbacks per train() before the
                                    # guard aborts loudly (a fault that
                                    # reproduces past the skipped window
                                    # is a bug, not a transient)
    keep_saves: int = 0             # >0: keep only the last k COMPLETE
                                    # saves per version dir (the retention
                                    # policy verified restore's fallback
                                    # assumes); 0 = unbounded (reference-
                                    # compatible). k >= 2 recommended so a
                                    # corrupt newest save has an intact
                                    # predecessor.
    harvest_timeout_s: float = 0.0  # >0: watchdog on the serve/harvest
                                    # path — escalating-patience stall
                                    # detection + exponential-backoff
                                    # retry of exceptions (resilience/
                                    # watchdog.py). 0 = off (default).
    harvest_retries: int = 3        # watchdog retry/extension budget
    harvest_backoff_s: float = 0.5  # base of the exponential retry backoff
    elastic: str = "off"            # off | on: elastic multihost membership
                                    # (resilience/elastic.py). "on" adds a
                                    # bounded liveness barrier at the
                                    # stop_poll_every cadence; when a peer
                                    # host dies mid-run the surviving
                                    # coordinator quiesces in-flight work,
                                    # re-meshes over its local devices
                                    # (mesh epoch +1), and resumes from the
                                    # newest verified save via restore-
                                    # with-respec. ZERO-COST off: the
                                    # compiled step is byte-identical
                                    # (hlo-elastic-off-identity).
    elastic_heartbeat_s: float = 1.0  # elastic="on": coordination-service
                                    # heartbeat interval (service + client)
                                    # — how fast a dead host is NOTICED;
                                    # detection fires after ~3 missed beats
    elastic_grace_s: float = 5.0    # elastic="on": bounded wait of each
                                    # liveness barrier — a peer slower than
                                    # this at a poll point is declared lost
                                    # (the slow-host SLO; >= heartbeat)
    elastic_suspect_probes: int = 2 # elastic="on": consecutive failed
                                    # liveness probes before peer loss is
                                    # DECLARED. Misses below the threshold
                                    # are absorbed (resilience/
                                    # elastic_suspects counter) so a flaky
                                    # or slow host triggers hysteresis, not
                                    # a remesh; torn-collective
                                    # confirmation stays immediate (a dead
                                    # peer mid-program is not a flake)
    elastic_grow: str = "off"       # off | on (requires elastic="on"):
                                    # scale back UP. The shrunk survivor
                                    # polls a filesystem rendezvous board
                                    # (<checkpoint_dir>/elastic_board) for
                                    # returned hosts, admits the debounced
                                    # set at a poll boundary (mesh epoch
                                    # +1), writes a boundary save both
                                    # sides restore, and re-forms the wider
                                    # world (docs/resilience.md "Elastic
                                    # scale-up"). ZERO-COST off: compiled
                                    # step byte-identical
                                    # (hlo-elastic-grow-off-identity)
    elastic_dwell_steps: int = 2    # elastic_grow="on": minimum steps the
                                    # current mesh epoch must dwell before
                                    # the next grow re-mesh — remesh-rate
                                    # hysteresis so flapping hosts cannot
                                    # thrash shrink/grow cycles
    elastic_grow_debounce: int = 2  # elastic_grow="on": consecutive polls
                                    # a rejoin candidate must stay FRESH on
                                    # the board (announce seq advancing)
                                    # before admission — a host that flaps
                                    # away mid-courtship is dropped, not
                                    # admitted
    elastic_policy: str = "fixed"   # fixed | score: mesh-shape policy on a
                                    # membership change (resilience/
                                    # fleet.py). fixed preserves
                                    # model_axis_size (TP width) and gives
                                    # the data axis every device; score
                                    # ranks candidate (data, model) splits
                                    # by the comm_model wire-byte model +
                                    # compiled-HLO cost analysis
    # --- multi-tenant fleet (train/fleet.py; docs/SCALING.md "Fleet
    # amortization"). Off by default and ZERO-COST off: none of these
    # knobs is read inside the compiled step, so the step lowering is
    # byte-identical to a build without them (contracts rule
    # hlo-fleet-off-identity).
    fleet: str = "off"              # off | on: run N crosscoder tenants
                                    # off ONE shared replay buffer — one
                                    # harvest stream, one serve gather per
                                    # cycle fanned out to every admitted
                                    # tenant, so the LM forward amortizes
                                    # across the whole sweep
    fleet_tenants: str = ""         # fleet="on" CLI sweep spec:
                                    # ';'-separated "name:k=v,k=v" tenant
                                    # overrides applied to the base config
                                    # (e.g. "a:seed=1;b:seed=2,l1_coeff=
                                    # 0.02;big:dict_size=65536"). seed/
                                    # l1_coeff-only variations stack under
                                    # one vmapped step; shape-changing
                                    # overrides compile into buckets
    fleet_max_buckets: int = 8      # fleet="on": cap on DISTINCT compiled
                                    # step signatures across heterogeneous
                                    # tenants (stacked cohorts count one) —
                                    # admission beyond the cap is refused
                                    # rather than compiling unboundedly
    # --- online serving (crosscoder_tpu/serve; docs/SERVING.md). Off by
    # default and ZERO-COST off: none of these knobs is read inside the
    # compiled train step, so the step lowering is byte-identical to a
    # build without them (contracts rule hlo-serve-off-identity).
    serve: str = "off"              # off | on: the online model-diffing
                                    # request path (serve/engine.py): token
                                    # streams admitted via ContinuousBatcher
                                    # into paged LM harvest slots,
                                    # encoder→TopK on the captured hooks,
                                    # per-request top-k latents + decoder-
                                    # norm diff scores returned — only
                                    # [B, k] ever leaves the device
    serve_max_batch: int = 8        # serve="on": micro-batch cap — the
                                    # largest AOT-prewarmed batch bucket;
                                    # power of two <= 128 so the bucket
                                    # ladder stays <= 8 compiled shapes
    serve_max_wait_ms: float = 5.0  # serve="on": deadline of the oldest
                                    # admitted request before a partial
                                    # plane flushes (flush on batch-full OR
                                    # this timer — deadline-aware
                                    # micro-batching)
    serve_queue: int = 64           # serve="on": bounded admission queue;
                                    # submits beyond it shed (429-style,
                                    # serve/shed_total) instead of growing
                                    # the queue unboundedly
    serve_shed_ms: float = 0.0      # serve="on", > 0: max queue wait —
                                    # queued requests older than this are
                                    # evicted (counted in serve/shed_total)
                                    # before a full queue sheds new arrivals
    # --- block-scaled int8 data plane (ops/quant.py; docs/SCALING.md
    # "Quantized data plane"). Both off by default and ZERO-COST off: the
    # compiled train step and the serve/refill paths are byte-identical to
    # a build without these fields (asserted in tests/test_quant.py).
    quant_buffer: bool = False      # replay store in block-scaled int8 +
                                    # f32 scales instead of bf16: ~0.51x
                                    # store bytes at quant_block=256, refill
                                    # chunks quantized at harvest time so
                                    # host↔device / ICI refill traffic
                                    # halves; the serve path dequantizes in
                                    # the same fused gather, so the trainer
                                    # still receives bf16 rows
    quant_grads: bool = False       # EQuARX-style quantized gradient
                                    # all-reduce under pure data
                                    # parallelism: per-device grads are
                                    # block-scaled int8 through an
                                    # all-to-all + all-gather pair (~2x
                                    # less grad-sync wire traffic than the
                                    # bf16 psum) with per-device error
                                    # feedback carried in TrainState.aux
                                    # ("quant_ef") so the compression bias
                                    # cancels across steps
    quant_block: int = 256          # elements per int8 scale block (the
                                    # last-axis granularity). Must divide
                                    # d_in when quant_buffer is on; store
                                    # overhead is 4/quant_block bytes/elem
    # --- observability (crosscoder_tpu/obs; docs/OBSERVABILITY.md) ---
    # Everything off by default and ZERO-COST off: with obs="off" the
    # compiled train step does not depend on these knobs, nothing is
    # constructed and no additional host↔device transfer happens anywhere
    # (asserted in tests/test_obs.py).
    obs: str = "off"                # "on": ONE telemetry plane for the job,
                                    # created by whichever of make_buffer
                                    # and Trainer runs first: span tracer on
                                    # time.perf_counter (Chrome trace-event
                                    # JSON under obs_dir, Perfetto-viewable,
                                    # host spans wrapped in jax.profiler
                                    # TraceAnnotations; set-up included),
                                    # perf/* + comm/* metrics in the log
                                    # stream — span time as per-log-interval
                                    # totals perf/span/<name>_s|_n with
                                    # perf/interval_s|_steps, and
                                    # perf/refill_bubble_frac — compile-event
                                    # reporting, SIGUSR1 profiler windows
    obs_dir: str = ""               # telemetry output dir; default
                                    # <checkpoint_dir>/obs (trace.json,
                                    # profile/ windows)
    profile_steps: str = ""         # "start:stop": capture a jax.profiler
                                    # device trace around exactly steps
                                    # [start, stop) — absolute step
                                    # indices; independent of cfg.obs.
                                    # Empty + profile_dir set keeps the
                                    # legacy steps-10..14 window.
    log_print_every: int = 1        # echo every Nth metrics line to
                                    # STDERR (0 = never). The echo left
                                    # stdout so executables owning a
                                    # machine-readable stdout contract
                                    # (bench.py's one-JSON-line) can
                                    # construct a real logger safely.
    # AuxK dead-mask cadence: how often the trainer REFRESHES the dead-
    # latent mask that gates the aux ranking/decode. 1 (default) =
    # recompute every step (the exact Gao et al. recipe — required for
    # engine-parity runs); N > 1 = refresh every N steps and reuse the
    # cached mask between refreshes; 0 = refresh at cfg.log_every cadence.
    # Fired-tracking (steps_since_fired) updates every step regardless, so
    # a refresh always sees current deadness; between refreshes a revived
    # latent keeps its aux gradient for at most one cadence window (the
    # same staleness class as cfg.aux_every amortization, measured within
    # noise — artifacts/ACT_QUALITY_r05.json).
    aux_mask_every: int = 1
    chaos: str = ""                 # fault-injection spec (resilience/
                                    # chaos.py grammar; tests/staging
                                    # only). Empty = no chaos objects
                                    # constructed anywhere.
    tuned: str = ""                 # path to a pinned TUNED.json autotuner
                                    # artifact (docs/TUNING.md). --tuned
                                    # applies its knobs during from_cli
                                    # resolution (after --config-json,
                                    # before explicit flags); the elastic
                                    # controller re-checks it on remesh.
                                    # Empty = no tuner involvement.
    # --- persistent AOT executable cache (docs/SCALING.md "Persistent
    # compile cache"). Empty dir (default) = tier off, ZERO-COST: the
    # compiled step HLO and transfer counts are byte-identical to a
    # build without it (tests/test_compile_cache_disk.py).
    compile_cache_dir: str = ""     # directory for serialized AOT
                                    # executables + cost sidecars; serve
                                    # warmup, elastic remesh/grow, fleet
                                    # admission, and tune calibration
                                    # deserialize instead of compiling
    compile_cache_max_bytes: int = 1 << 30   # byte cap on the disk tier;
                                    # least-recently-used entries evict
                                    # past it (compile/evictions)
    compile_cache_verify: str = "off"   # off | strict: strict re-lowers
                                    # on every disk load and rejects an
                                    # entry whose stored HLO hash differs
                                    # from the live lowering

    # master-weight/Adam-moment dtype. fp32 (default) is a quality upgrade
    # over the reference; "bf16" reproduces the reference exactly (its params
    # AND torch-Adam moments are bf16, train.py:5 + crosscoder.py:30-34) and
    # cuts the optimizer's HBM traffic ~2x.
    master_dtype: str = "fp32"

    # unknown keys from foreign cfg JSONs, preserved on round-trip
    extras: dict[str, Any] = field(default_factory=dict)

    # ------------------------------------------------------------------
    def __post_init__(self) -> None:
        if self.enc_dtype not in DTYPE_NAMES:
            raise ValueError(f"enc_dtype must be one of {DTYPE_NAMES}, got {self.enc_dtype!r}")
        if self.activation not in _ACTIVATIONS:
            raise ValueError(f"activation must be one of {_ACTIVATIONS}, got {self.activation!r}")
        if self.n_models < 1:
            raise ValueError("n_models must be >= 1")
        if isinstance(self.hook_points, list):
            self.hook_points = tuple(self.hook_points)
        if isinstance(self.model_names, list):
            self.model_names = tuple(self.model_names)
        if self.data_source not in ("gemma", "synthetic"):
            raise ValueError(f"data_source must be 'gemma' or 'synthetic', got {self.data_source!r}")
        if self.master_dtype not in ("fp32", "bf16"):
            raise ValueError(f"master_dtype must be fp32 or bf16, got {self.master_dtype!r}")
        if (self.shard_sources and self.model_axis_size > 1
                and self.n_sources % self.model_axis_size != 0):
            raise ValueError(
                f"shard_sources: n_sources {self.n_sources} must divide by "
                f"model_axis_size {self.model_axis_size}"
            )
        # refill_frac is a FRACTION of the buffer: anything outside (0, 1]
        # is meaningless, and anything above 0.5 would let a refill cycle
        # overwrite rows the serve trigger (fixed at the reference's
        # half-buffer point, buffer.py:121) has not yet served
        if not (0.0 < self.refill_frac <= 1.0):
            raise ValueError(
                f"refill_frac must be a buffer fraction in (0, 1], got "
                f"{self.refill_frac}; 0.5 is reference parity (1:1 "
                f"harvest:serve), smaller values re-serve survivors "
                f"~0.5/refill_frac times"
            )
        if self.refill_frac > 0.5:
            raise ValueError(
                f"refill_frac must be <= 0.5 (the serve trigger fires at "
                f"half-buffer, so a larger refill would overwrite unserved "
                f"rows), got {self.refill_frac}; set 0.5 for reference "
                f"parity"
            )
        if self.buffer_device not in ("host", "hbm"):
            raise ValueError(
                f"buffer_device must be 'host' or 'hbm', got {self.buffer_device!r}"
            )
        if self.seq_shards < 0:
            raise ValueError("seq_shards must be >= 0")
        if self.shard_lm and self.model_axis_size < 2:
            raise ValueError(
                "shard_lm needs model_axis_size >= 2 (a 1-wide model axis "
                "shards nothing)"
            )
        if self.shard_lm and self.seq_shards > 1:
            raise ValueError(
                "shard_lm is incompatible with seq_shards: the seq-parallel "
                "harvest replicates LM params (its shard_map in_specs), "
                "which would silently all-gather the TP shards onto every "
                "device — the OOM shard_lm exists to prevent"
            )
        if self.seq_shards > 1 and self.seq_len % self.seq_shards != 0:
            raise ValueError(
                f"seq_shards {self.seq_shards} must divide seq_len {self.seq_len}"
            )
        _check_choice("harvest_runtime", self.harvest_runtime,
                      ("padded", "paged"))
        if self.page_size < 1 or self.page_size & (self.page_size - 1):
            below = 1 << max(0, self.page_size.bit_length() - 1)
            raise ValueError(
                f"page_size must be a power of two (the KV page is the "
                f"attention kernel's DMA/compute quantum), got "
                f"{self.page_size}; try {below} or {2 * below}"
            )
        if self.harvest_runtime == "paged":
            if self.seq_len < self.page_size:
                raise ValueError(
                    f"harvest_runtime='paged': seq_len {self.seq_len} is "
                    f"smaller than page_size {self.page_size} — a document "
                    f"cannot fill even one KV page; lower page_size to a "
                    f"power of two <= {self.seq_len}"
                )
            if self.seq_len % self.page_size != 0:
                divisors = [p for p in (16, 32, 64, 128, 256, 512)
                            if p <= self.seq_len and self.seq_len % p == 0]
                raise ValueError(
                    f"harvest_runtime='paged': page_size {self.page_size} "
                    f"must divide seq_len {self.seq_len} (the KV block "
                    f"layout is whole pages); try one of "
                    f"{divisors or 'a power-of-two divisor of seq_len'}"
                )
            if self.seq_shards > 1:
                raise ValueError(
                    "harvest_runtime='paged' is incompatible with "
                    "seq_shards: the paged plane packs the sequence axis "
                    "densely, while the seq-parallel harvest shards it "
                    "over the mesh — pick one"
                )
        _check_choice("factored_decode", self.factored_decode,
                      ("auto", "on", "off"))
        if self.factored_decode == "on" and self.activation != "topk":
            raise ValueError(
                f"factored_decode='on' requires activation='topk', "
                f"got {self.activation!r}"
            )
        if self.factored_decode == "on" and self.l1_coeff != 0:
            raise ValueError(
                "factored_decode='on' requires l1_coeff=0: the factored "
                "forward's custom VJP carries no gradient path through "
                "(vals, idx), which a nonzero weighted-L1 objective needs"
            )
        _check_choice("sparse_bwd", self.sparse_bwd, ("auto", "on", "off"))
        if self.sparse_bwd == "on" and self.activation != "topk":
            raise ValueError(
                f"sparse_bwd='on' requires activation='topk' (the sparse "
                f"backward consumes the factored (vals, idx) the TopK tier "
                f"produces), got {self.activation!r}"
            )
        if self.sparse_bwd == "on" and self.l1_coeff != 0:
            raise ValueError(
                "sparse_bwd='on' requires l1_coeff=0: like the factored "
                "tier it extends, its custom VJP carries no gradient path "
                "through (vals, idx), which a nonzero weighted-L1 "
                "objective needs"
            )
        if self.l0_coeff > 0 and self.activation != "jumprelu":
            raise ValueError(
                f"l0_coeff requires activation='jumprelu' (the rectangle-"
                f"kernel STE needs a threshold), got {self.activation!r}"
            )
        if self.batchtopk_threshold > 0 and self.activation != "batchtopk":
            raise ValueError(
                f"batchtopk_threshold requires activation='batchtopk', "
                f"got {self.activation!r}"
            )
        if self.aux_k < 0:
            raise ValueError(f"aux_k must be >= 0, got {self.aux_k}")
        if self.aux_k > self.dict_size:
            raise ValueError(
                f"aux_k {self.aux_k} cannot exceed dict_size {self.dict_size}"
            )
        if self.aux_k > 0 and self.aux_dead_steps < 1:
            raise ValueError("aux_dead_steps must be >= 1 when aux_k > 0")
        if self.aux_every < 1:
            raise ValueError(f"aux_every must be >= 1, got {self.aux_every}")
        if self.resample_every < 0 or self.resample_dead_steps < 0:
            raise ValueError(
                f"resample_every/resample_dead_steps must be >= 0, got "
                f"{self.resample_every}/{self.resample_dead_steps}"
            )
        if self.resample_every > 0 and self.resample_threshold_steps < 1:
            raise ValueError(
                "resampling needs a deadness threshold: set "
                "resample_dead_steps (or aux_dead_steps) >= 1"
            )
        if self.stop_poll_every < 1:
            raise ValueError(
                f"stop_poll_every must be >= 1, got {self.stop_poll_every}"
            )
        _check_choice("refill_overlap", self.refill_overlap, ("off", "on"))
        if self.refill_dispatch_batch < 1:
            raise ValueError(
                f"refill_dispatch_batch must be >= 1 (harvest quanta fused "
                f"per dispatch), got {self.refill_dispatch_batch}"
            )
        if self.loss_spike_factor <= 1.0:
            raise ValueError(
                f"loss_spike_factor must be > 1 (it multiplies the last "
                f"healthy loss), got {self.loss_spike_factor}"
            )
        if self.max_rollbacks < 0:
            raise ValueError(f"max_rollbacks must be >= 0, got {self.max_rollbacks}")
        if self.keep_saves < 0:
            raise ValueError(f"keep_saves must be >= 0 (0 = unbounded), got {self.keep_saves}")
        if self.guard_loss and self.keep_saves == 1:
            raise ValueError(
                "guard_loss with keep_saves=1 leaves rollback no fallback "
                "save when the newest is corrupt/poisoned; use keep_saves=0 "
                "(unbounded) or >= 2"
            )
        if self.harvest_timeout_s < 0:
            raise ValueError(f"harvest_timeout_s must be >= 0, got {self.harvest_timeout_s}")
        if self.harvest_retries < 0 or self.harvest_backoff_s < 0:
            raise ValueError(
                f"harvest_retries/harvest_backoff_s must be >= 0, got "
                f"{self.harvest_retries}/{self.harvest_backoff_s}"
            )
        _check_choice("elastic", self.elastic, ("off", "on"))
        if self.elastic == "on":
            if self.elastic_heartbeat_s <= 0:
                raise ValueError(
                    f"elastic_heartbeat_s must be > 0, got "
                    f"{self.elastic_heartbeat_s}"
                )
            if self.elastic_grace_s < self.elastic_heartbeat_s:
                raise ValueError(
                    f"elastic_grace_s ({self.elastic_grace_s}) must be >= "
                    f"elastic_heartbeat_s ({self.elastic_heartbeat_s}): the "
                    f"liveness barrier cannot declare a peer lost faster "
                    f"than the heartbeat can notice it"
                )
            if self.seq_shards > 1:
                raise ValueError(
                    "elastic='on' cannot run with seq_shards > 1: the "
                    "sequence-parallel harvest pins the mesh data axis to "
                    "seq_shards, which a survivor re-mesh cannot preserve"
                )
            if self.elastic_suspect_probes < 1:
                raise ValueError(
                    f"elastic_suspect_probes must be >= 1, got "
                    f"{self.elastic_suspect_probes} (1 = declare on the "
                    f"first failed probe, no hysteresis)"
                )
        _check_choice("elastic_grow", self.elastic_grow, ("off", "on"))
        _check_choice("elastic_policy", self.elastic_policy,
                      ("fixed", "score"))
        if self.elastic_grow == "on":
            if self.elastic != "on":
                raise ValueError(
                    "elastic_grow='on' requires elastic='on': scale-up "
                    "re-forms the world the elastic membership layer owns"
                )
            if not self.checkpoint_dir:
                raise ValueError(
                    "elastic_grow='on' requires checkpoint_dir: the rejoin "
                    "rendezvous board and the admission boundary save both "
                    "live under it (joiners hydrate from that save)"
                )
            if self.elastic_dwell_steps < 0:
                raise ValueError(
                    f"elastic_dwell_steps must be >= 0, got "
                    f"{self.elastic_dwell_steps}"
                )
            if self.elastic_grow_debounce < 1:
                raise ValueError(
                    f"elastic_grow_debounce must be >= 1, got "
                    f"{self.elastic_grow_debounce}"
                )
        _check_choice("fleet", self.fleet, ("off", "on"))
        if self.fleet == "on":
            if self.fleet_max_buckets < 1:
                raise ValueError(
                    f"fleet_max_buckets must be >= 1, got "
                    f"{self.fleet_max_buckets} (each stacked cohort and "
                    f"each heterogeneous tenant signature costs one "
                    f"compile bucket)"
                )
            if self.quant_grads:
                raise ValueError(
                    "fleet='on' is incompatible with quant_grads: the "
                    "stacked (vmapped) tenant step cannot nest the "
                    "shard_map quantized all-reduce; train quantized "
                    "sweeps as sequential solo runs"
                )
        elif self.fleet_tenants:
            raise ValueError(
                "fleet_tenants is set but fleet='off'; pass --fleet on "
                "(the spec would otherwise be silently ignored)"
            )
        _check_choice("serve", self.serve, ("off", "on"))
        if self.serve == "on":
            b = self.serve_max_batch
            if not 1 <= b <= 128 or b & (b - 1):
                raise ValueError(
                    f"serve_max_batch must be a power of two in [1, 128], "
                    f"got {b} (each bucket in the 1..serve_max_batch "
                    f"ladder is one AOT-prewarmed compiled shape; the "
                    f"ladder must stay <= 8 buckets)"
                )
            if self.serve_max_wait_ms < 0:
                raise ValueError(
                    f"serve_max_wait_ms must be >= 0, got "
                    f"{self.serve_max_wait_ms}"
                )
            if self.serve_queue < self.serve_max_batch:
                raise ValueError(
                    f"serve_queue ({self.serve_queue}) must be >= "
                    f"serve_max_batch ({self.serve_max_batch}): the queue "
                    f"must be able to hold at least one full micro-batch"
                )
            if self.serve_shed_ms < 0:
                raise ValueError(
                    f"serve_shed_ms must be >= 0 (0 disables queue-age "
                    f"eviction), got {self.serve_shed_ms}"
                )
        if self.quant_block < 1:
            raise ValueError(
                f"quant_block must be >= 1, got {self.quant_block}; 256 is "
                f"the default (4/256 bytes/element of f32-scale overhead)"
            )
        if self.quant_buffer and self.d_in % self.quant_block != 0:
            divisors = [b for b in (32, 64, 128, 256, 512)
                        if self.d_in % b == 0]
            raise ValueError(
                f"quant_buffer: quant_block {self.quant_block} must divide "
                f"d_in {self.d_in} (scales are per contiguous feature "
                f"block); try one of {divisors or 'a divisor of d_in'}"
            )
        if self.quant_grads and (self.model_axis_size > 1 or self.shard_sources):
            raise ValueError(
                "quant_grads supports pure data parallelism only "
                "(model_axis_size == 1, shard_sources off): the quantized "
                "all-reduce replaces the DP gradient psum; TP/EP grad "
                "slices keep the exact bf16/f32 psum"
            )
        if self.quant_grads and self.activation == "batchtopk":
            raise ValueError(
                "quant_grads is incompatible with activation='batchtopk': "
                "the quantized step computes per-device losses, but "
                "batchtopk's threshold is a GLOBAL-batch order statistic"
            )
        _check_choice("obs", self.obs, ("off", "on"))
        if self.log_print_every < 0:
            raise ValueError(
                f"log_print_every must be >= 0 (0 = never echo), got "
                f"{self.log_print_every}"
            )
        if self.profile_steps:
            from crosscoder_tpu.obs.profiler import parse_profile_steps

            parse_profile_steps(self.profile_steps)   # raises on a bad spec
        if self.aux_mask_every < 0:
            raise ValueError(
                f"aux_mask_every must be >= 0 (1 = per-step exact, N = "
                f"refresh every N steps, 0 = follow log_every), got "
                f"{self.aux_mask_every}"
            )
        _check_choice("compile_cache_verify", self.compile_cache_verify,
                      ("off", "strict"))
        if self.compile_cache_max_bytes <= 0:
            raise ValueError(
                f"compile_cache_max_bytes must be > 0 (the disk tier "
                f"needs a positive byte cap; disable the tier with "
                f"compile_cache_dir='' instead), got "
                f"{self.compile_cache_max_bytes}"
            )
        if self.compile_cache_dir:
            # fail at config time, not mid-warmup: the tier directory
            # must be creatable/writable on this host
            try:
                os.makedirs(self.compile_cache_dir, exist_ok=True)
            except OSError as e:
                raise ValueError(
                    f"compile_cache_dir {self.compile_cache_dir!r} is not "
                    f"creatable ({e}); point it at writable storage or "
                    f"leave it empty to disable the persistent compile "
                    f"cache"
                ) from e

    # --- derived quantities -------------------------------------------------
    @property
    def total_steps(self) -> int:
        """Optimizer steps for the token budget (reference trainer.py:14)."""
        return self.num_tokens // self.batch_size

    @property
    def aux_mask_cadence(self) -> int:
        """Resolved dead-mask refresh cadence in steps (``aux_mask_every``,
        with 0 meaning the ``log_every`` interval)."""
        return self.aux_mask_every if self.aux_mask_every >= 1 else self.log_every

    @property
    def resample_threshold_steps(self) -> int:
        """Deadness threshold for resampling (resample_dead_steps, falling
        back to aux_dead_steps)."""
        return self.resample_dead_steps or self.aux_dead_steps

    @property
    def n_layers_hooked(self) -> int:
        """Number of hook points per model (multi-layer crosscoders)."""
        return max(1, len(self.hook_points))

    @property
    def n_sources(self) -> int:
        """Size of the crosscoder's 'model' axis: models × hooked layers.

        A multi-layer crosscoder over L hook points of M models is represented
        as a single source axis of length M*L, which generalizes the
        reference's hardcoded pair.
        """
        return self.n_models * self.n_layers_hooked

    @property
    def hook_layer(self) -> int:
        """Layer index parsed from ``hook_point`` ('blocks.N.hook_resid_pre')."""
        return parse_hook_point(self.hook_point)[0]

    def resolved_hook_points(self) -> tuple[str, ...]:
        return self.hook_points if self.hook_points else (self.hook_point,)

    # --- (de)serialization --------------------------------------------------
    def to_dict(self) -> dict[str, Any]:
        """Flat JSON-ready dict using the reference's key names."""
        d = dataclasses.asdict(self)
        extras = d.pop("extras")
        d["hook_points"] = list(self.hook_points)
        d.update(extras)
        return d

    def to_json_str(self) -> str:
        """The single serialized form — every cfg JSON writer (to_json, the
        checkpointer's atomic write) goes through this."""
        return json.dumps(self.to_dict(), indent=2)

    def to_json(self, path: str | Path) -> None:
        Path(path).write_text(self.to_json_str())

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "CrossCoderConfig":
        """Build from a flat dict; unknown keys (e.g. from the reference's
        published cfg JSONs) are preserved in ``extras``."""
        known = {f.name for f in dataclasses.fields(cls)} - {"extras"}
        kwargs = {k: v for k, v in d.items() if k in known}
        extras = {k: v for k, v in d.items() if k not in known}
        # published reference cfgs carry e.g. "device": "cuda:1" — keep it in
        # the field for round-trip but it has no effect on placement here.
        return cls(**kwargs, extras=extras)

    @classmethod
    def from_json(cls, path: str | Path) -> "CrossCoderConfig":
        return cls.from_dict(json.loads(Path(path).read_text()))

    def replace(self, **kwargs: Any) -> "CrossCoderConfig":
        return dataclasses.replace(self, **kwargs)

    # --- CLI ----------------------------------------------------------------
    @classmethod
    def from_cli(cls, argv: list[str] | None = None, base: "CrossCoderConfig | None" = None) -> "CrossCoderConfig":
        """Reflect config fields into argparse flags and apply overrides.

        This is the working version of the reference's dead CLI path
        (``utils.py:151-178`` is defined but never called from ``train.py``,
        so ``run_training.sh:4``'s ``"$@"`` is dropped on the floor).
        """
        base = base or cls()
        parser = argparse.ArgumentParser(description="crosscoder_tpu training config")
        parser.add_argument("--config-json", type=str, default=None, help="load a cfg JSON before applying flags")
        for f in dataclasses.fields(cls):
            if f.name == "extras":
                continue
            val = getattr(base, f.name)
            flag = f"--{f.name.replace('_', '-')}"
            if isinstance(val, bool):
                parser.add_argument(flag, type=_parse_bool, default=None)
            elif isinstance(val, tuple):
                parser.add_argument(flag, type=str, default=None, help="comma-separated list")
            elif isinstance(val, int):
                parser.add_argument(flag, type=int, default=None)
            elif isinstance(val, float):
                parser.add_argument(flag, type=float, default=None)
            else:
                parser.add_argument(flag, type=str, default=None)
        ns = parser.parse_args(argv)
        if ns.config_json:
            base = cls.from_json(ns.config_json)
        # tuned-artifact resolution order (docs/TUNING.md): defaults →
        # --config-json → TUNED.json knobs → explicit flags. The artifact
        # sits between the JSON and the flags so an operator can always
        # override a pinned knob from the command line; --tuned "" clears
        # an artifact a config JSON carried.
        tuned_path = ns.tuned if ns.tuned is not None else base.tuned
        if tuned_path:
            from crosscoder_tpu.tune.artifact import apply_tuned

            base = apply_tuned(base, tuned_path)
        elif ns.tuned == "":
            base = base.replace(tuned="")
        overrides: dict[str, Any] = {}
        for f in dataclasses.fields(cls):
            if f.name == "extras":
                continue
            v = getattr(ns, f.name, None)
            if v is not None:
                if isinstance(getattr(base, f.name), tuple):
                    v = tuple(x for x in v.split(",") if x)
                overrides[f.name] = v
        return base.replace(**overrides) if overrides else base


def known_attrs() -> frozenset[str]:
    """Every public name resolvable on a ``CrossCoderConfig`` instance:
    dataclass fields, properties, and methods. The static cfg-field lint
    (analysis/contracts/ast_lints.py) checks every ``cfg.<attr>`` read in
    the codebase against this surface, so a typo'd knob read fails lint
    instead of raising AttributeError three hours into a run."""
    names = {f.name for f in dataclasses.fields(CrossCoderConfig)}
    names.update(n for n in vars(CrossCoderConfig) if not n.startswith("_"))
    return frozenset(names)


def _parse_bool(s: str) -> bool:
    low = s.lower()
    if low in ("1", "true", "yes", "on"):
        return True
    if low in ("0", "false", "no", "off"):
        return False
    raise argparse.ArgumentTypeError(f"expected a boolean, got {s!r}")


def parse_hook_point(hook_point: str) -> tuple[int, str]:
    """Parse 'blocks.{L}.hook_{site}' → (L, site).

    The naming scheme follows the reference's TransformerLens hook strings
    (e.g. 'blocks.14.hook_resid_pre', reference train.py:32) so cfg JSONs and
    analysis code stay interoperable.
    """
    parts = hook_point.split(".")
    if len(parts) != 3 or parts[0] != "blocks" or not parts[2].startswith("hook_"):
        raise ValueError(f"unsupported hook point {hook_point!r}; expected 'blocks.N.hook_<site>'")
    return int(parts[1]), parts[2][len("hook_"):]


def get_default_cfg(d_in: int | None = None, **overrides: Any) -> CrossCoderConfig:
    """Default config, mirroring reference ``get_default_cfg`` (train.py:8-41).

    The reference injects ``d_in`` from the loaded model
    (``cfg["d_in"] = base_model.cfg.d_model``, train.py:38-40); pass it here
    the same way when a model is already loaded.
    """
    cfg = CrossCoderConfig(**overrides)
    if d_in is not None:
        cfg = cfg.replace(d_in=d_in)
    return cfg
