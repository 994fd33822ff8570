#!/usr/bin/env python
"""Replication walkthrough — the reference notebook's acceptance sequence
(nb:cells 13-42) as ONE command, with a pass/fail comparison against the
published values in BASELINE.md:

  checkpoint → 3-cluster relative-norm histogram → shared-latent cosine
  stats → CE-recovered table → feature dashboards

Modes
-----
published checkpoint + real Gemma-2-2B pair (network or warm HF cache):

    python scripts/replicate.py --hf --tokens data/tokens.npy --n-seqs 64 \
        --out artifacts/replicate

a locally-trained checkpoint (decoder-space analysis + dashboards; CE
needs --model-a/--model-b + --norm-factors):

    python scripts/replicate.py --version-dir checkpoints/version_0 --out out

air-gapped (trains the deterministic demo pair + crosscoder, then runs the
same four stages with machine-checked gates):

    python scripts/replicate.py --demo --out artifacts/replicate_demo

Published comparison surface (BASELINE.md): CE recovered 0.921875 (A) /
0.92578125 (B); norm factors 0.2758961 / 0.2442285; 3 visible clusters
with the shared band 0.3 < r < 0.7; shared-latent cosines concentrated
near 1 (log-y histogram, nb:cells 21-22).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

PUBLISHED = {
    "ce_recovered_A": 0.921875,
    "ce_recovered_B": 0.92578125,
    "norm_factor_A": 0.2758961493232058,
    "norm_factor_B": 0.24422852496546169,
}


def decoder_stage(params) -> dict:
    """Stage 1+2: the 3-cluster histogram counts and shared-latent cosine
    stats (reference analysis.py:9-58, nb:cells 13-22)."""
    from crosscoder_tpu.analysis import (
        cosine_sims, relative_norm_histogram, relative_norms, shared_latent_mask,
    )

    r = np.asarray(relative_norms(params))
    shared = np.asarray(shared_latent_mask(params))
    cos = np.asarray(cosine_sims(params))[shared]
    counts, edges = relative_norm_histogram(params)
    return {
        "d_hidden": int(r.shape[0]),
        "cluster_A_only": int((r <= 0.3).sum()),
        "cluster_shared": int(shared.sum()),
        "cluster_B_only": int((r >= 0.7).sum()),
        "three_clusters_present": bool(
            (r <= 0.3).sum() > 0 and shared.sum() > 0 and (r >= 0.7).sum() > 0
        ),
        "shared_cosine_median": float(np.median(cos)) if cos.size else None,
        "shared_cosine_frac_gt_0.95": float((cos > 0.95).mean()) if cos.size else None,
        "histogram": {"counts": np.asarray(counts).tolist(),
                      "edges": np.asarray(edges).tolist()},
    }


def ce_stage(tokens, lm_cfg, model_params, hook_point, folded_params, cfg, chunk=4) -> dict:
    from crosscoder_tpu.analysis.ce_eval import (
        crosscoder_reconstruct_fn, get_ce_recovered_metrics,
    )

    return get_ce_recovered_metrics(
        tokens, lm_cfg, model_params, hook_point,
        crosscoder_reconstruct_fn(folded_params, cfg), chunk=chunk,
    )


def firing_stage(folded_params, cfg, lm_cfg, model_params, tokens,
                 hook_point) -> dict:
    """Whole-dictionary feature-density stats (sae_vis reports these per
    feature, nb:cells 36-42): firing rates over harvested rows + the
    dead-latent fraction. Folded params take RAW rows (factors are baked
    into the weights)."""
    import jax
    import jax.numpy as jnp

    from crosscoder_tpu.analysis.decoder import dead_latent_fraction, firing_rates
    from crosscoder_tpu.models import lm as lm_mod

    toks = tokens[:16]
    n_models = len(model_params)

    def row_batches(chunk=4):
        # chunked harvest, same memory envelope as the CE stage's chunk=4
        for start in range(0, toks.shape[0], chunk):
            acts = lm_mod.run_with_cache_multi(
                model_params, jnp.asarray(toks[start:start + chunk]),
                lm_cfg, (hook_point,),
            )
            yield np.asarray(jax.device_get(acts))[:, 1:].reshape(
                -1, n_models, lm_cfg.d_model)

    rates = firing_rates(folded_params, cfg, row_batches())
    n_rows = toks.shape[0] * (toks.shape[1] - 1)
    return {
        "n_rows": int(n_rows),
        "dead_latent_frac": dead_latent_fraction(rates),
        "median_rate": float(np.median(rates)),
        "p95_rate": float(np.percentile(rates, 95)),
    }


def dashboards_stage(folded_params, cfg, lm_cfg, model_params, tokens,
                     hook_point, features, out_dir: Path,
                     tokenizer=None) -> dict:
    from crosscoder_tpu.analysis.dashboards import FeatureVisConfig, FeatureVisData

    vis_cfg = FeatureVisConfig(hook_point=hook_point, features=tuple(features))
    data = FeatureVisData.create(folded_params, cfg, lm_cfg, model_params,
                                 tokens, vis_cfg)
    path = data.save_feature_centric_vis(out_dir / "dashboards.html",
                                         tokenizer=tokenizer)
    doc = path.read_text()
    return {
        "path": str(path),
        "bytes": len(doc),
        "cards": doc.count('class="card"'),
        "has_logit_lens": "promoted:" in doc,
    }


def pick_features(params, k: int = 4) -> list[int]:
    """A mix the notebook browses: strongest A-only, B-only, and shared
    latents by decoder norm."""
    from crosscoder_tpu.analysis import relative_norms

    r = np.asarray(relative_norms(params))
    w = np.linalg.norm(np.asarray(params["W_dec"], np.float32), axis=-1).sum(-1)
    picks = []
    for mask in (r <= 0.3, (r > 0.3) & (r < 0.7), r >= 0.7):
        idx = np.flatnonzero(mask)
        if idx.size:
            picks.extend(idx[np.argsort(-w[idx])][: max(1, k // 3)].tolist())
    return picks[:k] or [0]


def compare(report: dict) -> dict:
    """Pass/fail vs BASELINE.md where the run produced comparable numbers."""
    checks = {}
    ce = report.get("ce", {})
    if report.get("mode") == "hf" and "ce_recovered_A" in ce:
        checks["ce_recovered_A_within_0.01"] = bool(
            abs(ce["ce_recovered_A"] - PUBLISHED["ce_recovered_A"]) < 0.01)
        checks["ce_recovered_B_within_0.01"] = bool(
            abs(ce["ce_recovered_B"] - PUBLISHED["ce_recovered_B"]) < 0.01)
    dec = report.get("decoder", {})
    if dec:
        checks["three_clusters_present"] = dec["three_clusters_present"]
        if dec["shared_cosine_median"] is not None:
            # nb:cells 21-22: shared-latent cosines concentrate near 1
            checks["shared_cosines_concentrate_high"] = bool(
                dec["shared_cosine_median"] > 0.8)
    if "ce_recovered_A" in ce:
        checks["ce_recovered_far_above_zero_floor"] = bool(
            ce["ce_recovered_A"] > 0.6 and ce["ce_recovered_B"] > 0.6)
    dash = report.get("dashboards", {})
    if dash:
        checks["dashboards_written"] = bool(
            dash["bytes"] > 2000 and dash["cards"] > 0)
    checks["all_pass"] = all(v for k, v in checks.items())
    return checks


def run(args) -> dict:
    import jax.numpy as jnp

    from crosscoder_tpu.models import crosscoder as cc

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    report: dict = {}

    if args.demo:
        from crosscoder_tpu import demo

        report["mode"] = "demo (air-gapped; synthetic-language pair)"
        print("[replicate] training demo LM pair + crosscoder ...")
        lm_cfg, model_params, tokens, lm_ces = demo.build_demo_pair(args.demo_lm_steps)
        params, cfg, factors, final = demo.train_demo_crosscoder(
            lm_cfg, model_params, tokens, args.demo_cc_steps)
        hook = demo.DEMO_HOOK
        eval_tokens = tokens[: args.n_seqs or 64]
        report["lm_train_ce"] = lm_ces
        report["crosscoder_final"] = {k: float(v) for k, v in final.items()}
    else:
        from crosscoder_tpu.models import lm

        if args.hf:
            from crosscoder_tpu.checkpoint import torch_compat

            report["mode"] = "hf"
            params, cfg = torch_compat.load_from_hf()
            factors = np.asarray(
                [PUBLISHED["norm_factor_A"], PUBLISHED["norm_factor_B"]], np.float32)
        else:
            from crosscoder_tpu.checkpoint.ckpt import Checkpointer

            report["mode"] = "local"
            params, cfg = Checkpointer.load_weights(args.version_dir, args.save)
            factors = (np.asarray([float(x) for x in args.norm_factors.split(",")],
                                  np.float32)
                       if args.norm_factors else None)
        hook = cfg.hook_point
        lm_cfg = model_params = eval_tokens = None
        if args.tokens:
            lm_cfg = lm.config_for(args.model_a)
            model_params = [lm.from_hf(args.model_a, lm_cfg)[0],
                            lm.from_hf(args.model_b, lm_cfg)[0]]
            tok = (np.load(args.tokens) if args.tokens.endswith(".npy")
                   else __import__("torch").load(args.tokens, map_location="cpu").numpy())
            eval_tokens = tok[: args.n_seqs] if args.n_seqs else tok

    print("[replicate] stage 1-2: decoder-space analysis ...")
    report["decoder"] = decoder_stage(params)

    folded = None
    if factors is not None:
        folded = cc.fold_scaling_factors(params, jnp.asarray(factors))
        report["norm_factors"] = [float(x) for x in np.asarray(factors)]

    if folded is not None and eval_tokens is not None and model_params is not None:
        print("[replicate] stage 3: CE-recovered table ...")
        report["ce"] = ce_stage(eval_tokens, lm_cfg, model_params, hook,
                                folded, cfg, chunk=args.chunk)
        print("[replicate] stage 4: firing rates ...")
        report["firing"] = firing_stage(folded, cfg, lm_cfg, model_params,
                                        eval_tokens, hook)
        print("[replicate] stage 5: dashboards ...")
        report["dashboards"] = dashboards_stage(
            folded, cfg, lm_cfg, model_params, eval_tokens, hook,
            pick_features(params), out_dir, tokenizer=args.tokenizer)
    else:
        report["ce"] = {}
        report["firing"] = {}
        report["dashboards"] = {}
        report["skipped"] = ("CE/firing-rates/dashboards need LM weights + "
                             "tokens (--tokens, and --norm-factors for "
                             "--version-dir)")

    report["published"] = PUBLISHED
    report["checks"] = compare(report)
    return report


def _positive_int(s: str) -> int:
    v = int(s)
    if v < 1:
        raise argparse.ArgumentTypeError("must be >= 1")
    return v


def main(argv=None):
    from crosscoder_tpu.utils import compile_cache

    compile_cache.enable()   # warm pods skip the 17s+ first-call compiles
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--hf", action="store_true")
    mode.add_argument("--version-dir", type=str)
    mode.add_argument("--demo", action="store_true")
    ap.add_argument("--save", type=int, default=None)
    ap.add_argument("--model-a", type=str, default="google/gemma-2-2b")
    ap.add_argument("--model-b", type=str, default="google/gemma-2-2b-it")
    ap.add_argument("--tokens", type=str, default=None)
    ap.add_argument("--n-seqs", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--norm-factors", type=str, default=None)
    ap.add_argument("--tokenizer", type=str, default=None,
                    help="local HF tokenizer.json (or its dir): dashboards "
                         "render real text instead of ⟨id⟩ placeholders")
    ap.add_argument("--demo-lm-steps", type=_positive_int, default=400)
    ap.add_argument("--demo-cc-steps", type=_positive_int, default=1500)
    ap.add_argument("--out", type=str, default="replicate_out")
    ap.add_argument(
        "--platform", type=str, default=None, choices=("cpu", "tpu"),
        help="force a jax backend (default: cpu for --demo — a toy-sized "
        "self-check that needs no accelerator — else the platform default)",
    )
    args = ap.parse_args(argv)

    platform = args.platform or ("cpu" if args.demo else None)
    if platform == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")

    report = run(args)
    out_dir = Path(args.out)
    (out_dir / "replicate_report.json").write_text(json.dumps(report, indent=2))

    print(json.dumps({k: v for k, v in report.items() if k != "decoder"}
                     | {"decoder": {k: v for k, v in report["decoder"].items()
                                    if k != "histogram"}}, indent=2))
    print(f"\nwrote {out_dir}/replicate_report.json")
    print("PASS" if report["checks"]["all_pass"] else "FAIL", "—",
          json.dumps(report["checks"]))
    return report


if __name__ == "__main__":
    main()
