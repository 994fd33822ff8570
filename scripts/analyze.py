#!/usr/bin/env python
"""Static correctness plane CLI (invoked from ``scripts/tier1.sh``).

Runs the three rule packs of ``crosscoder_tpu.analysis.contracts`` over
the shipped tree and exits nonzero on any error-severity finding:

- HLO/jaxpr contracts — lowers the real train step across the knob
  lattice and checks zero-cost-off identity, dtype bans, donation,
  host transfers, captured constants;
- Pallas kernel safety — captures every ``pallas_call`` in ops/ via
  interpret-mode probes and checks BlockSpec/grid consistency, VMEM
  budgets, index-map OOB on tails, grid-axis write races, scratch dtypes;
- repo-wide AST lints — gate registry, cfg.* field validity + doc
  coverage, stdout hygiene, span taxonomy, metric-key namespaces,
  unused imports.

Output: human report on stdout by default; ``--json`` emits exactly one
JSON document on stdout (progress and noise ride stderr). Rule catalog
and suppression syntax: docs/ANALYSIS.md.

``--mutate <rule>`` runs that rule over its seeded-violation fixture
(``mutations.py``) — the expected outcome is findings and a nonzero
exit, proving the rule can actually fail.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _parse_args(argv: list[str] | None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--json", action="store_true",
                   help="emit one JSON document on stdout")
    p.add_argument("--allow", action="append", default=[],
                   help="suppress a rule by name (repeatable, or "
                        "comma-separated); recorded as suppressed")
    p.add_argument("--skip-hlo", action="store_true",
                   help="skip the step-lowering HLO sweep (the slow pack)")
    p.add_argument("--skip-pallas", action="store_true",
                   help="skip the Pallas kernel probes")
    p.add_argument("--skip-lints", action="store_true",
                   help="skip the repo-wide AST lints")
    p.add_argument("--mutate", metavar="RULE",
                   help="run RULE over its seeded-violation fixture "
                        "(self-test; nonzero exit = rule fired = pass)")
    p.add_argument("--list", action="store_true", dest="list_rules",
                   help="list every rule with its description and exit")
    return p.parse_args(argv)


def _allow_set(args: argparse.Namespace) -> frozenset[str]:
    names: set[str] = set()
    for item in args.allow:
        names.update(s.strip() for s in item.split(",") if s.strip())
    return frozenset(names)


def build_report(args: argparse.Namespace):
    from crosscoder_tpu.analysis.contracts import (AST_RULES, CACHE_RULES,
                                                   HLO_RULES, PALLAS_RULES,
                                                   Report,
                                                   build_cache_key_context,
                                                   build_source_context,
                                                   build_step_context,
                                                   run_kernel_probes,
                                                   run_rules, vmem_summary)
    allow = _allow_set(args)
    report = Report()
    if not args.skip_lints:
        print("analyze: AST lints ...", file=sys.stderr)
        report.merge(run_rules(AST_RULES, build_source_context(), allow))
        print("analyze: compile-cache key completeness ...", file=sys.stderr)
        report.merge(run_rules(CACHE_RULES, build_cache_key_context(), allow))
    if not args.skip_pallas:
        print("analyze: Pallas kernel probes ...", file=sys.stderr)
        pctx = run_kernel_probes()
        pallas = run_rules(PALLAS_RULES, pctx, allow)
        pallas.info.update(vmem_summary(pctx))
        report.merge(pallas)
    if not args.skip_hlo:
        print("analyze: HLO knob-lattice sweep ...", file=sys.stderr)
        report.merge(run_rules(HLO_RULES, build_step_context(),
                               allow))
    return report


def main(argv: list[str] | None = None) -> int:
    args = _parse_args(argv)

    if args.list_rules:
        from crosscoder_tpu.analysis.contracts import ALL_RULES
        for rule in ALL_RULES:
            print(f"{rule.name:36s} {rule.description}")
        return 0

    if args.mutate:
        from crosscoder_tpu.analysis.contracts import MUTATIONS, run_mutation
        if args.mutate not in MUTATIONS:
            print(f"analyze: unknown rule {args.mutate!r}; choose from: "
                  f"{', '.join(sorted(MUTATIONS))}", file=sys.stderr)
            return 2
        report = run_mutation(args.mutate)
    else:
        # library modules may log to stdout during probes (e.g. the
        # dispatch gate banner rides stderr, but be defensive): anything
        # that is not the report must not land on the --json stream
        with contextlib.redirect_stdout(io.StringIO()) as buf:
            report = build_report(args)
        leaked = buf.getvalue()
        if leaked:
            sys.stderr.write(leaked)

    print(report.to_json() if args.json else report.format_human())
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
