"""Static correctness plane (docs/ANALYSIS.md): engine semantics, the
per-rule mutation self-tests (every rule must be able to fail), clean
spot checks over the shipped tree, and the ``scripts/analyze.py`` CLI
contract (``--json`` = exactly one JSON document on stdout). All CPU,
tier-1; the slow HLO lattice is exercised via its builder once."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from crosscoder_tpu.analysis.contracts import (ALL_RULES, AST_RULES,
                                               MUTATIONS, PALLAS_RULES,
                                               Finding, Rule,
                                               build_source_context,
                                               run_kernel_probes, run_mutation,
                                               run_rules, vmem_summary)

REPO = Path(__file__).parent.parent


# ---------------------------------------------------------------------------
# engine semantics


def test_crashing_rule_is_a_finding_not_a_pass():
    rule = Rule(name="boom", description="always crashes",
                applies_when=lambda ctx: True,
                check=lambda ctx: 1 / 0)
    rep = run_rules([rule], ctx=None)
    assert not rep.ok
    assert rep.findings[0].rule == "boom"
    assert "harness crashed" in rep.findings[0].message


def test_allow_suppresses_but_records():
    rule = Rule(name="noisy", description="", applies_when=lambda c: True,
                check=lambda c: [Finding(rule="noisy", message="x")])
    rep = run_rules([rule], ctx=None, allow={"noisy"})
    assert rep.ok and rep.suppressed == ["noisy"] and not rep.checked


def test_inapplicable_rule_is_skipped():
    rule = Rule(name="hlo-only", description="",
                applies_when=lambda c: False, check=lambda c: [])
    rep = run_rules([rule], ctx=object())
    assert rep.skipped == ["hlo-only"] and rep.ok


# ---------------------------------------------------------------------------
# mutation self-tests: a checker that cannot fail is not a check


def test_every_rule_has_a_mutation():
    assert {r.name for r in ALL_RULES} == set(MUTATIONS)


@pytest.mark.parametrize("rule_name", sorted(MUTATIONS))
def test_mutation_fires(rule_name):
    rep = run_mutation(rule_name)
    fired = [f for f in rep.findings if f.rule == rule_name]
    assert fired, f"seeded violation for {rule_name} produced no finding"
    assert all(f.severity == "error" for f in fired)
    assert not rep.ok


# ---------------------------------------------------------------------------
# shipped tree stays clean (fast packs; the HLO lattice rides analyze.py
# in tier1.sh and the dedicated zero-cost-off tests)


def test_ast_lints_clean_on_shipped_tree():
    rep = run_rules(AST_RULES, build_source_context())
    assert rep.ok, "\n".join(str(f) for f in rep.findings)
    assert len(rep.checked) == len(AST_RULES)


def test_pallas_pack_clean_and_covers_all_seven_kernels():
    ctx = run_kernel_probes()
    rep = run_rules(PALLAS_RULES, ctx)
    assert rep.ok, "\n".join(str(f) for f in rep.findings)
    families = {c.kernel for c in ctx.calls}
    assert {"topk", "sparsify", "batchtopk", "quant",
            "paged_attention", "flash_attention"} <= families
    summary = vmem_summary(ctx)
    assert len(summary) >= 6
    assert all("MiB" in v for v in summary.values())


def test_metric_key_lint_tracks_registry_bindings():
    """The folded-in metric-key lint sees keys on ANY name bound to
    ``MetricsRegistry()`` — the old standalone script's receiver-name
    heuristic (registry/reg/r) missed e.g. ``m = MetricsRegistry()``."""
    import ast

    from crosscoder_tpu.analysis.contracts.ast_lints import collect_keys

    tree = ast.parse(
        "from crosscoder_tpu.obs.registry import MetricsRegistry\n"
        "m = MetricsRegistry()\n"
        "m.observe('rogue_histogram_key', 1.0)\n"
        "m.gauge('perf/fine', 2.0)\n"
    )
    keys = {k for _, k in collect_keys(tree)}
    assert {"rogue_histogram_key", "perf/fine"} <= keys


# ---------------------------------------------------------------------------
# CLI contract


def _run_analyze(*args):
    return subprocess.run(
        [sys.executable, str(REPO / "scripts" / "analyze.py"), *args],
        capture_output=True, text=True, cwd=REPO, timeout=300,
    )


def test_analyze_json_emits_exactly_one_document_on_stdout():
    p = _run_analyze("--json", "--skip-hlo", "--skip-pallas")
    assert p.returncode == 0, p.stdout + p.stderr
    doc = json.loads(p.stdout)          # a second document would raise
    assert doc["ok"] is True
    assert set(doc) == {"ok", "findings", "checked", "skipped",
                        "suppressed", "info"}


def test_analyze_mutate_exits_nonzero():
    p = _run_analyze("--mutate", "lint-no-stdout-print", "--json")
    assert p.returncode == 1
    doc = json.loads(p.stdout)
    assert doc["ok"] is False
    assert doc["findings"][0]["rule"] == "lint-no-stdout-print"


def test_analyze_list_names_every_rule():
    p = _run_analyze("--list")
    assert p.returncode == 0
    for rule in ALL_RULES:
        assert rule.name in p.stdout
