"""Mutation self-tests: one deliberately-broken fixture per rule.

A checker that cannot fail is not a check. Every rule in the engine ships
a seeded violation here — a synthetic context carrying exactly the defect
the rule exists to catch — and ``tests/test_contracts.py`` asserts each
one fires (and that the shipped tree stays clean). ``scripts/analyze.py
--mutate <rule>`` runs a fixture from the CLI and exits nonzero when the
rule fires, which is the expected outcome.

All fixtures are pure data (no jax, no lowering): the rules are pure
functions of their contexts, so seeding a violation never needs a
compiler — which is also what keeps the self-test tier fast.
"""

from __future__ import annotations

from typing import Any, Callable

from crosscoder_tpu.analysis.contracts.ast_lints import (AST_RULES,
                                                         SourceContext)
from crosscoder_tpu.analysis.contracts.cache_keys import (CACHE_RULES,
                                                          CacheKeyContext)
from crosscoder_tpu.analysis.contracts.engine import Report, Rule, run_rules
from crosscoder_tpu.analysis.contracts.hlo_rules import (HLO_RULES,
                                                         StepContext,
                                                         VariantMeta)
from crosscoder_tpu.analysis.contracts.pallas_safety import (PALLAS_RULES,
                                                             CapturedCall,
                                                             PallasContext,
                                                             SpecView)

ALL_RULES: list[Rule] = HLO_RULES + PALLAS_RULES + AST_RULES + CACHE_RULES

_CLEAN_HLO = """\
module @jit_step {
  func.func public @main(%arg0: tensor<8x4xf32> {tf.aliasing_output = 0 : i32}) -> tensor<8x4xf32> {
    return %arg0 : tensor<8x4xf32>
  }
}
"""


def _step_ctx(**kw) -> StepContext:
    ctx = StepContext(
        texts={"base": _CLEAN_HLO},
        meta={"base": VariantMeta(n_donated_leaves=1)},
        jaxpr_consts={"base": []},
    )
    for k, v in kw.items():
        setattr(ctx, k, v)
    return ctx


def _mut_identity() -> StepContext:
    ctx = _step_ctx()
    ctx.texts["off:quant"] = _CLEAN_HLO + "// an extra lowered op\n"
    ctx.meta["off:quant"] = VariantMeta(n_donated_leaves=1)
    ctx.jaxpr_consts["off:quant"] = []
    ctx.identity_pairs = [("base", "off:quant", "quant")]
    return ctx


def _mut_refill_overlap() -> StepContext:
    ctx = _step_ctx()
    ctx.texts["off:refill_overlap"] = _CLEAN_HLO + "// an extra lowered op\n"
    ctx.meta["off:refill_overlap"] = VariantMeta(n_donated_leaves=1)
    ctx.jaxpr_consts["off:refill_overlap"] = []
    ctx.identity_pairs = [("base", "off:refill_overlap", "refill_overlap")]
    return ctx


def _mut_elastic() -> StepContext:
    ctx = _step_ctx()
    ctx.texts["off:elastic"] = _CLEAN_HLO + "// an extra lowered op\n"
    ctx.meta["off:elastic"] = VariantMeta(n_donated_leaves=1)
    ctx.jaxpr_consts["off:elastic"] = []
    ctx.identity_pairs = [("base", "off:elastic", "elastic")]
    return ctx


def _mut_elastic_grow() -> StepContext:
    ctx = _step_ctx()
    ctx.texts["off:elastic_grow"] = _CLEAN_HLO + "// an extra lowered op\n"
    ctx.meta["off:elastic_grow"] = VariantMeta(n_donated_leaves=1)
    ctx.jaxpr_consts["off:elastic_grow"] = []
    ctx.identity_pairs = [("base", "off:elastic_grow", "elastic_grow")]
    return ctx


def _mut_fleet() -> StepContext:
    ctx = _step_ctx()
    ctx.texts["off:fleet"] = _CLEAN_HLO + "// an extra lowered op\n"
    ctx.meta["off:fleet"] = VariantMeta(n_donated_leaves=1)
    ctx.jaxpr_consts["off:fleet"] = []
    ctx.identity_pairs = [("base", "off:fleet", "fleet")]
    return ctx


def _mut_serve() -> StepContext:
    ctx = _step_ctx()
    ctx.texts["off:serve"] = _CLEAN_HLO + "// an extra lowered op\n"
    ctx.meta["off:serve"] = VariantMeta(n_donated_leaves=1)
    ctx.jaxpr_consts["off:serve"] = []
    ctx.identity_pairs = [("base", "off:serve", "serve")]
    return ctx


def _mut_tuned() -> StepContext:
    ctx = _step_ctx()
    ctx.texts["off:tuned"] = _CLEAN_HLO + "// an extra lowered op\n"
    ctx.meta["off:tuned"] = VariantMeta(n_donated_leaves=1)
    ctx.jaxpr_consts["off:tuned"] = []
    ctx.identity_pairs = [("base", "off:tuned", "tuned")]
    return ctx


def _mut_serve_dense() -> StepContext:
    ctx = _step_ctx()
    ctx.meta["base"] = VariantMeta(n_donated_leaves=1, serve_step=True,
                                   forbid_dense_shape=(192, 1024))
    ctx.texts["base"] += "  %p = stablehlo.dot : tensor<192x1024xf32>\n"
    return ctx


def _mut_s8() -> StepContext:
    ctx = _step_ctx()
    ctx.texts["base"] += "  %q = stablehlo.convert : tensor<32x8xi8>\n"
    return ctx


def _mut_f64() -> StepContext:
    ctx = _step_ctx()
    ctx.texts["base"] += "  %d = stablehlo.convert : tensor<4xf64>\n"
    return ctx


def _mut_donation() -> StepContext:
    ctx = _step_ctx()
    ctx.meta["base"] = VariantMeta(n_donated_leaves=3)   # only 1 alias present
    return ctx


def _mut_dense_preacts() -> StepContext:
    ctx = _step_ctx()
    ctx.meta["base"] = VariantMeta(n_donated_leaves=1,
                                   forbid_dense_shape=(192, 1024))
    ctx.texts["base"] += "  %p = stablehlo.dot : tensor<192x1024xf32>\n"
    return ctx


def _mut_host_transfer() -> StepContext:
    ctx = _step_ctx()
    ctx.texts["base"] += "  %i = \"stablehlo.infeed\"(%token)\n"
    return ctx


def _mut_large_const() -> StepContext:
    ctx = _step_ctx()
    ctx.jaxpr_consts["base"] = [(1 << 20, "float32[512, 512]")]
    return ctx


def _spec(block, aval, index_map=None, space="vmem", itemsize=4) -> SpecView:
    return SpecView(block_shape=block, index_map=index_map,
                    memory_space=space, aval_shape=aval, itemsize=itemsize)


def _call(**kw) -> CapturedCall:
    base = dict(kernel="topk", name="_mut_kernel", grid=(2,),
                in_specs=[_spec((2, 4), (4, 4), lambda i: (i, 0))],
                out_specs=[_spec((2, 4), (4, 4), lambda i: (i, 0))])
    base.update(kw)
    return CapturedCall(**base)


def _mut_probe_coverage() -> PallasContext:
    # only one family probed; the other six are missing
    return PallasContext(calls=[_call()])


def _pallas_ctx(call: CapturedCall) -> PallasContext:
    calls = [_call(kernel=f) for f in
             ("topk", "sparsify", "batchtopk", "quant",
              "paged_attention", "fused_encoder_topk")]
    calls.append(call)
    return PallasContext(calls=calls)


def _mut_consistency() -> PallasContext:
    # 1-D block on a 2-D operand
    return _pallas_ctx(_call(
        in_specs=[_spec((2,), (4, 4), lambda i: (i,))]))


def _mut_vmem() -> PallasContext:
    # a single 64 MiB f32 block
    return _pallas_ctx(_call(
        in_specs=[_spec((4096, 4096), (4096, 4096), lambda i: (0, 0))]))


def _mut_oob() -> PallasContext:
    # grid 2 x block 2 over a 4-row operand, but the map shifts by one:
    # grid point (1,) addresses block 2 of [0, 2)
    return _pallas_ctx(_call(
        in_specs=[_spec((2, 4), (4, 4), lambda i: (i + 1, 0))]))


def _mut_race() -> PallasContext:
    # 4 'parallel' programs all writing output block (0, 0)
    return _pallas_ctx(_call(
        grid=(4,), dimension_semantics=("parallel",),
        out_specs=[_spec((2, 4), (8, 4), lambda i: (0, 0))]))


def _mut_scratch() -> PallasContext:
    return _pallas_ctx(_call(
        scratch=[((8, 128), "float64", 8 * 128 * 8, "vmem")]))


def _src_ctx(files: dict[str, str]) -> SourceContext:
    return SourceContext(
        files=files,
        docs_text="batch_size is documented here",
        span_taxonomy=frozenset({"step", "harvest"}),
        known_gates=frozenset({"CROSSCODER_QUANT_PALLAS",
                               "CROSSCODER_PALLAS"}),
        cfg_attrs=frozenset({"batch_size", "dict_size"}),
        cfg_fields=frozenset({"batch_size", "dict_size"}),
    )


def _mut_gate() -> SourceContext:
    return _src_ctx({"crosscoder_tpu/bad.py":
                     'GATE = "CROSSCODER_BATCHTOK_PALLAS"\n'})


def _mut_cfg_fields() -> SourceContext:
    return _src_ctx({"crosscoder_tpu/bad.py": "x = cfg.no_such_knob\n"})


def _mut_stdout_print() -> SourceContext:
    return _src_ctx({"crosscoder_tpu/bad.py": 'print("leaked to stdout")\n'})


def _mut_span() -> SourceContext:
    return _src_ctx({"crosscoder_tpu/bad.py":
                     'with trace.span("rogue_span"):\n    pass\n'})


def _mut_metric_key() -> SourceContext:
    return _src_ctx({"crosscoder_tpu/bad.py":
                     "reg.gauge('rogue_key', 1.0)\n"})


def _mut_unused_import() -> SourceContext:
    return _src_ctx({"crosscoder_tpu/bad.py": "import os\nx = 1\n"})


def _mut_cache_key() -> CacheKeyContext:
    # a digest that ignores 'seed': perturbing it cannot fork the key,
    # so two differently-seeded step programs would share one cache entry
    import hashlib
    import json

    fields = frozenset({"batch_size", "dict_size", "seed"})

    def leaky_digest(d):
        proj = {k: d.get(k) for k in sorted(fields - {"seed"})}
        blob = json.dumps(proj, sort_keys=True, default=str)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    return CacheKeyContext(
        fields=fields,
        base_cfg={"batch_size": 32, "dict_size": 64, "seed": 0},
        digest_fn=leaky_digest,
    )


MUTATIONS: dict[str, Callable[[], Any]] = {
    "hlo-knob-off-identity": _mut_identity,
    "hlo-refill-overlap-off-identity": _mut_refill_overlap,
    "hlo-elastic-off-identity": _mut_elastic,
    "hlo-elastic-grow-off-identity": _mut_elastic_grow,
    "hlo-fleet-off-identity": _mut_fleet,
    "hlo-serve-off-identity": _mut_serve,
    "hlo-tuned-config-identity": _mut_tuned,
    "hlo-serve-no-dense-preacts": _mut_serve_dense,
    "hlo-no-s8-when-quant-off": _mut_s8,
    "hlo-no-f64": _mut_f64,
    "hlo-donation-honored": _mut_donation,
    "hlo-fused-no-dense-preacts": _mut_dense_preacts,
    "hlo-no-host-transfers": _mut_host_transfer,
    "jaxpr-no-large-captured-consts": _mut_large_const,
    "pallas-probe-coverage": _mut_probe_coverage,
    "pallas-grid-blockspec-consistency": _mut_consistency,
    "pallas-vmem-budget": _mut_vmem,
    "pallas-indexmap-oob": _mut_oob,
    "pallas-write-race": _mut_race,
    "pallas-scratch-dtype": _mut_scratch,
    "lint-gate-registry": _mut_gate,
    "lint-cfg-fields": _mut_cfg_fields,
    "lint-no-stdout-print": _mut_stdout_print,
    "lint-span-taxonomy": _mut_span,
    "lint-metric-keys": _mut_metric_key,
    "lint-unused-imports": _mut_unused_import,
    "cache-key-completeness": _mut_cache_key,
}


def rule_by_name(name: str) -> Rule:
    for rule in ALL_RULES:
        if rule.name == name:
            return rule
    raise KeyError(name)


def run_mutation(name: str) -> Report:
    """Run one rule over its seeded-violation fixture. The report MUST
    carry findings attributed to the rule — asserted by the self-test."""
    ctx = MUTATIONS[name]()
    return run_rules([rule_by_name(name)], ctx)
