"""bench.py must never rot: the driver runs it at every round end to
produce the scored headline. This smoke runs the real script (subprocess,
CPU, tiny shapes) and checks the output contract — exactly one COMPACT
(≤2 KB: the driver truncates at 2000 chars, which is how BENCH_r05
shipped ``parsed: null``) JSON line on stdout with the headline fields
and gate booleans, full per-section detail in the artifact file."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

_ROOT = Path(__file__).parent.parent


@pytest.mark.slow
def test_bench_emits_one_compact_json_headline(tmp_path):
    artifact = tmp_path / "BENCH_DETAIL.json"
    env = dict(os.environ)
    env.update(
        BENCH_TINY="1", BENCH_CPU="1",
        BENCH_SECTIONS="step,e2e,harvest",
        BENCH_STEPS="4", BENCH_E2E_STEPS="4",
        BENCH_DIN="32", BENCH_DICT="256", BENCH_BATCH="64",
        BENCH_ARTIFACT=str(artifact),
        JAX_PLATFORMS="cpu",
    )
    env.pop("XLA_FLAGS", None)          # 1-device CPU: cheap and stable
    r = subprocess.run(
        [sys.executable, "bench.py"], cwd=str(_ROOT), env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"stdout must be ONE JSON line, got {lines}"
    # the whole point of the compact contract: the line survives the
    # driver's 2000-char truncation, so "parsed" can never be null
    assert len(lines[0]) <= 2000, f"summary line is {len(lines[0])} B"
    out = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "gates"):
        assert key in out, key
    assert out["value"] and out["value"] > 0
    assert out["gates"]["e2e.loss_finite"] is True
    assert out["e2e"]["loss_finite"] is True
    # the harvest section's contract (speedup itself is shape-dependent:
    # toy dims are dispatch-bound, so only the fields are asserted here)
    assert 0 < out["harvest"]["padding_efficiency"] <= 1
    assert out["harvest"]["paged_step_ms"] > 0
    # full detail lands in the artifact, not on stdout
    assert out["detail"] == str(artifact)
    detail = json.loads(artifact.read_text())
    for section in ("step", "e2e", "harvest"):
        assert section in detail, section
    assert detail["e2e"]["workload"]           # detail keeps the long fields
    assert detail["harvest"]["tokens_per_sec_paged"] > 0


def test_bench_compact_summary_is_small_and_gated():
    """The pure summary projection: full-size fake section results must
    compact to ≤2 KB with the gate booleans and per-dict relu ratios."""
    sys.path.insert(0, str(_ROOT))
    try:
        import bench
    finally:
        sys.path.pop(0)

    headline = {"metric": "end-to-end acts/sec/chip (x)", "value": 25000.0,
                "unit": "activations/s/chip", "vs_baseline": 1.1,
                "compile_cache": "warm"}
    matrix = []
    for d in (2**15, 2**16, 2**17):
        matrix.append({"variant": "relu", "dict_size": d,
                       "acts_per_sec_chip": 150000.0, "step_ms": 27.3,
                       "loss_finite": True, "n_devices": 1,
                       "workload": "w" * 80})
        for v in ("topk_dense", "topk_pallas", "jumprelu",
                  "topk_sparse_bwd", "batchtopk", "batchtopk_pallas"):
            matrix.append({"variant": v, "dict_size": d,
                           "acts_per_sec_chip": 140000.0, "step_ms": 29.0,
                           "fwd_ms": 9.0, "bwd_ms": 17.2,
                           "loss_finite": True, "n_devices": 1,
                           "workload": "w" * 80})
    matrix.append({"variant": "batchtopk_pallas", "dict_size": 2**18,
                   "skipped": "unsupported at this width"})
    results = {
        "step": {"acts_per_sec_chip": 148000.0, "vs_a100_step": 1.92,
                 "workload": "w" * 120},
        "matrix": matrix,
        "configs": [{"config": f"cfg{i}", "acts_per_sec_chip": 1000.0 * i,
                     "workload": "w" * 120} for i in range(5)],
        "e2e": {"acts_per_sec_chip": 25000.0, "vs_a100_e2e": 1.1,
                "step_ms_median": 40.0, "refresh_bubble_ms": 12.0,
                "loss_finite": True, "workload": "w" * 200},
        "refill_overlap": {"gate_ok": True, "n_steps_measured": 30},
        "harvest": {"padding_efficiency": 0.62, "paged_step_ms": 50.0,
                    "paged_speedup": 1.4, "workload": "w" * 120},
        "quant": {"roundtrip_rel_mse": 1.2e-4, "quality_gate_ok": True,
                  "grad_allreduce": {"big": "nested" * 40}},
        "obs": {"obs_overhead_frac": 0.004, "overhead_gate_ok": True,
                "spans_per_sec": 1e6},
        "dash": {"steady_s": 15.0, "vs_reference": 1.27},
        "elastic": {"remesh_ms": 1500, "bitwise_equal": True,
                    "resume_step": 6, "post_steps": 4,
                    "grow_ms": 1300, "autoscale_bitwise_equal": True,
                    "joiner_equal": True, "autoscale_cycle_s": 38.5,
                    "autoscale_resume_step": 10,
                    "workload": "w" * 80},
    }
    out = bench._compact(headline, results)
    line = json.dumps(out)
    assert len(line) <= 2000, f"{len(line)} B"
    assert out["gates"] == {
        "refill_overlap.gate_ok": True, "quant.quality_gate_ok": True,
        "obs.overhead_gate_ok": True, "e2e.loss_finite": True,
        "elastic.bitwise_equal": True,
        "elastic.autoscale_bitwise_equal": True,
    }
    assert out["elastic"]["remesh_ms"] == 1500
    # the scale-UP leg's headline numbers ride the same compact line
    assert out["elastic"]["grow_ms"] == 1300
    assert out["elastic"]["autoscale_cycle_s"] == 38.5
    assert out["step_ratio_vs_relu"]["topk_dense@32768"] == round(
        150000.0 / 140000.0, 3)
    assert out["step_ratio_vs_relu"]["batchtopk_pallas@262144"] == "skip"
    assert out["relu_acts_per_dict"] == {2**i: 150000.0
                                         for i in (15, 16, 17)}
    # a failed section surfaces as a compact error stub, not 300 chars
    out2 = bench._compact(headline, {
        "e2e": {"error": "RuntimeError: " + "x" * 290}})
    assert len(out2["e2e"]["error"]) <= 120


def test_bench_exits_nonzero_when_a_section_raised(tmp_path, monkeypatch,
                                                   capsys):
    """A section's exception is caught so the rest can run and the summary
    line is still written — but the run has failed, and its exit code says
    so (it used to exit 0)."""
    sys.path.insert(0, str(_ROOT))
    try:
        import bench
    finally:
        sys.path.pop(0)

    headline = {"metric": "m", "value": None, "unit": "u",
                "vs_baseline": None, "compile_cache": "cold"}
    results = {"step": {"acts_per_sec_chip": 1.0},
               "e2e": {"error": "RuntimeError: boom"},
               "configs": [{"config": "a", "acts_per_sec_chip": 1.0},
                           {"config": "b", "error": "ValueError: x"}]}
    assert bench._failed_sections(results) == ["e2e", "configs"]
    monkeypatch.setenv("BENCH_ARTIFACT", str(tmp_path / "detail.json"))
    monkeypatch.setattr(bench, "_run_sections", lambda: (headline, results))
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 1
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1 and json.loads(lines[0])["e2e"]["error"]
