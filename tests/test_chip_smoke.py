"""Tests of ``chip_smoke.py`` itself: the contract of its last line, its
refusal to run anything without the chip, and its phases rehearsed at tiny
size on the CPU (``on-chip-measurement`` guide §2.1 and §2.2)."""

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _assert_contract(line: str, ok: bool) -> dict:
    out = json.loads(line)
    assert set(out) == {"ok", "device"} and out["ok"] is ok
    assert set(out["device"]) == {"platform", "kind", "count"}
    assert "\n" not in line and line == json.dumps(out)   # one line, no indent
    return out


@pytest.mark.parametrize("ok", [True, False])
def test_final_line_has_exactly_the_contract_keys(ok):
    out = _assert_contract(chip_smoke.final_line(ok, "tpu", "TPU v5 lite", 1), ok)
    assert out["device"] == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_without_the_chip_it_fails_and_runs_nothing(args):
    """On the CPU the script exits non-zero, its last stdout line has the
    contract's shape with ok false, and nothing follows it — no phase ran
    (no ``[smoke]`` observation was printed)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.endswith("\n") and proc.stdout.count("\n") == 1
    out = _assert_contract(proc.stdout[:-1], False)
    assert out["device"]["platform"] == "cpu"
    assert "no phase was run" in proc.stderr


@pytest.fixture
def interpret_kernels():
    """Rehearsal 1: the default TopK tier is the Pallas kernel on a chip;
    here the same dispatch runs it through the interpreter."""
    from crosscoder_tpu.ops import activations as act_ops
    from crosscoder_tpu.ops import topk_pallas

    act_ops.set_topk_impl("pallas")
    topk_pallas.set_interpret(True)
    yield
    topk_pallas.set_interpret(False)
    act_ops.set_topk_impl("auto")


def test_train_and_serve_phases_tiny(interpret_kernels, tmp_path):
    sizes = chip_smoke.tiny_sizes()
    compiles = chip_smoke.CompileLog().install()
    lm_params = chip_smoke.init_lm_pair(sizes.lm_cfg)
    tokens = chip_smoke.make_tokens(sizes)
    for label, leg in sizes.legs:
        trained = chip_smoke.train_leg(sizes, label, leg, lm_params, tokens,
                                       compiles, str(tmp_path))
    assert trained["cfg"].activation == "topk"
    chip_smoke.serve_phase(sizes, lm_params, trained)


def test_mesh_phase_tiny_on_four_virtual_devices(tmp_path):
    """Rehearsal 2."""
    assert jax.device_count() >= 4
    compiles = chip_smoke.CompileLog().install()
    chip_smoke.mesh_phase(chip_smoke.tiny_sizes(), compiles, str(tmp_path))
