"""ops/dispatch.py kernel-gate resolution: the ``CROSSCODER_PALLAS``
umbrella (all|off, per-kernel override wins), the one-time resolved-state
startup log, and typo validation of unknown ``CROSSCODER_*_PALLAS``
names with difflib suggestions. All CPU, tier-1."""

import pytest

from crosscoder_tpu.ops import dispatch


@pytest.fixture(autouse=True)
def _clean_gate_env(monkeypatch):
    """Each test starts from a bare env (no umbrella, no per-kernel
    gates) and a reset one-time-log latch."""
    monkeypatch.delenv(dispatch.UMBRELLA_ENV, raising=False)
    for g in dispatch.KNOWN_GATES:
        monkeypatch.delenv(g, raising=False)
    dispatch._reset_log_state()
    yield
    dispatch._reset_log_state()


def test_default_everything_off():
    for g in dispatch.KNOWN_GATES:
        assert not dispatch.resolve_gate(g)


def test_umbrella_all_enables_every_gate(monkeypatch):
    monkeypatch.setenv(dispatch.UMBRELLA_ENV, "all")
    for g in dispatch.KNOWN_GATES:
        assert dispatch.resolve_gate(g)


def test_per_kernel_env_overrides_umbrella(monkeypatch):
    monkeypatch.setenv(dispatch.UMBRELLA_ENV, "all")
    monkeypatch.setenv("CROSSCODER_QUANT_PALLAS", "0")
    assert not dispatch.resolve_gate("CROSSCODER_QUANT_PALLAS")
    assert dispatch.resolve_gate("CROSSCODER_PAGED_ATTN_PALLAS")
    monkeypatch.setenv(dispatch.UMBRELLA_ENV, "off")
    monkeypatch.setenv("CROSSCODER_BATCHTOPK_PALLAS", "1")
    assert dispatch.resolve_gate("CROSSCODER_BATCHTOPK_PALLAS")
    assert not dispatch.resolve_gate("CROSSCODER_QUANT_PALLAS")


def test_malformed_umbrella_raises_with_suggestion(monkeypatch):
    monkeypatch.setenv(dispatch.UMBRELLA_ENV, "al")
    with pytest.raises(ValueError, match="did you mean 'all'"):
        dispatch.resolve_gate("CROSSCODER_QUANT_PALLAS")


def test_unknown_gate_names_get_difflib_suggestions(monkeypatch):
    monkeypatch.setenv("CROSSCODER_PAGED_ATTN_PALLAS", "1")      # known: quiet
    monkeypatch.setenv("CROSSCODER_PAGD_ATTN_PALLAS", "1")       # typo
    # gone with the kernel it gated (PR 32): now a name no kernel reads
    monkeypatch.setenv("CROSSCODER_SPARSE_GRAD_PALLAS", "1")
    warnings = dispatch.validate_env()
    assert len(warnings) == 2
    assert "CROSSCODER_PAGD_ATTN_PALLAS" in warnings[0]
    assert "did you mean CROSSCODER_PAGED_ATTN_PALLAS?" in warnings[0]
    assert "no-op" in warnings[0]
    assert "CROSSCODER_SPARSE_GRAD_PALLAS" in warnings[1] and "no-op" in warnings[1]


def test_typo_warning_prints_at_first_dispatch(monkeypatch, capsys):
    """The startup log validates the env BEFORE latching the one-time
    flag: a typo'd gate name is visible on stderr at the first dispatch
    decision, with its difflib suggestion."""
    monkeypatch.setenv("CROSSCODER_BATCHTOK_PALLAS", "1")        # typo
    dispatch.hw_kernel_enabled("CROSSCODER_QUANT_PALLAS", True)
    err = capsys.readouterr().err
    assert "unknown kernel gate CROSSCODER_BATCHTOK_PALLAS" in err
    assert "did you mean CROSSCODER_BATCHTOPK_PALLAS?" in err
    assert "pallas gates" in err


def test_malformed_umbrella_does_not_latch_the_log(monkeypatch, capsys):
    """A raising umbrella must leave the one-time latch unset, so the
    retry after the operator fixes the env still logs the gate table
    (and re-runs validation) instead of silently skipping both."""
    monkeypatch.setenv(dispatch.UMBRELLA_ENV, "laa")
    with pytest.raises(ValueError, match="must be all|off"):
        dispatch.hw_kernel_enabled("CROSSCODER_QUANT_PALLAS", True)
    capsys.readouterr()
    monkeypatch.setenv(dispatch.UMBRELLA_ENV, "all")
    dispatch.hw_kernel_enabled("CROSSCODER_QUANT_PALLAS", True)
    assert "pallas gates (CROSSCODER_PALLAS=all)" in capsys.readouterr().err


def test_interpret_mode_always_allowed(monkeypatch):
    # no env at all: the interpreter (CPU tests) still runs
    assert dispatch.hw_kernel_enabled("CROSSCODER_QUANT_PALLAS", True)
    # hardware path off-TPU stays off regardless of env
    monkeypatch.setenv("CROSSCODER_QUANT_PALLAS", "1")
    import jax

    if jax.default_backend() != "tpu":
        assert not dispatch.hw_kernel_enabled("CROSSCODER_QUANT_PALLAS",
                                              False)


def test_startup_log_emits_once_with_resolved_states(monkeypatch, capsys):
    monkeypatch.setenv(dispatch.UMBRELLA_ENV, "all")
    monkeypatch.setenv("CROSSCODER_QUANT_PALLAS", "0")
    dispatch.hw_kernel_enabled("CROSSCODER_QUANT_PALLAS", True)
    err = capsys.readouterr().err
    assert "pallas gates (CROSSCODER_PALLAS=all)" in err
    assert "quant=off" in err                  # per-kernel override visible
    assert "paged_attn=on" in err              # umbrella default visible
    assert "sparse_grad" not in err            # left the table with its kernel (PR 32)
    # second dispatch decision: no second log line
    dispatch.hw_kernel_enabled("CROSSCODER_QUANT_PALLAS", True)
    assert "pallas gates" not in capsys.readouterr().err


def test_every_known_gate_is_actually_read_somewhere():
    """The registry and the ops modules can't drift: every KNOWN_GATES
    name appears in exactly the module that dispatches on it."""
    import pathlib

    ops_dir = pathlib.Path(dispatch.__file__).parent
    blob = "".join(p.read_text() for p in ops_dir.glob("*.py"))
    for g in dispatch.KNOWN_GATES:
        assert blob.count(g) >= 1, f"{g} registered but never read"
