"""Shared hardware-dispatch gate for the opt-in Pallas kernels.

The kernel families gated here are interpret-verified and have never been
timed on a chip, so real-TPU dispatch is an explicit opt-in env var per
family — one rule, stated once: the interpreter (CPU tests) always may
run, hardware only with the opt-in. The chip's compiler has been asked:
tests/test_chip_compile.py compiles one case per family for a described
v5e at production shapes (all accepted). A family leaves this table when a
real-TPU A/B lands: it becomes a default chosen at trace time from what the
code can see, or a deletion — never a flag. So went the TopK kernel, the
fused attention, the expert kernels, and (PR 32) the sparse backward plane:
its sorted-pair scatter was refused by the chip's compiler, and its place
is taken by ``ops/row_gather.py``, which dispatches like those
(``models/crosscoder.rows_live``: TopK, ``l1_coeff == 0``, one TPU device,
a supported shape). The GATE shape itself is shared so a policy change (new
backend, global kill-switch) lands in one place.

Gate resolution (first ``hw_kernel_enabled`` call logs the full table to
stderr, once per process, so a run's kernel posture is always in its
log):

1. the kernel's own env var, if set: ``1`` forces on, anything else off;
2. else the ``CROSSCODER_PALLAS`` umbrella: ``all`` turns every known
   gate on, ``off`` (or unset) leaves them off.

A ``CROSSCODER_*_PALLAS`` name that matches no known gate is a silent
no-op — the exact bug class this module exists to prevent — so unknown
names are reported with a difflib suggestion, and a malformed umbrella
value raises (it is pure opt-in machinery; failing the first dispatch
beats silently running the wrong tier for a whole job).
"""

from __future__ import annotations

import difflib
import os
import sys

import jax

UMBRELLA_ENV = "CROSSCODER_PALLAS"

# every per-kernel gate the ops modules read (keep sorted; a new kernel
# family registers here so the umbrella + startup log + typo validation
# see it)
KNOWN_GATES = (
    "CROSSCODER_BATCHTOPK_PALLAS",
    "CROSSCODER_FUSED_TOPK_PALLAS",
    "CROSSCODER_PAGED_ATTN_PALLAS",
    "CROSSCODER_QUANT_PALLAS",
)

_LOGGED = False


def _reset_log_state() -> None:
    """Test hook: make the next hw_kernel_enabled call re-log/re-validate."""
    global _LOGGED
    _LOGGED = False


def resolve_gate(env_var: str) -> bool:
    """One gate's resolved state from the env alone (no backend check):
    the per-kernel var wins; otherwise the umbrella's ``all`` enables."""
    v = os.environ.get(env_var)
    if v is not None:
        return v == "1"
    return _umbrella_value() == "all"


def _umbrella_value() -> str:
    u = os.environ.get(UMBRELLA_ENV)
    if u is None:
        return "off"
    if u not in ("all", "off"):
        close = difflib.get_close_matches(u, ("all", "off"), n=1)
        hint = f"; did you mean {close[0]!r}?" if close else ""
        raise ValueError(
            f"{UMBRELLA_ENV} must be all|off, got {u!r}{hint}"
        )
    return u


def validate_env(environ=None) -> list[str]:
    """Warnings for ``CROSSCODER_*_PALLAS`` names that match no known
    gate (each with a difflib suggestion). Returns the warning lines so
    tests can assert on them; the startup path prints them to stderr."""
    env = os.environ if environ is None else environ
    warnings = []
    for name in sorted(env):
        if (name.startswith("CROSSCODER_") and name.endswith("_PALLAS")
                and name not in KNOWN_GATES and name != UMBRELLA_ENV):
            close = difflib.get_close_matches(name, KNOWN_GATES, n=1)
            hint = f" — did you mean {close[0]}?" if close else ""
            warnings.append(
                f"[crosscoder_tpu] unknown kernel gate {name}={env[name]!r}"
                f" (no kernel reads it, the setting is a no-op){hint}"
            )
    return warnings


def log_gate_state(force: bool = False) -> None:
    """One stderr line with every gate's RESOLVED state (plus umbrella
    typo validation) — emitted once per process at the first kernel
    dispatch decision, so a job log always records its kernel posture."""
    global _LOGGED
    if _LOGGED and not force:
        return
    # validate BEFORE latching: a malformed umbrella raises out of
    # _umbrella_value(), and latching first would mark the table as
    # already-logged so the retry after the caller handles the error
    # (or a test's second dispatch) silently skips validation forever
    warnings = validate_env()
    umbrella = _umbrella_value()
    states = ", ".join(
        f"{g.removeprefix('CROSSCODER_').removesuffix('_PALLAS').lower()}="
        f"{'on' if resolve_gate(g) else 'off'}"
        for g in KNOWN_GATES
    )
    _LOGGED = True
    for w in warnings:
        print(w, file=sys.stderr, flush=True)
    print(
        f"[crosscoder_tpu] pallas gates ({UMBRELLA_ENV}={umbrella}): "
        f"{states}",
        file=sys.stderr, flush=True,
    )


def hw_kernel_enabled(env_var: str, interpret: bool) -> bool:
    """Whether a Pallas kernel may dispatch: interpret mode (the CPU
    stand-in used by tests), or a real TPU backend with the gate
    resolved on (per-kernel env var, or the ``CROSSCODER_PALLAS=all``
    umbrella)."""
    log_gate_state()
    return interpret or (
        jax.default_backend() == "tpu" and resolve_gate(env_var)
    )
