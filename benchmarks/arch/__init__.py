"""What the harness knows about a subject LM's block, one module an
architecture, found by the ``arch`` key of a configuration file (absent:
``gemma2_block``). A module gives exactly:

- ``lm_config(config, overrides) -> lm.LMConfig``: the published keys of the
  file to the program's configuration. ``overrides`` is the CPU tests' tiny
  sizes (``LMConfig`` keywords); what they mean for fields of the
  architecture's own is the module's to say;
- ``resid_pre(params, tokens, lm_cfg, hook_layer)``: its plain float32
  reference (kept in ``benchmarks/reference/``), and ``HARVEST_RTOL``, the
  limit the runner holds the harvest to against it, with the reason for its
  value beside it;
- ``flops_per_token(lm_cfg, n_layers, seq_len)``: the forward FLOPs the
  mathematics needs, whatever implements them (``harvest_peak_share``'s
  numerator).
"""

from __future__ import annotations

import importlib
from types import ModuleType

DEFAULT = "gemma2_block"


def of(config: dict) -> ModuleType:
    """The module of the architecture a configuration file names."""
    return importlib.import_module(f"benchmarks.arch.{config.get('arch', DEFAULT)}")
