"""Device milliseconds per execution of ONE step variant: the self time
(``trace_reduce``: an op that contains others counts only its own) of the
device ops whose XLA module matches ``module``, averaged over devices, over
the steps of that variant in the traced window.

The traced window is ``traced_steps / spc`` whole cycles, each closed by one
log step: that many steps ran the full-metrics variant and the rest the bare
one. args: ``module`` (regex on the module name) and ``variant``
(``"full"`` | ``"bare"``). None where no op ran in such a module (a program
whose step variants share one module name).
"""

from __future__ import annotations

import re

from benchmarks import trace_reduce
from benchmarks.reducers import dig


def reduce(obs: dict, args: dict):
    devices = dig(obs, ["trace", "devices"])
    steps, spc = obs.get("traced_steps"), obs.get("spc")
    if not devices or not steps or not spc:
        return None
    full = int(steps) // int(spc)
    runs = full if args["variant"] == "full" else int(steps) - full
    pattern = re.compile(args["module"])
    self_ns = sum(op[4] for ops in devices.values()
                  for op in trace_reduce._self_times(ops)
                  if op[4] > 0 and pattern.search(op[1]))
    if not self_ns or runs <= 0:
        return None
    return self_ns / 1e6 / len(devices) / runs
