"""Device milliseconds of the ops of ONE kind inside a group's programs, per
unit of work in the traced window: the self time (``trace_reduce``) of the
device ops whose XLA module matches ``module`` and whose own name matches
``op`` (a Pallas kernel's ops are ``pallas:<its name>...``), averaged over
devices, over ``per`` (path of the count). The attribution groups stay whole:
this reads a part of one, it does not carve it out.

args: ``module`` and ``op`` (regexes), ``per``. None where no such op ran (a
program without the kernel; the CPU).
"""

from __future__ import annotations

import re

from benchmarks import trace_reduce
from benchmarks.reducers import dig


def seconds(obs: dict, args: dict):
    devices = dig(obs, ["trace", "devices"])
    if not devices:
        return None
    module, op = re.compile(args["module"]), re.compile(args["op"])
    self_ns = sum(o[4] for ops in devices.values()
                  for o in trace_reduce._self_times(ops)
                  if o[4] > 0 and module.search(o[1]) and op.search(o[0]))
    return self_ns / 1e9 / len(devices) if self_ns else None


def reduce(obs: dict, args: dict):
    s, per = seconds(obs, args), dig(obs, args["per"])
    if s is None or not per:
        return None
    return 1e3 * s / per
