"""A part of a group's needed work over what the device's peak could do in
the device time of the ops that compute it, in percent.

The needed work of the whole group is in the runner's shapes (``work``); the
part is the architecture's own ratio: the function ``share`` of
``benchmarks/arch/<arch>.py`` called with the configuration ``config``'s
``LMConfig``, hook depth and sequence length (the metric's file names all
three: it lists one configuration's cells). The time is ``op_device_ms``'s.

args: ``module``, ``op``, ``per`` (as ``op_device_ms``), ``work``, ``peak``
(key in peaks.json), ``arch``, ``share``, ``config``.
"""

from __future__ import annotations

import importlib

from benchmarks import manifest
from benchmarks.reducers import dig, op_device_ms


def reduce(obs: dict, args: dict):
    s, per = op_device_ms.seconds(obs, args), dig(obs, args["per"])
    work = dig(obs, ["shapes", args["work"]])
    peak = dig(obs, ["peaks", args["peak"]])
    if not s or not per or work is None or not peak:
        return None
    arch = importlib.import_module(f"benchmarks.arch.{args['arch']}")
    config = manifest.load_json(manifest.BENCH_DIR / "configs" / f"{args['config']}.json")
    cc = config["crosscoder"]
    part = getattr(arch, args["share"])(
        arch.lm_config(config), int(cc["hook_point"].split(".")[1]), cc["seq_len"])
    return 100.0 * work * part / peak / (s / per)
