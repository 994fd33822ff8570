"""Needed work over what the device's peak could do in the group's device
time, in percent: args ``group``, ``per``, ``work`` (key in the shapes),
``peak`` (key in peaks.json). Serves both FLOP/s shares and rooflines."""

from benchmarks.reducers import device_ms_per_unit, dig


def reduce(obs: dict, args: dict):
    s, per = device_ms_per_unit.seconds(obs, args), dig(obs, args["per"])
    work = dig(obs, ["shapes", args["work"]])
    peak = dig(obs, ["peaks", args["peak"]])
    if not s or not per or work is None or not peak:
        return None
    return 100.0 * work / peak / (s / per)
