"""The share of the traced window in which no op ran on the device, in percent."""

from benchmarks.reducers import dig


def reduce(obs: dict, args: dict):
    busy, window = (dig(obs, ["trace_reduced", k]) for k in ("busy_s", "window_s"))
    if busy is None or not window:
        return None
    return 100.0 * (1.0 - busy / window)
