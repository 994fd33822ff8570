"""One observed number over another: args ``num``, ``den`` (paths), ``scale``."""

from benchmarks.reducers import dig


def reduce(obs: dict, args: dict):
    num, den = dig(obs, args["num"]), dig(obs, args["den"])
    if num is None or not den:
        return None
    return float(num) / float(den) * args.get("scale", 1.0)
