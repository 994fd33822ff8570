"""A number the runner observed, by its path: args ``path`` and ``scale``."""

from benchmarks.reducers import dig


def reduce(obs: dict, args: dict):
    value = dig(obs, args["path"])
    return None if value is None else float(value) * args.get("scale", 1.0)
