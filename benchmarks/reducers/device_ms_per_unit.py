"""Device milliseconds of an attribution group per unit of work in the
traced window: args ``group`` (or ``groups``) and ``per`` (path of the count)."""

from benchmarks.reducers import dig


def seconds(obs: dict, args: dict):
    groups = dig(obs, ["trace_reduced", "groups"])
    if groups is None:
        return None
    names = args.get("groups", [args.get("group")])
    found = [groups[g] for g in names if g in groups]
    return sum(found) if found else None


def reduce(obs: dict, args: dict):
    s, per = seconds(obs, args), dig(obs, args["per"])
    if s is None or not per:
        return None
    return 1e3 * s / per
