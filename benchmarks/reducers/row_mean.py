"""The mean of one logged key over the window's log rows: args ``key``, ``scale``."""


def reduce(obs: dict, args: dict):
    values = [r[args["key"]] for r in obs.get("window_rows", []) if args["key"] in r]
    if not values:
        return None
    return sum(values) / len(values) * args.get("scale", 1.0)
