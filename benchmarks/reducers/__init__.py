"""Per-layer metric readers: one module each, found by the ``reducer`` a
metric's file names. ``reduce(obs, args)`` returns the value, or None when
there is nothing to read (the harness then leaves the metric out)."""


def dig(obs: dict, path: list):
    """The value at ``path`` in the observations, or None."""
    cur = obs
    for key in path:
        if not isinstance(cur, dict) or cur.get(key) is None:
            return None
        cur = cur[key]
    return cur
