"""Total seconds of the program's spans of one name (``obs["host_spans"]``:
the job's ``trace.json`` as [name, start_s, dur_s]): args ``name``, ``scale``.
None where the program recorded no such span."""

from __future__ import annotations


def reduce(obs: dict, args: dict):
    durs = [s[2] for s in obs.get("host_spans") or [] if s[0] == args["name"]]
    if not durs:
        return None
    return sum(durs) * args.get("scale", 1.0)
