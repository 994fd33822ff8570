"""From the profiler's ``.xplane.pb`` to the numbers the per-layer metrics read.

Two steps, so that the second can be checked on a small recorded trace kept
with the tests:

1. ``load_profile`` reads the newest ``.xplane.pb`` under a directory with
   ``jax.profiler.ProfileData`` into a plain dict: per device the op events
   ``[name, module, start_ns, dur_ns]``, and the host's annotated spans.
2. ``reduce_trace`` turns that dict into busy and window seconds, device
   seconds per attribution group (``attribution/*.json``: patterns over the XLA
   module and op names), the longest ops and the longest idle gaps named by
   the host span they fell in.

A device op's time is its SELF time: an op that contains others (a while
loop and the ops of its body) counts only what its children do not cover, so
nothing is counted twice.
"""

from __future__ import annotations

import re
from pathlib import Path

from benchmarks import manifest

_MODULE_SUFFIX = re.compile(r"\(\d+\)$")
_HLO = re.compile(r"^%?([\w.\-]+) = \(?(\w+)\[([\d,]*)\]")


def short_op(name: str) -> str:
    """On a TPU an op event is named by its whole HLO instruction: keep the
    instruction's name and its (first) result's type and shape, and mark a
    Pallas kernel (``tpu_custom_call``) as such."""
    m = _HLO.match(name)
    short = f"{m[1]}_{m[2]}_{m[3].replace(',', '_')}_" if m else name[:96]
    return f"pallas:{short}" if "tpu_custom_call" in name else short


def _newest_xplane(profile_dir: Path) -> Path | None:
    files = sorted(Path(profile_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def load_profile(profile_dir: Path, table: dict | None = None) -> dict | None:
    """The plain-dict form of the newest trace under ``profile_dir`` (None if
    there is none). Device ops are the events of a ``/device:`` plane's
    "XLA Ops" line, their module the "XLA Modules" event they start in; on a
    backend without device planes (the CPU, in tests) they are the host
    events that carry an ``hlo_module`` stat."""
    from jax.profiler import ProfileData

    path = _newest_xplane(profile_dir)
    if path is None:
        return None
    host_names = set((table or attribution())["host_spans"])
    data = ProfileData.from_file(str(path))
    devices: dict[str, list] = {}
    asyncs: dict[str, list] = {}
    host: list = []
    layout: list = []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:") and "TPU" in plane.name
        ops, modules, overlapped = [], [], []
        for line in plane.lines:
            events = list(line.events)
            layout.append([plane.name, line.name, len(events)])
            if is_dev and line.name == "XLA Modules":
                modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                                  _MODULE_SUFFIX.sub("", e.name)) for e in events)
            elif is_dev and line.name == "XLA Ops":
                ops = [(short_op(e.name), e.start_ns, e.duration_ns) for e in events]
                if events:      # what an op event carries, for the next reader
                    layout.append(["first op event", events[0].name[:400],
                                   {k: str(v)[:200] for k, v in events[0].stats}])
            elif is_dev and line.name == "Async XLA Ops":
                # asynchronous ops (copies, collectives) run beside the others:
                # kept apart, they add nothing to busy time or to a group
                overlapped = [[short_op(e.name), "", e.start_ns, e.duration_ns]
                              for e in events]
            elif not is_dev and plane.name.startswith("/host:"):
                for e in events:
                    if e.name in host_names:
                        host.append([e.name, e.start_ns, e.duration_ns])
                    elif e.duration_ns > 0:
                        stats = dict(e.stats)
                        if "hlo_module" in stats:
                            dev = f"cpu:{stats.get('device_ordinal', 0)}"
                            devices.setdefault(dev, []).append(
                                [e.name, str(stats["hlo_module"]),
                                 e.start_ns, e.duration_ns])
        if ops:
            devices[plane.name] = _with_modules(ops, modules)
            asyncs[plane.name] = overlapped
    return {"devices": devices, "async": asyncs, "host": host, "layout": layout,
            "file": path.name}


def _with_modules(ops: list, modules: list) -> list:
    """Each op with the module event it starts in (both sorted by start)."""
    out, j = [], 0
    for name, start, dur in sorted(ops, key=lambda o: o[1]):
        while j + 1 < len(modules) and modules[j + 1][0] <= start:
            j += 1
        mod = modules[j][2] if modules and modules[j][0] <= start < modules[j][1] \
            else ""
        out.append([name, mod, start, dur])
    return out


def sample(trace: dict, n_ops: int = 400) -> dict:
    """The first ``n_ops`` ops of each device and the host spans beside them:
    small enough to keep in the run's raw readings (and with the tests)."""
    devices = {d: sorted(ops, key=lambda o: o[2])[:n_ops]
               for d, ops in trace["devices"].items()}
    ends = [o[2] + o[3] for ops in devices.values() for o in ops]
    t1 = max(ends) if ends else 0
    return {"devices": devices, "layout": trace.get("layout", []),
            "async": {d: [o for o in ops if o[2] <= t1][:n_ops]
                      for d, ops in trace.get("async", {}).items()},
            "host": [h for h in trace["host"] if h[1] <= t1][-200:]}


def load_host_spans(path: Path) -> list:
    """The program's own span file (``obs/trace.py``): [name, start_s, dur_s]."""
    if not Path(path).is_file():
        return []
    events = manifest.load_json(Path(path)).get("traceEvents", [])
    return [[e["name"], e["ts"] / 1e6, e["dur"] / 1e6]
            for e in events if e.get("ph") == "X"]


def attribution(root: Path = manifest.ROOT, paths: tuple = ("benchmarks",)) -> dict:
    """The ``attribution/*.json`` files under the benchmark's directories,
    joined in the order of their names, so that a later PR names its own
    programs and spans in a file of its own."""
    table: dict = {"rules": [], "host_spans": []}
    files = [f for p in paths for f in (Path(root) / p / "attribution").glob("*.json")]
    for path in sorted(files, key=lambda f: f.name):
        part = manifest.load_json(path)
        table["rules"] += part.get("rules", [])
        table["host_spans"] += part.get("host_spans", [])
    return table


def group_of(module: str, op: str, table: dict) -> str | None:
    for rule in table["rules"]:
        if "op" in rule and not re.search(rule["op"], op):
            continue
        if "module" in rule and not re.search(rule["module"], module):
            continue
        return rule["group"]
    return None


def _self_times(ops: list) -> list:
    """[name, module, start, dur, self_dur] with nested ops' time taken out
    of the op that contains them."""
    out, stack = [], []     # stack of indices into out
    for name, mod, start, dur in sorted(ops, key=lambda o: (o[2], -o[3])):
        end = start + dur
        while stack and out[stack[-1]][2] + out[stack[-1]][3] <= start:
            stack.pop()
        if stack and end <= out[stack[-1]][2] + out[stack[-1]][3]:
            out[stack[-1]][4] -= dur
        out.append([name, mod, start, dur, dur])
        stack.append(len(out) - 1)
    return out


def _union(intervals: list) -> list:
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _span_at(host: list, t: float) -> str:
    best = None
    for name, start, dur in host:
        if start <= t < start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return f"host:{best[0]}" if best else "host:none"


def reduce_trace(trace: dict, table: dict | None = None) -> dict:
    """Busy/window seconds (averaged over devices), seconds per attribution
    group, the share left unattributed, the ten longest ops and idle gaps."""
    table = table or attribution()
    devices = trace["devices"]
    if not devices:
        return {"busy_s": 0.0, "window_s": 0.0, "groups": {}, "n_devices": 0}
    n = len(devices)
    groups: dict[str, float] = {}
    by_op: dict[tuple, float] = {}
    busy = window = 0.0
    gaps: list = []
    for dev, ops in devices.items():
        timed = _self_times(ops)
        merged = _union([(o[2], o[2] + o[3]) for o in timed])
        busy += sum(e - s for s, e in merged) / 1e9
        window += (merged[-1][1] - merged[0][0]) / 1e9
        for (s0, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((s1 - e0, e0))
        for name, mod, start, dur, self_dur in timed:
            if self_dur <= 0:
                continue
            g = group_of(mod, name, table) or "unattributed"
            groups[g] = groups.get(g, 0.0) + self_dur / 1e9
            by_op[(mod, name, g)] = by_op.get((mod, name, g), 0.0) + self_dur / 1e9
    for g in groups:
        groups[g] /= n
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    gaps.sort(reverse=True)
    total = sum(groups.values())
    return {
        "busy_s": busy / n, "window_s": window / n, "n_devices": n,
        "groups": groups,
        "unattributed_share": groups.get("unattributed", 0.0) / total if total else 0.0,
        "top_unattributed": [f"{m}:{o}" for (m, o, g), _ in top if g == "unattributed"],
        "device_ops": [[f"{m}:{o}" if m else o, s / n] for (m, o, _), s in top],
        "idle_gaps": [[_span_at(trace["host"], at), d / 1e9] for d, at in gaps[:10]],
    }
