"""Device time by the program's own scopes: the reader of a profile window.

``jax.profiler`` leaves an ``.xplane.pb``; this module reads it inside the
job, with no TensorFlow and no protobuf package, and turns it into
``{(XLA module, scope): self seconds}`` — what
:class:`~crosscoder_tpu.obs.profiler.ProfilerWindow` publishes as the
``perf/device/*`` gauges when a window closes (docs/OBSERVABILITY.md
"Device time by scope"). Imported only when a window closes.

- The time of an op is its SELF time: an op that contains others (a
  ``while`` and the ops of its body) counts only what its children do not
  cover, so nothing is counted twice.
- The scope of an op is the LEAF-MOST registered scope (``obs/scopes.py``)
  in its ``op_name`` path — the stat ``tf_op`` of the op's event metadata:
  ``jit(f)/jit(main)/harvest/block/mlp/harvest/block/moe/route/dot_general``
  is ``harvest/block/moe/route``. A path through ``transpose(`` is the
  scope's backward (``cc/encode:bwd``: JAX names transposed ops after the
  forward's). An op with no registered scope is ``<group>/unscoped`` under
  the group of its XLA module (``scopes.GROUPS``).
- A FUSION carries one ``op_name``, its root's: a fusion that swallowed ops
  of two scopes is credited whole to the one it names.

The file format is ``tsl/profiler/protobuf/xplane.proto``; the field numbers
below are those of the generated ``xplane_pb2.py`` (tensorflow 2.x):

    XSpace          planes=1
    XPlane          id=1 name=2 lines=3 event_metadata=4 stat_metadata=5 stats=6
                    (4 and 5 are map<int64, X…Metadata>: key=1 value=2)
    XLine           id=1 name=2 timestamp_ns=3 events=4 duration_ps=9
                    display_id=10 display_name=11
    XEvent          metadata_id=1 offset_ps=2 duration_ps=3 stats=4
                    num_occurrences=5
    XStat           metadata_id=1 double_value=2 uint64_value=3 int64_value=4
                    str_value=5 bytes_value=6 ref_value=7
    XEventMetadata  id=1 name=2 metadata=3 display_name=4 stats=5 child_id=6
    XStatMetadata   id=1 name=2 description=3
"""

from __future__ import annotations

import functools
import json
import os
import re
import time
from pathlib import Path
from typing import Any, Iterator

from crosscoder_tpu.obs import scopes

_BWD = ":bwd"
# the stat of an op's event metadata that holds its op_name path (on a TPU:
# ``jit(f)/…/cc/encode/dot_general:`` — a fusion's may join several by ``;``)
_NAME_STAT = "tf_op"
_SCOPE_RE = re.compile(
    r"(?:^|[/(])("
    + "|".join(re.escape(s) for s in sorted(scopes.SCOPES, key=len, reverse=True))
    + r")(?=[/)]|$)")
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")


# ---------------------------------------------------------------------------
# the wire format: varints and length-delimited fields, nothing else


def _fields(buf: bytes, pos: int, end: int) -> Iterator[tuple[int, int, Any]]:
    """(field number, wire type, value) of one message: an int for a varint,
    ``(start, end)`` for a length-delimited field; fixed-width fields are
    skipped (the messages read here hold a double in XStat only)."""
    while pos < end:
        tag = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            tag |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = tag & 7
        if wire == 0 or wire == 2:
            val = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            if wire == 2:
                yield tag >> 3, 2, (pos, pos + val)
                pos += val
            else:
                yield tag >> 3, 0, val
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {pos}")


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span: tuple[int, int]) -> tuple[int, tuple[int, int]]:
    key, value = 0, (0, 0)
    for no, _, v in _fields(buf, *span):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _stats(buf: bytes, spans: list, stat_names: dict[int, str],
           wanted: tuple[str, ...]) -> dict[str, str]:
    """The ``wanted`` string stats of a list of XStat messages (a ``ref_value``
    points at a stat metadata's name: the interned strings)."""
    out: dict[str, str] = {}
    for span in spans:
        name, value = None, None
        for no, _, v in _fields(buf, *span):
            if no == 1:
                name = stat_names.get(v)
            elif no == 5:
                value = _text(buf, v)
            elif no == 7:
                value = stat_names.get(v, "")
            elif no in (3, 4):
                value = str(v)
        if name in wanted and value is not None:
            out[name] = value
    return out


def _plane(buf: bytes, span: tuple[int, int]) -> dict:
    """One XPlane: its name, its lines as ``(name, timestamp_ns, [event
    spans])`` and its two metadata maps, still undecoded."""
    name, lines, events_meta, stat_names = "", [], {}, {}
    for no, _, v in _fields(buf, *span):
        if no == 2:
            name = _text(buf, v)
        elif no == 3:
            lname, t0, events = "", 0, []
            for lno, _, lv in _fields(buf, *v):
                if lno == 2:
                    lname = _text(buf, lv)
                elif lno == 3:
                    t0 = lv
                elif lno == 4:
                    events.append(lv)
            lines.append((lname, t0, events))
        elif no == 4:
            key, value = _map_entry(buf, v)
            events_meta[key] = value
        elif no == 5:
            key, value = _map_entry(buf, v)
            for sno, _, sv in _fields(buf, *value):
                if sno == 2:
                    stat_names[key] = _text(buf, sv)
    return {"name": name, "lines": lines, "events_meta": events_meta,
            "stat_names": stat_names}


def _event(buf: bytes, span: tuple[int, int]) -> tuple[int, int, int, list]:
    """(metadata id, offset ps, duration ps, [stat spans]) of one XEvent."""
    meta = offset = dur = 0
    stats = []
    for no, _, v in _fields(buf, *span):
        if no == 1:
            meta = v
        elif no == 2:
            offset = v
        elif no == 3:
            dur = v
        elif no == 4:
            stats.append(v)
    return meta, offset, dur, stats


def _metadata(buf: bytes, plane: dict, meta_id: int) -> tuple[str, dict[str, str]]:
    """An XEventMetadata's name (the short ``display_name`` where it has one:
    on a TPU ``name`` is the whole HLO instruction) and, of its stats, the
    op_name."""
    name, display, stat_spans = "", "", []
    for no, _, v in _fields(buf, *plane["events_meta"].get(meta_id, (0, 0))):
        if no == 2:
            name = _text(buf, v)
        elif no == 4:
            display = _text(buf, v)
        elif no == 5:
            stat_spans.append(v)
    return display or name, _stats(buf, stat_spans, plane["stat_names"], (_NAME_STAT,))


# ---------------------------------------------------------------------------
# from a file to {(module, scope): seconds}


@functools.lru_cache(maxsize=None)      # (a window's 67 k events share ~1 k paths)
def scope_of(op_name: str) -> str | None:
    """The leaf-most registered scope in an ``op_name`` path, ``:bwd`` where
    the path passes through ``transpose(``; None where it names none."""
    last = None
    for last in _SCOPE_RE.finditer(op_name):
        pass
    if last is None:
        return None
    return last[1] + (_BWD if "transpose(" in op_name else "")


def group_of(module: str) -> str:
    for group, pattern in scopes.GROUPS:
        if re.search(pattern, module):
            return group
    return "other"


def self_times(ops: list) -> list:
    """``[start, dur, self_dur, *rest]`` for ``[start, dur, *rest]``: a
    nested op's time taken out of the op that contains it."""
    out, stack = [], []
    for op in sorted(ops, key=lambda o: (o[0], -o[1])):
        start, dur = op[0], op[1]
        while stack and stack[-1][0] + stack[-1][1] <= start:
            stack.pop()
        if stack and start + dur <= stack[-1][0] + stack[-1][1]:
            stack[-1][2] -= dur
        row = [start, dur, dur, *op[2:]]
        out.append(row)
        stack.append(row)
    return out


def _device_ops(buf: bytes, plane: dict) -> list:
    """``[start_ps, dur_ps, module, op_name, op]`` of a TPU plane's "XLA Ops"
    line, the module being the "XLA Modules" event an op starts in."""
    modules, raw = [], []
    module_names: dict[int, str] = {}
    for lname, t0, events in plane["lines"]:
        if lname not in ("XLA Modules", "XLA Ops"):
            continue
        for span in events:
            meta, offset, dur, _ = _event(buf, span)
            start = t0 * 1000 + offset
            if lname == "XLA Ops":
                raw.append((start, dur, meta))
                continue
            if meta not in module_names:
                module_names[meta] = _MODULE_SUFFIX.sub("", _metadata(buf, plane, meta)[0])
            modules.append((start, start + dur, module_names[meta]))
    modules.sort()
    named: dict[int, tuple[str, str]] = {}      # metadata id -> (op_name, op)
    ops, j = [], 0
    for start, dur, meta in sorted(raw):
        while j + 1 < len(modules) and modules[j + 1][0] <= start:
            j += 1
        inside = modules and modules[j][0] <= start < modules[j][1]
        if meta not in named:
            op, stats = _metadata(buf, plane, meta)
            named[meta] = (stats.get(_NAME_STAT, ""), op)
        ops.append([start, dur, modules[j][2] if inside else "", *named[meta]])
    return ops


def _host_ops(buf: bytes, plane: dict, devices: dict[str, list] | None) -> int:
    """Of a host plane: the number of ``step`` annotations (the tracer's
    spans, mirrored into the profile), and — into ``devices``, where no
    device plane gave any op: the CPU — the events that carry an
    ``hlo_module`` stat, as ops of device ``cpu:<ordinal>``."""
    wanted = ("hlo_module", "device_ordinal", _NAME_STAT)
    names = {i: _metadata(buf, plane, i)[0] for i in plane["events_meta"]}
    step_ids = {i for i, name in names.items() if name == "step"}
    steps = 0
    for _, t0, events in plane["lines"]:
        for span in events:
            if devices is None and buf[span[0]] == 0x08 and buf[span[0] + 1] < 0x80:
                steps += buf[span[0] + 1] in step_ids   # metadata_id, one byte
                continue
            meta, offset, dur, stat_spans = _event(buf, span)
            if meta in step_ids:
                steps += 1
            elif devices is not None and stat_spans and dur > 0:
                stats = _stats(buf, stat_spans, plane["stat_names"], wanted)
                if "hlo_module" in stats:
                    devices.setdefault(f"cpu:{stats.get('device_ordinal', 0)}", []).append(
                        [t0 * 1000 + offset, dur, stats["hlo_module"],
                         stats.get(_NAME_STAT, ""), names.get(meta, "")])
    return steps


def newest_xplane(profile_dir: str | os.PathLike) -> Path | None:
    files = sorted(Path(profile_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def read_xplane(path: str | os.PathLike) -> dict:
    """The window's device time: ``by_scope`` — ``{module: {scope: seconds}}``
    of self time, averaged over devices —, ``busy_s`` (the union of the ops'
    intervals, likewise), the ``step`` annotations counted, the share of the
    self time whose op carried a registered scope, and ``longest`` — the ten
    ops of most self time as ``[module, op, scope, seconds]``: a fusion is
    credited whole to the scope it names, and these are the ones to judge."""
    buf = Path(path).read_bytes()
    devices: dict[str, list] = {}
    host_planes, steps = [], 0
    for no, _, span in _fields(buf, 0, len(buf)):
        if no != 1:
            continue
        plane = _plane(buf, span)
        if plane["name"].startswith("/device:") and "TPU" in plane["name"]:
            ops = _device_ops(buf, plane)
            if ops:
                devices[plane["name"]] = ops
        elif plane["name"].startswith("/host:"):
            host_planes.append(plane)
    on_host = None if devices else devices      # the CPU: ops are host events
    for plane in host_planes:
        steps += _host_ops(buf, plane, on_host)
    n = max(len(devices), 1)
    by_scope: dict[str, dict[str, float]] = {}
    by_op: dict[tuple, float] = {}
    busy = scoped = total = 0.0
    for ops in devices.values():
        end = 0
        for start, dur, self_dur, module, op_name, op in self_times(ops):
            if start + dur > end:
                busy += start + dur - max(start, end)
                end = start + dur
            if self_dur <= 0:
                continue
            scope = scope_of(op_name)
            total += self_dur
            if scope is None:
                scope = f"{group_of(module)}/unscoped"
            else:
                scoped += self_dur
            row = by_scope.setdefault(module, {})
            row[scope] = row.get(scope, 0.0) + self_dur / 1e12 / n
            by_op[module, op, scope] = by_op.get((module, op, scope), 0.0) + self_dur / 1e12 / n
    longest = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return {"file": str(path), "n_devices": len(devices), "steps": steps,
            "busy_s": busy / 1e12 / n,
            "scoped_share": scoped / total if total else 0.0,
            "by_scope": by_scope,
            "longest": [[*key, seconds] for key, seconds in longest]}


def gauges(reading: dict) -> dict[str, float]:
    """The ``perf/device/*`` gauges of one reading. Per step by the window's
    own count of ``step`` annotations; a parent scope inclusive of its
    children (``scopes.SCOPES``); no per-scope gauge where no op carried a
    scope (an executable served without names reads ``scoped_share`` 0)."""
    steps = reading["steps"]
    out = {"perf/device/window_steps": float(steps),
           "perf/device/scoped_share": 100.0 * reading["scoped_share"]}
    if not steps:
        return out
    ms: dict[str, float] = {}
    for row in reading["by_scope"].values():
        for scope, seconds in row.items():
            name, bwd, _ = scope.partition(_BWD)
            while name is not None:
                key = name + ("_bwd" if bwd else "")
                ms[key] = ms.get(key, 0.0) + 1e3 * seconds / steps
                name = scopes.SCOPES.get(name)
    out["perf/device/busy_ms_per_step"] = 1e3 * reading["busy_s"] / steps
    for key, value in ms.items():
        out[f"perf/device/{key.replace('/', '.')}_ms_per_step"] = value
    return out


def publish(profile_dir: str | os.PathLike, registry: Any | None) -> dict | None:
    """Read the newest window under ``profile_dir``, write
    ``device_scopes.json`` beside its xplane and set the gauges in
    ``registry`` (where there is one). None where there is no xplane."""
    path = newest_xplane(profile_dir)
    if path is None:
        return None
    t0 = time.perf_counter()
    reading = read_xplane(path)
    reading["read_s"] = time.perf_counter() - t0
    tmp = path.with_name("device_scopes.json.tmp")
    tmp.write_text(json.dumps(reading, indent=1, sort_keys=True))
    os.replace(tmp, path.with_name("device_scopes.json"))
    if registry is not None:
        for key, value in gauges(reading).items():
            registry.gauge(key, value)
    return reading
