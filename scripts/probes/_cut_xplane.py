#!/usr/bin/env python
"""Cut a profile window's ``.xplane.pb`` down to a sample small enough to keep
with the tests (tests/data/xplane_xing_sample.xplane.pb was made by this).

    python scripts/probes/_cut_xplane.py <in.xplane.pb> <out.xplane.pb> [before] [after]

Keeps, of each TPU plane, the "XLA Ops" events from ``before`` ops ahead of the
first op of the first ``jit_step_fn_bare`` module event to ``after`` ops past
it (harvest, store and one whole train step), the "XLA Modules" events they
start in, and of each event's metadata its id, a shortened name, its display
name and the ``tf_op`` stat; of the host planes the first two ``step``
annotations. Everything else — other planes, lines, stats — is dropped. The
walker is the job's own (``crosscoder_tpu/obs/device_scopes.py``)."""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from crosscoder_tpu.obs import device_scopes as ds     # noqa: E402


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(no: int, value) -> bytes:
    if isinstance(value, int):
        return varint(no << 3) + varint(value)
    data = value.encode() if isinstance(value, str) else value
    return varint(no << 3 | 2) + varint(len(data)) + data


def event(meta: int, offset: int, dur: int) -> bytes:
    return field(4, field(1, meta) + field(2, offset) + field(3, dur))


def metadata(buf: bytes, plane: dict, ident: int, keep_stat: int | None) -> bytes:
    body = field(1, ident)
    for no, _, v in ds._fields(buf, *plane["events_meta"][ident]):
        if no == 2:
            body += field(2, ds._text(buf, v)[:160])
        elif no == 4:
            body += field(4, buf[v[0]:v[1]])
        elif no == 5 and keep_stat is not None:
            first = next(iter(ds._fields(buf, *v)), None)
            if first and first[0] == 1 and first[2] == keep_stat:
                body += field(5, buf[v[0]:v[1]])
    return field(4, field(1, ident) + field(2, body))


def cut(buf: bytes, before: int, after: int) -> bytes:
    out = b""
    for no, _, span in ds._fields(buf, 0, len(buf)):
        if no != 1:
            continue
        plane = ds._plane(buf, span)
        name = plane["name"]
        body = field(2, name)
        if name.startswith("/device:") and "TPU" in name:
            tf_op = next((i for i, n in plane["stat_names"].items() if n == "tf_op"), None)
            lines = {l[0]: l for l in plane["lines"]}
            mods = [(t0 * 1000 + off, dur, meta, t0, off) for t0, evs in
                    [(lines["XLA Modules"][1], lines["XLA Modules"][2])]
                    for meta, off, dur, _ in (ds._event(buf, e) for e in evs)]
            t0_ops = lines["XLA Ops"][1]
            ops = sorted((t0_ops * 1000 + off, dur, meta, off) for meta, off, dur, _ in
                         (ds._event(buf, e) for e in lines["XLA Ops"][2]))
            first_step = min(m[0] for m in mods
                             if ds._metadata(buf, plane, m[2])[0].startswith("jit_step_fn_bare"))
            at = next(i for i, o in enumerate(ops) if o[0] >= first_step)
            kept = ops[max(at - before, 0):at + after]
            lo, hi = kept[0][0], kept[-1][0] + kept[-1][1]
            kept_mods = [m for m in mods if m[0] < hi and m[0] + m[1] > lo]
            body += field(3, field(2, "XLA Modules") + field(3, lines["XLA Modules"][1])
                          + b"".join(event(m[2], m[4], m[1]) for m in kept_mods))
            body += field(3, field(2, "XLA Ops") + field(3, t0_ops)
                          + b"".join(event(o[2], o[3], o[1]) for o in kept))
            for ident in sorted({o[2] for o in kept} | {m[2] for m in kept_mods}):
                body += metadata(buf, plane, ident, tf_op)
            if tf_op is not None:
                body += field(5, field(1, tf_op) + field(2, field(1, tf_op) + field(2, "tf_op")))
            print(f"{name}: {len(kept)} of {len(ops)} ops, {len(kept_mods)} of "
                  f"{len(mods)} module events", file=sys.stderr)
        elif name.startswith("/host:"):
            steps = {i for i in plane["events_meta"]
                     if ds._metadata(buf, plane, i)[0] == "step"}
            n = 0
            for lname, t0, events in plane["lines"]:
                evs = [e for e in (ds._event(buf, s) for s in events) if e[0] in steps]
                evs = evs[:max(0, 2 - n)]
                n += len(evs)
                if evs:
                    body += field(3, field(2, lname) + field(3, t0)
                                  + b"".join(event(m, off, dur) for m, off, dur, _ in evs))
            if not n:
                continue
            for ident in sorted(steps):
                body += metadata(buf, plane, ident, None)
        else:
            continue
        out += field(1, body)
    return out


def main(argv: list[str]) -> int:
    src, dst = Path(argv[1]), Path(argv[2])
    before, after = (int(a) for a in (argv[3:5] + ["350", "450"][len(argv[3:5]):]))
    dst.write_bytes(cut(src.read_bytes(), before, after))
    print(f"{dst}: {dst.stat().st_size} bytes", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
