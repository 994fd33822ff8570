"""Device time by the program's own scopes (crosscoder_tpu/obs/device_scopes.py,
obs/scopes.py; docs/OBSERVABILITY.md "Device time by scope"):

- the reader on a small recorded xplane cut from a chip window of
  ``train-live-xing-relu16k`` (tests/data/xplane_xing_sample.*);
- its rules on planted xplanes: the leaf-most scope wins, ``transpose(`` is
  the backward, a parent is inclusive, a ``while`` counts its self time, an op
  without a scope lands in ``<group>/unscoped``, an executable without names
  reads ``scoped_share`` 0 and no per-scope gauge;
- the window's close: the gauges on a LATER log line, ``_stop`` back before
  the read ends, nothing imported and no thread without a window;
- the scope table = the ``named_scope`` strings in the source = the docs' table.

All CPU, tier-1.
"""

import ast
import importlib.util
import json
import re
import subprocess
import sys
import threading
from pathlib import Path

import pytest

from crosscoder_tpu.obs import device_scopes as ds
from crosscoder_tpu.obs import scopes

ROOT = Path(__file__).resolve().parent.parent
DATA = Path(__file__).resolve().parent / "data"


# ---------------------------------------------------------------------------
# a writer of planted xplanes (the wire format the reader walks; the two
# encoders are the ones the recorded sample was cut with)

_spec = importlib.util.spec_from_file_location(
    "_cut_xplane", ROOT / "scripts" / "probes" / "_cut_xplane.py")
_cut = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cut)
_field = _cut.field


def xplane(ops, steps=2, device="/device:TPU:0", names_in="tf_op") -> bytes:
    """An XSpace of one device plane and one host plane. ``ops``:
    ``(module, op_name, start_ns, dur_ns)``; each module runs from its first
    op's start to its last op's end; the host plane holds ``steps`` events
    named ``step``."""
    stat_ids = {names_in: 1}
    meta, events = {}, []

    def meta_id(name, op_name=None):
        key = (name, op_name)
        if key not in meta:
            stats = b"" if op_name is None else _field(
                5, _field(1, stat_ids[names_in]) + _field(5, op_name))
            meta[key] = (len(meta) + 1, _field(2, name) + stats)
        return meta[key][0]

    spans = {}
    for i, (module, op_name, start, dur) in enumerate(ops):
        events.append(_field(4, _field(1, meta_id(f"%op.{i} = f32[8]", op_name))
                             + _field(2, start * 1000) + _field(3, dur * 1000)))
        lo, hi = spans.get(module, (start, start + dur))
        spans[module] = (min(lo, start), max(hi, start + dur))
    modules = [_field(4, _field(1, meta_id(f"{m}(7)")) + _field(2, lo * 1000)
                      + _field(3, (hi - lo) * 1000)) for m, (lo, hi) in spans.items()]
    plane = _field(2, device)
    plane += _field(3, _field(2, "XLA Modules") + b"".join(modules))
    plane += _field(3, _field(2, "XLA Ops") + b"".join(events))
    for ident, body in meta.values():
        plane += _field(4, _field(1, ident) + _field(2, _field(1, ident) + body))
    plane += _field(5, _field(1, 1) + _field(2, _field(1, 1) + _field(2, names_in)))
    host = _field(2, "/host:CPU")
    host += _field(3, _field(2, "main") + b"".join(
        _field(4, _field(1, 1) + _field(2, i * 10**6) + _field(3, 10**5))
        for i in range(steps)))
    host += _field(4, _field(1, 1) + _field(2, _field(1, 1) + _field(2, "step")))
    return _field(1, plane) + _field(1, host)


def _read(tmp_path, ops, **kw):
    path = tmp_path / "plugins" / "profile" / "t" / "vm.xplane.pb"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(xplane(ops, **kw))
    return ds.read_xplane(path)


SCAN, STEP = "jit__seg_scan_impl", "jit_step_fn_bare"
PRE = "jit(f)/jit(main)/"


# ---------------------------------------------------------------------------
# the rules


@pytest.mark.parametrize("path,want", [
    (PRE + "while/body/harvest/block/mlp/harvest/block/moe/route/dot_general",
     "harvest/block/moe/route"),
    (PRE + "harvest/block/attn/harvest/block/attn/rope/mul", "harvest/block/attn/rope"),
    (PRE + "harvest/block/attn/harvest/block/attn/latent/harvest/block/norm/rsqrt",
     "harvest/block/norm"),
    (PRE + "harvest/block/attn/dot_general:dot_general", "harvest/block/attn"),
    (PRE + "jvp(cc/encode)/dot_general", "cc/encode"),
    (PRE + "add", None),
    (PRE + "xharvest/embed/add", None),        # a scope is a whole path segment
    ("", None),
])
def test_the_leaf_most_registered_scope_wins(path, want):
    assert ds.scope_of(path) == want


@pytest.mark.parametrize("path,want", [
    (PRE + "transpose(jvp(cc/encode))/dot_general", "cc/encode:bwd"),
    (PRE + "transpose(jvp(cc/loss))/cc/decode/mul", "cc/decode:bwd"),
])
def test_a_path_through_transpose_is_the_scopes_backward(path, want):
    assert ds.scope_of(path) == want
    reading = {"steps": 1, "busy_s": 1e-3, "scoped_share": 1.0,
               "by_scope": {STEP: {want: 2e-3}}}
    key = f"perf/device/{want.replace('/', '.').replace(':bwd', '_bwd')}_ms_per_step"
    assert ds.gauges(reading)[key] == pytest.approx(2.0)


def test_a_parent_scope_is_inclusive_of_its_children(tmp_path):
    ops = [(SCAN, PRE + "harvest/block/attn/dot_general", 0, 100),
           (SCAN, PRE + "harvest/block/attn/harvest/block/attn/rope/mul", 100, 30),
           (SCAN, PRE + "harvest/block/mlp/harvest/block/moe/route/dot_general", 130, 20),
           (SCAN, PRE + "harvest/block/mlp/harvest/block/moe/experts/sort", 150, 50),
           (SCAN, PRE + "harvest/block/mhc/read/mul", 200, 10),
           (SCAN, PRE + "harvest/block/mhc/write/mul", 210, 15)]
    g = ds.gauges(_read(tmp_path, ops, steps=1))
    ms = lambda s: g[f"perf/device/{s}_ms_per_step"] * 1e6      # ns a step
    assert ms("harvest.block.attn.rope") == pytest.approx(30)
    assert ms("harvest.block.attn") == pytest.approx(130)
    assert ms("harvest.block.moe.route") == pytest.approx(20)
    assert ms("harvest.block.mlp") == pytest.approx(70)     # declared, not by name
    assert ms("harvest.block.mhc") == pytest.approx(25)     # opened nowhere: the sum
    assert ms("busy") == pytest.approx(225)
    assert g["perf/device/scoped_share"] == pytest.approx(100.0)
    # the roots partition the harvest: nothing is counted twice
    roots = [s for s, parent in scopes.SCOPES.items() if parent is None]
    total = sum(g.get(f"perf/device/{s.replace('/', '.')}_ms_per_step", 0.0) for s in roots)
    assert total * 1e6 == pytest.approx(225)


def test_a_while_counts_only_what_its_body_does_not_cover(tmp_path):
    ops = [(SCAN, PRE + "while", 0, 1000),                    # unscoped: 1000 - 900
           (SCAN, PRE + "while/body/harvest/block/mlp/dot_general", 100, 600),
           (SCAN, PRE + "while/body/while", 700, 300),        # nested: 300 - 200
           (SCAN, PRE + "while/body/while/body/harvest/block/norm/rsqrt", 750, 200)]
    reading = _read(tmp_path, ops, steps=2)
    row = {k: round(v * 1e9) for k, v in reading["by_scope"][SCAN].items()}
    assert row == {"harvest/unscoped": 200, "harvest/block/mlp": 600,
                   "harvest/block/norm": 200}
    assert round(reading["busy_s"] * 1e9) == 1000 and reading["steps"] == 2
    assert reading["scoped_share"] == pytest.approx(0.8)
    assert ds.gauges(reading)["perf/device/harvest.unscoped_ms_per_step"] \
        == pytest.approx(1e-4)


@pytest.mark.parametrize("module,group", [
    (SCAN, "harvest"), (STEP, "cc"), ("jit__dev_scatter", "store"),
    ("jit_convert_element_type", "other")])
def test_an_op_without_a_scope_lands_in_its_modules_group(tmp_path, module, group):
    reading = _read(tmp_path, [(module, PRE + "add", 0, 50)])
    assert reading["by_scope"] == {module: {f"{group}/unscoped": pytest.approx(50e-9)}}
    assert f"perf/device/{group}.unscoped_ms_per_step" in ds.gauges(reading)


def test_an_executable_without_names_reads_no_scope_and_says_so(tmp_path):
    """What a stale cache entry would look like: ops, modules, no op_name."""
    ops = [(SCAN, "", 0, 100), (STEP, "", 100, 100)]
    g = ds.gauges(_read(tmp_path, ops, names_in="hlo_category"))
    assert g["perf/device/scoped_share"] == 0.0
    assert sorted(g) == ["perf/device/busy_ms_per_step",
                         "perf/device/cc.unscoped_ms_per_step",
                         "perf/device/harvest.unscoped_ms_per_step",
                         "perf/device/scoped_share", "perf/device/window_steps"]


def test_a_window_without_steps_publishes_no_per_step_number(tmp_path):
    g = ds.gauges(_read(tmp_path, [(SCAN, PRE + "harvest/embed/gather", 0, 10)], steps=0))
    assert g == {"perf/device/window_steps": 0.0, "perf/device/scoped_share": 100.0}


def test_several_devices_are_averaged(tmp_path):
    one = xplane([(SCAN, PRE + "harvest/embed/gather", 0, 100)], steps=1)
    two = xplane([(SCAN, PRE + "harvest/embed/gather", 0, 300)], steps=0,
                 device="/device:TPU:1")
    path = tmp_path / "x.xplane.pb"
    path.write_bytes(one + two)
    reading = ds.read_xplane(path)
    assert reading["n_devices"] == 2 and reading["steps"] == 1
    assert reading["by_scope"][SCAN]["harvest/embed"] == pytest.approx(200e-9)
    assert reading["busy_s"] == pytest.approx(200e-9)


# ---------------------------------------------------------------------------
# the recorded window


def test_the_recorded_chip_window_reads_as_expected():
    """A few hundred op events around one train step of a traced run of
    ``train-live-xing-relu16k`` on a TPU v5 lite, cut by
    ``scripts/probes/_cut_xplane.py``; the table beside it was read once and
    looked over by hand."""
    reading = ds.read_xplane(DATA / "xplane_xing_sample.xplane.pb")
    want = json.loads((DATA / "xplane_xing_sample.expected.json").read_text())
    assert reading["steps"] == want["steps"] and reading["n_devices"] == 1
    assert reading["busy_s"] == pytest.approx(want["busy_s"])
    assert reading["scoped_share"] == pytest.approx(want["scoped_share"])
    assert set(reading["by_scope"]) == set(want["by_scope"])
    for module, row in want["by_scope"].items():
        assert reading["by_scope"][module] == pytest.approx(row), module
    # the names are the tree's: scopes of both programs, a backward, the kernels'
    flat = {s for row in reading["by_scope"].values() for s in row}
    assert {"harvest/block/mhc/write", "cc/encode", "cc/encode:bwd", "cc/adam"} <= flat
    assert [row[:3] for row in reading["longest"]] == [row[:3] for row in want["longest"]]
    # one whole train step is in the cut: its scopes with cc/unscoped are its module
    step = reading["by_scope"]["jit_step_fn_bare"]
    assert sum(step.values()) == pytest.approx(38.94e-3, rel=1e-3)      # ms: PERF.md §5
    assert step["cc/unscoped"] / sum(step.values()) < 0.002


# ---------------------------------------------------------------------------
# the table


def _named_scopes_in_the_source() -> set[str]:
    found = set()
    for path in (ROOT / "crosscoder_tpu").rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "named_scope"):
                assert node.args and isinstance(node.args[0], ast.Constant), \
                    f"{path}:{node.lineno}: a named_scope that is no literal"
                found.add(node.args[0].value)
    return found


def test_every_named_scope_in_the_source_is_in_the_table():
    in_source = _named_scopes_in_the_source()
    assert in_source <= set(scopes.SCOPES), in_source - set(scopes.SCOPES)
    # ... and the table holds nothing else but the parents that are sums
    sums = {p for p in scopes.SCOPES.values() if p} - in_source
    assert set(scopes.SCOPES) - in_source == sums == {"harvest/block/mhc"}
    assert all(p is None or p in scopes.SCOPES for p in scopes.SCOPES.values())


def test_the_docs_table_is_the_table():
    text = (ROOT / "docs" / "OBSERVABILITY.md").read_text()
    section = text.split("## Scopes: names inside the compiled programs")[1].split("\n## ")[0]
    in_docs = set()
    for line in section.splitlines():
        cells = line.split("|")
        if len(cells) >= 3 and cells[1].strip().startswith("`"):
            in_docs |= set(re.findall(r"`([a-z_]+(?:/[a-z_]+)+)`", cells[1]))
    assert in_docs == set(scopes.SCOPES)


def test_the_digest_is_of_the_names_alone(monkeypatch):
    before = scopes.digest()
    monkeypatch.setitem(scopes.SCOPES, "harvest/block/attn/rope", None)   # a parent
    assert scopes.digest() == before
    monkeypatch.setitem(scopes.SCOPES, "harvest/block/attn/qk_norm", None)
    assert scopes.digest() != before


def test_the_metric_key_lint_takes_the_family():
    from crosscoder_tpu.analysis.contracts.ast_lints import key_allowed

    assert key_allowed("perf/device/harvest.block.moe.route_ms_per_step")
    assert key_allowed("perf/device/scoped_share")


# ---------------------------------------------------------------------------
# the window's close


def _tiny_cfg(tmp_path, **kw):
    from crosscoder_tpu.config import CrossCoderConfig

    base = dict(d_in=16, dict_size=64, batch_size=32, num_tokens=32 * 400,
                enc_dtype="fp32", lr=2e-3, l1_coeff=0.02, log_backend="jsonl",
                checkpoint_dir=str(tmp_path), log_every=2, save_every=10**9)
    return CrossCoderConfig(**{**base, **kw})


def test_closing_a_window_sets_the_gauges_on_a_later_log_line(tmp_path):
    """A real ``jax.profiler`` window on the CPU backend (ops are host events
    there, and carry no names: ``scoped_share`` 0, time under ``cc.unscoped``)."""
    from crosscoder_tpu.train.trainer import Trainer
    from crosscoder_tpu.utils.logging import MetricsLogger

    cfg = _tiny_cfg(tmp_path, obs="on", profile_steps="4:8")
    Trainer(cfg, logger=MetricsLogger(cfg)).train(num_steps=40)
    rows = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    with_gauges = [r["step"] for r in rows if "perf/device/window_steps" in r]
    assert with_gauges and min(with_gauges) > 7          # after the close, not at it
    last = rows[-1]
    assert last["perf/device/window_steps"] == 4.0       # the step spans IN the xplane
    assert last["perf/device/busy_ms_per_step"] > 0
    assert last["perf/device/cc.unscoped_ms_per_step"] == pytest.approx(
        last["perf/device/busy_ms_per_step"], rel=0.2)
    assert last["perf/device/scoped_share"] == 0.0
    # carried on every later line, as the calibration gauges are
    assert with_gauges == [r["step"] for r in rows if r["step"] >= min(with_gauges)]
    # the file beside the xplane, and the read on the span clock
    found = list((tmp_path / "obs" / "profile").rglob("device_scopes.json"))
    assert len(found) == 1 and list(found[0].parent.glob("*.xplane.pb"))
    written = json.loads(found[0].read_text())
    assert written["steps"] == 4 and written["read_s"] > 0 and written["by_scope"]
    spans = json.loads((tmp_path / "obs" / "trace.json").read_text())["traceEvents"]
    read = [e for e in spans if e.get("name") == "profile_read"]
    stop = [e for e in spans if e.get("name") == "profile_stop"]
    assert len(read) == 1 and len(stop) == 1
    assert read[0]["ts"] >= stop[0]["ts"] + stop[0]["dur"]
    assert read[0]["tid"] != stop[0]["tid"]              # off the loop's thread


def test_stop_returns_before_the_read_ends(tmp_path, monkeypatch):
    import jax

    from crosscoder_tpu.obs.profiler import ProfilerWindow
    from crosscoder_tpu.obs.registry import MetricsRegistry

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    entered, release = threading.Event(), threading.Event()

    def slow_publish(profile_dir, registry):
        entered.set()
        assert release.wait(30)
        registry.gauge("perf/device/window_steps", 2.0)

    monkeypatch.setattr(ds, "publish", slow_publish)
    reg = MetricsRegistry()
    w = ProfilerWindow(_tiny_cfg(tmp_path, profile_steps="2:4"), registry=reg)
    for i in range(5):
        w.before_step(i)
        w.after_step(i)
    # the loop went on (step 4 ran) while the reader still holds the file
    assert entered.wait(30) and w.windows_captured == 1
    assert reg.get_gauge("perf/device/window_steps") is None
    assert w._reader.name == "profile-reader" and w._reader.is_alive()
    release.set()
    w.join_reader()
    assert reg.get_gauge("perf/device/window_steps") == 2.0 and w._reader is None


def test_a_window_that_left_no_file_costs_nothing(tmp_path, monkeypatch, capsys):
    import jax

    from crosscoder_tpu.obs.profiler import ProfilerWindow
    from crosscoder_tpu.obs.registry import MetricsRegistry

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    reg = MetricsRegistry()
    w = ProfilerWindow(_tiny_cfg(tmp_path, profile_steps="0:1"), registry=reg)
    w.before_step(0)
    w.after_step(0)
    w.join_reader()
    assert not any(k.startswith("perf/device/") for k in reg.snapshot())
    assert "not read" not in capsys.readouterr().err


def test_a_file_the_reader_cannot_walk_is_a_warning_not_an_error(tmp_path, monkeypatch, capsys):
    import jax

    from crosscoder_tpu.obs.profiler import ProfilerWindow

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    bad = tmp_path / "p" / "plugins" / "profile" / "t" / "vm.xplane.pb"
    bad.parent.mkdir(parents=True)
    bad.write_bytes(b"\x0a\xff\xff\xff\xff")          # a length past the file's end
    w = ProfilerWindow(_tiny_cfg(tmp_path, profile_steps="0:1",
                                 profile_dir=str(tmp_path / "p")))
    w.before_step(0)
    w.after_step(0)
    w.join_reader()
    assert "profile window not read" in capsys.readouterr().err


_NO_WINDOW_JOB = """
import sys, threading
from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.train.trainer import Trainer

obs, window = sys.argv[1], sys.argv[2]
cfg = CrossCoderConfig(d_in=16, dict_size=64, batch_size=32, num_tokens=32 * 400,
                       enc_dtype="fp32", log_backend="null", obs=obs,
                       checkpoint_dir=sys.argv[3], save_every=10**9,
                       profile_steps=window)
seen = set()
real = threading.Thread.start
def start(self):
    seen.add(self.name)
    real(self)
threading.Thread.start = start
Trainer(cfg).train(num_steps=12)
print("reader" if "crosscoder_tpu.obs.device_scopes" in sys.modules else "no-reader",
      "thread" if "profile-reader" in seen else "no-thread",
      "tensorflow" if "tensorflow" in sys.modules else "no-tensorflow")
"""


@pytest.mark.parametrize("obs,window,want", [
    ("off", "", "no-reader no-thread no-tensorflow"),
    ("on", "", "no-reader no-thread no-tensorflow"),
    ("on", "3:6", "reader thread no-tensorflow"),
])
def test_without_a_window_nothing_is_imported_and_no_thread_starts(tmp_path, obs, window, want):
    p = subprocess.run(
        [sys.executable, "-c", _NO_WINDOW_JOB, obs, window, str(tmp_path)],
        cwd=ROOT, env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
                       "PYTHONPATH": str(ROOT), "HOME": str(tmp_path)},
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split("\n")[-2] == want, p.stdout


def test_trace_report_prints_the_table_by_scope(tmp_path, capsys):
    spec = importlib.util.spec_from_file_location(
        "trace_report", ROOT / "scripts" / "trace_report.py")
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    from crosscoder_tpu.obs.trace import SpanTracer

    tracer = SpanTracer(tmp_path / "trace.json")
    with tracer.span("step"):
        pass
    tracer.flush()
    assert report.main([str(tmp_path / "trace.json")]) == 0
    assert "device time by scope" not in capsys.readouterr().out
    ops = [(SCAN, PRE + "harvest/block/mlp/dot_general", 0, 3_000_000),
           (STEP, PRE + "transpose(jvp(cc/encode))/dot_general", 3_000_000, 1_000_000)]
    window = tmp_path / "profile" / "plugins" / "profile" / "t"
    window.mkdir(parents=True)
    (window / "vm.xplane.pb").write_bytes(xplane(ops, steps=2))
    ds.publish(tmp_path / "profile", None)
    assert report.main([str(tmp_path / "trace.json")]) == 0
    out = capsys.readouterr().out
    assert "device time by scope" in out and "2 steps" in out
    row = next(l for l in out.splitlines() if "harvest/block/mlp" in l)
    assert SCAN in row and "1.500" in row                 # ms a step
    assert any("cc/encode:bwd" in l and "0.500" in l for l in out.splitlines())
