"""Observability plane (crosscoder_tpu/obs; docs/OBSERVABILITY.md):

- span tracer: nesting, thread-safety, Chrome trace-event schema validity
- metrics registry: all four shapes, untouched-snapshots-to-{} (the
  ResilienceCounters contract extended to perf/*)
- refill-bubble attribution: perf/refill_bubble_frac within ±0.05 of
  ground truth on a sleep-injected fake refill
- zero-cost off: step-HLO identity across cfg.obs, no extra host↔device
  transfers with obs on OR off
- profiler windows: exact [start, stop) capture, SIGUSR1 arming, legacy
  profile_dir behavior
- compile events + predicted-vs-measured comm keys in the log stream
- one plane per job on perf_counter: set-up spans in the loop's file, span
  time as per-interval totals, scope and variant names in the lowered programs
- scripts/trace_report.py summary + malformed-trace exit code
- scripts/check_metric_keys.py namespace lint
- MetricsLogger satellites: stdout stays clean, stderr echo cadence,
  non-scalar hardening

All CPU, tier-1.
"""

import importlib.util
import json
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.obs import trace
from crosscoder_tpu.obs.profiler import ProfilerWindow, parse_profile_steps
from crosscoder_tpu.obs.registry import MetricsRegistry
from crosscoder_tpu.obs.trace import NullTracer, SpanTracer
from crosscoder_tpu.train.trainer import Trainer
from crosscoder_tpu.utils.logging import MetricsLogger

_SCRIPTS = Path(__file__).parent.parent / "scripts"


def _load_script(name):
    spec = importlib.util.spec_from_file_location(name, _SCRIPTS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_cfg(**kw):
    base = dict(
        d_in=16, dict_size=64, batch_size=32, num_tokens=32 * 400,
        enc_dtype="fp32", lr=2e-3, l1_coeff=0.02, log_backend="null",
    )
    base.update(kw)
    return CrossCoderConfig(**base)


# ---------------------------------------------------------------------------
# span tracer


def test_spans_nest_and_schema_is_valid(tmp_path):
    tracer = SpanTracer(tmp_path / "trace.json")
    with tracer.span("outer", step=3):
        with tracer.span("inner"):
            time.sleep(0.002)
    tracer.instant("marker", note="x")
    path = tracer.flush()
    data = json.loads(path.read_text())
    events = data["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {"outer", "inner"}
    for e in events:
        assert e["ph"] in ("X", "i", "M")
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
            assert e["pid"] and "tid" in e
    outer = next(e for e in complete if e["name"] == "outer")
    inner = next(e for e in complete if e["name"] == "inner")
    # inner nests inside outer on the same thread track
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"step": 3}


def test_tracer_is_thread_safe(tmp_path):
    tracer = SpanTracer(tmp_path / "trace.json")
    n_threads, n_spans = 8, 200
    barrier = threading.Barrier(n_threads)      # all alive together, so
                                                # thread idents are distinct

    def worker(i):
        barrier.wait()
        for j in range(n_spans):
            with tracer.span("w", thread=i):
                pass

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events = [e for e in tracer.events() if e["ph"] == "X"]
    assert len(events) == n_threads * n_spans
    assert len({e["tid"] for e in events}) == n_threads
    json.loads(tracer.flush().read_text())      # serializes cleanly


def test_tracer_caps_events_and_counts_drops(tmp_path):
    tracer = SpanTracer(tmp_path / "trace.json")
    tracer.MAX_EVENTS = 10
    for _ in range(20):
        with tracer.span("s"):
            pass
    data = json.loads(tracer.flush().read_text())
    assert len(data["traceEvents"]) == 10
    assert data["dropped_events"] == 11     # 1 metadata event occupies a slot


def test_null_tracer_is_inert():
    t = NullTracer()
    with t.span("anything", k=1) as s:
        assert s is not None
    t.instant("x")
    t.close()
    # module-level hooks default to the null tracer
    assert isinstance(trace.get_tracer(), NullTracer) or True
    with trace.span("free"):
        pass


# ---------------------------------------------------------------------------
# registry


def test_registry_untouched_snapshots_empty():
    assert MetricsRegistry().snapshot() == {}


def test_registry_shapes_snapshot():
    r = MetricsRegistry()
    r.count("perf/things")
    r.count("perf/things", 2)
    r.gauge("perf/level", 0.5)
    r.gauge("perf/lat_ms", 10.0)
    r.gauge("perf/lat_ms", 20.0)
    for v in [1.0, 2.0, 3.0, 100.0]:
        r.observe("perf/hist", v)
    snap = r.snapshot()
    assert snap["perf/things"] == 3
    assert snap["perf/level"] == 0.5
    assert snap["perf/lat_ms"] == 20.0              # a gauge keeps the last value
    assert not hasattr(r, "ema")        # span time is per-interval totals now
    assert snap["perf/hist_n"] == 4
    assert snap["perf/hist_p50"] == 3.0
    assert snap["perf/hist_p99"] == 100.0
    assert snap["perf/hist_max"] == 100.0
    # zero counters are dropped (reference-surface discipline)
    r2 = MetricsRegistry()
    r2.count("perf/zero", 0)
    assert r2.snapshot() == {}


def test_registry_thread_safety():
    r = MetricsRegistry()

    def worker():
        for _ in range(500):
            r.count("perf/n")
            r.observe("perf/h", 1.0)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert r.get_count("perf/n") == 2000
    assert r.snapshot()["perf/h_n"] == 2000


# ---------------------------------------------------------------------------
# trainer integration: bubble fraction, compile events, trace output


class SleepySource:
    """Source whose next() stalls a fixed time and otherwise costs ~zero
    (one pre-generated batch, reserved every call) — the sleep-injected
    fake refill the bubble measurement is graded against: production time
    IS the sleep, so ground truth is exactly sleep/wall."""

    def __init__(self, cfg, sleep_s):
        from crosscoder_tpu.data.synthetic import SyntheticActivationSource

        self._batch = SyntheticActivationSource(cfg).next()
        self.sleep_s = sleep_s
        self.slept = 0.0

    def next(self):
        t0 = time.perf_counter()
        time.sleep(self.sleep_s)
        self.slept += time.perf_counter() - t0      # incl. sleep overshoot
        return self._batch


def test_refill_bubble_frac_matches_ground_truth(tmp_path):
    cfg = tiny_cfg(log_every=8, save_every=10**9, checkpoint_dir=str(tmp_path),
                   log_backend="jsonl", obs="on", prefetch=False,
                   num_tokens=32 * 30)
    src = SleepySource(cfg, sleep_s=0.06)
    tr = Trainer(cfg, buffer=src, logger=MetricsLogger(cfg))
    slept_at = []

    real_log = tr.log

    def spy_log(metrics, step):
        slept_at.append(src.slept)      # sleep total at each log point
        real_log(metrics, step)

    tr.log = spy_log
    tr.train(num_steps=17)              # logs at 0, 8, 16
    lines = [json.loads(l) for l in
             (tmp_path / "metrics.jsonl").read_text().splitlines()]
    # grade the steady-state interval (the first includes compile time):
    # ground truth = slept fraction of that interval's wall-clock (the
    # per-step interval wall is the logged step_time_ms mean × 8 steps)
    rec = lines[-1]
    assert "perf/refill_bubble_frac" in rec
    frac = rec["perf/refill_bubble_frac"]
    wall_s = rec["step_time_ms"] * (17 - 1 - 8) / 1000
    truth = (slept_at[-1] - slept_at[-2]) / wall_s
    assert frac == pytest.approx(min(1.0, truth), abs=0.05), (frac, truth)


def test_obs_on_logs_compile_and_comm_keys(tmp_path):
    cfg = tiny_cfg(log_every=2, save_every=10**9, checkpoint_dir=str(tmp_path),
                   log_backend="jsonl", obs="on", num_tokens=32 * 30)
    tr = Trainer(cfg, logger=MetricsLogger(cfg))
    tr.train(num_steps=5)
    lines = [json.loads(l) for l in
             (tmp_path / "metrics.jsonl").read_text().splitlines()]
    rec = lines[-1]
    assert rec["perf/compiles"] >= 1
    assert rec["perf/compile_s_p50"] > 0
    assert rec["perf/span/step_s"] > 0 and rec["perf/span/step_n"] == 2
    assert not any(k.endswith(("_ms", "_spans")) and k != "perf/step_wall_ms"
                   for k in rec if k.startswith("perf/")), sorted(rec)
    # predicted (comm model on the ACTUAL compiled step) next to measured
    assert "comm/predicted_wire_bytes" in rec
    assert rec["comm/h2d_transfers"] >= 5
    assert rec["comm/d2h_transfers"] >= 1
    # single-device mesh: no collectives, zero predicted wire bytes
    if jax.device_count() == 1:
        assert rec["comm/predicted_wire_bytes"] == 0.0


def test_obs_run_emits_valid_trace_with_span_taxonomy(tmp_path):
    cfg = tiny_cfg(log_every=4, save_every=10**9, checkpoint_dir=str(tmp_path),
                   obs="on", num_tokens=32 * 30)
    tr = Trainer(cfg)
    tr.train(num_steps=6)
    trace_path = tmp_path / "obs" / "trace.json"
    assert trace_path.exists()
    data = json.loads(trace_path.read_text())
    names = {e["name"] for e in data["traceEvents"] if e["ph"] == "X"}
    assert {"step", "refill_wait", "compile"} <= names
    # the global tracer is restored after close
    assert isinstance(trace.get_tracer(), NullTracer)


def test_obs_spans_cover_save_and_restore(tmp_path):
    from crosscoder_tpu.checkpoint.ckpt import Checkpointer

    cfg = tiny_cfg(checkpoint_dir=str(tmp_path), obs="on",
                   num_tokens=32 * 30, save_every=10**9)
    tr = Trainer(cfg, checkpointer=Checkpointer(cfg=cfg))
    tr.step()
    tr.save()
    tr.restore()
    tr.close()
    data = json.loads((tmp_path / "obs" / "trace.json").read_text())
    names = {e["name"] for e in data["traceEvents"] if e["ph"] == "X"}
    assert {"save", "save_write", "restore"} <= names


# ---------------------------------------------------------------------------
# zero-cost off


# the contract engine's public step-lowering harness (the same one
# scripts/analyze.py sweeps the knob lattice with) — the local copy this
# file used to carry is retired
from crosscoder_tpu.analysis.contracts.hlo_rules import \
    lower_step_text as _lower_step_text  # noqa: E402


@pytest.mark.parametrize("extra", [
    dict(obs="on", obs_dir="/tmp/x", profile_steps="3:5", log_print_every=7),
    dict(obs="on"),                                 # the plane, no window
    dict(profile_dir="/tmp/x/p"),                   # a window (read at its close), no plane
], ids=["obs-and-window", "obs-no-window", "window-no-obs"])
def test_step_hlo_independent_of_obs_config(extra):
    """cfg.obs / obs_dir / profile_steps / profile_dir / log_print_every are
    host-side knobs: the compiled train step must be byte-identical across
    them — the plane, a profile window and the reader of its close
    (obs/device_scopes.py) are nowhere in the program."""
    assert _lower_step_text(tiny_cfg()) == _lower_step_text(tiny_cfg(**extra))


def test_obs_adds_no_host_device_transfers(monkeypatch):
    """With obs ON the telemetry is host-side only: the same number of
    device_put/device_get calls as obs off over identical stepping."""
    counts = {}
    real_put, real_get = jax.device_put, jax.device_get

    def run(obs):
        put, get = [], []
        monkeypatch.setattr(jax, "device_put",
                            lambda *a, **k: (put.append(1), real_put(*a, **k))[1])
        monkeypatch.setattr(jax, "device_get",
                            lambda x: (get.append(1), real_get(x))[1])
        try:
            tr = Trainer(tiny_cfg(obs=obs, prefetch=False))
            for _ in range(5):
                tr.step(full_metrics=False)
            tr.close()
        finally:
            monkeypatch.setattr(jax, "device_put", real_put)
            monkeypatch.setattr(jax, "device_get", real_get)
        return len(put), len(get)

    counts["off"] = run("off")
    counts["on"] = run("on")
    assert counts["on"] == counts["off"], counts
    # and the off path performs zero device_get during bare steps
    assert counts["off"][1] == 0, counts


# ---------------------------------------------------------------------------
# profiler windows


class _FakeProfiler:
    def __init__(self):
        self.calls = []

    def start_trace(self, out_dir):
        self.calls.append(("start", out_dir))

    def stop_trace(self):
        self.calls.append(("stop", None))


def test_parse_profile_steps():
    assert parse_profile_steps("") is None
    assert parse_profile_steps("3:7") == (3, 7)
    for bad in ("3", "7:3", "3:3", "a:b", "-1:4", "1:2:3"):
        with pytest.raises(ValueError):
            parse_profile_steps(bad)


def test_profiler_window_exact_steps(tmp_path, monkeypatch):
    fake = _FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    cfg = tiny_cfg(profile_steps="2:4", profile_dir=str(tmp_path / "p"),
                   checkpoint_dir=str(tmp_path))
    pw = ProfilerWindow(cfg)
    synced = []
    pw.begin_stretch(0)
    for i in range(6):
        pw.before_step(i)
        started_now = pw._active
        pw.after_step(i, sync=lambda: synced.append(i))
        if i < 2 or i >= 4:
            assert not started_now or i == 3   # active only during [2, 4)
    assert fake.calls == [("start", str(tmp_path / "p")), ("stop", None)]
    assert synced == [3]                        # synced once, at the close
    assert pw.windows_captured == 1


def test_profiler_window_trainer_captures_configured_steps(tmp_path, monkeypatch):
    starts, stops = [], []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: starts.append(d))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: stops.append(1))
    cfg = tiny_cfg(profile_steps="2:4", obs="on", num_tokens=32 * 30,
                   checkpoint_dir=str(tmp_path), save_every=10**9)
    tr = Trainer(cfg)
    tr.train(num_steps=6)
    assert len(starts) == 1 and len(stops) == 1


def test_profiler_sigusr1_requests_window(tmp_path, monkeypatch):
    fake = _FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    cfg = tiny_cfg(checkpoint_dir=str(tmp_path), obs="on")
    pw = ProfilerWindow(cfg)
    assert not pw.configured            # no window configured...
    pw.begin_stretch(0)
    pw.before_step(0)
    assert fake.calls == []             # ...so nothing starts
    pw.request_window(2)                # what the SIGUSR1 handler calls
    pw.before_step(1)
    assert fake.calls and fake.calls[0][0] == "start"
    pw.after_step(1, sync=None)
    pw.before_step(2)
    pw.after_step(2, sync=None)
    assert fake.calls[-1][0] == "stop"
    assert pw.windows_captured == 1


def test_profiler_stale_window_discarded_unblocks_sigusr1(tmp_path, monkeypatch):
    """A configured absolute window whose start step already passed (a
    restore landed beyond it) is discarded, so it can neither fire at the
    wrong step nor block SIGUSR1 on-demand capture forever."""
    fake = _FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    cfg = tiny_cfg(profile_steps="2:4", checkpoint_dir=str(tmp_path))
    pw = ProfilerWindow(cfg)
    pw.begin_stretch(100)               # resumed far past the window
    pw.before_step(100)
    pw.after_step(100, sync=None)
    assert fake.calls == []             # stale window gone, nothing started
    pw.request_window(1)                # SIGUSR1 must still work
    pw.before_step(101)
    pw.after_step(101, sync=None)
    assert [c[0] for c in fake.calls] == ["start", "stop"]


def test_legacy_profile_dir_window_still_fires(tmp_path):
    """The pre-existing behavior (profile_dir set, nothing else): a real
    jax.profiler trace of the steps-10..14 window lands on disk."""
    cfg = tiny_cfg(profile_dir=str(tmp_path / "prof"), num_tokens=32 * 30,
                   checkpoint_dir=str(tmp_path), save_every=10**9)
    tr = Trainer(cfg)
    tr.train(num_steps=16)
    files = list((tmp_path / "prof").rglob("*"))
    assert any(f.is_file() for f in files), "no profiler trace written"


# ---------------------------------------------------------------------------
# scripts/trace_report.py


def test_trace_report_summarizes(tmp_path, capsys):
    tracer = SpanTracer(tmp_path / "t.json")
    for _ in range(4):
        with tracer.span("step"):
            time.sleep(0.001)
    with tracer.span("refill_wait"):
        time.sleep(0.004)
    tracer.flush()
    mod = _load_script("trace_report")
    rc = mod.main([str(tmp_path / "t.json")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "step" in out and "refill_wait" in out
    assert "refill_bubble_frac" in out
    rows, bubble = mod.summarize(mod.load_events(str(tmp_path / "t.json")))
    assert 0 < bubble < 1
    step_row = next(r for r in rows if r["span"] == "step")
    assert step_row["count"] == 4 and step_row["p50_ms"] >= 1.0


@pytest.mark.parametrize("payload", [
    "not json at all",
    '{"noTraceEvents": []}',
    '{"traceEvents": [{"ph": "X", "name": "a"}]}',       # missing ts/dur
    '{"traceEvents": [{"ph": "X", "name": "a", "ts": "x", "dur": 1}]}',
    '[42]',
])
def test_trace_report_rejects_malformed(tmp_path, payload):
    p = tmp_path / "bad.json"
    p.write_text(payload)
    mod = _load_script("trace_report")
    assert mod.main([str(p)]) != 0


# ---------------------------------------------------------------------------
# scripts/check_metric_keys.py


def test_metric_key_lint_passes_on_package():
    mod = _load_script("check_metric_keys")
    assert mod.main() == 0


def test_metric_key_lint_catches_violation():
    import ast

    mod = _load_script("check_metric_keys")
    bad = ast.parse(
        "reg.gauge('rogue_key', 1.0)\n"
        "metrics['another_rogue'] = 2\n"
        "scalars['perf/fine'] = 3\n"
        "metrics['loss'] = 0\n"
    )
    keys = [k for _, k in mod.collect_keys(bad)]
    assert set(keys) == {"rogue_key", "another_rogue", "perf/fine", "loss"}
    assert not mod.key_allowed("rogue_key")
    assert not mod.key_allowed("another_rogue")
    assert mod.key_allowed("perf/fine")
    assert mod.key_allowed("loss")
    assert mod.key_allowed("explained_variance_A")
    assert mod.key_allowed("explained_variance_3")
    assert not mod.key_allowed("perf/")          # empty tail is not a key


# ---------------------------------------------------------------------------
# MetricsLogger satellites


def test_logger_echo_goes_to_stderr_not_stdout(tmp_path, capsys):
    cfg = tiny_cfg(log_backend="jsonl", checkpoint_dir=str(tmp_path))
    logger = MetricsLogger(cfg)
    logger.log({"loss": 1.0}, step=0)
    logger.close()
    captured = capsys.readouterr()
    assert captured.out == ""                   # the bench stdout contract
    assert "loss" in captured.err


def test_logger_print_cadence(tmp_path, capsys):
    cfg = tiny_cfg(log_backend="jsonl", checkpoint_dir=str(tmp_path),
                   log_print_every=3)
    logger = MetricsLogger(cfg)
    for i in range(7):
        logger.log({"loss": float(i)}, step=i)
    logger.close()
    err = capsys.readouterr().err
    assert err.count("'loss'") == 3             # logs 0, 3, 6
    # log_print_every=0: never echo
    cfg0 = tiny_cfg(log_backend="jsonl", checkpoint_dir=str(tmp_path),
                    log_print_every=0)
    logger0 = MetricsLogger(cfg0)
    logger0.log({"loss": 1.0}, step=0)
    logger0.close()
    assert "'loss'" not in capsys.readouterr().err
    # every line still lands in the jsonl regardless of echo cadence
    lines = (tmp_path / "metrics.jsonl").read_text().strip().splitlines()
    assert len(lines) == 8


def test_logger_skips_non_scalars_with_one_warning(tmp_path, capsys):
    cfg = tiny_cfg(log_backend="jsonl", checkpoint_dir=str(tmp_path))
    logger = MetricsLogger(cfg)
    arr = np.arange(4, dtype=np.float32)
    for i in range(3):
        logger.log({"loss": 1.0, "explained_variance_per_source": arr,
                    "oops": None}, step=i)
    logger.close()
    err = capsys.readouterr().err
    assert err.count("non-scalar metric 'explained_variance_per_source'") == 1
    assert err.count("non-scalar metric 'oops'") == 1
    lines = [json.loads(l) for l in
             (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert len(lines) == 3
    for rec in lines:
        assert rec["loss"] == 1.0
        assert "explained_variance_per_source" not in rec
        assert "oops" not in rec


def test_config_validates_obs_fields():
    with pytest.raises(ValueError, match="obs"):
        tiny_cfg(obs="verbose")
    with pytest.raises(ValueError, match="log_print_every"):
        tiny_cfg(log_print_every=-1)
    with pytest.raises(ValueError, match="profile_steps"):
        tiny_cfg(profile_steps="10")
    with pytest.raises(ValueError):
        tiny_cfg(profile_steps="7:3")
    tiny_cfg(obs="on", profile_steps="3:9")     # valid combos construct


# ---------------------------------------------------------------------------
# one plane per job, one clock, per-interval totals, names in the programs


def _tiny_lm_job(tmp_path, **kw):
    """cfg, tiny LM pair and tokens for a harvest -> buffer -> train job."""
    from crosscoder_tpu.models import lm

    lm_cfg = lm.LMConfig.tiny()
    params = [lm.init_params(jax.random.key(i), lm_cfg) for i in (0, 1)]
    tokens = np.random.default_rng(7).integers(0, 257, size=(256, 17))
    cfg = CrossCoderConfig(**{**dict(
        batch_size=32, buffer_mult=8, seq_len=17, d_in=32, n_models=2,
        dict_size=64, model_batch_size=4, norm_calib_batches=2,
        hook_point="blocks.2.hook_resid_pre", seed=3, log_backend="null",
        num_tokens=32 * 40, save_every=10**9, checkpoint_dir=str(tmp_path),
    ), **kw})
    return cfg, lm_cfg, params, tokens


def test_one_plane_covers_setup_and_loop_in_one_file(tmp_path):
    """make_buffer (first) creates the plane, the Trainer adopts it: the
    set-up spans precede the first step in the same trace.json, and
    Trainer.close() hands the null tracer back."""
    from crosscoder_tpu import obs
    from crosscoder_tpu.data.buffer import make_buffer

    cfg, lm_cfg, params, tokens = _tiny_lm_job(tmp_path, obs="on", log_every=3)
    buffer = make_buffer(cfg, lm_cfg, params, tokens)
    plane = obs.acquire(cfg)
    assert plane is not None and trace.get_tracer() is plane.tracer
    tr = Trainer(cfg, buffer)
    assert tr._obs is plane
    tr.train(num_steps=7)
    assert isinstance(trace.get_tracer(), NullTracer)
    assert obs.acquire(cfg.replace(obs="off")) is None
    files = sorted(p.name for p in (tmp_path / "obs").iterdir())
    assert files == ["trace.json"]
    spans = [e for e in json.loads((tmp_path / "obs" / "trace.json").read_text())
             ["traceEvents"] if e["ph"] == "X"]
    first_step = min(e["ts"] for e in spans if e["name"] == "step")
    for name in ("calibrate", "first_fill", "init_state"):
        ends = [e["ts"] + e["dur"] for e in spans if e["name"] == name]
        assert len(ends) == 1 and ends[0] <= first_step, name
    names = {e["name"] for e in spans}
    assert {"produce", "serve_gather", "harvest_dispatch", "harvest", "refill",
            "log_interval", "log_sync", "refill_wait"} <= names
    # a production names the wait that consumed it, across threads
    waits = {e["args"]["id"]: e for e in spans if e["name"] == "refill_wait"}
    produced = [e for e in spans if e["name"] == "produce"]
    assert {e["args"]["wait"] for e in produced} >= set(waits)
    assert any(e["tid"] != waits[e["args"]["wait"]]["tid"] for e in produced
               if e["args"]["wait"] in waits)
    # step spans say which variant ran: log steps 0, 3, 6 are full
    variants = [e["args"]["variant"] for e in sorted(
        (e for e in spans if e["name"] == "step"), key=lambda e: e["ts"])]
    assert variants == ["full", "bare", "bare"] * 2 + ["full"]
    # serve_gather and harvest_dispatch nest inside a produce, by time
    for child in (e for e in spans if e["name"] in ("serve_gather", "harvest_dispatch")
                  and e["ts"] > first_step):
        assert any(p["tid"] == child["tid"] and p["ts"] <= child["ts"]
                   and child["ts"] + child["dur"] <= p["ts"] + p["dur"] + 1e-3
                   for p in produced), child


def test_span_timestamps_are_perf_counter(tmp_path):
    tracer = SpanTracer(tmp_path / "t.json")
    t0 = time.perf_counter()
    with tracer.span("a"):
        pass
    tracer.instant("b")
    a_ns = time.perf_counter_ns()
    tracer.complete("c", a_ns, a_ns + 5000, steps=2)
    t1 = time.perf_counter()
    for e in tracer.events():
        if e["ph"] in ("X", "i"):
            assert t0 * 1e6 <= e["ts"] <= t1 * 1e6, e
    c = next(e for e in tracer.events() if e["name"] == "c")
    assert c["dur"] == 5.0 and c["args"] == {"steps": 2}
    # totals since the last take, then reset
    assert tracer.take_interval() == {"a": (pytest.approx(next(
        e["dur"] for e in tracer.events() if e["name"] == "a") / 1e6), 1),
        "c": (5e-6, 1)}
    assert tracer.take_interval() == {}


def test_interval_totals_add_up_in_every_logged_row(tmp_path):
    cfg = tiny_cfg(log_every=4, save_every=10**9, checkpoint_dir=str(tmp_path),
                   log_backend="jsonl", obs="on", num_tokens=32 * 40)
    Trainer(cfg, logger=MetricsLogger(cfg)).train(num_steps=13)
    rows = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert [r["step"] for r in rows] == [0, 4, 8, 12]
    for r in rows:
        main = sum(r[f"perf/span/{n}_s"] for n in ("refill_wait", "step", "log_sync"))
        assert 0 < main <= r["perf/interval_s"]
        assert r["perf/interval_steps"] == (1 if r["step"] == 0 else 4)
        assert r["perf/span/step_n"] == r["perf/interval_steps"]
        assert r["perf/span/log_sync_n"] == 1
        assert r["perf/refill_bubble_frac"] == pytest.approx(
            r["perf/span/refill_wait_s"] / r["perf/interval_s"], rel=1e-4)
        assert r["perf/interval_s"] == pytest.approx(
            r["step_time_ms"] * r["perf/interval_steps"] / 1e3, rel=1e-4)
    # a span that did not end in an interval is absent from its row
    assert "perf/span/compile_s" in rows[0] and "perf/span/compile_s" not in rows[-1]


def test_obs_off_logs_no_perf_key_and_builds_no_plane(tmp_path):
    from crosscoder_tpu import obs
    from crosscoder_tpu.data.buffer import make_buffer

    cfg, lm_cfg, params, tokens = _tiny_lm_job(
        tmp_path, log_every=2, log_backend="jsonl")
    tr = Trainer(cfg, make_buffer(cfg, lm_cfg, params, tokens),
                 logger=MetricsLogger(cfg))
    assert tr._obs is None and not obs._PLANES
    assert isinstance(trace.get_tracer(), NullTracer)
    tr.train(num_steps=5)
    rows = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert rows and not any(k.startswith(("perf/", "comm/")) for r in rows for k in r)
    assert not (tmp_path / "obs").exists()


def _lowered_step(cfg, with_metrics):
    from crosscoder_tpu.parallel import mesh as mesh_lib
    from crosscoder_tpu.train import schedules
    from crosscoder_tpu.train.state import init_train_state, make_optimizer
    from crosscoder_tpu.train.trainer import make_train_step

    mesh = mesh_lib.make_mesh(1, 1, devices=jax.devices()[:1])
    tx = make_optimizer(cfg, schedules.lr_schedule(cfg))
    state = init_train_state(jax.random.key(0), cfg, tx, n_data=1)
    fn = make_train_step(cfg, mesh, tx, mesh_lib.state_shardings(mesh, state),
                         with_metrics=with_metrics)
    return fn.lower(
        state, jax.ShapeDtypeStruct((cfg.batch_size, cfg.n_sources, cfg.d_in), np.float32),
        jax.ShapeDtypeStruct((cfg.n_sources,), np.float32)).as_text(debug_info=True)


@pytest.mark.parametrize("activation", ["relu", "topk"])
def test_step_programs_carry_scope_and_variant_names(activation):
    cfg = tiny_cfg(activation=activation, topk_k=4,
                   l1_coeff=0.0 if activation == "topk" else 0.02)
    scopes = ["cc/encode", "cc/decode", "cc/loss", "cc/adam",
              "transpose(jvp(cc/encode))"]
    if activation == "topk":
        scopes.append("cc/select")
    for with_metrics, suffix in ((True, "step_fn_full"), (False, "step_fn_bare")):
        text = _lowered_step(cfg, with_metrics)
        assert f"module @jit_{suffix} " in text
        for scope in scopes:
            assert scope in text, (suffix, scope)


def test_harvest_store_and_serve_programs_carry_scope_names():
    from crosscoder_tpu.data import buffer as buffer_mod
    from crosscoder_tpu.models import lm
    from crosscoder_tpu.serve import step as serve_step

    lm_cfg = lm.LMConfig.tiny()
    params = lm.init_params(jax.random.key(0), lm_cfg)
    tok = jax.ShapeDtypeStruct((2, 9), np.int32)
    start = lm._seg_start_impl.lower(params, tok, cfg=lm_cfg, n_cap=1)
    assert "harvest/embed" in start.as_text(debug_info=True)
    resid, buf = jax.eval_shape(
        lambda p, t: lm._seg_start_impl(p, t, cfg=lm_cfg, n_cap=1), params, tok)
    scan = lm._seg_scan_impl.lower(
        params, resid, buf, np.int32(0), cfg=lm_cfg, capture=((2, 0),), k=2,
    ).as_text(debug_info=True)
    assert "harvest/block/attn" in scan and "harvest/block/mlp" in scan
    store = jax.ShapeDtypeStruct((64, 2, 8), jax.numpy.bfloat16)
    acts = jax.ShapeDtypeStruct((2, 5, 2, 8), jax.numpy.bfloat16)
    idx = jax.ShapeDtypeStruct((8,), np.int32)
    assert "store/scatter" in buffer_mod._dev_scatter.lower(
        store, idx, acts).as_text(debug_info=True)
    assert "store/gather" in buffer_mod._dev_gather.lower(
        store, idx).as_text(debug_info=True)
    from crosscoder_tpu.models import crosscoder

    cfg = tiny_cfg(activation="topk", topk_k=4, l1_coeff=0.0)
    cc_params = jax.eval_shape(lambda k: crosscoder.init_params(k, cfg), jax.random.key(0))
    text = serve_step.encode_topk_diff.lower(
        cc_params, jax.ShapeDtypeStruct((4, 8, cfg.n_sources, cfg.d_in), np.float32),
        jax.ShapeDtypeStruct((4,), np.int32),
        jax.ShapeDtypeStruct((cfg.n_sources,), np.float32),
        enc_dtype=cfg.enc_dtype, k=cfg.topk_k, fused=False,
        pair=serve_step.diff_pair(cfg.n_sources, cfg.n_models),
    ).as_text(debug_info=True)
    assert "serve/encode_topk_diff" in text and "cc/encode" in text


def test_profiler_window_records_its_place_on_the_span_clock(tmp_path, monkeypatch):
    fake = _FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    tracer = SpanTracer(tmp_path / "t.json")
    prev = trace.set_tracer(tracer)
    try:
        w = ProfilerWindow(tiny_cfg(profile_steps="2:4", profile_dir=str(tmp_path)))
        synced = []
        for i in range(6):
            w.before_step(i)
            w.after_step(i, sync=lambda: synced.append(i))
    finally:
        trace.set_tracer(prev)
    spans = {e["name"]: e for e in tracer.events() if e["ph"] == "X"}
    win, stop = spans["profile_window"], spans["profile_stop"]
    assert synced == [3] and [c[0] for c in fake.calls] == ["start", "stop"]
    assert win["ts"] <= stop["ts"]
    assert stop["ts"] + stop["dur"] <= win["ts"] + win["dur"] + 1e-3



def test_trace_report_prints_self_time_of_produce(tmp_path, capsys):
    """A span less the spans nested in it on its own thread; a span of the
    same time on another thread is no child."""
    tracer = SpanTracer(tmp_path / "t.json")
    t0 = time.perf_counter_ns()
    ms = 1_000_000
    tracer.complete("produce", t0, t0 + 10 * ms)
    tracer.complete("serve_gather", t0 + 1 * ms, t0 + 3 * ms)
    tracer.complete("harvest_dispatch", t0 + 4 * ms, t0 + 9 * ms)
    other = threading.Thread(
        target=lambda: tracer.complete("step", t0 + 2 * ms, t0 + 6 * ms))
    other.start()
    other.join()
    tracer.flush()
    mod = _load_script("trace_report")
    assert mod.main([str(tmp_path / "t.json")]) == 0
    assert "self_ms" in capsys.readouterr().out
    rows, _ = mod.summarize(mod.load_events(str(tmp_path / "t.json")))
    by = {r["span"]: r for r in rows}
    assert by["produce"]["total_ms"] == pytest.approx(10.0)
    assert by["produce"]["self_ms"] == pytest.approx(3.0)
    assert by["step"]["self_ms"] == pytest.approx(4.0)
    assert by["harvest_dispatch"]["self_ms"] == pytest.approx(5.0)
