"""Pieces every runner shares: what compiled, which device, the seeds, the
run's raw-readings file and its earlier output lines.

``CompileLog`` and ``init_lm_pair`` are copies of ``chip_smoke.py``'s (the
benchmark imports nothing from the smoke, so a later PR cannot change the
yardstick by editing it).
"""

from __future__ import annotations

import json
import os
import threading
import time
from pathlib import Path
from typing import Any

import numpy as np

from benchmarks import cycles, manifest


def say(msg: str) -> None:
    """An earlier line of standard output (the last one is the result)."""
    print(f"[bench] {msg}", flush=True)


class CompileLog:
    """This process's XLA compile requests (hit in the persistent cache or
    not), their seconds and the cache's hits, from JAX's monitoring events."""

    def __init__(self) -> None:
        self.requests = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self._lock = threading.Lock()   # prefetch and warm-up threads compile too

    def install(self) -> "CompileLog":
        import jax.monitoring

        def on_duration(event: str, duration: float, **_: Any) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                with self._lock:
                    self.requests += 1
                    self.seconds += duration

        def on_event(event: str, **_: Any) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                with self._lock:
                    self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def snapshot(self) -> dict:
        with self._lock:
            return {"requests": self.requests, "cache_hits": self.cache_hits,
                    "seconds": self.seconds}


def peaks_for(kind: str) -> dict:
    """The device's published peaks; an unknown kind is an error, not a default."""
    table = manifest.load_json(manifest.BENCH_DIR / "peaks.json")
    if kind not in table:
        raise RuntimeError(f"device kind {kind!r} is not in benchmarks/peaks.json")
    return table[kind]


def sub_seeds(seed: int, n: int = 4) -> list[int]:
    """Independent 31-bit seeds from ``--seed`` (which may pass 2**31)."""
    state = np.random.SeedSequence(int(seed)).generate_state(n)
    return [int(s) & 0x7FFFFFFF for s in state]


def init_lm_pair(lm_cfg: Any, seeds: list[int], sharding: Any = None) -> list:
    """Two subject models' weights from ``lm.init_params`` on the device in
    one jitted call each, in the type they are served in (eagerly the float32
    temporaries of every leaf pile up to a spike no job pays)."""
    import jax

    from crosscoder_tpu.models import lm

    init = jax.jit(lm.init_params, static_argnums=1, out_shardings=sharding)
    return [init(jax.random.key(s), lm_cfg) for s in seeds]


def device_block(devices: list, trace: dict | None = None) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        out.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
    return out


class HostLoad:
    """The process's own CPU time over the window's wall time and the
    one-minute load average at its ends: a starved host is the cheapest
    explanation of a slow run to rule in or out with tracing off."""

    def __init__(self) -> None:
        self.t0 = self.cpu0 = self.load0 = None

    def open(self) -> None:
        self.t0, self.cpu0 = time.perf_counter(), time.process_time()
        self.load0 = os.getloadavg()[0]

    def close(self) -> dict:
        wall = time.perf_counter() - self.t0
        return {"window_wall_s": wall,
                "process_cpu_over_wall": (time.process_time() - self.cpu0) / wall,
                "load1_open": self.load0, "load1_close": os.getloadavg()[0],
                "cpus": os.cpu_count()}


def say_host(h: dict) -> None:
    if h:
        say(f"host: process CPU/wall {h['process_cpu_over_wall']:.3f} over "
            f"{h['window_wall_s']:.2f} s, load1 {h['load1_open']:.2f} -> "
            f"{h['load1_close']:.2f} on {h['cpus']} CPUs")


class RunRecord:
    """The run's raw readings, written as one JSON file under
    ``benchmarks/out/<workload>/`` (listed in .gitignore), and the summaries
    printed on earlier lines."""

    def __init__(self, root: Path, workload: str, seed: int, trace: int,
                 t_start: float) -> None:
        self.dir = Path(root) / "benchmarks" / "out" / workload
        self.dir.mkdir(parents=True, exist_ok=True)
        self.path = self.dir / f"seed{seed}-trace{trace}.json"
        self.t_start = t_start
        self.data: dict[str, Any] = {"workload": workload, "seed": seed,
                                     "trace": trace, "phases": [], "series": {}}
        self._last = t_start

    def phase(self, name: str) -> None:
        """Close the set-up phase that just ended (seconds since the last)."""
        now = time.perf_counter()
        self.data["phases"].append([name, now - self._last])
        self._last = now

    def series(self, name: str, values: list[float], unit: str) -> None:
        self.data["series"][name] = {"unit": unit, "values": list(values)}
        s = cycles.summary(list(values))
        if s["n"]:
            say(f"{name}: n={s['n']} min={s['min']:.6g} median={s['median']:.6g} "
                f"max={s['max']:.6g} {unit}")
        else:
            say(f"{name}: n=0")

    def note(self, key: str, value: Any) -> None:
        self.data[key] = value

    def write(self) -> None:
        say("set-up phases (s): " + ", ".join(
            f"{n} {s:.2f}" for n, s in self.data["phases"]))
        tmp = self.path.with_suffix(".tmp")
        tmp.write_text(json.dumps(self.data, default=float))
        os.replace(tmp, self.path)
        say(f"raw readings: {self.path.relative_to(self.dir.parents[2])}")
