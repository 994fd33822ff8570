"""The benchmark's own tests. ``benchmarks`` is imported from the repo's root."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for place in (ROOT, Path(__file__).resolve().parent):    # ``benchmarks``; ``accepted``
    if str(place) not in sys.path:
        sys.path.insert(0, str(place))


@pytest.fixture
def root(tmp_path):
    """A copy of the benchmark's data (raw readings land under it)."""
    from benchmarks import manifest

    for sub in ("configs", "traffic", "metrics", "attribution"):
        shutil.copytree(manifest.BENCH_DIR / sub, tmp_path / "benchmarks" / sub)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest.load()))
    return tmp_path


@pytest.fixture
def code_root(root):
    """The copy, with its ``benchmarks/arch/`` and ``benchmarks/runners/``
    on the search path of those packages: a test adds an architecture or a
    runner as a later PR does, as a new file. What was imported from there is
    forgotten afterwards."""
    import benchmarks.arch
    import benchmarks.runners

    packages = (benchmarks.arch, benchmarks.runners)
    was = [list(p.__path__) for p in packages]
    for p in packages:
        home = root / "benchmarks" / p.__name__.rpartition(".")[2]
        home.mkdir(exist_ok=True)
        p.__path__.append(str(home))
    yield root
    for p, paths in zip(packages, was):
        p.__path__[:] = paths
    for name, mod in list(sys.modules.items()):
        if str(getattr(mod, "__file__", None) or "").startswith(str(root)):
            del sys.modules[name]
