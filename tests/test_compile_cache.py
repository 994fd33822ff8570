"""Tests for the persistent-compile-cache helper
(crosscoder_tpu/utils/compile_cache.py)."""

import os

import jax
import pytest


@pytest.mark.parametrize("env", ["set", "empty", "unset"])
def test_compile_cache_enable(tmp_path, monkeypatch, env):
    """compile_cache.enable(): JAX_COMPILATION_CACHE_DIR set → JAX's own
    handling stands and no directory is configured here (empty = off);
    unset → the fixed <checkout>/.jax_cache. Process-global jax config
    restored whatever happens."""
    from crosscoder_tpu.utils import compile_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    prev_re = jax.config.jax_hlo_source_file_canonicalization_regex
    from jax._src import cache_key

    prev_hook = cache_key.custom_hook
    updates = []
    real_update = jax.config.update

    def spy(name, value):
        updates.append(name)
        real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    try:
        if env == "unset":
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            got = compile_cache.enable()
            # default lands inside the repo, at a fixed name
            assert got.endswith(".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            want = str(tmp_path / "env") if env == "set" else ""
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
            assert compile_cache.enable() == (want or None)
            assert "jax_compilation_cache_dir" not in updates
            assert jax.config.jax_compilation_cache_dir == prev_dir
        # file names in lowered programs lose the checkout's root, whatever
        # the directory
        import re

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        pattern = jax.config.jax_hlo_source_file_canonicalization_regex
        assert re.sub(pattern, "", os.path.join(root, "crosscoder_tpu", "x.py")) \
            == os.path.join("crosscoder_tpu", "x.py")
        # the registered scope names, and nothing else of the metadata, are
        # in every key from here on
        from crosscoder_tpu.obs import scopes

        assert cache_key.custom_hook() == f"scopes:{scopes.digest()}"
    finally:
        cache_key.custom_hook = prev_hook
        monkeypatch.undo()
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
        jax.config.update("jax_hlo_source_file_canonicalization_regex", prev_re)


_LOWER_FROM_A_COPY = """
import hashlib, os, sys
import jax, jax.numpy as jnp
import crosscoder_tpu
from crosscoder_tpu.ops import flash_attention, topk_pallas
from crosscoder_tpu.utils import compile_cache

assert crosscoder_tpu.__file__.startswith(os.getcwd()), crosscoder_tpu.__file__
compile_cache.enable()
h = jax.ShapeDtypeStruct((256, 4096), jnp.bfloat16)
q = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
for fn, args in (
        (lambda x: topk_pallas.topk(x, 32), (h,)),
        (lambda q, k, v: flash_attention.flash_attention(
            q, k, v, scale=0.1, softcap=50.0), (q, q, q))):
    text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    print(hashlib.sha256(text.encode()).hexdigest())
"""


def test_lowering_is_the_same_from_a_checkout_at_another_path(tmp_path):
    """A program that holds a Pallas call carries, in the call's payload,
    the MLIR locations of the kernel's equations — file names of the
    traceback's user frames — and the payload is in the HLO that JAX's
    persistent-cache key hashes. After ``compile_cache.enable()`` those
    names are relative to the checkout: the TopK kernel (the step programs)
    and the fused attention (the harvest programs) lower to the same text
    from two copies of the package at different paths."""
    import shutil
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "crosscoder_tpu")
    digests = []
    for name in ("a", "somewhere/else/b"):
        root = tmp_path / name
        shutil.copytree(src, root / "crosscoder_tpu",
                        ignore=shutil.ignore_patterns("__pycache__"))
        env = {**os.environ, "PYTHONPATH": str(root), "JAX_PLATFORMS": "cpu",
               "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
        p = subprocess.run(
            [sys.executable, "-c", _LOWER_FROM_A_COPY], cwd=root, env=env,
            capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        digests.append(p.stdout.split())
    assert len(digests[0]) == 2 and digests[0] == digests[1], digests


_CACHE_UNDER_A_SCOPE = """
import sys
import jax, jax.numpy as jnp
import jax.monitoring
from crosscoder_tpu.obs import scopes
from crosscoder_tpu.utils import compile_cache

scope, registered = sys.argv[1], sys.argv[2:]
for name in registered:                 # this tree's table
    scopes.SCOPES[name] = None
hits = []
jax.monitoring.register_event_listener(
    lambda event, **_: hits.append(event) if event.endswith("/cache_hits") else None)
compile_cache.enable()

@jax.jit
def f(x):
    with jax.named_scope(scope):
        return jnp.tanh(x) @ x

text = f.lower(jnp.ones((8, 8))).as_text(debug_info=True)
assert scope in text
f(jnp.ones((8, 8))).block_until_ready()
print("HIT" if hits else "MISS")
"""


def test_a_change_of_the_scope_table_and_only_that_misses_the_cache(tmp_path):
    """JAX strips op metadata before it hashes a program, so an executable
    compiled under an older table would be served with the older names. With
    the table's digest in the key (``enable``): the same program under a
    RENAMED scope of a changed table misses; the table equal, it hits — from
    the same tree again, and from a tree whose lines moved (the second call
    lowers another source string: no file name or line is in the key)."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "PYTHONPATH": root, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}

    def run(source, scope, *registered):
        p = subprocess.run([sys.executable, "-c", source, scope, *registered],
                           cwd=tmp_path, env=env, capture_output=True, text=True,
                           timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        return p.stdout.split()[-1]

    assert run(_CACHE_UNDER_A_SCOPE, "cc/encode") == "MISS"            # cold
    assert run(_CACHE_UNDER_A_SCOPE, "cc/encode") == "HIT"             # warm
    assert run("\n\n" + _CACHE_UNDER_A_SCOPE, "cc/encode") == "HIT"    # lines moved
    assert run(_CACHE_UNDER_A_SCOPE, "cc/renamed", "cc/renamed") == "MISS"
    assert run(_CACHE_UNDER_A_SCOPE, "cc/renamed", "cc/renamed") == "HIT"
    assert run(_CACHE_UNDER_A_SCOPE, "cc/encode") == "HIT"             # the old table's
