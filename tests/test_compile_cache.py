"""Tests for the persistent-compile-cache helper
(crosscoder_tpu/utils/compile_cache.py)."""

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
    finally:
        monkeypatch.undo()
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
