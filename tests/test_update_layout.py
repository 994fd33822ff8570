"""The layout the optimizer update is computed in (train/trainer.py;
docs/TUNING.md "The optimizer update's layout").

A step built for a mesh of TPU devices hands each gradient to the optimizer
in the layout its master is held in — the one the mesh's devices keep such
a shard in, asked of the device when the step is traced — so that the
compiled step ends without copying the new master and both new moments back
into the layout they were donated in (tests/test_chip_compile.py holds, for
a described v5e, what that removes from the step). Here, on the CPU:

- off the TPU the Trainer builds and calls exactly the step it always built;
- the constraint moves no value: a step built with the layouts its masters
  already have computes the same losses and the same state;
- steered onto the TPU branch by the test, the Trainer announces the layouts
  and lives as it lived: both variants, save and restore, a resample step;
- the remesh prewarm stores, for the mesh it targets, the program and the
  disk key the Trainer rebuilt on that mesh asks for.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crosscoder_tpu.checkpoint.ckpt import Checkpointer
from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.parallel import mesh as mesh_lib
from crosscoder_tpu.train import trainer as trainer_mod
from crosscoder_tpu.train.trainer import Trainer, make_train_step
from crosscoder_tpu.utils import compile_cache

BARE, FULL = (False, True, True), (True, True, True)
GAUGE = "perf/step_update_in_held_layout"


def tiny_cfg(tmp_path, **kw):
    base = dict(d_in=16, dict_size=64, batch_size=64, num_tokens=64 * 100,
                enc_dtype="bf16", lr=1e-3, l1_coeff=0.1, log_backend="null",
                checkpoint_dir=str(tmp_path))
    base.update(kw)
    return CrossCoderConfig(**base)


def one_device(cfg, **kw):
    """A Trainer on a 1x1 mesh (the process sees eight CPU devices)."""
    return Trainer(cfg, mesh=mesh_lib.make_mesh(1, 1, devices=jax.devices()[:1]), **kw)


def losses(tr, n):
    """n steps as the loop takes them: a full one every third, bare between."""
    return [float(tr.step(full_metrics=(i % 3 == 0))["loss"]) for i in range(n)]


def same(a, b):
    return all(np.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(jax.device_get(a)),
        jax.tree_util.tree_leaves(jax.device_get(b)), strict=True))


@pytest.fixture
def own_disk_tier():
    """The compile cache's process-global state, put back after the test;
    JAX's persistent compilation cache off meanwhile. An earlier test file
    in the same worker may have turned that cache on (tests/test_compile_cache.py),
    and on XLA:CPU an executable it serves serializes again WITHOUT its
    functions' code: a disk-tier entry written from one then fails when it
    runs (``NOT_FOUND: ... Function convert_bitcast_fusion not found``)."""
    from jax._src import compilation_cache as jax_cache

    names = ("_AOT_CACHE", "_COST_CACHE", "_COST_PENDING", "_COLLECTIVES")
    with compile_cache._LOCK:
        saved = [dict(getattr(compile_cache, n)) for n in names]
        disk, verify = compile_cache._DISK, compile_cache._VERIFY
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    jax_cache.reset_cache()
    with compile_cache._LOCK:
        for n, old in zip(names, saved):
            getattr(compile_cache, n).clear()
            getattr(compile_cache, n).update(old)
        compile_cache._DISK, compile_cache._VERIFY = disk, verify


@pytest.fixture
def as_on_tpu(monkeypatch):
    monkeypatch.setattr(trainer_mod, "update_in_held_layout", lambda mesh: True)


def test_cpu_builds_the_step_it_always_built(tmp_path):
    """Off the TPU no layout is handed over: the Trainer's variants lower to
    the text of the step built the way every Trainer built it; the gauge
    reads 0 and nothing is announced."""
    cfg = tiny_cfg(tmp_path, obs="on", obs_dir=str(tmp_path / "obs"))
    tr = one_device(cfg)
    try:
        losses(tr, 2)
        assert not trainer_mod.update_in_held_layout(tr.mesh)
        assert tr._obs.snapshot()[GAUGE] == 0.0
        assert tr._obs.snapshot()["perf/compiles"] == 2
    finally:
        tr.close()
    tr = one_device(tiny_cfg(tmp_path))
    losses(tr, 2)
    batch = jax.ShapeDtypeStruct((cfg.batch_size, cfg.n_sources, cfg.d_in), jnp.float32)
    scale = jax.ShapeDtypeStruct((cfg.n_sources,), jnp.float32)
    for key in (BARE, FULL):
        plain = make_train_step(
            tr.cfg, tr.mesh, tr._tx, mesh_lib.state_shardings(tr.mesh, tr.state),
            with_metrics=key[0])
        text = tr._step_fns[key].lower(tr.state, batch, scale).as_text()
        assert text == plain.lower(tr.state, batch, scale).as_text()
        assert "@LayoutConstraint" not in text


@pytest.mark.parametrize("over", [
    {}, dict(activation="topk", topk_k=4, l1_coeff=0.0),
    dict(activation="jumprelu", l0_coeff=0.01), dict(master_dtype="bf16"),
], ids=["relu", "topk", "jumprelu", "bf16-masters"])
def test_constraint_moves_no_value(tmp_path, monkeypatch, capfd, over):
    """The Trainer on the TPU branch — every gradient constrained to the
    layout its master is held in, which is the one the live arrays have —
    against the Trainer off it: the same mathematics (on the CPU the extra
    op moves a fusion's boundary, so a few last bits of a float32 may
    differ; on the chip it moves one partial sum of the gradient's norm:
    PERF.md §6)."""
    cfg = tiny_cfg(tmp_path, **over)
    plain = one_device(cfg)
    assert "optimizer update computed" not in capfd.readouterr().err
    monkeypatch.setattr(trainer_mod, "update_in_held_layout", lambda mesh: True)
    tr = one_device(cfg)
    for x in tr.state.params.values():
        assert mesh_lib.held_layout(x, x.sharding) == x.format.layout
    assert ("optimizer update computed in the layout each master is held in: W_dec"
            in capfd.readouterr().err)
    batch = jax.ShapeDtypeStruct((cfg.batch_size, cfg.n_sources, cfg.d_in), jnp.float32)
    scale = jax.ShapeDtypeStruct((cfg.n_sources,), jnp.float32)
    text = tr._step_fns[FULL].lower(tr.state, batch, scale).as_text()
    assert text.count("@LayoutConstraint") == len(tr.state.params)
    assert "@LayoutConstraint" not in plain._step_fns[FULL].lower(
        plain.state, batch, scale).as_text()
    got = losses(tr, 6)
    np.testing.assert_allclose(losses(plain, 6), got, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(plain.state)),
                    jax.tree_util.tree_leaves(jax.device_get(tr.state)), strict=True):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=2e-2 if a.dtype == jnp.bfloat16 else 1e-4,
                                   atol=1e-6)


def test_gauge_says_which_step_a_job_got(tmp_path, as_on_tpu):
    tr = one_device(tiny_cfg(tmp_path, obs="on", obs_dir=str(tmp_path / "obs")))
    try:
        losses(tr, 2)
        assert tr._obs.snapshot()[GAUGE] == 1.0
        assert tr._obs.snapshot()["perf/compiles"] == 2
    finally:
        tr.close()


def test_life_of_a_state_is_unchanged(tmp_path, as_on_tpu):
    """save -> restore gives equal arrays and a state the next steps take
    without compiling again; a resample step runs between constrained steps."""
    cfg = tiny_cfg(tmp_path, activation="topk", topk_k=4, l1_coeff=0.0,
                   enc_dtype="fp32", resample_every=3, resample_dead_steps=5)
    tr = one_device(cfg, checkpointer=Checkpointer(cfg=cfg))
    losses(tr, 3)
    dead = np.zeros(cfg.dict_size, np.int32)
    dead[[1, 7, 40]] = 1000
    tr.state = tr.state._replace(aux={"steps_since_fired": jnp.asarray(dead)})
    assert int(tr.step()["resampled"]) == 3
    tr.save()
    before = jax.device_get(tr.state)
    compiled = {k: fn._cache_size() for k, fn in tr._step_fns.items()}
    losses(tr, 2)
    tr.restore()
    assert same(before, tr.state) and tr._params_finite()
    losses(tr, 2)
    assert {k: fn._cache_size() for k, fn in tr._step_fns.items()} == compiled


def test_remesh_prewarm_stores_what_the_rebuilt_trainer_asks_for(
        tmp_path, as_on_tpu, own_disk_tier):
    """The prewarm builds its steps for the mesh it targets through the
    function every step is built through, so the layouts are that mesh's
    (not the live process's), and keys them as the Trainer rebuilt there
    will: both variants are then served from the disk tier — under strict
    verification, which re-lowers and refuses an entry whose program is
    not the one asked for — and nothing compiles. A cache directory filled
    by a tree whose step copied its state back is keyed apart."""
    cfg = tiny_cfg(tmp_path, compile_cache_dir=str(tmp_path / "cc"),
                   compile_cache_verify="strict", obs="on",
                   obs_dir=str(tmp_path / "obs"))
    tr = one_device(cfg)
    try:
        assert tr._compile_scope()[-1] == "update-in-held-layout"
        tr._prewarm_for_local_mesh([BARE, FULL])
        assert compile_cache.disk_entry_count() == 2
    finally:
        tr.close()
    with compile_cache._LOCK:
        compile_cache._AOT_CACHE.clear()
    target = mesh_lib.make_mesh(devices=jax.local_devices())
    assert target.devices.size > 1
    hits = compile_cache.disk_stats()["disk_hit"]
    tr = Trainer(cfg, mesh=target)
    try:
        losses(tr, 2)
        assert compile_cache.disk_stats()["disk_hit"] == hits + 2
        assert tr._obs.snapshot().get("perf/compiles", 0) == 0
    finally:
        tr.close()
