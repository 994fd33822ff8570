"""AuxK dead-latent mitigation (cfg.aux_k — the standard TopK-SAE recipe,
Gao et al. 2024; no reference counterpart, the reference's dense ReLU never
faces mass latent death).

Oracle strategy (SURVEY.md §4): an independent numpy re-statement of the
aux-loss math, fed identical inputs, asserted against the jitted path in
fp32; plus behavioral tests — fired-tracking semantics, the no-dead-latents
noninterference guarantee, the gradient path to dead latents that the main
TopK objective cannot provide, checkpoint round-trip of the tracker, and
the sharded step on an 8-device mesh.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from crosscoder_tpu.config import CrossCoderConfig
from crosscoder_tpu.models import crosscoder as cc
from crosscoder_tpu.train.trainer import Trainer
from crosscoder_tpu.train import schedules
from crosscoder_tpu.train.state import init_train_state, make_optimizer


def _cfg(**kw):
    base = dict(
        d_in=16, dict_size=64, n_models=2, batch_size=32,
        num_tokens=32 * 1000, enc_dtype="fp32", log_backend="null",
        aux_k=8, aux_dead_steps=3, l1_coeff=0.0,
    )
    base.update(kw)
    return CrossCoderConfig(**base)


def _numpy_aux_loss(params, x, dead_mask, k_aux):
    """Independent fp32 oracle of the AuxK loss: residual of the MAIN
    reconstruction, reconstructed by the top-k_aux raw pre-acts among dead
    latents through W_dec (no b_dec), normalized by the residual's power."""
    w_enc = np.asarray(params["W_enc"], np.float32)
    w_dec = np.asarray(params["W_dec"], np.float32)
    b_enc = np.asarray(params["b_enc"], np.float32)
    b_dec = np.asarray(params["b_dec"], np.float32)
    h = np.einsum("bnd,ndh->bh", x, w_enc) + b_enc
    f = np.maximum(h, 0.0)
    recon = np.einsum("bh,hnd->bnd", f, w_dec) + b_dec
    e = x - recon
    masked = np.where(dead_mask[None, :], h, -np.inf)
    order = np.argsort(-masked, axis=-1, kind="stable")[:, :k_aux]
    vals = np.take_along_axis(masked, order, axis=-1)
    vals = np.where(np.isfinite(vals), vals, 0.0)
    e_hat = np.einsum("bk,bknd->bnd", vals, w_dec[order])
    num = np.mean(np.sum((e_hat - e) ** 2, axis=(-2, -1)))
    den = np.mean(np.sum(e ** 2, axis=(-2, -1)))
    if not dead_mask.any():
        return 0.0
    return num / (den + 1e-8)


def test_aux_loss_matches_numpy_oracle():
    cfg = _cfg(activation="relu")
    rng = np.random.default_rng(0)
    params = cc.init_params(jax.random.key(1), cfg, dtype=jnp.float32)
    x = rng.standard_normal((cfg.batch_size, cfg.n_sources, cfg.d_in)).astype(np.float32)
    dead = np.zeros(cfg.dict_size, bool)
    dead[::5] = True                      # 13 dead > aux_k=8: real top-k path
    losses = cc.get_losses(params, jnp.asarray(x), cfg, dead_mask=jnp.asarray(dead))
    want = _numpy_aux_loss(params, x, dead, cfg.aux_k)
    np.testing.assert_allclose(float(losses.aux_loss), want, rtol=1e-5)


def test_aux_loss_fewer_dead_than_aux_k():
    # -inf padding rows must contribute exactly nothing
    cfg = _cfg(activation="relu")
    rng = np.random.default_rng(2)
    params = cc.init_params(jax.random.key(3), cfg, dtype=jnp.float32)
    x = rng.standard_normal((cfg.batch_size, cfg.n_sources, cfg.d_in)).astype(np.float32)
    dead = np.zeros(cfg.dict_size, bool)
    dead[[4, 17]] = True                  # 2 dead < aux_k=8
    losses = cc.get_losses(params, jnp.asarray(x), cfg, dead_mask=jnp.asarray(dead))
    want = _numpy_aux_loss(params, x, dead, cfg.aux_k)
    np.testing.assert_allclose(float(losses.aux_loss), want, rtol=1e-5)


def test_aux_loss_zero_when_nothing_dead():
    cfg = _cfg(activation="relu")
    rng = np.random.default_rng(4)
    params = cc.init_params(jax.random.key(5), cfg, dtype=jnp.float32)
    x = rng.standard_normal((cfg.batch_size, cfg.n_sources, cfg.d_in)).astype(np.float32)
    dead = np.zeros(cfg.dict_size, bool)
    losses = cc.get_losses(params, jnp.asarray(x), cfg, dead_mask=jnp.asarray(dead))
    assert float(losses.aux_loss) == 0.0


@pytest.mark.parametrize("activation,factored", [
    ("relu", False), ("topk", False), ("topk", True), ("batchtopk", False),
])
def test_fired_matches_dense_activity(activation, factored, monkeypatch):
    """``factored``: the TopK step through the factored decode (the TopK
    kernel in the interpreter), whose ``fired`` is the scatter of the k
    active latents' hits rather than a reduction over dense activations."""
    cfg = _cfg(activation=activation, topk_k=4)
    if factored:
        from crosscoder_tpu.ops import topk_pallas

        monkeypatch.setattr(topk_pallas, "_INTERPRET", True)
        # the kernel's narrowest row is 256 latents
        cfg = cfg.replace(factored_decode="on", dict_size=256)
        assert cc.use_factored_decode(cfg)
    rng = np.random.default_rng(6)
    params = cc.init_params(jax.random.key(7), cfg, dtype=jnp.float32)
    x = jnp.asarray(
        rng.standard_normal((cfg.batch_size, cfg.n_sources, cfg.d_in)), jnp.float32
    )
    dead = jnp.zeros(cfg.dict_size, bool)
    losses = cc.get_losses(params, x, cfg, dead_mask=dead)
    f = cc.encode(params, x, cfg)
    want = np.asarray(jnp.any(f > 0, axis=0))
    np.testing.assert_array_equal(np.asarray(losses.fired), want)


def test_no_dead_latents_means_identical_training():
    # aux_dead_steps larger than the run: the aux term must never engage and
    # the trajectory must equal the aux-free config's exactly
    cfg_off = _cfg(activation="topk", topk_k=4, aux_k=0)
    cfg_on = _cfg(activation="topk", topk_k=4, aux_k=8, aux_dead_steps=10**6)
    losses = {}
    for name, cfg in (("off", cfg_off), ("on", cfg_on)):
        tr = Trainer(cfg)
        vals = []
        for _ in range(4):
            vals.append(float(jax.device_get(tr.step()["loss"])))
        tr.close()
        losses[name] = vals
    np.testing.assert_allclose(losses["on"], losses["off"], rtol=1e-6)


def test_aux_gives_dead_latent_a_gradient_path():
    # a latent TopK never selects gets NO gradient from the main objective;
    # with it marked dead, the aux loss must deliver one to its encoder row
    cfg = _cfg(activation="topk", topk_k=2)
    params = cc.init_params(jax.random.key(11), cfg, dtype=jnp.float32)
    # bury latent 0: huge negative encoder bias → never in the top-k
    params["b_enc"] = params["b_enc"].at[0].set(-100.0)
    rng = np.random.default_rng(12)
    x = jnp.asarray(
        rng.standard_normal((cfg.batch_size, cfg.n_sources, cfg.d_in)), jnp.float32
    )

    def loss_with(dead_mask):
        def f(p):
            loss, _ = cc.training_loss(p, x, 0.0, cfg, dead_mask=dead_mask)
            return loss
        return jax.grad(f)(params)

    grads_free = loss_with(None)
    g0_free = float(jnp.abs(grads_free["W_enc"][..., 0]).max())
    assert g0_free == 0.0, "buried latent should get no main-objective grad"

    dead = jnp.zeros(cfg.dict_size, bool).at[0].set(True)
    grads_aux = loss_with(dead)
    g0_aux = float(jnp.abs(grads_aux["W_enc"][..., 0]).max())
    assert g0_aux > 0.0, "aux loss must give the dead latent a gradient"


def test_trainer_tracks_steps_since_fired():
    cfg = _cfg(activation="topk", topk_k=4, aux_dead_steps=2)
    tr = Trainer(cfg)
    assert tr.state.aux is not None
    m = tr.step()
    since = np.asarray(jax.device_get(tr.state.aux["steps_since_fired"]))
    # after one step: fired latents at 0, silent ones at 1
    assert set(np.unique(since)).issubset({0, 1})
    assert (since == 0).sum() >= cfg.topk_k  # at least the batch's top-k fired
    for _ in range(4):
        m = tr.step()
    assert "dead_frac" in m and "aux_loss" in m
    assert np.isfinite(float(jax.device_get(m["loss"])))
    tr.close()


def test_checkpoint_roundtrips_aux_state(tmp_path):
    from crosscoder_tpu.checkpoint.ckpt import Checkpointer

    cfg = _cfg(activation="topk", topk_k=4, checkpoint_dir=str(tmp_path))
    tr = Trainer(cfg, checkpointer=Checkpointer(cfg=cfg))
    for _ in range(3):
        tr.step()
    since_before = np.asarray(jax.device_get(tr.state.aux["steps_since_fired"]))
    tr.save()
    tr.close()

    tr2 = Trainer(cfg, checkpointer=Checkpointer(cfg=cfg))
    tr2.restore()
    since_after = np.asarray(jax.device_get(tr2.state.aux["steps_since_fired"]))
    np.testing.assert_array_equal(since_after, since_before)
    assert tr2.step_counter == 3
    tr2.close()


def test_auxk_sharded_step_runs():
    # 8-device mesh, TP over the dict axis: steps_since_fired shards with
    # b_enc and the step stays finite
    from crosscoder_tpu.parallel import mesh as mesh_lib

    cfg = _cfg(activation="topk", topk_k=4, batch_size=32,
               data_axis_size=4, model_axis_size=2, aux_dead_steps=1)
    mesh = mesh_lib.mesh_from_cfg(cfg)
    tr = Trainer(cfg, mesh=mesh)
    for _ in range(3):
        m = tr.step()
    assert np.isfinite(float(jax.device_get(m["loss"])))
    assert np.isfinite(float(jax.device_get(m["aux_loss"])))
    since = tr.state.aux["steps_since_fired"]
    assert since.shape == (cfg.dict_size,)
    tr.close()


def test_auxk_with_source_sharding_matches_dict_sharding():
    """EP-style source-axis sharding (cfg.shard_sources) with AuxK: the
    replicated steps_since_fired tracker and the aux loss must produce
    the same trajectory as the default dict-axis TP sharding."""
    from crosscoder_tpu.parallel import mesh as mesh_lib

    def run(shard_sources):
        cfg = _cfg(
            activation="topk", topk_k=4, aux_dead_steps=1, n_models=2,
            hook_points=("blocks.1.hook_resid_pre", "blocks.2.hook_resid_pre"),
            data_axis_size=2, model_axis_size=4, shard_sources=shard_sources,
        )
        mesh = mesh_lib.mesh_from_cfg(cfg)
        tr = Trainer(cfg, mesh=mesh)
        losses, aux_losses = [], []
        for _ in range(3):
            m = tr.step()
            losses.append(float(jax.device_get(m["loss"])))
            # the aux term itself, not just its (warmup-scaled, tiny)
            # contribution to the total — an EP-specific mis-scaling of
            # the aux loss must fail loudly here
            aux_losses.append(float(jax.device_get(m["aux_loss"])))
        since = np.asarray(jax.device_get(tr.state.aux["steps_since_fired"]))
        tr.close()
        return losses, aux_losses, since

    l_tp, a_tp, s_tp = run(False)
    l_ep, a_ep, s_ep = run(True)
    np.testing.assert_allclose(l_ep, l_tp, rtol=1e-5)
    np.testing.assert_allclose(a_ep, a_tp, rtol=1e-5)
    assert any(a > 0 for a in a_tp)        # the aux path actually engaged
    np.testing.assert_array_equal(s_ep, s_tp)


def test_config_rejects_bad_aux_k():
    with pytest.raises(ValueError):
        _cfg(aux_k=-1)
    with pytest.raises(ValueError):
        _cfg(aux_k=10**9)


def test_aux_every_amortization_semantics():
    """cfg.aux_every > 1 (VERDICT r04 #1): the aux ranking+decode runs only
    on every Nth step, but fired-tracking (steps_since_fired) updates on
    EVERY step, and the dead_frac metric stays present throughout. The
    off-step variant must behave exactly like the on-step variant minus the
    aux loss term."""
    from crosscoder_tpu.data.synthetic import SyntheticActivationSource

    cfg = _cfg(activation="topk", topk_k=4, aux_every=3, aux_dead_steps=2,
               prefetch=False)
    tr = Trainer(cfg, SyntheticActivationSource(cfg))
    seen_keys = []
    for i in range(7):
        m = tr.step()
        seen_keys.append("aux_loss" in m)
        assert "dead_frac" in m
        # fired-tracking ran this step regardless of the aux cadence
        ssf = np.asarray(tr.state.aux["steps_since_fired"])
        assert ssf.max() <= i + 1
    # aux steps at host steps 0, 3, 6
    assert seen_keys == [True, False, False, True, False, False, True]
    assert tr._host_step == 7
    # both compiled variants exist (keys: with_metrics, aux_on, mask_refresh)
    assert (True, True, True) in tr._step_fns
    assert (True, False, True) in tr._step_fns
    tr.close()


def test_aux_every_no_dead_matches_perstep():
    """With nothing dead (aux_dead_steps beyond the horizon) the aux term
    contributes 0 either way, so an amortized run must produce the same
    trajectory as the per-step run."""
    from crosscoder_tpu.data.synthetic import SyntheticActivationSource

    outs = []
    for aux_every in (1, 4):
        cfg = _cfg(activation="topk", topk_k=4, aux_every=aux_every,
                   aux_dead_steps=10_000, prefetch=False)
        tr = Trainer(cfg, SyntheticActivationSource(cfg))
        for _ in range(6):
            m = tr.step()
        outs.append(np.asarray(jax.device_get(m["loss"]), np.float64))
        tr.close()
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5)


def test_config_rejects_bad_aux_every():
    with pytest.raises(ValueError):
        _cfg(aux_every=0)
    with pytest.raises(ValueError):
        _cfg(aux_every=-3)


def test_aux_mask_cache_refresh_and_reuse_semantics():
    """cfg.aux_mask_every > 1: the dead mask refreshes only at the cadence
    and is REUSED in between — with aux_dead_steps=1, latents dying at
    step 1 cannot draw aux gradient until the step-3 refresh, so aux_loss
    is exactly 0 on the stale-mask steps and engages at the refresh."""
    from crosscoder_tpu.data.synthetic import SyntheticActivationSource

    cfg = _cfg(activation="topk", topk_k=4, aux_k=8, aux_dead_steps=1,
               aux_mask_every=3, prefetch=False)
    tr = Trainer(cfg, SyntheticActivationSource(cfg))
    assert "dead_mask" in tr.state.aux
    aux_losses, dead_fracs = [], []
    for _ in range(7):
        m = tr.step()
        aux_losses.append(float(jax.device_get(m["aux_loss"])))
        dead_fracs.append(float(jax.device_get(m["dead_frac"])))
    tr.close()
    # steps 0-2 use the step-0 mask (nothing dead yet: tracker starts 0);
    # the step-3 refresh sees the step-1+ deaths and engages the aux loss
    assert aux_losses[0] == 0 and aux_losses[1] == 0 and aux_losses[2] == 0
    assert dead_fracs[0] == 0 and dead_fracs[2] == 0
    assert any(a > 0 for a in aux_losses[3:]), aux_losses
    assert dead_fracs[3] > 0
    # refresh/reuse variants both compiled
    assert (True, True, True) in tr._step_fns
    assert (True, True, False) in tr._step_fns


def test_aux_mask_cache_matches_perstep_when_masks_agree():
    """With a horizon no latent ever crosses, the cached mask equals the
    per-step mask on every step, so the trajectories must be identical
    (the caching changes WHICH mask is used, never the step math)."""
    from crosscoder_tpu.data.synthetic import SyntheticActivationSource

    outs = []
    for mask_every in (1, 4):
        cfg = _cfg(activation="topk", topk_k=4, aux_k=8,
                   aux_dead_steps=10_000, aux_mask_every=mask_every,
                   prefetch=False)
        tr = Trainer(cfg, SyntheticActivationSource(cfg))
        for _ in range(6):
            m = tr.step()
        outs.append(np.asarray(jax.device_get(m["loss"]), np.float64))
        tr.close()
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6)


def test_config_rejects_bad_aux_mask_every():
    with pytest.raises(ValueError):
        _cfg(aux_mask_every=-1)
    assert _cfg(aux_mask_every=0, log_every=50).aux_mask_cadence == 50
    assert _cfg(aux_mask_every=7).aux_mask_cadence == 7
