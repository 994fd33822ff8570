"""Mesh construction and sharding rules for the crosscoder train step.

Replaces the reference's absent parallelism (it is a single-process,
single-GPU program — SURVEY.md §2 "parallelism statement") with the
idiomatic JAX recipe: one explicit 2-axis ``Mesh``

- ``data``: batch-axis data parallelism (DP) — activation rows are sharded,
  gradients are psum-reduced by XLA under ``jit`` (component N2),
- ``model``: tensor parallelism (TP) over the dictionary axis ``d_hidden``
  of ``W_enc``/``W_dec``/``b_enc`` — L1/L0 latent reductions become XLA
  psums over the shard axis (component N3).

The crosscoder's source axis (``n_models``/layers) is replicated by
default (small, 2-6). For many-model/many-layer diffs the source axis can
instead be the sharded one (component N4): ``cfg.shard_sources`` switches
to ``_SOURCE_SPECS`` below — whole per-source slabs per device, with XLA
psumming the contracted source axis in encode.

Multi-host: ``jax.distributed.initialize`` + the same mesh over
``jax.devices()`` spanning hosts; XLA routes ICI within a slice and DCN
across slices. See :mod:`crosscoder_tpu.parallel.multihost`.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.experimental.layout import Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# leaf-name → PartitionSpec for the crosscoder param pytree.
# W_enc [n, d_in, H]: shard the dict axis; W_dec [H, n, d_in]: likewise.
_PARAM_SPECS: dict[str, P] = {
    "W_enc": P(None, None, "model"),
    "W_dec": P("model", None, None),
    "b_enc": P("model"),
    "b_dec": P(None, None),
    "log_theta": P("model"),
    # AuxK dead-latent tracker (TrainState.aux): latent-axis, like b_enc
    "steps_since_fired": P("model"),
    # cached dead mask (cfg.aux_mask_every): latent-axis, like the tracker
    "dead_mask": P("model"),
}

# EP-style alternative (cfg.shard_sources, component N4 as a sharding mode):
# the SOURCE axis (n_models × n_hooked_layers) shards over the 'model' mesh
# axis instead of the dict axis — each device holds whole models'/layers'
# encoder/decoder slabs. The encode einsum contracts the source axis, so
# XLA inserts a psum over 'model' for the pre-activations; decode outputs
# come back source-sharded and the per-source reductions stay local. The
# right trade when n_sources is large (many-model diffs / many hooked
# layers) and the dictionary is small enough to replicate.
_SOURCE_SPECS: dict[str, P] = {
    "W_enc": P("model", None, None),
    "W_dec": P(None, "model", None),
    "b_enc": P(None),              # latent-axis params replicate in this mode
    "b_dec": P("model", None),
    "log_theta": P(None),
    "steps_since_fired": P(None),
    "dead_mask": P(None),
}

BATCH_SPEC = P("data", None, None)


def _specs(shard_sources: bool = False) -> dict[str, P]:
    return _SOURCE_SPECS if shard_sources else _PARAM_SPECS


def make_mesh(
    data_axis_size: int = -1,
    model_axis_size: int = 1,
    devices: list[Any] | None = None,
) -> Mesh:
    """Build the 2-axis ``('data', 'model')`` mesh.

    ``data_axis_size=-1`` takes every device not claimed by the model axis.
    On one device this degenerates to a 1×1 mesh and the whole train step
    compiles exactly as the single-chip program.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if model_axis_size < 1 or n % model_axis_size:
        raise ValueError(f"model_axis_size {model_axis_size} must divide device count {n}")
    if data_axis_size == -1:
        data_axis_size = n // model_axis_size
    if data_axis_size * model_axis_size != n:
        raise ValueError(
            f"mesh {data_axis_size}x{model_axis_size} != {n} devices; "
            "use data_axis_size=-1 to auto-fill"
        )
    arr = np.asarray(devices).reshape(data_axis_size, model_axis_size)
    return Mesh(arr, ("data", "model"))


def mesh_from_cfg(cfg) -> Mesh:
    return make_mesh(cfg.data_axis_size, cfg.model_axis_size)


def param_spec(name: str, shard_sources: bool = False) -> P:
    try:
        return _specs(shard_sources)[name]
    except KeyError:
        raise ValueError(f"no sharding rule for param {name!r}") from None


def param_shardings(
    mesh: Mesh, params: dict[str, Any], shard_sources: bool = False
) -> dict[str, NamedSharding]:
    return {k: NamedSharding(mesh, param_spec(k, shard_sources)) for k in params}


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Activation batches ``[batch, n_sources, d_in]`` shard over ``data``."""
    return NamedSharding(mesh, BATCH_SPEC)


def state_shardings(mesh: Mesh, state: Any, shard_sources: bool = False) -> Any:
    """Shardings for a full TrainState pytree (params + optimizer state + step).

    Optimizer moments mirror their parameter's sharding; anything that is not
    under a recognized param name (e.g. Adam's ``count``, the step counter)
    is replicated. Matching is by the dict key on the leaf's path, so any
    optax state that nests the param tree (mu/nu) is covered without
    special-casing optax internals.
    """
    replicated = NamedSharding(mesh, P())
    specs = _specs(shard_sources)

    def spec_of(path, leaf) -> NamedSharding:
        keys = [getattr(entry, "key", None) for entry in path]
        if "quant_ef" in keys:
            # quantized-grad error-feedback residuals (parallel/quant_ar):
            # [n_data, L] per param, each device owning exactly its own row
            # — sharded over 'data' regardless of which param they shadow
            return NamedSharding(mesh, P("data", None))
        for key in reversed(keys):
            if key in specs:
                if hasattr(leaf, "ndim") and leaf.ndim == len(specs[key]):
                    return NamedSharding(mesh, specs[key])
                return replicated
        return replicated

    return jax.tree_util.tree_map_with_path(spec_of, state)


def held_layout(x: Any, sharding: NamedSharding) -> Layout:
    """The layout a device of ``sharding``'s mesh holds its shard of an array
    like ``x`` (anything with a shape and a dtype) in — the layout a plain
    ``device_put`` or a ``jit`` with no layout given leaves it in — asked of
    the device itself, so it answers for a mesh this process has no array on
    yet (a remesh target, a described topology) as for the live one."""
    dev = sharding.mesh.devices.flat[0]
    return Layout.from_pjrt_layout(dev.client.get_default_layout(
        np.dtype(x.dtype), sharding.shard_shape(x.shape), dev))


def shard_state(mesh: Mesh, state: Any, shard_sources: bool = False) -> Any:
    """Place a host-built TrainState onto the mesh per the rules above."""
    from crosscoder_tpu.parallel import multihost

    return multihost.put_global(state, state_shardings(mesh, state, shard_sources))
