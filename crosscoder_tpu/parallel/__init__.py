"""Distributed layer: mesh construction, sharding rules, collectives.

This package IS the framework's "distributed communication backend"
(SURVEY.md §2.2 N1): the reference has none (single hardcoded CUDA device,
reference ``train.py:4``), while here every array placement is expressed as
a ``NamedSharding`` over an explicit ``jax.sharding.Mesh`` and XLA compiles
the required collectives (psum/all-gather/reduce-scatter) onto ICI within a
slice and DCN across slices. There is no hand-written transport.
"""

from crosscoder_tpu.parallel.mesh import (  # noqa: F401
    batch_sharding,
    make_mesh,
    param_shardings,
    state_shardings,
)
from crosscoder_tpu.parallel.ring_attention import ring_attention  # noqa: F401
from crosscoder_tpu.parallel import multihost  # noqa: F401
