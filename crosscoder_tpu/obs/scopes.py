"""The registered ``jax.named_scope`` names, as data.

ONE table: docs/OBSERVABILITY.md "Scopes" prints it, every
``jax.named_scope`` string in ``crosscoder_tpu/`` is in it
(tests/test_device_scopes.py holds both), the reader of a profile window
(:mod:`crosscoder_tpu.obs.device_scopes`) credits device time by it, and
its :func:`digest` is part of the persistent compilation cache's key
(``utils/compile_cache.enable``): JAX strips op metadata before it hashes a
program, so without the digest a tree that adds or renames a scope is
served an older tree's executable, with the older tree's names.

``parent`` is whose ``perf/device/<scope>_ms_per_step`` gauge a scope's time
is ALSO in (a parent is inclusive of its children); it is declared, not read
off the name: the expert layer's scopes open inside ``harvest/block/mlp``.
``harvest/block/mhc`` is opened nowhere: it is the sum of its two children.
"""

from __future__ import annotations

import hashlib

# scope -> the scope whose gauge holds it too (None: a root)
SCOPES: dict[str, str | None] = {
    "harvest/embed": None,
    "harvest/capture": None,
    "harvest/leaves": None,
    "harvest/block/norm": None,
    "harvest/block/attn": None,
    "harvest/block/attn/rope": "harvest/block/attn",
    "harvest/block/attn/gate": "harvest/block/attn",
    "harvest/block/attn/latent": "harvest/block/attn",
    "harvest/block/mlp": None,
    "harvest/block/moe/route": "harvest/block/mlp",
    "harvest/block/moe/experts": "harvest/block/mlp",
    "harvest/block/moe/shared": "harvest/block/mlp",
    "harvest/block/mhc": None,
    "harvest/block/mhc/read": "harvest/block/mhc",
    "harvest/block/mhc/write": "harvest/block/mhc",
    "store/scatter": None,
    "store/gather": None,
    "cc/encode": None,
    "cc/select": None,
    "cc/decode": None,
    "cc/loss": None,
    "cc/adam": None,
    "serve/prefill": None,
    "serve/encode_topk_diff": None,
}

# An op that carries no registered scope is ``<group>/unscoped`` under the
# group of the XLA module (jitted program) it ran in; first match wins. The
# patterns are the docs' ("Scopes": which programs are the harvest's, the
# step's, the store's); a module of no group is ``other``.
GROUPS: tuple[tuple[str, str], ...] = (
    ("cc", r"step_fn|_dense_step|train_step"),
    ("harvest", r"_seg_start_impl|_seg_scan_impl|_seg_finish_impl"
                r"|_multi_cache_impl|_forward_impl|seg_"),
    ("store", r"_dev_gather|_dev_scatter|jit_gather|jit_scatter|^gather$"
              r"|^scatter$|dynamic_update_slice|_store"),
    ("serve", r"encode_topk_diff|prefill"),
)


def digest() -> str:
    """What of the metadata reaches the cache key: the scope names, sorted.
    No file name, no line number — a moved line recompiles nothing."""
    return hashlib.sha256("\n".join(sorted(SCOPES)).encode()).hexdigest()[:16]
