"""JAX subject-LM runtime with residual-stream capture and splicing.

This module replaces the reference's entire "external model runtime" layer —
TransformerLens ``HookedTransformer`` (reference ``train.py:45-55``,
``buffer.py:81-89``, ``nb:cell 29``) — with a TPU-native functional LM:

- ``forward(params, tokens, cfg, capture=..., edit=...)`` is ONE jittable,
  mesh-shardable function. ``capture`` replaces ``run_with_cache(
  names_filter=hook_point)``; ``edit`` replaces ``run_with_hooks(
  fwd_hooks=[(hook_point, fn)])`` used by the CE-recovered eval
  (reference ``nb:cell 29``'s ``splice_act_hook`` / ``zero_ablation_hook``).
- Hook names follow the reference's TransformerLens strings
  (``blocks.{L}.hook_resid_pre`` — reference ``train.py:32``) so configs and
  analysis code carry over unchanged.

TPU-first design decisions (why this is not a TransformerLens translation):

- Layers are STACKED pytrees run under ``lax.scan`` — one traced block,
  compiled once, instead of 26 unrolled layer graphs. Capture and editing
  inside the scan use arithmetic masking on the layer index (each requested
  layer matches exactly one slot of a preallocated capture buffer), so
  arbitrary hook layers cost one fused multiply-add per layer and the graph
  stays static — no Python callbacks in the hot path.
- All attention/MLP matmuls are bf16 einsums with fp32 accumulation
  (``preferred_element_type``) sized for the MXU; softmax/RMSNorm reductions
  run in fp32.
- Batch/sequence axes shard over the mesh ``data`` axis (harvest-side
  sharding, SURVEY.md component N5); params are replicated by default
  (Gemma-2-2B bf16 ≈ 5.2 GB/model fits one chip's HBM) — shardings are
  expressed at the call site, not baked in here.

One config type (:class:`LMConfig`) carries a layer table (attention kind
and MLP kind per layer), the block's style, RoPE per attention kind and the
expert sizes; every forward below is ONE layer loop (:func:`_scan_blocks`)
that takes a layer's kind from ONE lookup (:func:`_layer_kind`) and runs ONE
block (:func:`_block`), and hands in only its positions and how it reaches
attention. Four families run through it: Gemma-2 (next paragraph), the pre-norm sparse-expert block of
Mellum2 (``LMConfig.mellum2_12b``: plain-weight RMSNorm before each sublayer
only, no soft-caps, no embedding scale, three window layers to one full
layer with YaRN on the full layers only, every MLP ``ops/moe.py``'s routed
experts; checked against ``benchmarks/reference/mellum_ref.py`` by
``tests/test_mellum.py``), and Laguna-S-2.1's (``LMConfig.laguna_s_2_1``),
whose layers differ in SHAPE: a dense layer 0, window layers of 72 query
heads and full layers of 48 with a per-head output gate and a half-rotated
head, a shared expert beside the routed ones, and — on one chip — a held
share of each layer's experts. Layers of one shape are a class, one stack of
leaves a class (:func:`layer_classes`); a table of one class — every other
family — keeps the tree ``params["layers"][leaf]`` and the programs it had
(checked against ``benchmarks/reference/laguna_ref.py`` by
``tests/test_laguna.py``). A fourth, Xing4.0-29B-A4B's
(``LMConfig.xing4_0_29b``), changes what the loop CARRIES: four residual
streams a token, side by side in one row ``[B, S, n·D]``, which each sublayer
reads and writes through the maps of ``ops/mhc.py`` (:func:`_read` /
:func:`_write`: with one stream the stream itself and an add), with latent
attention (:func:`_latent_qkv`) and sigmoid-routed experts; its residual hooks
see the streams' mean (checked against ``benchmarks/reference/xing_ref.py`` by
``tests/test_xing.py``).

Gemma-2 architecture facts implemented (validated against the HF
``transformers`` Gemma2 implementation by ``tests/test_lm.py``): RMSNorm with
(1+w) scaling in fp32; embedding scaled by sqrt(d_model); GeGLU MLP with
tanh-approximate GELU; GQA; RoPE; attention-logit softcapping (50.0) and
final-logit softcapping (30.0); alternating sliding-window/global attention
(even layers local); query scale ``query_pre_attn_scalar**-0.5``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Any, Callable, Mapping, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from crosscoder_tpu.config import parse_hook_point
from crosscoder_tpu.utils.dtypes import dtype_of


def _put_global(tree, shardings):
    # collective-free host->mesh placement (multihost.put_global); local
    # alias avoids repeating the deferred import at three call sites
    from crosscoder_tpu.parallel import multihost

    return multihost.put_global(tree, shardings)

LMParams = dict[str, Any]


# layer kinds, under the names public configs give them (``layer_types``,
# ``mlp_layer_types``)
SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"


def _alternate(n_layers: int) -> tuple[str, ...]:
    """Gemma-2's pattern: even layers attend within the sliding window."""
    return tuple(SLIDING if i % 2 == 0 else FULL for i in range(n_layers))


@dataclass(frozen=True)
class Rope:
    """Rotary-embedding parameters of ONE attention kind. ``yarn_factor``
    0 is plain RoPE; otherwise static YaRN as HF computes it: frequencies
    blended between ``theta``'s own and those divided by the factor over a
    ramp found from ``original_max_position`` and the two betas, cos and sin
    multiplied by ``attention_factor``, at every length. ``rotary_factor``
    is the LEADING share of each head that rotates (split-half pairs inside
    it, frequencies computed at that width); the rest passes unrotated."""

    theta: float = 10_000.0
    yarn_factor: float = 0.0
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    rotary_factor: float = 1.0


@dataclass(frozen=True)
class LMConfig:
    """Architecture config of a decoder-only subject LM: sizes, a layer
    table (attention kind and MLP kind per layer) and the block's style.

    ``block_style``: ``"sandwich"`` is Gemma-2's block — (1 + w) RMSNorms
    before AND after each sublayer, the embedding scaled by sqrt(d_model), a
    tanh-GELU gate; ``"prenorm"`` is the plain pre-norm block — RMSNorm with
    weight ``w`` before each sublayer only, no embedding scale, a SiLU gate.
    Soft-caps are their own fields (0 = none). ``layer_types`` left None is
    Gemma-2's alternate pattern, ``mlp_types`` left None is all dense; both
    are tuples so the config stays hashable (it is a static jit argument).
    ``rope`` maps an attention kind to its :class:`Rope`; a kind it does not
    list rotates by plain ``rope_theta``. A sparse layer routes each token to
    ``experts_per_tok`` of ``n_experts`` gated MLPs of width ``d_expert``
    (:mod:`crosscoder_tpu.ops.moe`); ``d_ff`` is the dense layers' width.

    Layers may differ in SHAPE: ``heads_by_layer`` gives each layer's query
    heads (None: ``n_heads`` everywhere) and ``mlp_types`` may mix dense and
    sparse layers. Layers of one shape form a class, one stack of leaves a
    class (:func:`layer_classes`). ``attn_gate`` ``"per_head"`` multiplies
    each attended head by ``sigmoid(x·Wg)`` before the output projection;
    ``d_shared_expert`` > 0 adds one always-on gated MLP of that width to
    every sparse layer's routed sum; ``routed_scale`` multiplies the routed
    gates. A chip may hold a SHARE of each sparse layer's experts:
    ``n_experts`` stays the model's count and the router's width,
    ``experts_held`` (0: all) is how many this chip holds and ``expert_rank``
    which share (experts ``[rank·held, (rank+1)·held)``); the layer's output
    is then the held experts' part of the routed sum (plus the shared
    expert), and nothing stands in for the absent chips. ``embed_std`` is the
    seeded fixture's embedding scale (None: ``d_model ** -0.5``).

    ``router`` ``"sigmoid_bias"`` scores each expert by a sigmoid, chooses by
    score plus a per-expert bias and gates by the unbiased scores
    (``moe.route``). ``n_streams`` > 1 gives a token that many residual
    streams, read and written by each sublayer through the maps of
    :mod:`crosscoder_tpu.ops.mhc` (``hc_sinkhorn_iters``, ``hc_eps``,
    ``hc_clamp``); a residual hook is then their MEAN (:func:`_stream_mean`).
    ``kv_lora_rank`` > 0 makes attention the LATENT form: ``q_lora_rank`` and
    ``kv_lora_rank`` are the two low-rank paths' widths, ``head_dim`` the score
    head — its own part, then ``qk_rope_dim`` rotary dims whose key all heads
    share — and ``v_head_dim`` the value head.
    """

    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float = 10_000.0
    rms_eps: float = 1e-6
    attn_softcap: float = 50.0
    final_softcap: float = 30.0
    sliding_window: int = 4096
    query_pre_attn_scalar: float = 256.0
    dtype: str = "bf16"
    layer_types: tuple[str, ...] | None = None
    mlp_types: tuple[str, ...] | None = None
    block_style: str = "sandwich"
    rope: tuple[tuple[str, Rope], ...] = ()
    n_experts: int = 0
    experts_per_tok: int = 0
    d_expert: int = 0
    norm_topk_prob: bool = True
    tie_embeddings: bool = True
    heads_by_layer: tuple[int, ...] | None = None
    attn_gate: str = "none"
    d_shared_expert: int = 0
    routed_scale: float = 1.0
    experts_held: int = 0
    expert_rank: int = 0
    embed_std: float | None = None
    router: str = "softmax"
    n_streams: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple[float, float] = (-30.0, 30.0)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    def __post_init__(self) -> None:
        def fill(name, default, valid):
            table = getattr(self, name)
            table = default if table is None else tuple(table)
            if len(table) != self.n_layers or set(table) - set(valid):
                raise ValueError(
                    f"{name} must give one of {valid} for each of "
                    f"{self.n_layers} layers, got {table}")
            object.__setattr__(self, name, table)

        fill("layer_types", _alternate(self.n_layers), (SLIDING, FULL))
        fill("mlp_types", (DENSE,) * self.n_layers, (DENSE, SPARSE))
        if self.block_style not in ("sandwich", "prenorm"):
            raise ValueError(f"block_style must be sandwich|prenorm, got {self.block_style!r}")
        if self.attn_gate not in ("none", "per_head"):
            raise ValueError(f"attn_gate must be none|per_head, got {self.attn_gate!r}")
        if self.router not in ("softmax", "sigmoid_bias"):
            raise ValueError(f"router must be softmax|sigmoid_bias, got {self.router!r}")
        if self.n_streams < 1 or (self.n_streams > 1 and self.block_style != "prenorm"):
            raise ValueError(
                f"n_streams {self.n_streams}: at least 1, and several only in a prenorm block")
        object.__setattr__(self, "hc_clamp", tuple(float(c) for c in self.hc_clamp))
        if self.latent and not (
                self.q_lora_rank > 0 and 0 < self.qk_rope_dim < self.head_dim
                and self.qk_rope_dim % 2 == 0 and self.v_head_dim > 0
                and self.n_kv_heads == self.n_heads and self.heads_by_layer is None
                and self.attn_gate == "none"):
            raise ValueError(
                "latent attention (kv_lora_rank > 0) needs q_lora_rank, an even "
                "qk_rope_dim inside head_dim (the score head: nope | rope), "
                "v_head_dim, and one key/value head a query head")
        if self.heads_by_layer is not None:
            heads = tuple(self.heads_by_layer)
            if len(heads) != self.n_layers or any(
                    h <= 0 or h % self.n_kv_heads for h in heads):
                raise ValueError(
                    f"heads_by_layer must give whole GQA groups of "
                    f"{self.n_kv_heads} for each of {self.n_layers} layers, got {heads}")
            object.__setattr__(self, "heads_by_layer", heads)
        if self.sparse and not (0 < self.experts_per_tok <= self.n_experts
                                and self.d_expert > 0):
            raise ValueError(
                f"sparse layers need 0 < experts_per_tok <= n_experts and "
                f"d_expert > 0, got {self.experts_per_tok} of {self.n_experts} "
                f"experts of width {self.d_expert}")
        if self.sparse and (self.n_experts % self.n_held
                            or not 0 <= self.expert_rank < self.n_experts // self.n_held):
            raise ValueError(
                f"a share of {self.experts_held} experts (rank {self.expert_rank}) "
                f"does not divide the layer's {self.n_experts}")

    @property
    def sparse(self) -> bool:
        """Whether any MLP layer is an expert layer."""
        return SPARSE in self.mlp_types

    @property
    def latent(self) -> bool:
        """Whether attention is the latent (low-rank) form."""
        return self.kv_lora_rank > 0

    @property
    def rope_dim(self) -> int:
        """The width RoPE's frequencies are computed at."""
        return self.qk_rope_dim or self.head_dim

    @property
    def hc(self):
        """The static side of the stream maps (``ops/mhc.HC``)."""
        from crosscoder_tpu.ops import mhc

        return mhc.HC(self.n_streams, self.hc_sinkhorn_iters, self.hc_eps,
                      self.hc_clamp, self.rms_eps)

    @property
    def n_held(self) -> int:
        """Experts of each sparse layer this chip holds."""
        return self.experts_held or self.n_experts

    @property
    def first_expert(self) -> int:
        """The first expert of this chip's share."""
        return self.expert_rank * self.n_held

    def heads_of(self, layer: int) -> int:
        return self.n_heads if self.heads_by_layer is None else self.heads_by_layer[layer]

    def rope_of(self, kind: str) -> Rope:
        return dict(self.rope).get(kind, Rope(theta=self.rope_theta))

    @classmethod
    def gemma2_2b(cls) -> "LMConfig":
        """Gemma-2-2B — the reference's subject model pair (train.py:10-12)."""
        return cls(
            vocab_size=256_000, d_model=2304, n_layers=26, n_heads=8,
            n_kv_heads=4, head_dim=256, d_ff=9216, query_pre_attn_scalar=256.0,
        )

    @classmethod
    def gemma2_9b(cls) -> "LMConfig":
        """Gemma-2-9B (d_model 3584) — BASELINE scale-out config 3."""
        return cls(
            vocab_size=256_000, d_model=3584, n_layers=42, n_heads=16,
            n_kv_heads=8, head_dim=256, d_ff=14_336, query_pre_attn_scalar=256.0,
        )

    @classmethod
    def gemma2_27b(cls) -> "LMConfig":
        """Gemma-2-27B — the family's largest member (NB: unlike 2B/9B its
        query scale is d_model/n_heads = 144, not head_dim)."""
        return cls(
            vocab_size=256_000, d_model=4608, n_layers=46, n_heads=32,
            n_kv_heads=16, head_dim=128, d_ff=36_864,
            query_pre_attn_scalar=144.0,
        )

    @classmethod
    def mellum2_12b(cls) -> "LMConfig":
        """Mellum2-12B-A2.5B (JetBrains): a pre-norm block, every MLP 64
        experts of width 896 with top-8 routing, three 1024-window layers
        to one full layer, YaRN x16 on the full layers only, untied head."""
        n = 28
        return cls(
            vocab_size=98_304, d_model=2304, n_layers=n, n_heads=32,
            n_kv_heads=4, head_dim=128, d_ff=7168, rope_theta=500_000.0,
            attn_softcap=0.0, final_softcap=0.0, sliding_window=1024,
            query_pre_attn_scalar=128.0,
            layer_types=tuple(FULL if i % 4 == 3 else SLIDING for i in range(n)),
            mlp_types=(SPARSE,) * n, block_style="prenorm",
            rope=((FULL, Rope(theta=500_000.0, yarn_factor=16.0,
                              original_max_position=8192, beta_fast=32.0,
                              beta_slow=1.0,
                              attention_factor=1.2772588722239782)),),
            n_experts=64, experts_per_tok=8, d_expert=896,
            norm_topk_prob=True, tie_embeddings=False,
        )

    @classmethod
    def laguna_s_2_1(cls) -> "LMConfig":
        """Laguna-S-2.1 (poolside): a pre-norm block; layer 0 dense (12,288),
        the rest 256 experts of width 1024 with top-10 routing (gates times
        2.5) beside one shared expert; one full layer (48 query heads, the
        leading half of each head rotated by YaRN x128) to three 512-window
        layers (72 heads, plain RoPE); a per-head sigmoid gate on the
        attended heads; untied head. The whole model: every expert held."""
        n = 48
        full = tuple(i % 4 == 0 for i in range(n))
        return cls(
            vocab_size=100_352, d_model=3072, n_layers=n, n_heads=48,
            n_kv_heads=8, head_dim=128, d_ff=12_288, rope_theta=10_000.0,
            attn_softcap=0.0, final_softcap=0.0, sliding_window=512,
            query_pre_attn_scalar=128.0,
            layer_types=tuple(FULL if f else SLIDING for f in full),
            mlp_types=(DENSE,) + (SPARSE,) * (n - 1), block_style="prenorm",
            rope=((FULL, Rope(theta=500_000.0, yarn_factor=128.0,
                              original_max_position=8192, beta_fast=32.0,
                              beta_slow=1.0,
                              attention_factor=1.4852030263919618,
                              rotary_factor=0.5)),),
            n_experts=256, experts_per_tok=10, d_expert=1024,
            norm_topk_prob=True, tie_embeddings=False,
            heads_by_layer=tuple(48 if f else 72 for f in full),
            attn_gate="per_head", d_shared_expert=1024, routed_scale=2.5,
        )

    @classmethod
    def xing4_0_29b(cls) -> "LMConfig":
        """Xing4.0-29B-A4B (XingChen-AGI): four residual streams a token,
        read and written through input-dependent maps and mixed by a
        Sinkhorn-normalised 4 x 4 matrix a sublayer (mHC); latent attention
        (query rank 768, key/value rank 512, a score head of 128 + 64 rotary
        dims whose rotary key is shared by all 32 heads, a value head of
        128, static YaRN x64 at dim 64 with its m² on the softmax scale);
        two dense layers (9,216), then 64 sigmoid-scored experts of width
        1024, top-4 chosen by score plus a bias and gated by the unbiased
        scores (renormalised, times 2) beside one shared expert; untied
        head. The whole model: every expert held; the MTP module is not
        modelled."""
        n = 40
        m = 0.1 * 1.0 * math.log(64.0) + 1.0        # yarn_get_mscale(factor, mscale_all_dim)
        return cls(
            vocab_size=131_072, d_model=3584, n_layers=n, n_heads=32,
            n_kv_heads=32, head_dim=192, d_ff=9216, rope_theta=10_000.0,
            attn_softcap=0.0, final_softcap=0.0, sliding_window=0,
            # scores · 192^-0.5 · m²  ==  scores · (192 / m⁴)^-0.5
            query_pre_attn_scalar=192.0 / m ** 4,
            layer_types=(FULL,) * n,
            mlp_types=(DENSE,) * 2 + (SPARSE,) * (n - 2), block_style="prenorm",
            rope=((FULL, Rope(theta=10_000.0, yarn_factor=64.0,
                              original_max_position=4096, beta_fast=32.0,
                              beta_slow=1.0, attention_factor=1.0)),),
            n_experts=64, experts_per_tok=4, d_expert=1024,
            norm_topk_prob=True, tie_embeddings=False,
            d_shared_expert=1024, routed_scale=2.0, router="sigmoid_bias",
            n_streams=4, hc_sinkhorn_iters=20, hc_eps=1e-6, hc_clamp=(-30.0, 30.0),
            q_lora_rank=768, kv_lora_rank=512, qk_rope_dim=64, v_head_dim=128,
        )

    @classmethod
    def tiny(cls, vocab_size: int = 257, n_layers: int = 4) -> "LMConfig":
        """Deterministic test-sized config (the 'fake LM' of SURVEY.md §4 —
        same hook semantics as the real model, no 2.6B-param download)."""
        return cls(
            vocab_size=vocab_size, d_model=32, n_layers=n_layers, n_heads=4,
            n_kv_heads=2, head_dim=8, d_ff=64, sliding_window=8,
            query_pre_attn_scalar=8.0, dtype="fp32",
        )

    def replace(self, **kw: Any) -> "LMConfig":
        """``dataclasses.replace``; a new ``n_layers`` refills a layer table
        that was the default pattern (a table given by hand must be given
        again at the new depth)."""
        if kw.get("n_layers", self.n_layers) != self.n_layers:
            if self.layer_types == _alternate(self.n_layers):
                kw.setdefault("layer_types", None)
            if self.mlp_types:
                kw.setdefault("mlp_types", (self.mlp_types[0],) * kw["n_layers"])
        return dataclasses.replace(self, **kw)


_NAMED_CONFIGS = {
    "gemma-2-2b": LMConfig.gemma2_2b,
    "gemma-2-2b-it": LMConfig.gemma2_2b,
    "gemma-2-9b": LMConfig.gemma2_9b,
    "gemma-2-9b-it": LMConfig.gemma2_9b,
    "gemma-2-27b": LMConfig.gemma2_27b,
    "gemma-2-27b-it": LMConfig.gemma2_27b,
    "mellum2-12b-a2.5b": LMConfig.mellum2_12b,
    "mellum2-12b-a2.5b-base": LMConfig.mellum2_12b,
    "mellum2-12b-a2.5b-instruct": LMConfig.mellum2_12b,
    "laguna-s-2.1": LMConfig.laguna_s_2_1,
    "laguna-s-2.1-base": LMConfig.laguna_s_2_1,
    "laguna-s-2.1-instruct": LMConfig.laguna_s_2_1,
    "xing4.0-29b-a4b": LMConfig.xing4_0_29b,
    "xing4.0-29b-a4b-base": LMConfig.xing4_0_29b,
}


def config_for(model_name: str) -> LMConfig:
    """Architecture config by HF-style model name (reference train.py:25)."""
    key = model_name.split("/")[-1].lower()
    if key not in _NAMED_CONFIGS:
        raise ValueError(f"unknown model {model_name!r}; known: {sorted(_NAMED_CONFIGS)}")
    return _NAMED_CONFIGS[key]()


# ---------------------------------------------------------------------------
# params


class LayerClass(NamedTuple):
    """Layers whose leaves have one shape: one stack of leaves."""

    n_heads: int
    mlp: str                    # DENSE | SPARSE
    layers: tuple[int, ...]     # the model's layer ids, ascending
    kind: str | None            # the attention kind, where all its layers share one


@functools.lru_cache(maxsize=64)
def layer_classes(cfg: LMConfig) -> tuple[LayerClass, ...]:
    """The config's layers by the SHAPE of their leaves (query heads, MLP
    kind), in order of first appearance. One class is the common case and
    keeps the tree ``params["layers"][leaf]`` ``[L, ...]``; with more,
    ``params["layers"]`` is a tuple of such dicts, one a class, each stacked
    over its own layers."""
    keys = [(cfg.heads_of(i), cfg.mlp_types[i]) for i in range(cfg.n_layers)]
    out = []
    for key in dict.fromkeys(keys):
        layers = tuple(i for i, k in enumerate(keys) if k == key)
        kinds = {cfg.layer_types[i] for i in layers}
        out.append(LayerClass(*key, layers, kinds.pop() if len(kinds) == 1 else None))
    return tuple(out)


@functools.lru_cache(maxsize=64)
def _class_slots(cfg: LMConfig) -> tuple[tuple[int, int], ...]:
    """Per layer: (its class, its slot in that class's stack)."""
    where = {}
    for c, cls in enumerate(layer_classes(cfg)):
        where.update({layer: (c, s) for s, layer in enumerate(cls.layers)})
    return tuple(where[i] for i in range(cfg.n_layers))


def class_stacks(params: LMParams, cfg: LMConfig) -> tuple[Mapping[str, jax.Array], ...]:
    """The stacks of leaves, one a class, whichever tree ``params`` is."""
    layers = params["layers"]
    return (layers,) if len(layer_classes(cfg)) == 1 else tuple(layers)


def _from_stacks(stacks: Sequence[Any]) -> Any:
    """``params["layers"]`` from one stack a class."""
    return stacks[0] if len(stacks) == 1 else tuple(stacks)


def init_params(key: jax.Array, cfg: LMConfig) -> LMParams:
    """Random-init params (the fake-LM fixture; real runs use ``from_hf``).

    Layer leaves are stacked on a leading axis over the layers of their
    class (:func:`layer_classes`; one class: ``[n_layers]``) for ``lax.scan``.
    The leaves follow the config: a ``"prenorm"`` block has no post-norms
    (and its norm weights start at 1, a ``"sandwich"`` block's (1 + w) at
    0), a sparse MLP has ``router`` [D, E], ``we_gate_up`` [E_held, D, 2·Fe]
    (gate columns first) and ``we_down`` [E_held, Fe, D] in place of the dense
    three (and ``ws_gate``/``ws_up``/``ws_down``, the shared expert, where
    the config has one), a gated attention ``w_attn_gate`` [D, H], an untied
    head is ``unembed`` [V, D].
    """
    dt = dtype_of(cfg.dtype)
    D, F = cfg.d_model, cfg.d_ff
    kd = cfg.n_kv_heads * cfg.head_dim
    ks = jax.random.split(key, 9)

    def nrm(k, shape, scale):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * scale).astype(dt)

    sandwich = cfg.block_style == "sandwich"
    unit = jnp.zeros if sandwich else jnp.ones

    def stack(cls: LayerClass, ks, key) -> dict:
        L, qd = len(cls.layers), cls.n_heads * cfg.head_dim
        layers = {"attn_norm": unit((L, D), dt), "pre_ffw_norm": unit((L, D), dt)}
        if cfg.latent:
            H, rq, rkv = cls.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank
            dr, dv = cfg.qk_rope_dim, cfg.v_head_dim
            kl = jax.random.split(jax.random.fold_in(key, 103), 4)
            layers.update(
                wq_a=nrm(ks[1], (L, D, rq), D ** -0.5), q_a_norm=unit((L, rq), dt),
                wq_nope=nrm(ks[2], (L, rq, H * (cfg.head_dim - dr)), rq ** -0.5),
                wq_rope=nrm(kl[0], (L, rq, H * dr), rq ** -0.5),
                wkv_a=nrm(ks[3], (L, D, rkv + dr), D ** -0.5), kv_a_norm=unit((L, rkv), dt),
                wk_nope=nrm(kl[1], (L, rkv, H * (cfg.head_dim - dr)), rkv ** -0.5),
                wv=nrm(kl[2], (L, rkv, H * dv), rkv ** -0.5),
                wo=nrm(ks[4], (L, H * dv, D), (H * dv) ** -0.5))
        else:
            layers.update(
                wq=nrm(ks[1], (L, D, qd), D ** -0.5), wk=nrm(ks[2], (L, D, kd), D ** -0.5),
                wv=nrm(ks[3], (L, D, kd), D ** -0.5), wo=nrm(ks[4], (L, qd, D), qd ** -0.5))
        if cfg.n_streams > 1:
            # the seeded fixture's maps (the configuration file's
            # ``assumed.weights``): phi drawn so that z has unit variance,
            # alpha 1, no bias but 2·I under the mixing logits
            n, W = cfg.n_streams, cfg.hc.width
            base = jnp.concatenate([jnp.zeros((2 * n,)), 2.0 * jnp.eye(n).reshape(-1)])
            for j, site in enumerate(("attn", "ffn")):
                layers[f"hc_{site}_phi"] = jax.random.normal(
                    jax.random.fold_in(key, 104 + j), (L, n * D, W), jnp.float32
                ) * (n * D) ** -0.5
                layers[f"hc_{site}_alpha"] = jnp.ones((L, 3), jnp.float32)
                layers[f"hc_{site}_bias"] = jnp.broadcast_to(base, (L, W)).astype(jnp.float32)
        if sandwich:
            layers["post_attn_norm"] = unit((L, D), dt)
            layers["post_ffw_norm"] = unit((L, D), dt)
        if cls.mlp == SPARSE:
            E, Fe = cfg.n_held, cfg.d_expert
            layers["router"] = nrm(ks[5], (L, D, cfg.n_experts), D ** -0.5)
            layers["we_gate_up"] = nrm(ks[6], (L, E, D, 2 * Fe), D ** -0.5)
            layers["we_down"] = nrm(ks[7], (L, E, Fe, D), Fe ** -0.5)
            if cfg.router == "sigmoid_bias":
                # the fixture's choice bias: large enough to move choices (the
                # 4th and 5th of 64 scores lie ~0.03 apart), small enough not
                # to UNBALANCE the load a trained bias exists to balance (at
                # 0.1 the busiest expert takes 5-8x the mean: PERF.md §6, PR 35)
                layers["router_bias"] = 0.01 * jax.random.normal(
                    jax.random.fold_in(key, 106), (L, cfg.n_experts), jnp.float32)
            if cfg.d_shared_expert:
                Fs = cfg.d_shared_expert
                k_g, k_u, k_d = jax.random.split(jax.random.fold_in(key, 101), 3)
                layers["ws_gate"] = nrm(k_g, (L, D, Fs), D ** -0.5)
                layers["ws_up"] = nrm(k_u, (L, D, Fs), D ** -0.5)
                layers["ws_down"] = nrm(k_d, (L, Fs, D), Fs ** -0.5)
        else:
            layers["w_gate"] = nrm(ks[5], (L, D, F), D ** -0.5)
            layers["w_up"] = nrm(ks[6], (L, D, F), D ** -0.5)
            layers["w_down"] = nrm(ks[7], (L, F, D), F ** -0.5)
        if cfg.attn_gate == "per_head":
            layers["w_attn_gate"] = nrm(
                jax.random.fold_in(key, 102), (L, D, cls.n_heads), D ** -0.5)
        return layers

    classes = layer_classes(cfg)
    if len(classes) == 1:
        stacks = [stack(classes[0], ks, key)]
    else:       # each class draws from a key of its own
        stacks = [stack(cls, jax.random.split(k, 9), k) for cls, k in zip(
            classes, (jax.random.fold_in(key, 1 + c) for c in range(len(classes))))]
    params = {
        "embed": nrm(ks[0], (cfg.vocab_size, D),
                     D ** -0.5 if cfg.embed_std is None else cfg.embed_std),
        "final_norm": unit((D,), dt),
        "layers": _from_stacks(stacks),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = nrm(ks[8], (cfg.vocab_size, D), D ** -0.5)
    if cfg.n_streams > 1:       # the learned read in front of the final norm
        n = cfg.n_streams
        params["hc_head_phi"] = jax.random.normal(
            jax.random.fold_in(key, 107), (n * D, n), jnp.float32) * (n * D) ** -0.5
        params["hc_head_alpha"] = jnp.ones((1,), jnp.float32)
        params["hc_head_bias"] = jnp.zeros((n,), jnp.float32)
    return params


def param_count(cfg: LMConfig) -> int:
    """Parameters this chip holds (of a sparse layer: its share of the experts)."""
    D, F = cfg.d_model, cfg.d_ff
    kd = cfg.n_kv_heads * cfg.head_dim
    norms = 4 * D if cfg.block_style == "sandwich" else 2 * D
    n, W = cfg.n_streams, cfg.hc.width
    maps = 2 * (n * D * W + 3 + W) if n > 1 else 0
    layers = 0
    for cls in layer_classes(cfg):
        qd = cls.n_heads * cfg.head_dim
        if cls.mlp == SPARSE:
            mlp = (D * cfg.n_experts + cfg.n_held * 3 * D * cfg.d_expert
                   + 3 * D * cfg.d_shared_expert
                   + (cfg.n_experts if cfg.router == "sigmoid_bias" else 0))
        else:
            mlp = 3 * D * F
        if cfg.latent:
            rq, rkv, vd = cfg.q_lora_rank, cfg.kv_lora_rank, cls.n_heads * cfg.v_head_dim
            attn = (D * rq + rq + rq * qd + D * (rkv + cfg.qk_rope_dim) + rkv
                    + rkv * (qd - cls.n_heads * cfg.qk_rope_dim) + rkv * vd + vd * D)
        else:
            attn = D * qd + 2 * D * kd + qd * D
        gate = D * cls.n_heads if cfg.attn_gate == "per_head" else 0
        layers += len(cls.layers) * (norms + attn + gate + mlp + maps)
    heads = 1 if cfg.tie_embeddings else 2
    head_read = n * D * n + 1 + n if n > 1 else 0
    return heads * cfg.vocab_size * D + D + layers + head_read


# ---------------------------------------------------------------------------
# numerics


def _rms_norm(x: jax.Array, w: jax.Array, eps: float, plain: bool = False) -> jax.Array:
    """RMSNorm in fp32: Gemma's (1 + w) scale, or ``plain`` w."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    scale = w.astype(jnp.float32) if plain else 1.0 + w.astype(jnp.float32)
    return (xf * scale).astype(x.dtype)


@jax.named_scope("harvest/block/norm")
def _norm(x: jax.Array, w: jax.Array, cfg: LMConfig) -> jax.Array:
    return _rms_norm(x, w, cfg.rms_eps, plain=cfg.block_style == "prenorm")


def _softcap(x: jax.Array, cap: float) -> jax.Array:
    return cap * jnp.tanh(x / cap)


def rope_inv_freq(rope: Rope, head_dim: int) -> jax.Array:
    """The rotation frequencies of one attention kind: one a pair of the
    ``head_dim · rotary_factor`` leading dims that rotate."""
    d = int(head_dim * rope.rotary_factor)
    if not rope.yarn_factor:
        return 1.0 / (rope.theta ** (jnp.arange(0, d // 2, dtype=jnp.float32) * 2.0 / d))
    # YaRN (HF ``_compute_yarn_parameters``), closed form, in float64
    extra = rope.theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    inter = extra / rope.yarn_factor

    def correction_dim(rotations: float) -> float:
        return (d * math.log(rope.original_max_position / (rotations * 2 * math.pi))
                / (2 * math.log(rope.theta)))

    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return jnp.asarray(inter * ramp + extra * (1.0 - ramp), jnp.float32)


class _LayerKind(NamedTuple):
    """What a layer's place in the config's table selects."""

    index: Any          # the (traced) layer id itself
    is_local: Any       # the sliding-window mask: bool scalar, traced or (a
                        # class of one attention kind) a static ``np.bool_``
    inv_freq: Any       # RoPE frequencies of the layer's kind, one a rotated pair
    rope_factor: Any    # what cos and sin are multiplied by; python 1.0 = not
    slot: Any = None    # the layer's place in its class's stack (None: ``index``)


def _layer_kind(cfg: LMConfig, i: jax.Array, cls: LayerClass | None = None,
                slot: Any = None) -> _LayerKind:
    """The ONE lookup of the traced layer id ``i`` in ``cfg.layer_types``,
    shared by every forward. Where both attention kinds rotate alike (the
    Gemma-2 family) the RoPE side is static and only the mask is looked up;
    where the layer's class ``cls`` is of one attention kind (a table whose
    kinds differ in shape) everything is static and nothing is looked up."""
    if cls is not None and cls.kind is not None:
        rope = cfg.rope_of(cls.kind)
        return _LayerKind(i, np.bool_(cls.kind == SLIDING),
                          rope_inv_freq(rope, cfg.rope_dim), rope.attention_factor, slot)
    is_local = jnp.asarray([k == SLIDING for k in cfg.layer_types])[i]
    local, full = cfg.rope_of(SLIDING), cfg.rope_of(FULL)
    if local == full:
        return _LayerKind(i, is_local, rope_inv_freq(local, cfg.rope_dim),
                          local.attention_factor, slot)
    if local.rotary_factor != full.rotary_factor:
        raise ValueError(
            "attention kinds that rotate different shares of a head cannot "
            "share a stack of leaves (the rotated width is static)")
    return _LayerKind(
        i, is_local,
        jnp.where(is_local, rope_inv_freq(local, cfg.rope_dim),
                  rope_inv_freq(full, cfg.rope_dim)),
        jnp.where(is_local, jnp.float32(local.attention_factor),
                  jnp.float32(full.attention_factor)),
        slot,
    )


@jax.named_scope("harvest/block/attn/rope")
def _rope(x: jax.Array, positions: jax.Array, inv_freq: jax.Array,
          factor: Any = 1.0) -> jax.Array:
    """Rotate pairs (x[..., :r/2], x[..., r/2:r]) — HF 'split-half' layout —
    of the ``r = 2 · len(inv_freq)`` leading dims; the rest pass unrotated.

    x: [B, S, n_heads, head_dim]; positions: [S] (shared across the batch,
    the padded path) or [B, S] (per-token — the paged runtime's packed
    plane carries each document's own within-document positions).
    """
    d = 2 * inv_freq.shape[-1]
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [(B,) S, d/2]
    cos = jnp.expand_dims(jnp.cos(ang), -2)                  # [(B,) S, 1, d/2]
    sin = jnp.expand_dims(jnp.sin(ang), -2)
    if not (isinstance(factor, float) and factor == 1.0):
        cos, sin = cos * factor, sin * factor
    x1, x2 = x[..., : d // 2], x[..., d // 2: d]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    parts = [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin]
    if d < x.shape[-1]:
        parts.append(x[..., d:].astype(jnp.float32))
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)


def _qkv(
    x: jax.Array, lp: Mapping[str, jax.Array], cfg: LMConfig, pos: jax.Array,
    kind: _LayerKind,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Project + RoPE: q [B,S,H,hd], k/v [B,S,KV,hd]. ``pos`` carries GLOBAL
    positions so sequence-sharded callers rotate correctly."""
    B, S, _ = x.shape
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    H = lp["wq"].shape[-1] // hd        # the layer's own (``heads_by_layer``)
    q = jnp.einsum("bsd,dq->bsq", x, lp["wq"], preferred_element_type=jnp.float32)
    k = jnp.einsum("bsd,dk->bsk", x, lp["wk"], preferred_element_type=jnp.float32)
    v = jnp.einsum("bsd,dk->bsk", x, lp["wv"], preferred_element_type=jnp.float32)
    q = _rope(q.astype(x.dtype).reshape(B, S, H, hd), pos, kind.inv_freq, kind.rope_factor)
    k = _rope(k.astype(x.dtype).reshape(B, S, KV, hd), pos, kind.inv_freq, kind.rope_factor)
    return q, k, v.astype(x.dtype).reshape(B, S, KV, hd)


@jax.named_scope("harvest/block/attn/latent")
def _latent_qkv(
    x: jax.Array, lp: Mapping[str, jax.Array], cfg: LMConfig, pos: jax.Array,
    kind: _LayerKind,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """The latent form's two low-rank paths, each with an RMSNorm in its
    middle: ``q_nope [B,S,H,dn]``, ``q_rope [B,S,H,dr]`` (rotated),
    ``k_nope [B,S,H,dn]``, the ONE rotary key ``k_rope [B,S,1,dr]`` all
    heads share (rotated), ``v [B,S,H,dv]``."""
    B, S, _ = x.shape
    dr, rkv = cfg.qk_rope_dim, cfg.kv_lora_rank
    H = lp["wq_rope"].shape[-1] // dr

    def proj(a, w):
        return jnp.einsum("bsd,dq->bsq", a, w, preferred_element_type=jnp.float32).astype(x.dtype)

    cq = _norm(proj(x, lp["wq_a"]), lp["q_a_norm"], cfg)
    ckv = proj(x, lp["wkv_a"])
    c = _norm(ckv[..., :rkv], lp["kv_a_norm"], cfg)
    q_rope = _rope(proj(cq, lp["wq_rope"]).reshape(B, S, H, dr), pos,
                   kind.inv_freq, kind.rope_factor)
    k_rope = _rope(ckv[..., rkv:].reshape(B, S, 1, dr), pos, kind.inv_freq, kind.rope_factor)
    return (proj(cq, lp["wq_nope"]).reshape(B, S, H, -1), q_rope,
            proj(c, lp["wk_nope"]).reshape(B, S, H, -1), k_rope,
            proj(c, lp["wv"]).reshape(B, S, H, -1))


def _latent_attend(
    parts: tuple, cfg: LMConfig, kind: _LayerKind, attend: Callable | None,
) -> jax.Array:
    """Attention on the latent form's heads → ``[B, S, H·dv]``. Two forms,
    chosen here as :func:`_attn_core` chooses: the padded path on a
    one-device TPU backend at a supported shape runs the fused kernel's
    latent instance (score and value head sizes of their own; the shared
    rotary key one more band in VMEM, never copied a head); everything else
    attends on the EXPANDED heads — q and k as ``[nope | rope]`` of
    ``head_dim``, the rotary key repeated a head, v zero-padded to that width
    and cut back — through whatever the forward reaches attention by."""
    from crosscoder_tpu import obs
    from crosscoder_tpu.ops import flash_attention as fa

    q_nope, q_rope, k_nope, k_rope, v = parts
    B, S, H, dv = v.shape
    obs.count("harvest/attn_latent_traces")
    if attend is None and fa.enabled() and fa.latent_supported(
            S, q_nope.shape[-1], q_rope.shape[-1], dv, v.dtype):
        obs.count("harvest/attn_fused_traces")
        return fa.flash_attention_latent(
            q_nope, q_rope, k_nope, k_rope, v, scale=cfg.query_pre_attn_scalar ** -0.5)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
    hd = q.shape[-1]
    v = jnp.pad(v, ((0, 0),) * 3 + ((0, hd - dv),))
    a = (_attn_core(q, k, v, cfg, kind.is_local) if attend is None
         else attend(q, k, v, kind))
    return a.reshape(B, S, H, hd)[..., :dv].reshape(B, S, H * dv)


def _attn_core(
    q: jax.Array, k: jax.Array, v: jax.Array, cfg: LMConfig,
    is_local: jax.Array, lengths: jax.Array | None = None,
) -> jax.Array:
    """Masked-softmax attention on projected heads: q [B, S, H, hd],
    k/v [B, S, KV, hd] → [B, S, H·hd] (pre output-projection).

    One algorithm with cfg-derived scalars, two forms, chosen here — when
    the program is traced — from what the code can observe:

    - the padded path (``lengths is None``) on a one-device TPU backend at
      a shape :func:`crosscoder_tpu.ops.flash_attention.supported` accepts
      runs the fused online-softmax kernel, which never writes the [S, S]
      scores to HBM. Where the window cannot bind (0, or ≥ S) local and
      global layers are one kernel instance; otherwise a static ``is_local``
      names its instance and a traced one has ``lax.cond`` pick between
      the two;
    - everything else — the CPU backend, a mesh, an unsupported shape, the
      paged runtime (``lengths`` given) — runs the XLA form
      (:func:`crosscoder_tpu.ops.paged_attention.ragged_attention_reference`),
      which is also the oracle the kernel is pinned against.

    ``lengths`` (the paged runtime's per-document valid token counts) adds
    a key-side validity mask — a no-op for valid queries (causal ⊆
    in-length), which is what makes the paged XLA path bit-identical to
    the padded XLA forward at valid positions (rows at t >= length are
    computed on whatever the gather clamped to, and discarded).

    The choice is counted in the job's telemetry plane, once per trace:
    ``harvest/attn_fused_traces`` / ``harvest/attn_xla_traces``."""
    from crosscoder_tpu import obs
    from crosscoder_tpu.ops import flash_attention as fa
    from crosscoder_tpu.ops import paged_attention as pa

    S, H, hd = q.shape[1:]
    scale = cfg.query_pre_attn_scalar ** -0.5
    if lengths is None and fa.enabled() and fa.supported(
            S, H, k.shape[2], hd, q.dtype):
        obs.count("harvest/attn_fused_traces")

        def fused(window):
            return lambda qkv: fa.flash_attention(
                *qkv, scale=scale, softcap=cfg.attn_softcap, window=window)

        if not 0 < cfg.sliding_window < S:
            return fused(0)((q, k, v))
        if isinstance(is_local, np.bool_):      # a class of one attention kind
            return fused(cfg.sliding_window if is_local else 0)((q, k, v))
        return jax.lax.cond(
            is_local, fused(cfg.sliding_window), fused(0), (q, k, v))
    obs.count("harvest/attn_xla_traces")
    return pa.ragged_attention_reference(
        q, k, v, lengths,
        scale=scale, softcap=cfg.attn_softcap, window=cfg.sliding_window,
        is_local=is_local,
    )


# Every leaf of a class's stack is read where it lies: the scan's body takes
# layer ``s``'s leaves out of the stack by index (:func:`_layer_leaves`), and
# the product that consumes one reads it from there. What sets the expert
# leaves apart is that a KERNEL consumes them: a ``pallas_call``'s operand
# is a whole array, so an index in front of it would be a copy (0.8 GB a
# layer at Mellum2's sizes). They stay STACKED and the expert layer's kernels
# index them themselves, by the layer's slot in its class's stack
# (``ops/moe.py``).
_HELD_LEAVES = ("we_gate_up", "we_down")


@jax.named_scope("harvest/leaves")
def _layer_leaves(stack: Mapping[str, jax.Array], s: jax.Array) -> dict:
    """Layer ``s``'s leaves out of its class's WHOLE stack, by index — no
    range of the stack is ever cut out ahead of the scan — with the
    ``_HELD_LEAVES`` left stacked."""
    return {k: v if k in _HELD_LEAVES
            else jax.lax.dynamic_index_in_dim(v, s, 0, keepdims=False)
            for k, v in stack.items()}


def _attn_out(a: jax.Array, lp: Mapping[str, jax.Array], cfg: LMConfig) -> jax.Array:
    """Output projection of the attended heads [B, S, H·hd], then the
    sandwich block's post-norm: the contribution as ADDED to the stream."""
    a = jnp.einsum("bsq,qd->bsd", a, lp["wo"], preferred_element_type=jnp.float32).astype(a.dtype)
    if cfg.block_style == "sandwich":
        a = _norm(a, lp["post_attn_norm"], cfg)
    return a


@jax.named_scope("harvest/block/attn")
def _attention(
    x: jax.Array, lp: Mapping[str, jax.Array], cfg: LMConfig, kind: _LayerKind,
    pos: jax.Array | None = None, attend: Callable | None = None,
) -> jax.Array:
    """One attention sublayer on the normed stream [B, S, D], as added to
    the stream. ``kind.is_local`` selects the sliding-window mask (traced
    scalar — both masks are static precomputes). ``pos`` and ``attend`` are
    the ONE thing the forwards differ in (see :func:`_block`)."""
    pos = jnp.arange(x.shape[1]) if pos is None else pos
    if "wkv_a" in lp:
        return _attn_out(
            _latent_attend(_latent_qkv(x, lp, cfg, pos, kind), cfg, kind, attend), lp, cfg)
    q, k, v = _qkv(x, lp, cfg, pos, kind)
    a = (_attn_core(q, k, v, cfg, kind.is_local) if attend is None
         else attend(q, k, v, kind))
    if "w_attn_gate" in lp:
        with jax.named_scope("harvest/block/attn/gate"):
            # one sigmoid scalar a head and position, from the block's normed
            # input, on the attended heads before the output projection
            g = jax.nn.sigmoid(jnp.einsum(
                "bsd,dh->bsh", x, lp["w_attn_gate"], preferred_element_type=jnp.float32))
            B, S, H = g.shape
            a = (a.reshape(B, S, H, -1) * g[..., None]).astype(a.dtype).reshape(a.shape)
    return _attn_out(a, lp, cfg)


def _gated_mlp(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
               cfg: LMConfig) -> jax.Array:
    """``act(x·W_gate) ⊙ (x·W_up) · W_down``: tanh-GELU in the sandwich
    block, SiLU in the pre-norm one."""
    gate = jnp.einsum("bsd,df->bsf", x, w_gate, preferred_element_type=jnp.float32)
    up = jnp.einsum("bsd,df->bsf", x, w_up, preferred_element_type=jnp.float32)
    if cfg.block_style == "sandwich":
        gate = jax.nn.gelu(gate, approximate=True)
    else:
        gate = jax.nn.silu(gate)
    h = (gate * up).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", h, w_down, preferred_element_type=jnp.float32).astype(x.dtype)


@jax.named_scope("harvest/block/mlp")
def _mlp(x: jax.Array, lp: Mapping[str, jax.Array], cfg: LMConfig, slot: Any) -> jax.Array:
    """The gated MLP of the layer's kind (by its leaves) on the normed
    stream: the dense one, or the routed experts (``ops/moe.py``; their
    leaves in ``lp`` are the STACKED ones, indexed by ``slot``) — this
    chip's share of them where the config holds one — plus, where the
    config has one, the shared expert on the same input."""
    if "router" not in lp:
        return _gated_mlp(x, lp["w_gate"], lp["w_up"], lp["w_down"], cfg)
    from crosscoder_tpu.ops import moe

    m = moe.moe_mlp(
        x, lp["router"], lp["we_gate_up"], lp["we_down"], slot,
        top_k=cfg.experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
        routed_scale=cfg.routed_scale, first_expert=cfg.first_expert,
        router=cfg.router, router_bias=lp.get("router_bias"))
    if "ws_gate" in lp:
        with jax.named_scope("harvest/block/moe/shared"):
            m = m + _gated_mlp(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"], cfg)
    return m


def _mlp_out(resid: jax.Array, lp: Mapping[str, jax.Array], cfg: LMConfig, slot: Any) -> jax.Array:
    """The MLP sublayer of the layer at ``slot`` of its class's stack on
    what it reads of the stream, as written back to it."""
    m = _mlp(_norm(resid, lp["pre_ffw_norm"], cfg), lp, cfg, slot)
    if cfg.block_style == "sandwich":
        m = _norm(m, lp["post_ffw_norm"], cfg)
    return m


def _embed(params: LMParams, tokens: jax.Array, cfg: LMConfig) -> jax.Array:
    with jax.named_scope("harvest/embed"):
        dt = dtype_of(cfg.dtype)
        resid = params["embed"][tokens].astype(dt)
        if cfg.block_style == "sandwich":
            resid = resid * jnp.asarray(math.sqrt(cfg.d_model), dt)
        return resid


@jax.named_scope("harvest/capture")
def _stream_mean(resid: jax.Array, cfg: LMConfig) -> jax.Array:
    """What the residual hooks see, ``[B, S, D]``: the stream itself, or —
    of a token's ``n_streams`` — their MEAN, the one quantity the doubly
    stochastic mixing conserves (``m' = m + mean(h_post) · y``)."""
    if cfg.n_streams == 1:
        return resid
    from crosscoder_tpu.ops import mhc

    parts = [s.astype(jnp.float32) for s in mhc.streams_of(resid, cfg.n_streams)]
    return (functools.reduce(jnp.add, parts) * (1.0 / cfg.n_streams)).astype(resid.dtype)


def _read(resid: jax.Array, lp: Mapping[str, jax.Array], cfg: LMConfig, site: str):
    """What a sublayer reads of the stream (before its own norm), and what
    its write needs: the stream itself and nothing; or, of ``n_streams``,
    the input-dependent weighted read and the maps (``ops/mhc.py``)."""
    if cfg.n_streams == 1:
        return resid, None
    from crosscoder_tpu.ops import mhc

    with jax.named_scope("harvest/block/mhc/read"):
        return mhc.read(resid, lp[f"hc_{site}_phi"], lp[f"hc_{site}_alpha"],
                        lp[f"hc_{site}_bias"], cfg.hc)


def _write(resid: jax.Array, y: jax.Array, maps: Any, cfg: LMConfig):
    """A sublayer's output joins the stream: ``(the stream after it, ``y``
    as ADDED to what the residual hooks see)``."""
    if maps is None:
        return resid + y, y
    from crosscoder_tpu.ops import mhc

    with jax.named_scope("harvest/block/mhc/write"):
        added = (y.astype(jnp.float32) * mhc.mean_gain(maps, y.shape[:-1])).astype(y.dtype)
        return mhc.write(resid, y, maps, cfg.hc), added


class _Seen(NamedTuple):
    """What a block hands a forward's ``emit`` besides its leaves."""

    mlp_in: jax.Array       # what the MLP sublayer read, before its norm
    maps: tuple             # the two sublayers' stream maps (none: one stream)


def _block(
    resid: jax.Array, lp: Mapping[str, jax.Array], cfg: LMConfig, kind: _LayerKind,
    edit_attn: Callable[[jax.Array], jax.Array] | None = None,
    edit_mlp: Callable[[jax.Array], jax.Array] | None = None,
    pos: jax.Array | None = None, attend: Callable | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, _Seen]:
    """One transformer block of the config's style and kinds — the ONLY
    place read → norm → QKV → attention → out-projection → write → read →
    norm → MLP → write is spelled; every forward runs it through
    :func:`_scan_blocks`. With one stream a read is the stream and a write
    an add; with ``n_streams`` they are the maps of ``ops/mhc.py``.

    Returns ``(resid, attn_out, mlp_out, seen)`` — the updated stream plus
    the two sublayer contributions exactly as they are ADDED to what the
    residual hooks see (in the sandwich block: after its post-norms; of
    several streams: ``mean(h_post) · y``, what joins their mean), which is
    what ``hook_attn_out``/``hook_mlp_out`` capture: the intermediates exist
    anyway, so exposing them is free. ``edit_attn``/``edit_mlp`` intervene
    on a sublayer's output BEFORE it joins the stream (and before its
    capture) — the sublayer-site analogue of the residual edits, used by
    CE-recovered evals of sublayer crosscoders.

    ``pos`` (the positions RoPE rotates by: ``[S]`` or per-token ``[B, S]``)
    and ``attend(q, k, v, kind) -> [B, S, H·hd]`` (pre output-projection)
    are how a forward that is not the padded one reaches attention: the
    paged runtime passes each document's own positions and a gather →
    per-document attention → scatter, the sequence-sharded one its shard's
    global positions and the ring. Left None they are ``arange(S)`` and
    :func:`_attn_core`."""
    u, maps_a = _read(resid, lp, cfg, "attn")
    attn_out = _attention(_norm(u, lp["attn_norm"], cfg), lp, cfg, kind, pos, attend)
    if edit_attn is not None:
        attn_out = edit_attn(attn_out)
    resid, attn_out = _write(resid, attn_out, maps_a, cfg)
    u, maps_m = _read(resid, lp, cfg, "ffn")
    mlp_out = _mlp_out(u, lp, cfg, kind.index if kind.slot is None else kind.slot)
    if edit_mlp is not None:
        mlp_out = edit_mlp(mlp_out)
    resid, mlp_out = _write(resid, mlp_out, maps_m, cfg)
    return resid, attn_out, mlp_out, _Seen(u, (maps_a, maps_m) if cfg.n_streams > 1 else ())


# ---------------------------------------------------------------------------
# hooks: capture + edits


def splice_edit(resid: jax.Array, value: jax.Array) -> jax.Array:
    """Replace all post-BOS positions, keep position 0 clean — the
    reference's ``splice_act_hook`` (``act[:, 1:, :] = spliced_act``,
    nb:cell 29)."""
    return jnp.concatenate([resid[:, :1], value[:, 1:].astype(resid.dtype)], axis=1)


def zero_edit(resid: jax.Array, value: jax.Array) -> jax.Array:
    """Zero the whole hook activation — the reference's
    ``zero_ablation_hook`` (nb:cell 29)."""
    del value
    return jnp.zeros_like(resid)


def replace_edit(resid: jax.Array, value: jax.Array) -> jax.Array:
    return value.astype(resid.dtype)


@dataclass(frozen=True)
class Edit:
    """An activation intervention at one hook point.

    ``fn(resid, value) -> resid`` must be shape-preserving and jit-pure;
    ``value`` is a traced [B, S, d_model] operand (ignored by ``zero_edit``).
    """

    hook_point: str
    fn: Callable[[jax.Array, jax.Array], jax.Array]
    value: jax.Array | None = None


# hook-site codes (static, baked into the capture tuples)
_SITE_RESID, _SITE_ATTN, _SITE_MLP = 0, 1, 2


def _slots(capture: tuple[tuple[int, int], ...]):
    """The capture slots of a forward from its static ``capture`` tuple, as
    two [n_cap] int32 arrays: each slot's layer, and its site code."""
    if not capture:
        return None
    return (jnp.asarray([l for l, _ in capture], jnp.int32),
            jnp.asarray([c for _, c in capture], jnp.int32))


@jax.named_scope("harvest/capture")
def _capture_into(
    buf: jax.Array | None, x: jax.Array, i, slots, site: int = _SITE_RESID,
) -> jax.Array | None:
    """Accumulate ``x`` into the capture slot whose (layer, site) equals
    ``(i, site)`` (one-hot over slots)."""
    if buf is None:
        return None
    layers, sites = slots
    match = ((layers == i) & (sites == site)).astype(x.dtype)
    return buf + match[:, None, None, None] * x[None]


def _unembed(params: LMParams, resid: jax.Array, cfg: LMConfig) -> jax.Array:
    """(Of several streams: the learned read.) Final RMSNorm → unembedding
    (the embedding again where tied) → final-logit softcap."""
    if cfg.n_streams > 1:
        from crosscoder_tpu.ops import mhc

        resid = mhc.head_read(resid, params["hc_head_phi"], params["hc_head_alpha"],
                              params["hc_head_bias"], cfg.rms_eps)
    x = _norm(resid, params["final_norm"], cfg)
    head = params["embed" if cfg.tie_embeddings else "unembed"]
    logits = jnp.einsum("bsd,vd->bsv", x, head, preferred_element_type=jnp.float32)
    if cfg.final_softcap:
        logits = _softcap(logits, cfg.final_softcap)
    return logits


def _hook_layers(cfg: LMConfig, hook_points: Sequence[str]) -> tuple[tuple[int, int], ...]:
    """Map hook strings to capture ``(layer, site)`` pairs.

    Residual sites: ``resid_pre`` of layer L is the stream entering block L
    (slot (L, resid)); ``resid_post`` of L is ``resid_pre`` of L+1 (the
    final layer's post-stream is slot (n_layers, resid)). Sublayer sites
    (TransformerLens exposes these; the reference only ever uses
    ``resid_pre``, reference train.py:32): ``attn_out`` / ``mlp_out`` of
    layer L are the block's attention/MLP contributions as ADDED to the
    stream — i.e. after Gemma-2's post-sublayer sandwich norms."""
    pairs = []
    for hp in hook_points:
        layer, site = parse_hook_point(hp)
        if site == "resid_pre":
            code = _SITE_RESID
        elif site == "resid_post":
            layer, code = layer + 1, _SITE_RESID
        elif site == "attn_out":
            code = _SITE_ATTN
        elif site == "mlp_out":
            code = _SITE_MLP
        else:
            raise ValueError(
                f"unsupported hook site {site!r} "
                "(resid_pre/resid_post/attn_out/mlp_out)"
            )
        max_layer = cfg.n_layers if code == _SITE_RESID else cfg.n_layers - 1
        if not 0 <= layer <= max_layer:
            raise ValueError(f"hook layer {layer} out of range for {cfg.n_layers}-layer model")
        pairs.append((layer, code))
    return tuple(pairs)


def _scan_stop(pairs: tuple[tuple[int, int], ...]) -> int:
    """Layers that must run for every capture/edit to be observable: a
    resid slot at L needs blocks [0, L); a sublayer slot at L needs block L
    itself."""
    return max(
        (layer + (1 if code != _SITE_RESID else 0) for layer, code in pairs),
        default=0,
    )


def hooked_depth(cfg: LMConfig, hook_points: Sequence[str]) -> int:
    """Blocks a capture-only forward over ``hook_points`` runs."""
    return min(cfg.n_layers, _scan_stop(_hook_layers(cfg, tuple(hook_points))))


# ---------------------------------------------------------------------------
# forward


def _fresh_carry(params: LMParams, tokens: jax.Array, cfg: LMConfig, n_cap: int):
    """What a forward's layer loop starts from: the embedded stream (of
    ``n_streams``: ``[B, S, n·D]``, a token's streams side by side, the
    embedding in each) and a zero capture buffer ``[n_cap, B, S, D]`` (None
    where nothing is captured)."""
    B, S = tokens.shape
    resid = _embed(params, tokens, cfg)
    if cfg.n_streams > 1:
        resid = jnp.tile(resid, (1, 1, cfg.n_streams))
    buf = jnp.zeros((n_cap, B, S, cfg.d_model), resid.dtype) if n_cap else None
    return resid, buf


class _Run(NamedTuple):
    """Consecutive layers of one class."""

    cls: int        # index into :func:`layer_classes`
    layer: int      # the first layer's id in the model
    slot: int       # ... and its slot in the class's stack
    n: int


def _runs(cfg: LMConfig, lo: int, hi: int) -> list[_Run]:
    """Layers ``[lo, hi)`` as maximal runs of one class."""
    where, out = _class_slots(cfg), []
    for i in range(lo, hi):
        c, s = where[i]
        if out and out[-1].cls == c:
            out[-1] = out[-1]._replace(n=out[-1].n + 1)
        else:
            out.append(_Run(c, i, s, 1))
    return out


def _periodic(runs: list[_Run]) -> tuple[int, int, int]:
    """``(lead, period, repeats)`` over a list of runs: after ``lead`` runs,
    ``period`` runs repeat ``repeats`` ≥ 2 times in class and length, chosen
    so that the fewest runs are written out (lead + period + what is left);
    ``repeats`` 0 where nothing repeats."""
    shape = [(r.cls, r.n) for r in runs]
    best = (len(runs), 0, 0, 0)
    for lead in range(len(runs)):
        for period in range(1, (len(runs) - lead) // 2 + 1):
            reps = 1
            while shape[lead + reps * period: lead + (reps + 1) * period] == \
                    shape[lead: lead + period]:
                reps += 1
            if reps >= 2:
                best = min(best, (len(runs) - (reps - 1) * period, lead, period, reps))
    return best[1:]


def _scan_blocks(
    params: LMParams, cfg: LMConfig, capture: tuple[tuple[int, int], ...],
    carry: tuple[jax.Array, jax.Array | None], k: int, lo: jax.Array | None = None,
    *, cls: int | None = None, pos: jax.Array | None = None,
    attend: Callable | None = None,
    edits: tuple[tuple, tuple, tuple] = ((), (), ()),
    emit: Callable | None = None,
):
    """THE layer loop, behind every forward in this module: blocks
    ``[lo, lo + k)`` carrying ``carry = (resid, buf)`` — ``resid`` ``[B, S,
    D]``, or ``[B, S, n·D]`` where a token carries ``n_streams`` (side by
    side: one row a token, so no relayout between the carry and the stream
    maps' tiles); the
    residual hooks then see the streams' mean (:func:`_stream_mean`). Per
    layer: residual-site edits, the residual-site capture, :func:`_block`
    (with the sublayer-site edits inside it), the sublayer-site captures.
    Returns ``((resid, buf), ys)`` as a scan does.

    Layers of one class (:func:`layer_classes`) are one stack of leaves and
    run under one ``lax.scan`` (``run`` below); a table of ONE class — the
    common case — is exactly that scan and nothing else. A range that spans
    classes is walked run by run; where its runs repeat (a period of the
    table) the repeats are ONE outer ``lax.scan`` whose body holds the
    period's runs, so the program does not grow with depth.

    What a caller hands in is what truly differs between the forwards:

    - the range. ``lo=None`` is the static ``[0, k)`` of a whole forward,
      which ends with the VIRTUAL layer ``k`` — resid_pre of the first
      unscanned block (== the final resid_post when ``k == n_layers``),
      edited and captured like any other. A TRACED ``lo`` is one segment of
      a longer job: one compiled program serves every segment of a given
      width (no per-range recompiles), and static or traced, a range's
      layers take their leaves out of the class's whole stack by index
      inside the scan's body (:func:`_layer_leaves`) — the range is never
      cut out of the stack, which would copy its weights once a call. On a
      table of several classes the segment lies inside one run and names its
      class ``cls`` (static). Its virtual layer is the job's business
      (:func:`_seg_finish_impl`);
    - the carry: :func:`_fresh_carry`, or a segment's donated one;
    - ``pos`` / ``attend``: how attention is reached (:func:`_block`);
    - ``edits``: ``(fns, (layer, site) pairs, values)``, parallel tuples.
      With none, the body traces exactly the capture-only op sequence;
    - ``emit(lp, seen)``: a per-layer output of the caller's own (``ys``, in
      layer order), from the block's leaves and what it saw (:class:`_Seen`).
    """
    slots = _slots(capture)
    # static: skip the sublayer-capture FMAs entirely on resid-only runs
    captured_sites = {c for _, c in capture}
    edit_fns, edit_layers, edit_values = edits
    edit_arr = (
        jnp.asarray([l for l, _ in edit_layers], dtype=jnp.int32)
        if edit_layers else None
    )

    def edited(x, i, site):
        # the edits at this site whose layer is ``i``: the site selection is
        # static, layer matching a one-hot where-chain like the capture's
        for j, fn in enumerate(edit_fns):
            if edit_layers[j][1] == site:
                new = fn(x, edit_values[j])
                x = jnp.where(edit_arr[j] == i, new, x)
        return x

    def edited_resid(resid, i):
        if cfg.n_streams == 1:
            return edited(resid, i, _SITE_RESID)
        if not any(site == _SITE_RESID for _, site in edit_layers):
            return resid
        # an edit of what the hooks see — the streams' mean — shifts every
        # stream by the same amount: the mean becomes the edited value, the
        # streams' deviations from it stay
        m = _stream_mean(resid, cfg)
        return resid + jnp.tile(edited(m, i, _SITE_RESID) - m, (1, 1, cfg.n_streams))

    # a residual slot at ``n_layers`` is only ever the virtual layer's: with
    # several streams the loop then skips forming their mean in every block
    in_body = slots if cfg.n_streams == 1 or any(
        layer < cfg.n_layers for layer, site in capture if site == _SITE_RESID) else None

    classes, stacks = layer_classes(cfg), class_stacks(params, cfg)
    one_class = len(classes) == 1

    def run(carry, c, slot, n, shift=0):
        """``n`` layers of class ``c`` from ``slot`` of its stack (static or
        traced), under one scan; a layer's id is its slot plus ``shift``
        (static 0 on a table of one class)."""
        # TransformerLens-style stop_at_layer: scan only the blocks below the
        # highest needed layer (the reference harvests with FULL forwards even
        # for a mid-stack hook — reference buffer.py:81-89 — wasting every layer
        # above it; at blocks.14 of 26 that is ~46% of the forward FLOPs).
        # The scan runs over the layers' slots ALONE and its body indexes the
        # class's whole stack: a range of the stack cut out in front of the
        # loop is a copy of every scanned weight, once a call.
        at = slot + jnp.arange(n, dtype=jnp.int32)

        def body(carry, s):
            resid, buf = carry
            lp = _layer_leaves(stacks[c], s)
            i = s if isinstance(shift, int) and shift == 0 else s + shift
            resid = edited_resid(resid, i)
            if in_body is not None:
                buf = _capture_into(buf, _stream_mean(resid, cfg), i, slots)
            kind = _layer_kind(cfg, i) if one_class else _layer_kind(cfg, i, classes[c], s)
            resid, attn_out, mlp_out, seen = _block(
                resid, lp, cfg, kind,
                edit_attn=functools.partial(edited, i=i, site=_SITE_ATTN),
                edit_mlp=functools.partial(edited, i=i, site=_SITE_MLP),
                pos=pos, attend=attend,
            )
            if _SITE_ATTN in captured_sites:
                buf = _capture_into(buf, attn_out, i, slots, _SITE_ATTN)
            if _SITE_MLP in captured_sites:
                buf = _capture_into(buf, mlp_out, i, slots, _SITE_MLP)
            return (resid, buf), (emit(lp, seen) if emit else None)

        return jax.lax.scan(body, carry, at)

    if one_class:
        carry, ys = run(carry, 0, 0 if lo is None else lo, k)
    elif lo is not None:
        # one segment: ``k`` layers of class ``cls`` from the traced layer ``lo``
        slot = jnp.asarray([s for _, s in _class_slots(cfg)], jnp.int32)[lo]
        carry, ys = run(carry, cls, slot, k, lo - slot)
    else:
        runs = _runs(cfg, 0, k)
        lead, period, reps = _periodic(runs)
        if not reps:
            lead = len(runs)
        span = runs[lead: lead + period]
        step = sum(r.n for r in span)                       # layers a period
        per_class = {r.cls: sum(q.n for q in span if q.cls == r.cls) for r in span}

        def walk(carry, some, t=0):
            out = []
            for r in some:      # (t: the traced repeat inside the outer scan; else 0)
                slot = r.slot + t * per_class.get(r.cls, 0)
                carry, ys = run(carry, r.cls, slot, r.n, r.layer + t * step - slot)
                out.append(ys)
            return carry, out

        carry, parts = walk(carry, runs[:lead])
        if reps:
            carry, ys = jax.lax.scan(
                lambda c, t: walk(c, span, t), carry, jnp.arange(reps, dtype=jnp.int32))
            if emit:            # [reps, n, ...] a run -> the layers in order
                ys = jax.tree.map(lambda *a: jnp.concatenate(a, axis=1), *ys)
                parts.append(jax.tree.map(
                    lambda a: a.reshape((reps * step,) + a.shape[2:]), ys))
            carry, rest = walk(carry, runs[lead + reps * period:])
            parts += rest
        ys = (jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *parts)
              if emit and parts else None)
    if lo is None:
        resid, buf = carry
        resid = edited_resid(resid, jnp.int32(k))
        buf = _capture_into(buf, _stream_mean(resid, cfg), jnp.int32(k), slots)
        carry = (resid, buf)
    return carry, ys


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "capture", "edit_fns", "edit_layers", "return_logits", "n_scan"
    ),
)
def _forward_impl(
    params: LMParams,
    tokens: jax.Array,
    cfg: LMConfig,
    capture: tuple[tuple[int, int], ...],
    edit_fns: tuple[Callable, ...],
    edit_layers: tuple[tuple[int, int], ...],
    edit_values: tuple[jax.Array, ...],
    return_logits: bool,
    n_scan: int | None = None,
):
    if n_scan is None:
        n_scan = cfg.n_layers
    (resid, cap_buf), _ = _scan_blocks(
        params, cfg, capture, _fresh_carry(params, tokens, cfg, len(capture)),
        n_scan, edits=(edit_fns, edit_layers, edit_values))
    logits = _unembed(params, resid, cfg) if return_logits else None
    return logits, cap_buf


def forward(
    params: LMParams,
    tokens: jax.Array,
    cfg: LMConfig,
    *,
    capture: Sequence[str] = (),
    edits: Sequence[Edit] = (),
    return_logits: bool = True,
) -> tuple[jax.Array | None, dict[str, jax.Array]]:
    """Run the LM; returns ``(logits, cache)``.

    - ``capture``: hook-point strings to record — the ``run_with_cache(
      names_filter=...)`` equivalent (reference buffer.py:81-89). The cache
      maps each string to a [B, S, d_model] array.
    - ``edits``: interventions applied BEFORE capture at the same hook —
      the ``run_with_hooks`` equivalent (nb:cell 29). Residual sites edit
      the stream; ``attn_out``/``mlp_out`` sites edit that sublayer's
      contribution before it joins the stream (so CE-recovered splicing
      works for sublayer-trained crosscoders too).
    - ``return_logits=False`` skips the unembedding (the d_model→256k matmul
      dominates harvest FLOPs above the hook layer; harvesting never needs it).
    """
    cap_pairs = _hook_layers(cfg, capture)
    edit_pairs = _hook_layers(cfg, [e.hook_point for e in edits])
    edit_fns = tuple(e.fn for e in edits)
    zeros = None
    values = []
    for e in edits:
        if e.value is not None:
            values.append(e.value)
        else:
            if zeros is None:
                zeros = jnp.zeros((tokens.shape[0], tokens.shape[1], cfg.d_model), dtype_of(cfg.dtype))
            values.append(zeros)
    # without logits, nothing above the highest hooked layer is observable
    n_scan = (
        cfg.n_layers
        if return_logits
        else min(cfg.n_layers, max(_scan_stop(cap_pairs), _scan_stop(edit_pairs)))
    )
    logits, cap_buf = _forward_impl(
        params, tokens, cfg, cap_pairs, edit_fns, edit_pairs, tuple(values),
        return_logits, n_scan=n_scan,
    )
    cache = {hp: cap_buf[i] for i, hp in enumerate(capture)}
    return logits, cache


def loss_fn(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy — TransformerLens ``return_type="loss"``
    (the CE metric of the reference eval, nb:cell 29)."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def run_with_cache(
    params: LMParams, tokens: jax.Array, cfg: LMConfig, hook_points: Sequence[str]
) -> dict[str, jax.Array]:
    """Capture-only forward (no unembedding) — the harvest primitive."""
    _, cache = forward(params, tokens, cfg, capture=hook_points, return_logits=False)
    return cache


@functools.partial(jax.jit, static_argnames=("cfg", "capture"))
def _multi_cache_impl(params_tuple, tokens, cfg: LMConfig, capture: tuple[str, ...]):
    per_source = []
    for p in params_tuple:
        cache = run_with_cache(p, tokens, cfg, capture)
        per_source.extend(cache[hp] for hp in capture)
    return jnp.stack(per_source, axis=2)                   # [B, S, n_sources, D]


def run_with_cache_multi(
    params_seq: Sequence[LMParams],
    tokens: jax.Array,
    cfg: LMConfig,
    hook_points: Sequence[str],
) -> jax.Array:
    """All models' captures in ONE compiled dispatch:
    ``[B, S, n_models·n_hooks, d_model]``, source axis model-major.

    The reference runs one ``run_with_cache`` per model per chunk (reference
    ``buffer.py:81-89``) — two kernel launches and two host round trips where
    one suffices (SURVEY.md §3.3 harvest path). Same architecture is required
    (the reference's models share it by construction, train.py:45-55).
    """
    return _multi_cache_impl(tuple(params_seq), tokens, cfg, tuple(hook_points))


def ce_loss(
    params: LMParams, tokens: jax.Array, cfg: LMConfig, edits: Sequence[Edit] = ()
) -> jax.Array:
    """CE of a (possibly intervened) forward — one number, on device."""
    logits, _ = forward(params, tokens, cfg, edits=edits)
    return loss_fn(logits, tokens)


@functools.partial(jax.jit, static_argnames=("cfg", "n_scan"))
def expert_load(params: LMParams, tokens: jax.Array, cfg: LMConfig, n_scan: int) -> jax.Array:
    """Routed rows per expert in the first ``n_scan`` layers of a forward
    over ``tokens``: ``[n_scan, n_experts]`` int32, over the router's whole
    width whatever share of the experts is held (a dense layer's row is 0).
    A diagnostic forward of its own (the harvest programs carry no such
    output) — the buffer runs it once, at calibration, for the
    ``harvest/moe_load_max_over_mean`` and ``harvest/moe_local_row_share``
    gauges, and only with ``obs`` on."""
    from crosscoder_tpu.ops import moe

    def routed(lp, seen):
        counts = jnp.zeros((cfg.n_experts,), jnp.int32)
        if "router" not in lp:
            return counts
        # the router reads what the block's MLP sublayer reads
        x = _norm(seen.mlp_in, lp["pre_ffw_norm"], cfg)
        idx, _ = moe.route(x.reshape(-1, cfg.d_model), lp["router"],
                           cfg.experts_per_tok, cfg.norm_topk_prob,
                           kind=cfg.router, bias=lp.get("router_bias"))
        return counts.at[idx.reshape(-1)].add(1)

    _, counts = _scan_blocks(
        params, cfg, (), _fresh_carry(params, tokens, cfg, 0), n_scan, emit=routed)
    return counts


@functools.partial(jax.jit, static_argnames=("cfg", "n_scan"))
def mhc_col_err(params: LMParams, tokens: jax.Array, cfg: LMConfig, n_scan: int) -> jax.Array:
    """``max |colsum(M) − 1|`` over the tokens and the two sublayers of each
    of the first ``n_scan`` layers, ``[n_scan]`` float32: how far Sinkhorn's
    last iteration leaves the mixing matrices from doubly stochastic, which
    is how exactly the streams' mean is the hooked residual stream. A
    diagnostic forward like :func:`expert_load`, for the gauge
    ``harvest/mhc_col_err``."""
    from crosscoder_tpu.ops import mhc

    def err(lp, seen):
        return jnp.maximum(*(mhc.col_err(m, cfg.n_streams) for m in seen.maps))

    _, errs = _scan_blocks(
        params, cfg, (), _fresh_carry(params, tokens, cfg, 0), n_scan, emit=err)
    return errs


# ---------------------------------------------------------------------------
# segmented harvest (sub-forward dispatch quanta for the refill pipeline)


@functools.partial(jax.jit, static_argnames=("cfg", "n_cap"))
def _seg_start_impl(params: LMParams, tokens: jax.Array, cfg: LMConfig, n_cap: int):
    return _fresh_carry(params, tokens, cfg, n_cap)


@functools.partial(
    jax.jit, static_argnames=("cfg", "capture", "k", "cls"), donate_argnums=(1, 2)
)
def _seg_scan_impl(
    params: LMParams, resid: jax.Array, buf: jax.Array, lo: jax.Array,
    cfg: LMConfig, capture: tuple[tuple[int, int], ...], k: int,
    cls: int | None = None,
):
    """Blocks [lo, lo+k) of the capture forward, carrying (resid, buf):
    :func:`_scan_blocks` with a TRACED ``lo`` (and, on a table of several
    classes, the one class ``cls`` the blocks are of). Per-layer math is that
    of ``_forward_impl`` (same ops in the same order); only the scan is cut
    into sub-scans."""
    return _scan_blocks(params, cfg, capture, (resid, buf), k, lo, cls=cls)[0]


@functools.partial(jax.jit, static_argnames=("cfg", "capture", "n_scan", "out_dtype"))
def _seg_finish_impl(
    resids: tuple, bufs: tuple, cfg: LMConfig,
    capture: tuple[tuple[int, int], ...], n_scan: int, out_dtype,
):
    """Virtual-layer capture per model + the model-major source stack —
    output shape/order identical to :func:`run_with_cache_multi`."""
    slots = _slots(capture)
    outs = []
    for resid, buf in zip(resids, bufs):
        buf = _capture_into(buf, _stream_mean(resid, cfg), jnp.int32(n_scan), slots)
        outs.extend(buf[i] for i in range(buf.shape[0]))
    out = jnp.stack(outs, axis=2)                  # [B, S, n_sources, D]
    return out.astype(out_dtype) if out_dtype is not None else out


class SegmentedHarvest:
    """:func:`run_with_cache_multi` as a sequence of ~equal small device
    dispatches instead of one monolithic one.

    Why: the replay buffer's incremental refill interleaves harvest
    forwards with train steps on ONE serial device queue, where a
    whole-chunk forward is an indivisible quantum that lands in whichever
    train step queues behind it (the harvest is 57–94 ms of device time a
    train step in the benchmark's cells: ledger, PR 30,
    ``harvest_device_ms_per_step``). Cut into ``⌈n_scan / SEG_LAYERS⌉``
    near-equal sub-scans, the refill can be metered evenly across serves;
    the math is the same per-layer op sequence, so results match the
    monolithic path (asserted by tests/test_lm.py). No reference
    counterpart — the reference harvests in one blocking stall (reference
    buffer.py:78-96).

    Protocol: ``step()`` dispatches one quantum (async, never blocks on the
    device) and returns False once the final stacked result has been
    dispatched; ``result()`` returns the ``[B, S, n_sources, D]`` capture
    array (dispatching any remainder first). ``n_steps`` is the total
    ``step()`` budget, for pacing.
    """

    # Harvest quantum granularity: layers per sub-scan. Trade: smaller
    # segments bound the refresh bubble tighter (a quantum lands inside
    # whichever train step queues behind it) but each segment dispatch
    # costs host time; where the balance sits on a chip is not measured
    # (``PERF.md`` §7; no ROADMAP item holds it).
    SEG_LAYERS = 3

    def __init__(
        self,
        params_seq: Sequence[LMParams],
        tokens: jax.Array,
        cfg: LMConfig,
        hook_points: Sequence[str],
        out_dtype=None,
    ) -> None:
        self.params_seq = tuple(params_seq)
        self.tokens = tokens
        self.cfg = cfg
        self.capture = _hook_layers(cfg, tuple(hook_points))
        self.n_scan = hooked_depth(cfg, hook_points)
        self.out_dtype = out_dtype
        self._runs = _runs(cfg, 0, self.n_scan)
        self._bounds = self.quanta(
            self.n_scan, self.SEG_LAYERS, [r.n for r in self._runs])
        self.n_steps = len(self.params_seq) * max(1, len(self._bounds))
        self._model_idx = 0
        self._lo = self._q = 0          # next layer; next quantum
        self._cls = None                # the next sub-scan's class (None: the one)
        self._resid = self._buf = None
        self._done_resids: list = []
        self._done_bufs: list = []
        self._out = None

    @classmethod
    def count(cls, cfg: LMConfig, hook_points: Sequence[str], n_models: int) -> int:
        """``step()`` calls a job over these hooks will need (for pacing)."""
        n_scan = hooked_depth(cfg, hook_points)
        runs = [r.n for r in _runs(cfg, 0, n_scan)]
        return n_models * max(1, len(cls.quanta(n_scan, cls.SEG_LAYERS, runs)))

    @staticmethod
    def quanta(n_scan: int, seg_layers: int, runs: Sequence[int] | None = None) -> list[int]:
        """The layer each quantum ends before: ``⌈n_scan / seg_layers⌉``
        quanta of NEAR-EQUAL depth, the deeper ones first (14 layers by 3:
        3, 3, 3, 3, 2; 4 layers by 3: 2, 2 — not 3, 1). The refill paces by
        quanta as if they cost the same (``data/buffer.py``
        ``_segs_per_chunk``), which a 3 + 1 split of four expert layers
        would miss by half. ``runs`` (the depths of the table's runs of one
        class, summing to ``n_scan``; None: one run) are cut each on its
        own: a quantum is one scan over one stack of leaves and never
        straddles two classes (runs of 1, 3, 1 by 3: 1, 3, 1)."""
        ends, lo = [], 0
        for n in [n_scan] if runs is None else runs:
            n_q = -(-n // seg_layers)
            for q in range(n_q):
                lo += n // n_q + (q < n % n_q)
                ends.append(lo)
        return ends

    def inflight(self):
        """Arrays dispatched but possibly still executing — for callers
        that must drive the pipeline to quiescence before releasing a
        dispatch guard (utils/pipeline.sharded_program_guard)."""
        return [x for x in (self._resid, self._buf, self._out)
                if x is not None]

    def _advance(self, quanta: int, scan: Callable[[int], tuple]) -> tuple[int, bool]:
        """The job's ONE state machine, behind :meth:`step` and
        :meth:`step_many`: start a model, take up to ``quanta`` of its
        quanta as one ``scan(k)`` dispatch of their ``k`` layers, retire the
        model at its last layer, stack the result after the last model.
        Returns ``(quanta consumed, alive)``."""
        used = 0
        while used < quanta:
            if self._out is not None:
                return used, False
            if self._resid is None:
                self._resid, self._buf = _seg_start_impl(
                    self.params_seq[self._model_idx], self.tokens, self.cfg,
                    len(self.capture),
                )
            if self._lo < self.n_scan:
                # consecutive quanta of the same model — and of the same run
                # of one class — fuse into one sub-scan
                run = next(r for r in self._runs if self._lo < r.layer + r.n)
                inside = sum(self._lo < b <= run.layer + run.n for b in self._bounds)
                n_q = min(quanta - used, inside)
                self._cls = run.cls if len(layer_classes(self.cfg)) > 1 else None
                self._q += n_q
                k = self._bounds[self._q - 1] - self._lo
                self._resid, self._buf = scan(k)
                self._lo += k
                used += n_q
            else:
                used += 1       # a hook at the embedding: the model's one step
            if self._lo >= self.n_scan:
                self._done_resids.append(self._resid)
                self._done_bufs.append(self._buf)
                self._resid = self._buf = None
                self._lo = self._q = 0
                self._model_idx += 1
                if self._model_idx == len(self.params_seq):
                    self._out = _seg_finish_impl(
                        tuple(self._done_resids), tuple(self._done_bufs),
                        self.cfg, self.capture, self.n_scan, self.out_dtype,
                    )
                    self._done_resids = self._done_bufs = []
                    return used, False
        return used, True

    def _scan_plain(self, k: int):
        # lo as a HOST scalar: jit uploads it straight to every device
        # of a sharded harvest; a jnp scalar would sit on the default
        # device and be re-replicated device-to-device per dispatch
        return _seg_scan_impl(
            self.params_seq[self._model_idx], self._resid, self._buf,
            np.int32(self._lo), self.cfg, self.capture, k, self._cls,
        )

    def _scan_batched(self, k: int):
        """One ``k``-wide sub-scan dispatch through a pre-built donated
        executable (utils/compile_cache.aot_get): the AOT compile happens
        once per width, off the per-quantum path, and later dispatches
        skip the jit call machinery — the host-cost half of the refill
        engine's batched dispatch. Any AOT failure falls back to the
        plain jit call (same program, just dispatched the ordinary way)."""
        from crosscoder_tpu.utils import compile_cache

        params = self.params_seq[self._model_idx]
        args = (params, self._resid, self._buf, np.int32(self._lo))
        key = ("seg_scan", self.cfg, self.capture, k, self._cls, self.tokens.shape,
               str(self._resid.dtype),
               getattr(self._resid, "sharding", None),
               getattr(params["embed"], "sharding", None))
        try:
            compiled = compile_cache.aot_get(
                key,
                lambda: _seg_scan_impl.lower(
                    *args, cfg=self.cfg, capture=self.capture, k=k, cls=self._cls
                ).compile(),
            )
        except Exception:   # noqa: BLE001 — AOT is an optimization only
            compiled = None
        if compiled is None:
            return _seg_scan_impl(
                *args, cfg=self.cfg, capture=self.capture, k=k, cls=self._cls)
        return compiled(*args)

    def step(self) -> bool:
        """Dispatch the next quantum; False once fully dispatched."""
        return self._advance(1, self._scan_plain)[1]

    def step_many(self, quanta: int) -> tuple[int, bool]:
        """Advance by up to ``quanta`` dispatch quanta, FUSING consecutive
        same-model quanta into one wide sub-scan dispatch (``k`` up to
        ``quanta × SEG_LAYERS`` layers in a single compiled program) —
        the refill engine's batched dispatch (cfg.refill_dispatch_batch).

        Returns ``(quanta_consumed, alive)`` with the same accounting as
        ``quanta_consumed`` calls to :meth:`step`: the scan carry is
        sequential, so a k-wide sub-scan is bitwise identical to k/SEG
        narrow ones (asserted by tests/test_refill_overlap.py).
        """
        return self._advance(quanta, self._scan_batched)

    def result(self) -> jax.Array:
        while self._out is None:
            self.step()
        return self._out


# ---------------------------------------------------------------------------
# paged/ragged harvest (continuous batching; cfg.harvest_runtime="paged")


def _paged_capture_one(
    params: LMParams,
    plane_tokens: jax.Array,      # [R, Sp] packed token plane
    pos2d: jax.Array,             # [R, Sp] within-document positions
    doc_idx: jax.Array,           # [D, S] flat plane index per document token
    plane_idx: jax.Array,         # [R, Sp] flat doc*S+t index per plane slot
    lengths: jax.Array,           # [D]
    cfg: LMConfig,
    capture: tuple[tuple[int, int], ...],
    n_scan: int,
    page_size: int,
    use_kernel: bool,
) -> jax.Array:
    """One model's capture forward over the PACKED token plane.

    Every position-local op (embedding, norms, Q/K/V/output projections,
    MLP, capture FMAs — ~93% of harvest FLOPs at Gemma-2-2B shapes) runs
    on the dense ``[R, Sp]`` plane, so its cost is proportional to real
    tokens. Attention runs per DOCUMENT: heads are gathered through
    ``doc_idx`` into per-document padded buffers, attended with the ragged
    length mask (XLA path — bit-identical to the padded forward at valid
    positions) or the ragged-paged-attention kernel
    (:mod:`crosscoder_tpu.ops.paged_attention`, page loop bounded by
    ``ceil(len/page_size)``), and scattered back through ``plane_idx``.
    Returns the capture buffer ``[n_cap, R, Sp, d_model]`` (still packed;
    the caller unpacks per document). Unused plane positions carry
    finite garbage (pad-token forwards) that no document ever gathers.
    """
    from crosscoder_tpu.ops import paged_attention as pa

    R, Sp = plane_tokens.shape
    D, S = doc_idx.shape

    def gather_docs(x):          # [R, Sp, ...] -> [D, S, ...]
        return x.reshape((R * Sp,) + x.shape[2:])[doc_idx]

    def scatter_plane(x):        # [D, S, ...] -> [R, Sp, ...]
        return x.reshape((D * S,) + x.shape[2:])[plane_idx]

    def attn_docs(qd, kd, vd, is_local):
        if not use_kernel:
            return _attn_core(qd, kd, vd, cfg, is_local, lengths=lengths)
        # the kernel bakes the window statically; the traced layer parity
        # selects between the two compiled instances
        def run(window):
            def fn(args):
                return pa.paged_attention(
                    *args, lengths, page_size=page_size,
                    scale=cfg.query_pre_attn_scalar ** -0.5,
                    softcap=cfg.attn_softcap, window=window,
                )
            return fn
        return jax.lax.cond(
            is_local, run(cfg.sliding_window), run(0), (qd, kd, vd)
        )

    def attend(q, k, v, kind):
        return scatter_plane(attn_docs(
            gather_docs(q), gather_docs(k), gather_docs(v), kind.is_local))

    (_, buf), _ = _scan_blocks(
        params, cfg, capture, _fresh_carry(params, plane_tokens, cfg, len(capture)),
        n_scan, pos=pos2d, attend=attend)
    return buf


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "capture", "n_scan", "page_size", "use_kernel",
                     "pad_mode", "out_dtype"),
)
def _paged_multi_impl(
    params_tuple, plane_tokens, pos2d, doc_idx, plane_idx, lengths,
    cfg: LMConfig, capture: tuple[tuple[int, int], ...], n_scan: int,
    page_size: int, use_kernel: bool, pad_mode: str = "zero", out_dtype=None,
):
    D, S = doc_idx.shape
    n_cap = len(capture)
    outs = []
    for p in params_tuple:
        buf = _paged_capture_one(
            p, plane_tokens, pos2d, doc_idx, plane_idx, lengths, cfg,
            capture, n_scan, page_size, use_kernel,
        )
        flat = buf.reshape(n_cap, -1, cfg.d_model)
        docs = flat[:, doc_idx]                    # [n_cap, D, S, d_model]
        outs.extend(docs[i] for i in range(n_cap))
    out = jnp.stack(outs, axis=2)                  # [D, S, n_sources, d]
    t = jnp.arange(S)[None]                        # [1, S]
    if pad_mode == "zero":
        # the emitted stream carries an explicit valid-length mask
        # instead of the padded path's garbage pad rows
        valid = t < lengths[:, None]
        out = jnp.where(valid[:, :, None, None], out, jnp.zeros((), out.dtype))
    else:                                          # "wrap" (the replay buffer)
        # pad positions cycle the document's own post-BOS rows, so every
        # emitted row is a REAL activation and the replay store never
        # trains on zero vectors; single-token documents (no post-BOS
        # rows) fall back to their BOS row
        ln = lengths[:, None]
        src = jnp.where(t < ln, t, 1 + (t - 1) % jnp.maximum(ln - 1, 1))
        src = jnp.where((t >= ln) & (ln == 1), 0, src)
        out = jnp.take_along_axis(out, src[:, :, None, None], axis=1)
    return out.astype(out_dtype) if out_dtype is not None else out


def run_with_cache_multi_paged(
    params_seq: Sequence[LMParams],
    tokens,
    lengths,
    cfg: LMConfig,
    hook_points: Sequence[str],
    *,
    page_size: int,
    n_rows: int | None = None,
    row_multiple: int = 1,
    batch_sharding: Any | None = None,
    pad_mode: str = "zero",
    out_dtype=None,
) -> jax.Array:
    """All models' captures through the PAGED runtime: mixed-length
    documents (``tokens [D, seq_len]`` padded layout + per-document
    ``lengths``) are packed host-side into a dense token plane
    (:func:`crosscoder_tpu.data.paging.pack_chunk`), the forward runs on
    the plane with per-document ragged attention, and the result is
    unpacked back to the padded layout: ``[D, seq_len, n_models·n_hooks,
    d_model]``, source axis model-major — shape/order-compatible with
    :func:`run_with_cache_multi`, with positions at ``t >= lengths[d]``
    zeroed (``pad_mode="zero"``, the valid-length mask made material) or
    cycled from the document's own post-BOS rows (``pad_mode="wrap"`` —
    the replay buffer's choice, so no all-zero row ever becomes training
    data; single-token documents fall back to their BOS row).

    On an all-full-length chunk the packing is the identity layout and the
    output is BIT-identical to :func:`run_with_cache_multi` — the CPU
    parity gate ``tests/test_paging.py`` pins. On ragged chunks the plane
    has ``~sum(len)/seq_len`` rows instead of ``D``, so the projections/
    MLP (the dominant harvest cost) scale with real tokens; the Pallas
    ragged-paged-attention kernel (``CROSSCODER_PAGED_ATTN_PALLAS=1``)
    makes attention ragged too.
    """
    from crosscoder_tpu.data import paging

    cap_pairs = _hook_layers(cfg, tuple(hook_points))
    n_scan = min(cfg.n_layers, _scan_stop(cap_pairs))
    chunk = paging.pack_chunk(
        np.asarray(tokens), np.asarray(lengths),
        n_rows=n_rows, row_multiple=row_multiple,
    )
    from crosscoder_tpu.ops import paged_attention as pa

    use_kernel = pa.kernel_enabled() and pa.supported(
        chunk.n_docs, chunk.seq_len, max(c.n_heads for c in layer_classes(cfg)),
        cfg.n_kv_heads, cfg.head_dim, page_size,
    )
    if batch_sharding is not None:
        plane = _put_global(chunk.tokens, batch_sharding)
    else:
        plane = jnp.asarray(chunk.tokens)
    if pad_mode not in ("zero", "wrap"):
        raise ValueError(f"pad_mode must be zero|wrap, got {pad_mode!r}")
    return _paged_multi_impl(
        tuple(params_seq), plane, jnp.asarray(chunk.pos),
        jnp.asarray(chunk.doc_idx), jnp.asarray(chunk.plane_idx),
        jnp.asarray(chunk.lengths), cfg, cap_pairs, n_scan, page_size,
        use_kernel, pad_mode, out_dtype,
    )


def paged_capture_aot(
    params_seq: Sequence[LMParams],
    chunk,
    cfg: LMConfig,
    hook_points: Sequence[str],
    *,
    page_size: int,
    pad_mode: str = "zero",
    out_dtype=None,
    on_build=None,
) -> jax.Array:
    """:func:`run_with_cache_multi_paged` for a PRE-PACKED fixed-shape
    chunk, dispatched through an AOT-compiled executable.

    ``chunk`` is a :class:`crosscoder_tpu.data.paging.PackedChunk` whose
    plane height the caller pinned (the serve engine's bucket ladder pins
    both the document count and the plane height per bucket, so every
    steady-state request hits a memoized executable). Numerics are the
    implicit-jit path's exactly — :func:`compile_cache.aot_get` compiles
    the same program ``jax.jit`` would have — the AOT hop only removes
    the per-call tracing/cache machinery from the latency path and makes
    compiles COUNTABLE (``on_build`` fires once per executable actually
    built; docs/SERVING.md "Zero compiles after warmup").
    """
    from crosscoder_tpu.ops import paged_attention as pa
    from crosscoder_tpu.utils import compile_cache

    cap_pairs = _hook_layers(cfg, tuple(hook_points))
    n_scan = min(cfg.n_layers, _scan_stop(cap_pairs))
    use_kernel = pa.kernel_enabled() and pa.supported(
        chunk.n_docs, chunk.seq_len, max(c.n_heads for c in layer_classes(cfg)),
        cfg.n_kv_heads, cfg.head_dim, page_size,
    )
    if pad_mode not in ("zero", "wrap"):
        raise ValueError(f"pad_mode must be zero|wrap, got {pad_mode!r}")
    args = (
        tuple(params_seq), jnp.asarray(chunk.tokens),
        jnp.asarray(chunk.pos), jnp.asarray(chunk.doc_idx),
        jnp.asarray(chunk.plane_idx), jnp.asarray(chunk.lengths),
    )
    key = ("paged_capture", cfg, cap_pairs, n_scan, page_size, use_kernel,
           pad_mode, str(out_dtype), chunk.tokens.shape, chunk.doc_idx.shape,
           str(chunk.tokens.dtype), len(args[0]))
    def lower():
        return _paged_multi_impl.lower(
            *args, cfg=cfg, capture=cap_pairs, n_scan=n_scan,
            page_size=page_size, use_kernel=use_kernel, pad_mode=pad_mode,
            out_dtype=out_dtype,
        )

    compiled = compile_cache.aot_get(
        key, lambda: lower().compile(), on_build=on_build, lower=lower,
    )
    return compiled(*args)


# ---------------------------------------------------------------------------
# tensor-parallel harvest (models too big for one chip's HBM)


def tp_shardings(mesh, axis: str = "model", cfg: LMConfig | None = None) -> LMParams:
    """``NamedSharding`` pytree for TENSOR-PARALLEL LM params over
    ``mesh[axis]`` — the Megatron layout expressed as annotations only;
    GSPMD inserts the collectives (psum after ``wo``/``w_down``).

    The reference fits its 2.6B pair on one GPU (train.py:45-55), so it
    never needs this; BASELINE config 3 (Gemma-2-9B) does NOT fit one v5e
    chip (both models' sub-hook layers ≈ 16.6 GB bf16), which makes the
    harvest forward itself the thing to shard:

    - ``wq``/``wk``/``wv``: head (output) axis sharded — each shard owns a
      head group; the [B,S,heads,hd] reshape splits the sharded axis
      cleanly when ``n_heads`` (and ideally ``n_kv_heads``) divide the
      axis size.
    - ``wo``/``w_down``: CONTRACTING axis sharded — partial products psum.
    - ``w_gate``/``w_up``: hidden (output) axis sharded.
    - ``embed``: d_model axis sharded — the token lookup stays shard-local.
    - norms: replicated (tiny).

    The leaves follow ``cfg`` (None: the Gemma-2 family's), one stack a
    class of layers. Expert leaves have no layout here yet: a sparse config
    on an axis larger than 1 is refused rather than mis-sharded.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    def stack(sparse: bool) -> dict:
        layers = {
            "attn_norm": ns(None, None),
            "pre_ffw_norm": ns(None, None),
            "wq": ns(None, None, axis),
            "wk": ns(None, None, axis),
            "wv": ns(None, None, axis),
            "wo": ns(None, axis, None),
        }
        if cfg is not None and cfg.latent:
            # the low-rank halves whole (small), the per-head halves by head
            del layers["wq"], layers["wk"]
            layers.update(
                wq_a=ns(None, None, None), q_a_norm=ns(None, None),
                wkv_a=ns(None, None, None), kv_a_norm=ns(None, None),
                wq_nope=ns(None, None, axis), wq_rope=ns(None, None, axis),
                wk_nope=ns(None, None, axis))
        if cfg is not None and cfg.n_streams > 1:
            for site in ("attn", "ffn"):        # the maps' parameters: whole
                layers.update({f"hc_{site}_phi": ns(None, None, None),
                               f"hc_{site}_alpha": ns(None, None),
                               f"hc_{site}_bias": ns(None, None)})
        if cfg is None or cfg.block_style == "sandwich":
            layers["post_attn_norm"] = ns(None, None)
            layers["post_ffw_norm"] = ns(None, None)
        if cfg is not None and cfg.attn_gate == "per_head":
            layers["w_attn_gate"] = ns(None, None, axis)    # heads, as wq's
        if sparse:
            if mesh.shape[axis] > 1:
                raise NotImplementedError(
                    f"sparse-expert layers on a {axis!r} axis of {mesh.shape[axis]}: "
                    "expert parallelism (experts split over the axis, the token "
                    "exchange before and after them) is not implemented; "
                    "ops/moe.py computes every expert it holds on one device")
            layers.update(router=ns(None, None, None),
                          we_gate_up=ns(None, None, None, None),
                          we_down=ns(None, None, None, None))
            if cfg.router == "sigmoid_bias":
                layers["router_bias"] = ns(None, None)
            if cfg.d_shared_expert:
                layers.update(ws_gate=ns(None, None, None), ws_up=ns(None, None, None),
                              ws_down=ns(None, None, None))
        else:
            layers.update(w_gate=ns(None, None, axis), w_up=ns(None, None, axis),
                          w_down=ns(None, axis, None))
        return layers

    stacks = [stack(False)] if cfg is None else [
        stack(c.mlp == SPARSE) for c in layer_classes(cfg)]
    out = {"embed": ns(None, axis), "final_norm": ns(None), "layers": _from_stacks(stacks)}
    if cfg is not None and not cfg.tie_embeddings:
        out["unembed"] = ns(None, axis)
    if cfg is not None and cfg.n_streams > 1:
        out.update(hc_head_phi=ns(None, None), hc_head_alpha=ns(None), hc_head_bias=ns(None))
    return out


def shard_params_tp(params: LMParams, mesh, axis: str = "model") -> LMParams:
    """Place (or re-place) LM params in the tensor-parallel layout. The
    returned pytree feeds every forward/harvest entry point unchanged —
    jit picks the layout up from the arrays and partitions accordingly."""
    return _put_global(params, tp_shardings(mesh, axis))


# ---------------------------------------------------------------------------
# sequence-parallel forward (long-context harvest; SURVEY component N5)


def forward_seq_parallel(
    params: LMParams,
    tokens: jax.Array,
    cfg: LMConfig,
    mesh,
    *,
    axis_name: str = "data",
    capture: Sequence[str] = (),
    return_logits: bool = False,
) -> tuple[jax.Array | None, dict[str, jax.Array]]:
    """Gemma-2 forward with the SEQUENCE axis sharded over a mesh axis.

    The context-length analogue of :func:`forward`: the per-device score
    matrix shrinks by n², so contexts far beyond one chip's HBM harvest
    fine — attention runs as an exact ring (K/V blocks rotate over ICI via
    ``ppermute``; :mod:`crosscoder_tpu.parallel.ring_attention`), every
    other op is position-local. Params are replicated; ``tokens [B, S]``
    must have S divisible by the axis size. Capture semantics match
    :func:`forward` (cache values come back as globally-stitched arrays);
    activation *edits* are a short-context eval feature and are not
    supported here.

    Numerics are asserted equal to the dense forward by
    ``tests/test_ring_attention.py``.
    """
    _check_seq_divisible(tokens, mesh, axis_name)
    cap_layers = _hook_layers(cfg, tuple(capture))
    fn = _seq_parallel_fn(cfg, mesh, axis_name, cap_layers, return_logits)
    logits, cap_buf = fn(params, tokens)
    cache = {hp: cap_buf[i] for i, hp in enumerate(capture)}
    return logits, cache


def _check_seq_divisible(tokens: jax.Array, mesh, axis_name: str) -> None:
    n = mesh.shape[axis_name]
    if tokens.shape[1] % n != 0:
        raise ValueError(
            f"seq len {tokens.shape[1]} not divisible by {n} sequence shards"
        )


def _seq_local_body(
    params, tok_local, cfg: LMConfig, axis_name: str, n: int,
    cap_layers: tuple[tuple[int, int], ...], return_logits: bool,
):
    """Per-shard forward over the local sequence slice (shared by the
    single-model and fused multi-model sequence-parallel entry points).

    Mirrors ``_forward_impl``'s stop-at-layer: without logits, nothing above
    the highest captured layer is observable, so the scan is truncated there
    — at blocks.14 of Gemma-2-2B's 26 layers that is ~46% of the layer
    FLOPs, and long-context harvest is exactly where it matters.
    """
    from crosscoder_tpu.parallel.ring_attention import ring_attention

    n_scan = cfg.n_layers if return_logits else min(
        cfg.n_layers, _scan_stop(cap_layers)
    )
    B, Sl = tok_local.shape

    def ring(q, k, v, kind):
        return ring_attention(
            q, k, v, axis_name=axis_name, n_shards=n,
            scale=cfg.query_pre_attn_scalar ** -0.5,
            softcap=cfg.attn_softcap, sliding_window=cfg.sliding_window,
            is_local=kind.is_local,
        ).reshape(B, Sl, -1)

    (resid, buf), _ = _scan_blocks(
        params, cfg, cap_layers,
        _fresh_carry(params, tok_local, cfg, len(cap_layers)), n_scan,
        # GLOBAL positions: the shard's offset into the sequence
        pos=jax.lax.axis_index(axis_name) * Sl + jnp.arange(Sl), attend=ring)
    logits = _unembed(params, resid, cfg) if return_logits else None
    return logits, buf


@functools.lru_cache(maxsize=32)
def _seq_parallel_fn(
    cfg: LMConfig, mesh, axis_name: str, cap_layers: tuple[tuple[int, int], ...], return_logits: bool
):
    """Compile-once builder for the sequence-parallel forward (keyed on
    everything that changes the traced program; token/batch shapes go
    through the inner jit's normal shape-keyed cache)."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis_name]
    n_cap = len(cap_layers)

    def local_fn(params, tok_local):
        return _seq_local_body(
            params, tok_local, cfg, axis_name, n, cap_layers, return_logits
        )

    out_logits_spec = P(None, axis_name, None) if return_logits else P()
    out_cap_spec = P(None, None, axis_name, None) if n_cap else P()
    return jax.jit(jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(), P(None, axis_name)),
        out_specs=(out_logits_spec, out_cap_spec),
        check_vma=False,
    ))


@functools.lru_cache(maxsize=32)
def _seq_parallel_multi_fn(
    cfg: LMConfig, mesh, axis_name: str, cap_layers: tuple[tuple[int, int], ...]
):
    """Fused multi-model sequence-parallel capture: ONE jitted shard_map
    dispatch runs every model's truncated forward over the same local token
    slice — the sequence-sharded analogue of ``_multi_cache_impl``, keeping
    the per-dispatch fixed cost at one per chunk. (Kept separate from
    ``_seq_parallel_fn``: the out-tree is a single stacked capture array,
    not the (logits, buffer) pair; the model count keys the inner jit's
    retrace via the params-tuple length.)"""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis_name]

    def local_fn(params_tuple, tok_local):
        bufs = []
        for p in params_tuple:
            _, buf = _seq_local_body(
                p, tok_local, cfg, axis_name, n, cap_layers, False
            )
            bufs.append(buf)                       # each [n_cap, B, Sl, D]
        out = jnp.concatenate(bufs, axis=0)        # model-major sources
        return jnp.transpose(out, (1, 2, 0, 3))    # [B, Sl, n_sources, D]

    return jax.jit(jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(), P(None, axis_name)),
        out_specs=P(None, axis_name, None, None),
        check_vma=False,
    ))


def run_with_cache_multi_seq_parallel(
    params_seq: Sequence[LMParams],
    tokens: jax.Array,
    cfg: LMConfig,
    hook_points: Sequence[str],
    mesh,
    *,
    axis_name: str = "data",
) -> jax.Array:
    """All models' captures with the SEQUENCE axis sharded over ``axis_name``
    (ring attention): ``[B, S, n_models·n_hooks, d_model]``, source axis
    model-major — shape/order-compatible with :func:`run_with_cache_multi`,
    in one compiled dispatch."""
    _check_seq_divisible(tokens, mesh, axis_name)
    cap_layers = _hook_layers(cfg, tuple(hook_points))
    fn = _seq_parallel_multi_fn(cfg, mesh, axis_name, cap_layers)
    return fn(tuple(params_seq), tokens)


# ---------------------------------------------------------------------------
# HF weight conversion (torch checkpoint → stacked JAX pytree)


def from_torch_state_dict(
    sd: Mapping[str, Any], cfg: LMConfig, dtype: str | None = None,
    shardings: LMParams | None = None,
) -> LMParams:
    """Convert an HF-transformers ``state_dict`` (Gemma2's names; for a
    pre-norm or sparse ``cfg`` the names assumed below) to our stacked layout.

    Works on anything indexable with ``.numpy()``-able values (torch CPU
    tensors or numpy arrays). HF projections are [out, in]; ours are [in, out].

    ``shardings`` (a :func:`tp_shardings`-shaped pytree of NamedShardings)
    places each leaf DIRECTLY in its sharded layout as it is converted —
    peak device memory is one shard per leaf, never the whole model, which
    is what lets a pair bigger than one chip's HBM (BASELINE config 3) be
    loaded at all. Without it, leaves go to the default device whole.
    """
    dt = dtype_of(dtype or cfg.dtype)

    def get(name: str) -> np.ndarray:
        v = sd[name]
        if hasattr(v, "detach"):
            v = v.detach().to("cpu").float().numpy()
        return np.asarray(v, dtype=np.float32)

    def leaf(path: tuple[str, ...], arr: np.ndarray, as_dtype=None) -> jax.Array:
        # host-side cast (ml_dtypes)
        arr = arr.astype(np.dtype(dt if as_dtype is None else as_dtype), copy=False)
        if shardings is None:
            return jnp.asarray(arr)
        sh = shardings
        for k in path:
            sh = sh[k]
        return _put_global(arr, sh)

    classes = layer_classes(cfg)
    p = "model.layers.{}."

    def latent_leaves(cls: LayerClass, where: tuple) -> dict:
        """The latent attention's leaves from DeepSeek-V3's names (ASSUMED
        for Xing4.0: no checkpoint was read here). ``q_b`` and ``kv_b`` are
        laid out a head — ``[nope | rope]`` and ``[k_nope | v]`` — and cut
        here into one leaf a part; the checkpoint's rotary columns are
        INTERLEAVED pairs (2j, 2j + 1) and are permuted to this runtime's
        split-half layout (j, j + dr/2)."""
        H, dr, dv = cls.n_heads, cfg.qk_rope_dim, cfg.v_head_dim
        dn, rkv = cfg.head_dim - dr, cfg.kv_lora_rank
        halves = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
        out: dict[str, list] = {k: [] for k in (
            "wq_a", "q_a_norm", "wq_nope", "wq_rope", "wkv_a", "kv_a_norm", "wk_nope", "wv")}
        for i in cls.layers:
            a = (p + "self_attn.").format(i)
            q_b = get(a + "q_b_proj.weight").T.reshape(-1, H, dn + dr)
            kv_a = get(a + "kv_a_proj_with_mqa.weight").T
            kv_b = get(a + "kv_b_proj.weight").T.reshape(rkv, H, dn + dv)
            out["wq_a"].append(get(a + "q_a_proj.weight").T)
            out["q_a_norm"].append(get(a + "q_a_layernorm.weight"))
            out["wq_nope"].append(q_b[..., :dn].reshape(-1, H * dn))
            out["wq_rope"].append(q_b[..., dn:][..., halves].reshape(-1, H * dr))
            out["wkv_a"].append(np.concatenate(
                [kv_a[:, :rkv], kv_a[:, rkv:][:, halves]], axis=1))
            out["kv_a_norm"].append(get(a + "kv_a_layernorm.weight"))
            out["wk_nope"].append(kv_b[..., :dn].reshape(rkv, H * dn))
            out["wv"].append(kv_b[..., dn:].reshape(rkv, H * dv))
        return {k: leaf((*where, k), np.stack(v)) for k, v in out.items()}

    def stack_of(c: int, cls: LayerClass) -> dict:
        where = ("layers",) if len(classes) == 1 else ("layers", c)

        def stack(key: str, fmt: str, transpose: bool) -> jax.Array:
            mats = [get(fmt.format(i)) for i in cls.layers]
            arr = np.stack([m.T if transpose else m for m in mats])
            return leaf((*where, key), arr)

        layers = {
            "attn_norm": stack("attn_norm", p + "input_layernorm.weight", False),
            "wo": stack("wo", p + "self_attn.o_proj.weight", True),
        }
        if cfg.latent:
            layers.update(latent_leaves(cls, where))
        else:
            layers.update(
                wq=stack("wq", p + "self_attn.q_proj.weight", True),
                wk=stack("wk", p + "self_attn.k_proj.weight", True),
                wv=stack("wv", p + "self_attn.v_proj.weight", True))
        if cfg.n_streams > 1:
            # ASSUMED names (no Xing4.0 checkpoint was read here): a sublayer's
            # map as a Linear ``hc_{attn,ffn}_fn.weight [n² + 2n, n·D]`` with
            # ``hc_*_scale [3]`` and ``hc_*_base [n² + 2n]``, kept in float32
            for site in ("attn", "ffn"):
                for key, name, t in (("phi", "fn.weight", True), ("alpha", "scale", False),
                                     ("bias", "base", False)):
                    mats = [get((p + f"hc_{site}_{name}").format(i)) for i in cls.layers]
                    layers[f"hc_{site}_{key}"] = leaf(
                        (*where, f"hc_{site}_{key}"),
                        np.stack([m.T if t else m for m in mats]), np.float32)
        if cfg.attn_gate == "per_head":
            # ASSUMED name (no Laguna checkpoint was read here)
            layers["w_attn_gate"] = stack("w_attn_gate", p + "self_attn.g_proj.weight", True)
        if cfg.block_style == "sandwich":
            layers.update(
                post_attn_norm=stack("post_attn_norm", p + "post_attention_layernorm.weight", False),
                pre_ffw_norm=stack("pre_ffw_norm", p + "pre_feedforward_layernorm.weight", False),
                post_ffw_norm=stack("post_ffw_norm", p + "post_feedforward_layernorm.weight", False),
            )
        else:
            # the pre-norm families' second norm goes by this name (Llama's
            # convention; ASSUMED for Mellum2: no checkpoint was read here)
            layers["pre_ffw_norm"] = stack(
                "pre_ffw_norm", p + "post_attention_layernorm.weight", False)
        if cls.mlp == SPARSE:
            # ASSUMED key names (the Mixtral/Qwen-MoE convention; no Mellum2
            # or Laguna checkpoint was read here): ``mlp.gate.weight`` [E, D]
            # and, per expert e, ``mlp.experts.{e}.{gate,up,down}_proj.weight``
            # (the whole model's names; the chip's share of them is kept),
            # the shared expert ``mlp.shared_expert.{gate,up,down}_proj.weight``
            held = range(cfg.first_expert, cfg.first_expert + cfg.n_held)

            def experts(fmt: str) -> np.ndarray:      # -> [L, E_held, in, out]
                return np.stack([
                    np.stack([get((p + fmt).format(i, e)).T for e in held])
                    for i in cls.layers])

            layers["router"] = stack("router", p + "mlp.gate.weight", True)
            if cfg.router == "sigmoid_bias":
                layers["router_bias"] = leaf((*where, "router_bias"), np.stack([
                    get((p + "mlp.gate.e_score_correction_bias").format(i))
                    for i in cls.layers]), np.float32)
            layers["we_gate_up"] = leaf((*where, "we_gate_up"), np.concatenate(
                [experts("mlp.experts.{}.gate_proj.weight"),
                 experts("mlp.experts.{}.up_proj.weight")], axis=-1))
            layers["we_down"] = leaf(
                (*where, "we_down"), experts("mlp.experts.{}.down_proj.weight"))
            if cfg.d_shared_expert:
                # (the sigmoid-routed family names it in the plural: ASSUMED)
                se = "mlp.shared_experts." if cfg.router == "sigmoid_bias" else "mlp.shared_expert."
                layers.update(
                    ws_gate=stack("ws_gate", p + se + "gate_proj.weight", True),
                    ws_up=stack("ws_up", p + se + "up_proj.weight", True),
                    ws_down=stack("ws_down", p + se + "down_proj.weight", True),
                )
        else:
            layers.update(
                w_gate=stack("w_gate", p + "mlp.gate_proj.weight", True),
                w_up=stack("w_up", p + "mlp.up_proj.weight", True),
                w_down=stack("w_down", p + "mlp.down_proj.weight", True),
            )
        return layers

    layers = _from_stacks([stack_of(c, cls) for c, cls in enumerate(classes)])
    params = {
        "embed": leaf(("embed",), get("model.embed_tokens.weight")),
        "final_norm": leaf(("final_norm",), get("model.norm.weight")),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["unembed"] = leaf(("unembed",), get("lm_head.weight"))
    if cfg.n_streams > 1:
        params.update(
            hc_head_phi=leaf(("hc_head_phi",), get("model.hc_head_fn.weight").T, np.float32),
            hc_head_alpha=leaf(("hc_head_alpha",), get("model.hc_head_scale"), np.float32),
            hc_head_bias=leaf(("hc_head_bias",), get("model.hc_head_base"), np.float32))
    return params


def from_hf(
    model_name_or_path: str, cfg: LMConfig | None = None,
    shardings: LMParams | None = None,
) -> tuple[LMParams, LMConfig]:
    """Load Gemma-2 weights from a local HF checkpoint dir or the hub cache
    (the reference loads via TransformerLens ``from_pretrained_no_processing``,
    train.py:45-55). Gated behind an import so offline/test runs never touch
    the hub.

    Pass ``shardings=lm.tp_shardings(mesh)`` for models that do NOT fit one
    chip (BASELINE config 3): each leaf is placed straight into its
    tensor-parallel shards during conversion, so peak per-device memory is
    the sharded footprint, never the whole model.
    """
    import transformers  # deferred: heavyweight

    model = transformers.AutoModelForCausalLM.from_pretrained(
        model_name_or_path, torch_dtype="bfloat16"  # keep host peak at ckpt size
    )
    hf_cfg = model.config
    if cfg is None:
        cfg = LMConfig(
            vocab_size=hf_cfg.vocab_size,
            d_model=hf_cfg.hidden_size,
            n_layers=hf_cfg.num_hidden_layers,
            n_heads=hf_cfg.num_attention_heads,
            n_kv_heads=hf_cfg.num_key_value_heads,
            head_dim=hf_cfg.head_dim,
            d_ff=hf_cfg.intermediate_size,
            rope_theta=hf_cfg.rope_theta,
            rms_eps=hf_cfg.rms_norm_eps,
            attn_softcap=hf_cfg.attn_logit_softcapping,
            final_softcap=hf_cfg.final_logit_softcapping,
            sliding_window=hf_cfg.sliding_window,
            query_pre_attn_scalar=float(hf_cfg.query_pre_attn_scalar),
        )
    params = from_torch_state_dict(model.state_dict(), cfg, shardings=shardings)
    return params, cfg
