"""Operations and bytes the algorithms need, computed from shapes.

These are the numerators of every peak share and roofline share the
benchmark reports. They count what the mathematics requires (recomputed or
padded work does not count), so a share cannot pass 100% unless the time
leaves out part of the work.
"""

from __future__ import annotations

from typing import Any


def harvest_flops_per_step(cfg: Any, lm_cfg: Any, spc: int, arch: Any) -> float:
    """The steady cycle re-harvests ``refill_frac`` of the store's sequences
    through every model to the hook, spread over ``spc`` steps; a token's
    FLOPs are the count of the configuration's architecture
    (``benchmarks/arch/``)."""
    rows_per_seq = cfg.seq_len - 1
    seqs = cfg.batch_size * cfg.buffer_mult // rows_per_seq
    refill = max(1, int(seqs * cfg.refill_frac))
    hook_layer = int(cfg.hook_point.split(".")[1])
    per_seq = cfg.seq_len * arch.flops_per_token(lm_cfg, hook_layer, cfg.seq_len)
    return refill * cfg.n_models * per_seq / spc


def crosscoder_flops_per_step(cfg: Any) -> float:
    """Forward and backward of one optimizer step. Dense (ReLU): encode,
    decode, and three backward matmuls (dW_dec, df, dW_enc; the input needs
    no gradient) of 2*B*(n*d)*H each. TopK: the dense encode, and five
    products over the k active latents of each row."""
    B, nd, H = cfg.batch_size, cfg.n_sources * cfg.d_in, cfg.dict_size
    dense = 2.0 * B * nd * H
    if cfg.activation == "topk":
        return dense + 5 * 2.0 * B * nd * cfg.topk_k
    return 5 * dense


def topk_kernel_bytes(cfg: Any) -> float:
    """The TopK selection reads the [B, H] pre-activations once and writes k
    values and indices per row."""
    item = 2 if cfg.enc_dtype == "bf16" else 4
    return float(cfg.batch_size * cfg.dict_size * item
                 + cfg.batch_size * cfg.topk_k * (item + 4))


def train_shapes(cfg: Any, lm_cfg: Any, spc: int, mesh_shape: tuple,
                 arch: Any) -> dict:
    data, model = mesh_shape
    out = {
        # needed work is split over every chip: a harvest repeated along
        # the model axis shows as a lower share, which is what it is
        "harvest_flops_per_step_per_chip":
            harvest_flops_per_step(cfg, lm_cfg, spc, arch) / (data * model),
        "cc_flops_per_step_per_chip":
            crosscoder_flops_per_step(cfg) / (data * model),
    }
    if cfg.activation == "topk":
        out["topk_kernel_bytes_per_step_per_chip"] = \
            topk_kernel_bytes(cfg) / (data * model)
    return out
