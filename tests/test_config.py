"""Config round-trip, reference-cfg compatibility, and CLI tests."""

import json

import pytest

from crosscoder_tpu.config import CrossCoderConfig, get_default_cfg, parse_hook_point


def test_defaults_match_reference():
    # the reference defaults (train.py:13-35) are the parity surface
    cfg = get_default_cfg()
    assert cfg.seed == 49
    assert cfg.batch_size == 4096
    assert cfg.buffer_mult == 128
    assert cfg.lr == 5e-5
    assert cfg.num_tokens == 400_000_000
    assert cfg.l1_coeff == 2.0
    assert (cfg.beta1, cfg.beta2) == (0.9, 0.999)
    assert cfg.dict_size == 2**14
    assert cfg.seq_len == 1024
    assert cfg.enc_dtype == "bf16"
    assert cfg.hook_point == "blocks.14.hook_resid_pre"
    assert cfg.dec_init_norm == 0.08
    assert cfg.total_steps == 97_656  # trainer.py:14


def test_reference_cfg_json_loads(tmp_path):
    # shape of the published checkpoint cfg JSON (crosscoder.py:151-155):
    # the reference dict plus d_in, with a cuda device string
    ref = {
        "seed": 49, "batch_size": 4096, "buffer_mult": 128, "lr": 5e-5,
        "num_tokens": 400000000, "l1_coeff": 2, "beta1": 0.9, "beta2": 0.999,
        "dict_size": 16384, "seq_len": 1024, "enc_dtype": "bf16",
        "model_name": "gemma-2-2b", "site": "resid_pre", "device": "cuda:1",
        "model_batch_size": 4, "log_every": 100, "save_every": 30000,
        "dec_init_norm": 0.08, "hook_point": "blocks.14.hook_resid_pre",
        "wandb_project": "crosscoders", "wandb_entity": "someone", "d_in": 2304,
        "some_unknown_key": [1, 2, 3],
    }
    p = tmp_path / "cfg.json"
    p.write_text(json.dumps(ref))
    cfg = CrossCoderConfig.from_json(p)
    assert cfg.d_in == 2304
    assert cfg.device == "cuda:1"  # preserved verbatim; placement is mesh-driven
    assert cfg.extras["some_unknown_key"] == [1, 2, 3]
    # round-trip preserves every original key
    out = cfg.to_dict()
    for k, v in ref.items():
        assert out[k] == v or out[k] == float(v)


def test_removed_encoder_keys_land_in_extras(tmp_path):
    """A config saved when the TopK step had a fused encoder tier and a
    gathered decode still loads: the three keys no field reads any more are
    kept, verbatim, as foreign keys."""
    old = {"activation": "topk", "topk_k": 32, "l1_coeff": 0.0,
           "fused_encoder": "auto", "quant_encoder": False, "sparse_decode": True}
    p = tmp_path / "cfg.json"
    p.write_text(json.dumps(old))
    cfg = CrossCoderConfig.from_json(p)
    assert cfg.extras == {"fused_encoder": "auto", "quant_encoder": False,
                          "sparse_decode": True}
    assert (cfg.activation, cfg.topk_k) == ("topk", 32)
    assert {k: cfg.to_dict()[k] for k in cfg.extras} == cfg.extras


def test_parse_hook_point():
    assert parse_hook_point("blocks.14.hook_resid_pre") == (14, "resid_pre")
    assert parse_hook_point("blocks.6.hook_resid_post") == (6, "resid_post")
    with pytest.raises(ValueError):
        parse_hook_point("ln_final.hook_scale")


def test_cli_overrides():
    cfg = CrossCoderConfig.from_cli(["--dict-size", "32768", "--activation", "topk", "--topk-k", "64"])
    assert cfg.dict_size == 32768
    assert cfg.activation == "topk"
    assert cfg.topk_k == 64


def test_cli_config_json_then_flags(tmp_path):
    p = tmp_path / "c.json"
    CrossCoderConfig(dict_size=8192, lr=1e-4).to_json(p)
    cfg = CrossCoderConfig.from_cli(["--config-json", str(p), "--lr", "3e-4"])
    assert cfg.dict_size == 8192
    assert cfg.lr == 3e-4


def test_validation():
    with pytest.raises(ValueError):
        CrossCoderConfig(enc_dtype="int8")
    with pytest.raises(ValueError):
        CrossCoderConfig(activation="gelu")


def test_n_sources_multilayer():
    cfg = CrossCoderConfig(n_models=3, hook_points=("blocks.6.hook_resid_pre", "blocks.20.hook_resid_pre"))
    assert cfg.n_sources == 6
    assert cfg.resolved_hook_points() == ("blocks.6.hook_resid_pre", "blocks.20.hook_resid_pre")
