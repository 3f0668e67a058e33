"""CPU tests of how a configuration file states its block and names its
reference: block keys reach the program's ModelConfig and the model
numbers, or are refused by name; a block that the named reference does
not model is refused by name."""
import dataclasses
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def smoke_conf():
    return harness.load_json(HERE, "testdata", "smoke-config.json")


def cell_for(conf, tmp_path):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(conf))
    return {"name": "smoke", "config": "smoke",
            "traffic": "testdata/smoke-backlog", "chips": 1,
            "config_entry": {"file": str(path)}}


def test_smoke_config_runs_as_before(tmp_path):
    """The ModelConfig and numbers the smoke file gave before block keys
    and named references existed, and ``ref.py`` as its reference."""
    from repro.configs.base import load_arch
    conf = smoke_conf()
    cfg = load_arch("smollm_360m").full()
    cfg = dataclasses.replace(
        cfg, d_model=64, d_ff=128, n_heads=4, n_kv_heads=2, n_layers=2,
        vocab=256, norm_eps=1e-05, rope_theta=10000.0, tie_embeddings=True,
        head_dim=16, dtype="bfloat16", kv_dtype="bfloat16",
        ppac=dataclasses.replace(cfg.ppac, enabled=True, weight_bits=4,
                                 act_bits=8, weight_format="int",
                                 act_format="int", min_features=32,
                                 backend="auto"))
    assert harness.model_config(conf) == cfg
    _, _, got, m, reference = harness.cell_model(cell_for(conf, tmp_path))
    assert got == cfg
    assert m == dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                     d_ff=128, vocab=256, n_layers=2, norm_eps=1e-05,
                     rope_theta=10000.0, tie_embeddings=True, weight_bits=4,
                     act_bits=8, **harness.LLAMA)
    assert os.path.basename(reference.__file__) == "ref.py"
    assert reference.BLOCK == harness.LLAMA


@pytest.mark.parametrize("key,value", [
    ("layer_norm_eps", 1e-05),
    ("qk_layernorm", True),
    ("use_parallel_residual", True),
    ("partial_rotary_factor", 0.25),
])
def test_block_the_program_cannot_carry_is_refused_by_name(key, value,
                                                           tmp_path):
    conf = smoke_conf()
    if key == "layer_norm_eps":
        del conf["rms_norm_eps"]
    conf[key] = value
    with pytest.raises(harness.BenchError, match=key):
        harness.model_config(conf)
    with pytest.raises(harness.BenchError, match=key):
        harness.cell_model(cell_for(conf, tmp_path))


@pytest.mark.parametrize("key,value", [
    ("qk_layernorm", False), ("use_parallel_residual", False),
    ("partial_rotary_factor", 1.0), ("use_qkv_bias", False),
    ("hidden_act", "silu")])
def test_llama_values_of_block_keys_pass(key, value, tmp_path):
    conf = dict(smoke_conf(), **{key: value})
    assert harness.cell_model(cell_for(conf, tmp_path))[3] == \
        harness.cell_model(cell_for(smoke_conf(), tmp_path))[3]


def test_layer_norm_is_never_served_as_rms_norm():
    conf = dict(smoke_conf(), layer_norm_eps=1e-05)
    with pytest.raises(harness.BenchError, match="two norms"):
        harness.model_config(conf)
    del conf["rms_norm_eps"]
    with pytest.raises(harness.BenchError,
                       match="layer_norm_eps states norm='layer'"):
        harness.model_config(conf)


def test_other_activation_is_refused():
    with pytest.raises(harness.BenchError, match="hidden_act"):
        harness.model_config(dict(smoke_conf(), hidden_act="gelu"))


def test_qkv_bias_needs_a_reference_that_models_it(tmp_path):
    conf = dict(smoke_conf(), use_qkv_bias=True)
    # the program's ModelConfig carries it ...
    assert harness.model_config(conf).qkv_bias is True
    # ... but ref.py models the llama block
    with pytest.raises(harness.BenchError,
                       match=r"use_qkv_bias gives qkv_bias=True.*ref\.py"):
        harness.cell_model(cell_for(conf, tmp_path))
    conf["reference"] = "testdata/ref_qkv_bias.py"
    _, _, cfg, m, reference = harness.cell_model(cell_for(conf, tmp_path))
    assert cfg.qkv_bias is True and m["qkv_bias"] is True
    assert reference.BLOCK["qkv_bias"] is True
    # a reference that models the bias refuses a block without it
    conf["use_qkv_bias"] = False
    with pytest.raises(harness.BenchError, match="use_qkv_bias"):
        harness.cell_model(cell_for(conf, tmp_path))


@pytest.mark.parametrize("name", ["no_such_ref.py", "../chip/ref",
                                  "../../README.md", "/etc/hostname"])
def test_reference_must_be_a_module_file_here(name):
    with pytest.raises(harness.BenchError, match="no module file"):
        harness.load_reference(name)


def test_reference_is_loaded_once():
    assert harness.load_reference("ref.py") is \
        harness.load_reference("ref.py")


def test_stated_head_dim_is_honoured(tmp_path):
    conf = smoke_conf()
    assert harness.model_config(conf).hd == 16
    conf["head_dim"] = 32
    _, _, cfg, m, _ = harness.cell_model(cell_for(conf, tmp_path))
    assert cfg.head_dim == cfg.hd == 32 and m["head_dim"] == 32
