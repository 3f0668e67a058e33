"""CPU tests of how a configuration file states its block and names its
reference: block keys reach the program's ModelConfig and the model
numbers, or are refused by name; a block that the named reference does
not model is refused by name.

Whether a block key is refused or carried depends on the fields of the
program's ModelConfig. The tests of both cases run against stand-ins
that lack the fields or have them, so they hold whichever the program
has."""
import dataclasses
import json
import os
import sys
import types

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


# the block fields beside ``qkv_bias``, which the stand-ins lack or have
BLOCK_FIELDS = ("norm", "qk_norm", "parallel_residual", "rotary_fraction")


def stand_in(with_block: bool):
    """A stand-in for the program's ModelConfig class: its fields and
    properties without :data:`BLOCK_FIELDS`, or with them at the llama
    values."""
    from repro.configs.base import ModelConfig
    fields = [(f.name, f.type,
               dataclasses.field(default=f.default,
                                 default_factory=f.default_factory))
              for f in dataclasses.fields(ModelConfig)
              if f.name not in BLOCK_FIELDS]
    if with_block:
        fields += [(f, type(harness.LLAMA[f]),
                    dataclasses.field(default=harness.LLAMA[f]))
                   for f in BLOCK_FIELDS]
    methods = {k: v for k, v in vars(ModelConfig).items()
               if not k.startswith("__")
               and isinstance(v, (property, types.FunctionType))}
    return dataclasses.make_dataclass("ModelConfig", fields, frozen=True,
                                      namespace=methods)


def use_program(monkeypatch, with_block: bool):
    """Serve ``harness.program_preset`` from the stand-in: the real
    preset's values, and the llama values of block fields it lacks."""
    cls = stand_in(with_block)
    real = harness.program_preset

    def preset(arch):
        cfg = real(arch)
        return cls(**{f.name: getattr(cfg, f.name)
                      for f in dataclasses.fields(cls)
                      if hasattr(cfg, f.name)})
    monkeypatch.setattr(harness, "program_preset", preset)


@pytest.fixture
def program_without(monkeypatch):
    use_program(monkeypatch, False)


@pytest.fixture
def program_with(monkeypatch):
    use_program(monkeypatch, True)


# source key, value, ModelConfig field, the field's value
STABLELM = [("layer_norm_eps", 1e-05, "norm", "layer"),
            ("qk_layernorm", True, "qk_norm", True),
            ("use_parallel_residual", True, "parallel_residual", True),
            ("partial_rotary_factor", 0.25, "rotary_fraction", 0.25)]


def stating(key, value):
    conf = smoke_conf()
    if key == "layer_norm_eps":
        del conf["rms_norm_eps"]
    conf[key] = value
    return conf


@pytest.mark.parametrize("key,value", [x[:2] for x in STABLELM])
def test_block_the_program_cannot_carry_is_refused_by_name(
        key, value, tmp_path, program_without):
    conf = stating(key, value)
    with pytest.raises(harness.BenchError, match=key):
        harness.model_config(conf)
    with pytest.raises(harness.BenchError, match=key):
        harness.cell_model(cell_for(conf, tmp_path))


@pytest.mark.parametrize("key,value,field,got", STABLELM)
def test_block_the_program_carries_reaches_it(key, value, field, got,
                                              tmp_path, program_with,
                                              monkeypatch):
    conf = stating(key, value)
    cfg = harness.model_config(conf)
    assert getattr(cfg, field) == got
    m = harness.model_numbers(cfg)
    assert m[field] == got and m["norm_eps"] == 1e-05
    # ref.py models the llama block
    with pytest.raises(harness.BenchError,
                       match=rf"{key} gives {field}=.*ref\.py"):
        harness.cell_model(cell_for(conf, tmp_path))
    # a reference that declares the field takes it
    one = types.SimpleNamespace(BLOCK=dict(harness.LLAMA, **{field: got}))
    load = harness.load_reference
    monkeypatch.setattr(harness, "load_reference", lambda name: (
        one if name == "one-key" else load(name)))
    conf["reference"] = "one-key"
    _, _, cfg, m, reference = harness.cell_model(cell_for(conf, tmp_path))
    assert reference is one and getattr(cfg, field) == m[field] == got


def test_stablelm_block_passes_under_its_reference(tmp_path, program_with):
    conf = smoke_conf()
    del conf["rms_norm_eps"]
    conf.update({key: value for key, value, _, _ in STABLELM})
    with pytest.raises(harness.BenchError, match="layer_norm_eps"):
        harness.cell_model(cell_for(conf, tmp_path))
    conf["reference"] = "testdata/ref_stablelm_block.py"
    _, _, cfg, m, reference = harness.cell_model(cell_for(conf, tmp_path))
    for _, _, field, got in STABLELM:
        assert getattr(cfg, field) == m[field] == reference.BLOCK[field] \
            == got
    assert m["qkv_bias"] is False and m["norm_eps"] == 1e-05


@pytest.mark.parametrize("key,value", [
    ("qk_layernorm", False), ("use_parallel_residual", False),
    ("partial_rotary_factor", 1.0), ("use_qkv_bias", False),
    ("hidden_act", "silu")])
def test_llama_values_of_block_keys_pass(key, value, tmp_path):
    conf = dict(smoke_conf(), **{key: value})
    assert harness.cell_model(cell_for(conf, tmp_path))[3] == \
        harness.cell_model(cell_for(smoke_conf(), tmp_path))[3]


def test_two_norm_keys_are_refused():
    conf = dict(smoke_conf(), layer_norm_eps=1e-05)
    with pytest.raises(harness.BenchError, match="two norms"):
        harness.model_config(conf)


def test_layer_norm_is_never_served_as_rms_norm(program_without):
    conf = stating("layer_norm_eps", 1e-05)
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
