"""CPU tests of the benchmark's work counts and peak table."""
import json
import os
import sys

import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import work  # noqa: E402
import weights  # noqa: E402


def smoke():
    with open(os.path.join(HERE, "testdata", "smoke-config.json")) as f:
        conf = json.load(f)
    cfg = harness.model_config(conf)
    return cfg, harness.model_numbers(cfg)


def test_projections_match_the_converted_containers():
    from repro.core.engine import QuantContainer
    cfg, m = smoke()
    params = weights.served_params(cfg, 3)
    layers = params["layers"]
    found = {"wqkv": layers["attn"]["wqkv"]["w"],
             "wo": layers["attn"]["wo"]["w"],
             "wig": layers["mlp"]["wig"]["w"],
             "down": layers["mlp"]["wo"]["w"]}
    conts = [x for x in jax.tree_util.tree_leaves(
        params, is_leaf=lambda x: isinstance(x, QuantContainer))
        if isinstance(x, QuantContainer)]
    # every packed container of the model is one of the four per layer
    assert len(conts) == len(work.projections(m))
    for name, n, m_out in work.projections(m):
        c = found[name]
        assert c.kind == "packed4" and c.bits == m["weight_bits"]
        layers_, k, out, words = c.wq.shape
        assert (layers_, k, out, c.n_in) == (m["n_layers"], 4, m_out, n)
        assert words * 32 >= n
    assert work.launches_per_step(m) == 4 * m["n_layers"]


def test_mvp_work_counts_the_product_not_the_planes():
    ops, nbytes = work.mvp_work(960, 1600, 32, weight_bits=4, act_bits=8)
    assert ops == 2 * 32 * 960 * 1600
    assert nbytes == 960 * 1600 / 2 + 4 * 1600 + 32 * 960 + 4 * 32 * 1600
    # doubling the activation bits a kernel streams changes nothing here
    assert work.mvp_work(960, 1600, 32, weight_bits=4, act_bits=8) == \
        (ops, nbytes)


def test_useful_ops():
    _, m = smoke()
    macs = sum(n * mo for _, n, mo in work.projections(m)) * m["n_layers"]
    head = m["d_model"] * m["vocab"]
    att = 4 * m["n_heads"] * m["head_dim"] * m["n_layers"]
    assert work.decode_useful_ops(m, [10, 20]) == \
        2 * (2 * (macs + head)) + att * 30
    # prefill: every prompt token, one head row per prompt
    got = work.prefill_useful_ops(m, [3])
    assert got == 2 * 3 * macs + 2 * head + att / 2 * 3 * 4


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
    pk = work.peaks("TPU v5 lite")
    assert (pk["int8_op_s"], pk["bf16_flop_s"], pk["hbm_byte_s"]) == \
        (393e12, 197e12, 819e9)
