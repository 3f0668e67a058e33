"""Serving path: generation loop, PPAC weight conversion, quantized decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import load_arch
from repro.core.engine import QuantContainer
from repro.core.ppac import PPACConfig
from repro.models import lm
from repro.serve.step import (
    convert_params_for_serving,
    greedy_generate,
    serving_cycle_report,
)


def test_greedy_generate_shapes():
    cfg = load_arch("smollm_360m").smoke()
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 8), jnp.int32)}
    out = greedy_generate(params, cfg, batch, steps=5, max_seq=32)
    assert out.shape == (2, 5)
    assert (np.asarray(out) >= 0).all() and (np.asarray(out) < cfg.vocab).all()


def test_convert_params_replaces_projections():
    cfg = load_arch("stablelm_12b").smoke()
    cfg = dataclasses.replace(
        cfg, ppac=dataclasses.replace(cfg.ppac, enabled=True, weight_bits=4,
                                      min_features=32))
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    served = convert_params_for_serving(params, cfg)
    containers = [l for l in jax.tree.leaves(
        served, is_leaf=lambda x: isinstance(x, QuantContainer))
        if isinstance(x := l, QuantContainer)]
    assert len(containers) > 0
    # the embedding table held in the compute dtype both its readers cast
    # it to, with the same values; norms untouched
    table = params["embed"]["table"]
    assert served["embed"]["table"].dtype == jnp.dtype(cfg.dtype)
    assert np.array_equal(np.asarray(served["embed"]["table"]),
                          np.asarray(table.astype(cfg.dtype)))
    norm = params["final_norm"]["scale"]
    assert served["final_norm"]["scale"].dtype == norm.dtype
    assert np.array_equal(np.asarray(served["final_norm"]["scale"]),
                          np.asarray(norm))
    # packed4 halves the `in` dim
    c = containers[0]
    assert c.kind == "packed4"


@pytest.mark.parametrize("bits", [8, 4])
def test_quantized_decode_close_to_float(bits):
    cfg = dataclasses.replace(load_arch("stablelm_12b").smoke(),
                              dtype="float32")
    cfg = dataclasses.replace(
        cfg, ppac=dataclasses.replace(cfg.ppac, enabled=True,
                                      weight_bits=bits, act_bits=8,
                                      min_features=32))
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    served = convert_params_for_serving(params, cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (2, 12)), jnp.int32)

    logits_f, _ = lm.forward(params, cfg, {"tokens": tokens})
    logits_q, _ = lm.forward(served, cfg, {"tokens": tokens}, mode="serve")
    lf, lq = np.asarray(logits_f), np.asarray(logits_q)
    corr = np.corrcoef(lf.ravel(), lq.ravel())[0, 1]
    assert corr > 0.97, corr
    # top-1 agreement on most positions
    agree = (lf.argmax(-1) == lq.argmax(-1)).mean()
    assert agree > 0.7, agree


@pytest.mark.parametrize("bits,kind,kl", [(1, "packed1", 1), (4, "packed4", 32)])
def test_serving_cycle_report(bits, kind, kl):
    cfg = load_arch("stablelm_12b").smoke()
    cfg = dataclasses.replace(
        cfg, ppac=dataclasses.replace(cfg.ppac, enabled=True,
                                      weight_bits=bits, act_bits=8,
                                      min_features=32))
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    served = convert_params_for_serving(params, cfg)
    rep = serving_cycle_report(served, cfg)
    assert rep.num_projections > 0
    assert rep.cycles_per_token > 0
    # every converted projection runs on the fused kernels
    assert all(p.fused and p.kind == kind for p in rep.projections)
    assert rep.fused_cycles_per_token == rep.cycles_per_token
    # K*L plane-pair passes per tile-grid scan (packed1: one XNOR pass)
    one = rep.projections[0]
    assert one.k_bits * one.l_bits == kl
    assert rep.est_us_per_token() is not None  # 256x256 is in Table II
    d = rep.as_dict()
    assert d["cycles_per_token"] == rep.cycles_per_token
    # a 16x16 array needs strictly more tile-grid scans than the default
    # 256x256 for this model's projections — guards that the geometry
    # actually flows into the accounting
    tiny = serving_cycle_report(served, cfg,
                                config=PPACConfig(m=16, n=16))
    assert tiny.cycles_per_token > rep.cycles_per_token


def test_quantized_generation_runs():
    cfg = load_arch("smollm_360m").smoke()
    cfg = dataclasses.replace(
        cfg, ppac=dataclasses.replace(cfg.ppac, enabled=True, weight_bits=8,
                                      act_bits=8, min_features=32))
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    served = convert_params_for_serving(params, cfg)
    batch = {"tokens": jnp.ones((1, 8), jnp.int32)}
    out = greedy_generate(served, cfg, batch, steps=4, max_seq=32,
                          mode="serve")
    assert out.shape == (1, 4)
