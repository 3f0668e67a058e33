"""CPU tests of the weights and the reference made in pieces.

The benchmark makes its float weights leaf by leaf and layer by layer,
converts them for serving one layer at a time, and runs the reference
layer by layer over all compared requests, so that a model at 12B
widths fits one chip. At the smoke size each piece is checked against
the one-call form it replaced, kept here as the oracle: the same
weights and served containers bit for bit, and the same gaps (to 1e-6)
for the program's tokens and the control's.
"""
import dataclasses
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import ref  # noqa: E402
import weights  # noqa: E402

SEED = 2**31 + 11


def smoke_cfg(**over):
    conf = harness.load_json(HERE, "testdata", "smoke-config.json")
    return dataclasses.replace(harness.model_config(conf), **over)


def one_call_float_params(cfg, seed):
    """Every float leaf in one jitted call, each drawn whole."""
    from repro.models import lm
    shapes, _ = lm.abstract_init(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def fill(key, i, path, sds):
        name = str(getattr(path[-1], "key", path[-1]))
        x = jax.random.normal(jax.random.fold_in(key, i), sds.shape,
                              sds.dtype)
        if name == "scale":
            return x * weights.SIGMA + 1.0
        if name == "b":
            return x * weights.SIGMA
        return x * (0.02 if name == "table" else sds.shape[-2] ** -0.5)

    def make(key):
        return jax.tree_util.tree_unflatten(
            treedef, [fill(key, i, p, s) for i, (p, s) in enumerate(flat)])
    return make, jax.jit(make)(weights.seed_key(seed))


CFGS = {"tied": {}, "untied-biased": {"tie_embeddings": False,
                                      "qkv_bias": True}}


@pytest.mark.parametrize("variant", list(CFGS))
def test_float_pieces_equal_one_call(variant):
    cfg = smoke_cfg(**CFGS[variant])
    _, whole = one_call_float_params(cfg, SEED)
    w = weights.FloatWeights(cfg, SEED)
    top = w.top()
    assert set(top) | {"layers"} == set(whole)
    for k in top:
        assert jax.tree.structure(top[k]) == jax.tree.structure(whole[k])
        for a, b in zip(jax.tree.leaves(top[k]), jax.tree.leaves(whole[k])):
            assert a.dtype == b.dtype and np.array_equal(a, b)
    assert w.n_layers == cfg.n_layers
    for layer in range(w.n_layers):
        piece = w.layer(layer)
        assert jax.tree.structure(piece) == \
            jax.tree.structure(whole["layers"])
        for a, b in zip(jax.tree.leaves(piece),
                        jax.tree.leaves(whole["layers"])):
            assert np.array_equal(a, b[layer])
    with pytest.raises(IndexError):
        w.layer(w.n_layers)


@pytest.mark.parametrize("variant", list(CFGS))
def test_served_pieces_equal_one_call(variant):
    from repro.serve.step import convert_params_for_serving
    cfg = smoke_cfg(**CFGS[variant])
    make, _ = one_call_float_params(cfg, SEED)
    whole = jax.jit(lambda k: convert_params_for_serving(make(k), cfg))(
        weights.seed_key(SEED))
    pieces = weights.served_params(cfg, SEED)
    assert jax.tree.structure(pieces) == jax.tree.structure(whole)
    for a, b in zip(jax.tree.leaves(pieces), jax.tree.leaves(whole)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("shape", [(3, 5, 7), (1, 33), (64, 96)])
def test_normal_pieces_are_slices_of_jax_normal(shape):
    key = jax.random.fold_in(weights.seed_key(5), 3)
    whole = jax.random.normal(key, shape, jnp.float32)
    per = int(np.prod(shape[1:]))
    for i in range(shape[0]):
        off = jnp.asarray([0, i * per], jnp.uint32)
        got = weights.normal_piece(key, shape[1:], off)
        assert np.array_equal(got, whole[i])


def test_counts_carry_into_the_high_word():
    off = 2**32 * 3 + 2**32 - 5
    hi, lo = weights._counts((4, 3), jnp.asarray(
        [off >> 32, off & 0xFFFFFFFF], jnp.uint32))
    want = off + np.arange(12, dtype=np.uint64).reshape(4, 3)
    got = (np.asarray(hi).astype(np.uint64) << np.uint64(32)) | \
        np.asarray(lo).astype(np.uint64)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("path,shape", [
    (("layers", "attn", "gate", "w"), (2, 64)),
    (("head_gain",), (64,))])
def test_layout_refuses_a_leaf_it_cannot_draw(path, shape, monkeypatch):
    """A leaf that is no norm scale, bias or table, with one dimension
    past the layer axis, has no fan-in: it is refused by its path."""
    from repro.models import lm
    sds = jax.ShapeDtypeStruct
    tree = {"embed": {"table": sds((256, 64), jnp.float32)},
            "layers": {"ln1": {"scale": sds((2, 64), jnp.float32)},
                       "attn": {"wq": {"w": sds((2, 64, 64), jnp.float32),
                                       "b": sds((2, 64), jnp.float32)}}}}
    node = tree
    for k in path[:-1]:
        node = node.setdefault(k, {})
    node[path[-1]] = sds(shape, jnp.float32)
    monkeypatch.setattr(lm, "abstract_init", lambda cfg: (tree, None))
    with pytest.raises(ValueError, match="/".join(path)):
        weights.layout(smoke_cfg())
    del node[path[-1]]
    got = {x.path: (x.mean, x.std) for x in weights.layout(smoke_cfg())}
    assert got == {("embed", "table"): (0.0, 0.02),
                   ("layers", "ln1", "scale"): (1.0, weights.SIGMA),
                   ("layers", "attn", "wq", "w"): (0.0, 0.125),
                   ("layers", "attn", "wq", "b"): (0.0, weights.SIGMA)}


# -- the one-piece reference it replaced --------------------------------------

def _old_layer(h, p, c, act_bits):
    """One layer, each float weight quantized inside the projection."""
    def proj(x, w):
        qw, sw = ref.quantize(w, c["weight_bits"], axis=0)
        qx, sx = ref.quantize(x, act_bits, axis=-1)
        return (qx @ qw) * sx * sw
    s = h.shape[0]
    nh, nkv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    pos = jnp.arange(s)
    a = ref.rmsnorm(h, p["ln1"]["scale"], c["norm_eps"])
    at = p["attn"]
    q = ref.rope(proj(a, at["wq"]["w"]).reshape(s, nh, hd), pos,
                 c["rope_theta"])
    k = ref.rope(proj(a, at["wk"]["w"]).reshape(s, nkv, hd), pos,
                 c["rope_theta"])
    v = proj(a, at["wv"]["w"]).reshape(s, nkv, hd)
    k, v = jnp.repeat(k, nh // nkv, 1), jnp.repeat(v, nh // nkv, 1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    scores = jnp.where((pos[None, :] <= pos[:, None])[None], scores,
                       -jnp.inf)
    att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    h = h + proj(att.reshape(s, nh * hd), at["wo"]["w"])
    m = ref.rmsnorm(h, p["ln2"]["scale"], c["norm_eps"])
    mp = p["mlp"]
    up, gate = proj(m, mp["wi"]["w"]), proj(m, mp["wg"]["w"])
    return h + proj(jax.nn.silu(gate) * up, mp["wo"]["w"])


def _old_logits(params, c, tokens, act_bits):
    with jax.default_matmul_precision("highest"):
        h = params["embed"]["table"][tokens].astype(jnp.float32)
        h, _ = jax.lax.scan(
            lambda h, p: (_old_layer(h, p, c, act_bits), None), h,
            params["layers"])
        h = ref.rmsnorm(h, params["final_norm"]["scale"], c["norm_eps"])
        if c["tie_embeddings"]:
            return h @ params["embed"]["table"].T
        return h @ params["lm_head"]["w"]


@functools.partial(jax.jit, static_argnames=("items", "ctrl_bits"))
def _old_gaps(params, tokens, targets, mask, *, items, ctrl_bits):
    c = dict(items)
    r = _old_logits(params, c, tokens, c["act_bits"])
    best = r.max(-1)
    gap = jnp.where(mask, best - jnp.take_along_axis(
        r, targets[:, None], -1)[:, 0], 0.0)
    pick = jnp.argmax(_old_logits(params, c, tokens, ctrl_bits), -1)
    ctrl = best - jnp.take_along_axis(r, pick[:, None], -1)[:, 0]
    return gap.max(), jnp.where(mask, ctrl, 0.0).max()


@pytest.fixture(scope="module")
def kept(tmp_path_factory):
    """The compared requests of a smoke run of the timed path."""
    path = tmp_path_factory.mktemp("kept") / "kept.json"
    cell = {"name": "smoke", "config": "smoke",
            "traffic": "testdata/smoke-backlog", "chips": 1,
            "config_entry": {"file": "benchmarks/chip/testdata/"
                                     "smoke-config.json"},
            "bench": harness.benchmark(),
            "limits": {"max_logit_gap": 0.08}}
    out = harness.execute(cell, SEED, 1.5, False,
                          t_start=time.perf_counter(), keep=str(path))
    assert out["correct"] is True
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("variant", list(CFGS)[:1] + ["untied"])
def test_layer_by_layer_reference_gives_the_one_piece_gaps(kept, variant):
    cfg = smoke_cfg(**({} if variant == "tied" else
                       {"tie_embeddings": False}))
    m = harness.model_numbers(cfg)
    recs = [dict(r) for r in kept["records"]]
    assert len(recs) >= 4
    harness.reference_gaps(recs, ref, cfg, m, SEED, 96, ctrl_bits=4)
    _, params = one_call_float_params(cfg, SEED)
    items = tuple(sorted(m.items()))
    for r in recs:
        tokens, targets, mask = ref._rows(r["prompt"], r["served"], 96)
        gap, ctrl = _old_gaps(params, jnp.asarray(tokens),
                              jnp.asarray(targets), jnp.asarray(mask),
                              items=items, ctrl_bits=4)
        assert r["gap"] == pytest.approx(float(gap), abs=1e-6)
        assert r["ctrl"] == pytest.approx(float(ctrl), abs=1e-6)
        assert r["tokens"] == len(r["served"])
    if variant == "tied":
        # the served model's own head: the control reads far above the
        # program, as the check needs
        assert max(r["ctrl"] for r in recs) > \
            4 * max(r["gap"] for r in recs)


def _no_layer(h, p, c, *, act_bits):
    """A block whose layers pass the stream through."""
    return h


def test_served_gaps_drives_the_block_it_is_given(kept):
    """``served_gaps`` runs the block's functions it is handed: the llama
    block's own give its default gaps, exactly; another block other
    gaps."""
    cfg = smoke_cfg()
    m = harness.model_numbers(cfg)
    reqs = [(r["prompt"], r["served"]) for r in kept["records"]]

    def gaps(**block):
        return ref.served_gaps(weights.FloatWeights(cfg, SEED), m, reqs,
                               length=96, ctrl_bits=4, **block)
    default = gaps()
    assert gaps(prepare=ref.prepare, layer=ref.layer,
                logits=ref.logits) == default
    assert gaps(layer=_no_layer) != default
