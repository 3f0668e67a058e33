"""KV cache writes on the position-minor layout ([L, B, Hkv, D, T]).

A decode step, or a speculative verify window, changes each layer's
cache only at the rows it writes — ``(b, :, :, row)`` — and writes there
the K/V a prefill of the same tokens computes; a row at or past T
changes nothing. Contiguous, int8 and ring (sliding-window) stores."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import load_arch
from repro.models import lm

T = 16
# store -> (arch, kv_dtype, sliding window)
STORES = {"contiguous": ("smollm_360m", "bfloat16", 0),
          "int8": ("stablelm_12b", "int8", 0),
          "ring": ("h2o_danube3_4b", "bfloat16", 8)}
LENS = (5, 9, 3)


def _model(store):
    arch, kv, window = STORES[store]
    cfg = dataclasses.replace(load_arch(arch).smoke(), dtype="float32",
                              kv_dtype=kv, sliding_window=window)
    params, _ = lm.init(cfg, jax.random.PRNGKey(3))
    return cfg, params


def _prefilled(cfg, params, tokens, lens):
    """A float32 cache of T rows prefilled with right-padded prompts."""
    cache, _ = lm.init_cache(cfg, tokens.shape[0], T, dtype=jnp.float32)
    _, cache = lm.prefill(params, cfg, {"tokens": jnp.asarray(tokens)},
                          cache, lengths=jnp.asarray(lens, jnp.int32))
    return cache


def _prompts(cfg, rng, lens, width):
    toks = np.zeros((len(lens), width), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(0, cfg.vocab, n)
    return toks


def _assert_written_at(old, new, cols):
    """Every leaf of ``new`` equals ``old`` but at ``cols[b]`` (a list of
    positions per slot) along the minor axis of slot b."""
    for name in old:
        a, b = np.asarray(old[name]), np.asarray(new[name])
        want = a.copy()
        for i, cs in enumerate(cols):
            for c in cs:
                want[:, i, ..., c] = b[:, i, ..., c]
        assert np.array_equal(b, want), name


def _assert_holds(ref, new, cols, int8):
    """The written columns hold the K/V ``ref`` (a prefill of the same
    tokens) holds there: float stores to float32 rounding, the int8
    store to one quantization level."""
    for name in ref:
        for i, cs in enumerate(cols):
            for c in cs:
                got = np.asarray(new[name][:, i, ..., c], np.float32)
                exp = np.asarray(ref[name][:, i, ..., c], np.float32)
                if int8 and name in ("k", "v"):
                    np.testing.assert_allclose(got, exp, atol=1)
                else:
                    np.testing.assert_allclose(got, exp, rtol=2e-2 if int8
                                               else 1e-5, atol=1e-5)


@pytest.mark.parametrize("store", sorted(STORES))
def test_decode_writes_only_its_row(rng, store):
    cfg, params = _model(store)
    lens = np.asarray(LENS)
    toks = _prompts(cfg, rng, lens, T)
    cache = _prefilled(cfg, params, toks[:, :lens.max()], lens)
    nxt = rng.integers(0, cfg.vocab, len(lens)).astype(np.int32)
    _, new = lm.decode_step(params, cfg, jnp.asarray(nxt)[:, None],
                            jax.tree.map(jnp.copy, cache))
    t = cache["layers"]["k"].shape[-1]
    cols = [[p % t] for p in lens]
    _assert_written_at(cache["layers"], new["layers"], cols)
    assert np.array_equal(np.asarray(new["pos"]), lens + 1)
    for i, n in enumerate(lens):
        toks[i, n] = nxt[i]
    ref = _prefilled(cfg, params, toks[:, :lens.max() + 1], lens + 1)
    _assert_holds(ref["layers"], new["layers"], cols, store == "int8")


@pytest.mark.parametrize("store", ["contiguous", "int8"])
def test_decode_past_the_last_row_changes_nothing(rng, store):
    """A slot whose position is at or past T writes nothing (the other
    slots still write their rows)."""
    cfg, params = _model(store)
    lens = np.asarray(LENS)
    cache = _prefilled(cfg, params, _prompts(cfg, rng, lens, lens.max()),
                       lens)
    cache["pos"] = jnp.asarray([T, T + 3, lens[2]], jnp.int32)
    _, new = lm.decode_step(params, cfg, jnp.ones((3, 1), jnp.int32),
                            jax.tree.map(jnp.copy, cache))
    _assert_written_at(cache["layers"], new["layers"], [[], [], [lens[2]]])


@pytest.mark.parametrize("store", sorted(STORES))
def test_verify_window_writes_only_its_rows(rng, store):
    """A verify window of S rows at pos + i writes exactly those rows
    (ring: slots (pos + i) % t) and, on a linear store, drops the rows
    that run past T."""
    cfg, params = _model(store)
    s = 4
    lens = np.asarray(LENS)
    toks = _prompts(cfg, rng, lens, T + s)
    cache = _prefilled(cfg, params, toks[:, :lens.max()], lens)
    window = rng.integers(0, cfg.vocab, (len(lens), s)).astype(np.int32)
    _, new = lm.verify(params, cfg, jnp.asarray(window),
                       jax.tree.map(jnp.copy, cache))
    t = cache["layers"]["k"].shape[-1]
    cols = [[(p + i) % t for i in range(s)] for p in lens]
    _assert_written_at(cache["layers"], new["layers"], cols)
    for i, n in enumerate(lens):
        toks[i, n:n + s] = window[i]
    ref = _prefilled(cfg, params, toks[:, :lens.max() + s], lens + s)
    _assert_holds(ref["layers"], new["layers"], cols, store == "int8")
    if cfg.sliding_window:
        return
    cache["pos"] = jnp.asarray([T - 2, T, lens[2]], jnp.int32)
    _, new = lm.verify(params, cfg, jnp.asarray(window),
                       jax.tree.map(jnp.copy, cache))
    cols = [[T - 2, T - 1], [], [lens[2] + i for i in range(s)]]
    _assert_written_at(cache["layers"], new["layers"], cols)


def test_contiguous_decode_matches_forward(rng):
    """Several decode steps out of a prefill give the full forward's
    logits at every position (the contiguous store; the ring store's
    twin is in test_generate.py)."""
    cfg, params = _model("contiguous")
    b, n, t0 = 2, 12, 5
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (b, n)), jnp.int32)
    full, _ = lm.forward(params, cfg, {"tokens": tokens})
    cache, _ = lm.init_cache(cfg, b, T, dtype=jnp.float32)
    logits, cache = lm.prefill(params, cfg, {"tokens": tokens[:, :t0]},
                               cache)
    for i in range(t0, n):
        logits, cache = lm.decode_step(params, cfg, tokens[:, i][:, None],
                                       cache)
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(full[:, i]), rtol=5e-3,
                                   atol=5e-3, err_msg=f"pos {i}")
