"""Seeded float weights in the served model's parameter layout.

The benchmark, not the program, makes the weights, so that the plain
reference (``ref.py``) can read the very same float values. The layout
(which leaves exist and their shapes) comes from the program's abstract
init; the values are drawn here, each leaf from its own key folded out
of the seed: embedding rows from N(0, 0.02^2) as the program draws them,
every [.., in, out] weight from N(0, 1/in), every norm scale 1, every
bias 0. One jitted call makes them on the device.

The scale matters for the output comparison. With the program's own
init (every weight at 0.02) each layer adds little to the residual
stream, so a tied head gives the current token the largest logit by a
wide margin and the served stream repeats its last token whatever the
arithmetic: no comparison could tell a coarser arithmetic from the
stated one. Scaled by fan-in, every layer moves the stream by about as
much as its input, and the largest logit depends on the whole
computation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A 32-bit PRNG key from any whole-number seed."""
    word = np.random.SeedSequence([int(seed), 7]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _fill(key, i: int, path, sds):
    name = _leaf_name(path)
    if name == "scale":
        return jnp.ones(sds.shape, sds.dtype)
    if name == "b":
        return jnp.zeros(sds.shape, sds.dtype)
    std = 0.02 if name == "table" else sds.shape[-2] ** -0.5
    return jax.random.normal(jax.random.fold_in(key, i), sds.shape,
                             sds.dtype) * std


def float_params_fn(cfg):
    """key -> float params pytree (the program's init layout)."""
    from repro.models import lm
    shapes, _ = lm.abstract_init(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        return jax.tree_util.tree_unflatten(
            treedef, [_fill(key, i, p, s) for i, (p, s) in enumerate(flat)])
    return make


def float_params(cfg, seed: int):
    """The float weights of ``seed``, made on the device in one call."""
    return jax.jit(float_params_fn(cfg))(seed_key(seed))


def served_params(cfg, seed: int):
    """Float weights of ``seed`` converted by the program's own serving
    load path (``convert_params_for_serving``), in one jitted call, so
    the float projections are never all resident at once."""
    from repro.serve.step import convert_params_for_serving
    make = float_params_fn(cfg)

    @jax.jit
    def build(key):
        return convert_params_for_serving(make(key), cfg)
    return build(seed_key(seed))
