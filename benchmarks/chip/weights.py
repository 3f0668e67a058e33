"""Seeded float weights in the served model's parameter layout.

The benchmark, not the program, makes the weights, so that the plain
reference (``ref.py``) can read the very same float values. The layout
(which leaves exist and their shapes) comes from the program's abstract
init; the values are drawn here, each leaf from its own key folded out
of the seed: embedding rows from N(0, 0.02^2) as the program draws them,
every [.., in, out] weight from N(0, 1/in), every norm scale from
1 + N(0, SIGMA^2) and every bias from N(0, SIGMA^2).

The scale matters for the output comparison. With the program's own
init (every weight at 0.02) each layer adds little to the residual
stream, so a tied head gives the current token the largest logit by a
wide margin and the served stream repeats its last token whatever the
arithmetic: no comparison could tell a coarser arithmetic from the
stated one. Scaled by fan-in, every layer moves the stream by about as
much as its input, and the largest logit depends on the whole
computation. Norm scales and biases are drawn, not left at 1 and 0,
so that a program that drops a norm scale or a bias serves other tokens
than the reference and the comparison sees it.

The weights are made on the device in pieces, so that a model at 12B
widths never holds all of its float weights at once: every leaf outside
``layers`` in a call of its own, and the leaves under ``layers`` (stacked
over a leading layer axis) one layer at a time. A layer's piece of a
leaf is drawn at its place in the whole leaf's stream: JAX's
partitionable threefry hashes each value's bits from the key and the
value's flat index alone, so a piece holds exactly the values that
``jax.random.normal`` gives for the whole leaf, bit for bit.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

# the top-level subtree whose leaves are stacked over a leading layer axis
STACKED = "layers"
# the spread of norm scales about 1 and of biases about 0: at 0.2 the
# program's widest gap on the smollm-360m cell passed a third of its
# limit on some seeds (TPU v5 lite), at 0.1 it stays below
SIGMA = 0.1


def seed_key(seed: int):
    """A 32-bit PRNG key from any whole-number seed."""
    word = np.random.SeedSequence([int(seed), 7]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


@dataclasses.dataclass(frozen=True)
class Leaf:
    index: int          # position in the layout; the key is fold_in(key, index)
    path: Tuple[str, ...]
    shape: Tuple[int, ...]
    dtype: np.dtype
    mean: float         # values are mean + std * N(0, 1)
    std: float

    @property
    def stacked(self) -> bool:
        return self.path[0] == STACKED


def _key_name(k) -> str:
    return str(getattr(k, "key", getattr(k, "name", k)))


def layout(cfg) -> List[Leaf]:
    """The leaves of the program's init layout for ``cfg``. A leaf that
    is neither a norm ``scale``, a bias ``b`` nor the embedding ``table``
    is a weight [.., in, out] drawn by its fan-in; one with fewer than
    two dimensions (past the layer axis) has none, and is refused."""
    from repro.models import lm
    shapes, _ = lm.abstract_init(cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    for i, (path, sds) in enumerate(flat):
        names = tuple(_key_name(k) for k in path)
        shape = tuple(sds.shape)
        last = names[-1]
        if last == "scale":
            mean, std = 1.0, SIGMA
        elif last == "b":
            mean, std = 0.0, SIGMA
        elif last == "table":
            mean, std = 0.0, 0.02
        elif len(shape) - (names[0] == STACKED) < 2:
            raise ValueError(f"{'/'.join(names)}: a leaf of shape {shape} "
                             f"is no norm scale, bias, table or [.., in, "
                             f"out] weight; no draw is known for it")
        else:
            mean, std = 0.0, shape[-2] ** -0.5
        leaves.append(Leaf(i, names, shape, np.dtype(sds.dtype), mean, std))
    return leaves


def _counts(shape, offset):
    """The 64-bit flat indices ``offset`` + 0 .. size-1 of a piece, as
    (high, low) uint32 arrays of ``shape``. ``offset`` is a uint32 pair
    (high, low); a piece holds fewer than 2^32 values."""
    size = math.prod(shape)
    local = jax.lax.iota(jnp.uint32, size).reshape(shape)
    lo = offset[1] + local
    hi = offset[0] + (lo < local).astype(jnp.uint32)
    return hi, lo


def normal_piece(key, shape, offset):
    """The float32 values of ``jax.random.normal(key, whole)`` at flat
    indices ``offset`` onwards, in ``shape``: JAX's normal, written out
    over the partitionable threefry bits of those indices."""
    f32 = np.float32
    hi, lo = _counts(shape, offset)
    b1, b2 = threefry2x32_p.bind(key[0], key[1], hi, lo)
    # uniform on [nextafter(-1, 0), 1) from the top 23 bits, as JAX draws it
    fbits = jax.lax.shift_right_logical(b1 ^ b2, jnp.uint32(32 - 23))
    fbits = jax.lax.bitwise_or(fbits, jnp.uint32(np.array(1.0, f32).view(
        np.uint32)))
    floats = jax.lax.bitcast_convert_type(fbits, f32) - jnp.array(1.0, f32)
    minval = jnp.asarray(np.nextafter(f32(-1.0), f32(0.0)), f32)
    maxval = jnp.asarray(1.0, f32)
    u = jax.lax.max(minval, floats * (maxval - minval) + minval)
    return jax.lax.mul(np.array(np.sqrt(2), f32), jax.lax.erf_inv(u))


def _piece(key, leaf: Leaf, offset):
    """Leaf ``leaf`` (or, stacked, one layer's slice of it from flat
    index ``offset``)."""
    shape = leaf.shape[1:] if leaf.stacked else leaf.shape
    if np.dtype(leaf.dtype) != np.float32:
        raise ValueError(f"{'/'.join(leaf.path)}: weights are drawn in "
                         f"float32, the layout says {leaf.dtype}")
    x = normal_piece(jax.random.fold_in(key, leaf.index), shape,
                     offset) * leaf.std
    return x + leaf.mean if leaf.mean else x


def _nest(items) -> Dict:
    """{path: value} -> nested dicts."""
    out: Dict = {}
    for path, v in items:
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    return out


class FloatWeights:
    """The float weights of one seed, made on the device in pieces."""

    def __init__(self, cfg, seed: int):
        self.key = seed_key(seed)
        leaves = layout(cfg)
        self.top_leaves = [x for x in leaves if not x.stacked]
        self.layer_leaves = [x for x in leaves if x.stacked]
        self.n_layers = (self.layer_leaves[0].shape[0]
                         if self.layer_leaves else 0)
        zero = jnp.zeros(2, jnp.uint32)
        self._leaf = jax.jit(lambda key, leaf: _piece(key, leaf, zero),
                             static_argnums=1)
        self._layer_jit = jax.jit(self._layer)

    def _layer(self, key, offsets):
        return _nest((x.path[1:], _piece(key, x, offsets[j]))
                     for j, x in enumerate(self.layer_leaves))

    def offsets(self, layer: int):
        """uint32 [leaves, 2]: where layer ``layer``'s slice of each
        stacked leaf starts in the leaf's stream."""
        if not 0 <= layer < self.n_layers:
            raise IndexError(f"layer {layer} of {self.n_layers}")
        flat = [layer * math.prod(x.shape[1:]) for x in self.layer_leaves]
        return jnp.asarray([(n >> 32, n & 0xFFFFFFFF) for n in flat],
                           jnp.uint32).reshape(len(flat), 2)

    def top(self) -> Dict:
        """Every leaf outside ``layers``, each made in a call of its own."""
        return _nest((x.path, self._leaf(self.key, x))
                     for x in self.top_leaves)

    def layer(self, layer: int) -> Dict:
        """Layer ``layer``'s slice of every ``layers`` leaf, in one call."""
        return self._layer_jit(self.key, self.offsets(layer))


def served_params(cfg, seed: int):
    """Float weights of ``seed`` converted by the program's own serving
    load path (``convert_params_for_serving``), layer by layer: each
    call makes one layer's float weights and converts them into its
    slot of the stacked result, which it updates in place, so the float
    projections are never all resident at once. The result equals one
    conversion of all the float weights, bit for bit."""
    from repro.serve.step import convert_params_for_serving
    w = FloatWeights(cfg, seed)
    params = convert_params_for_serving(w.top(), cfg)
    if not w.n_layers:
        return params

    def convert(key, offsets):
        return convert_params_for_serving(w._layer(key, offsets), cfg)

    one = jax.eval_shape(convert, w.key, w.offsets(0))
    stacked = jax.jit(lambda: jax.tree.map(
        lambda s: jnp.zeros((w.n_layers,) + s.shape, s.dtype), one))()

    def write(stacked, key, offsets, layer):
        return jax.tree.map(lambda a, b: a.at[layer].set(b), stacked,
                            convert(key, offsets))
    write = jax.jit(write, donate_argnums=0)
    for layer in range(w.n_layers):
        stacked = write(stacked, w.key, w.offsets(layer), layer)
    params[STACKED] = stacked
    return params
