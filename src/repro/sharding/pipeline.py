"""GPipe-style pipeline parallelism over a 'pipe' mesh axis.

Stages hold disjoint layer slices; microbatches flow through a rotating
``lax.ppermute`` ring inside a fully-manual ``shard_map`` (fully manual —
the partial-manual form crashes the CPU XLA backend, see EXPERIMENTS.md).
The schedule is the classic M+S-1-tick GPipe pipeline:

    tick t: stage s computes microbatch (t - s) if 0 <= t-s < M,
            then passes its activation to stage s+1.

Differentiable end-to-end (ppermute has a transpose rule), so the same
function serves training; bubble fraction = (S-1)/(M+S-1).

This maps pods to stages on the production mesh (pod axis = pipe) as the
alternative to pure cross-pod DP; the dry-run default keeps DP because
the assigned shapes are batch-rich, but the feature is here and tested.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from .compat import shard_map


def pipeline_apply(stage_fn: Callable, stage_params, x, *, mesh: Mesh,
                   axis: str = "pipe", microbatches: int):
    """Run ``y = stage_{S-1}(...stage_0(x))`` pipelined over `axis`.

    stage_params: pytree stacked on a leading stage dim (sharded over
    `axis`). x: [B, ...] global batch (replicated); B % microbatches == 0.
    Returns y with x's shape. stage_fn(params_slice, h) -> h.
    """
    s_count = mesh.shape[axis]
    m = microbatches
    b = x.shape[0]
    assert b % m == 0, (b, m)
    mb = b // m
    xs = x.reshape((m, mb) + x.shape[1:])

    def body(params_local, xs_rep):
        # params_local: stage slice [1, ...]; xs_rep: full [M, mb, ...]
        sid = lax.axis_index(axis)
        p_slice = jax.tree.map(lambda t: t[0], params_local)
        perm = [(i, (i + 1) % s_count) for i in range(s_count)]

        state = jnp.zeros_like(xs_rep[0])
        outs = jnp.zeros_like(xs_rep)
        for t in range(m + s_count - 1):
            # stage 0 ingests microbatch t (while it exists)
            inject = xs_rep[min(t, m - 1)]
            h_in = jnp.where(sid == 0, inject, state)
            h_out = stage_fn(p_slice, h_in)
            # last stage emits microbatch t - (S-1)
            emit_idx = t - (s_count - 1)
            if 0 <= emit_idx < m:
                outs = outs.at[emit_idx].set(
                    jnp.where(sid == s_count - 1, h_out, outs[emit_idx]))
            state = lax.ppermute(h_out, axis, perm)
        # non-last stages contributed exact zeros, so a psum replicates
        # the last stage's result everywhere
        outs = lax.psum(outs, axis)
        return outs

    # an all-Auto view of the mesh: the result comes back untyped by any
    # mesh axis, so the caller's eager ops and their transposes under
    # jax.grad need no mesh context
    auto = Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    fn = shard_map(body, mesh=auto, in_specs=(P(axis), P()), out_specs=P())
    y = fn(stage_params, xs)
    return y.reshape((b,) + x.shape[1:])
