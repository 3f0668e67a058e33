"""The one shard_map entry point of the repo.

Replication/VMA checking is always off: the repo uses fully-manual or
pod-manual bodies that the checker rejects.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, PartitionSpec as P


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None):
    """axis_names: iterable of *manual* mesh axes; None -> fully manual."""
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kw)


def split_over_mesh(fn, x, a, *whole, x_rows: int, a_rows: int):
    """``fn(x, a, *whole) -> [B, M]`` per device under the active mesh.

    Mosaic kernels cannot be partitioned automatically, so inside a
    ``jax.set_mesh`` context the call becomes a shard_map: x's batch dim
    (``x_rows``) splits over 'data' and a's row dim (``a_rows``) over
    'model' wherever those Auto axes divide them; ``whole`` operands and
    every other dim stay whole on each device. Each device computes its
    own [B, M] block, so the result is the unsplit call's exactly. The
    blocks are gathered over 'model' inside the body: left split, the
    float code after the kernel (a norm over M) would sum partial sums
    across devices, in another order than one device does, and round
    differently.
    Outside a mesh (or inside a body that already holds every axis
    manual) it is just ``fn(x, a, *whole)``.
    """
    mesh = jax.sharding.get_abstract_mesh()
    auto = ({n: s for n, s, t in zip(mesh.axis_names, mesh.axis_sizes,
                                     mesh.axis_types) if t == AxisType.Auto}
            if not mesh.empty else {})

    def axis(name, size):
        return name if name in auto and size % auto[name] == 0 else None

    d = axis("data", x.shape[x_rows])
    m = axis("model", a.shape[a_rows])
    if d is None and m is None:
        return fn(x, a, *whole)

    def spec(ndim, dim, ax):
        return P(*[ax if i == dim else None for i in range(ndim)])

    def body(x, a, *whole):
        out = fn(x, a, *whole)
        return out if m is None else jax.lax.all_gather(out, m, axis=1,
                                                        tiled=True)

    return shard_map(
        body, mesh=mesh,
        in_specs=(spec(x.ndim, x_rows, d), spec(a.ndim, a_rows, m))
        + (P(),) * len(whole),
        out_specs=P(d, None), axis_names=[ax for ax in (d, m) if ax])(
            x, a, *whole)
