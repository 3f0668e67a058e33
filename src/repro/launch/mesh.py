"""Production meshes. Import must never touch jax device state —
everything is a function.

``make_serving_mesh`` is the serving entry point: it raises at server
construction when the requested shape exceeds the attached devices, so a
missing device is never hidden behind a smaller mesh.
"""
from __future__ import annotations

import math
from typing import Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes, devices=None):
    """A mesh whose axes are all ``Auto``: the model code places work with
    logical-axis sharding constraints, which only bind to Auto axes.
    ``devices``, when given, fill the mesh in order."""
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    return Mesh(np.asarray(devices).reshape(tuple(shape)), tuple(axes),
                axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2, *, pod: int = 0):
    """Small mesh for CPU tests (requires enough placeholder devices)."""
    if pod:
        return _auto_mesh((pod, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))


def parse_mesh_spec(spec: str) -> Tuple[int, ...]:
    """'2x2' / '1x4' / '2x2x2' -> mesh shape tuple (data, model[, pod-first
    when 3 axes])."""
    try:
        shape = tuple(int(p) for p in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"bad mesh spec {spec!r}: want e.g. '2x2'") from None
    if not shape or any(s < 1 for s in shape) or len(shape) > 3:
        raise ValueError(f"bad mesh spec {spec!r}: want 1-3 positive axes")
    return shape


def carve_devices(prefill: int, decode: int,
                  devices=None) -> Tuple[list, list]:
    """Split the attached devices into disjoint prefill/decode pools.

    The first ``prefill`` devices feed the worker pool, the next
    ``decode`` the resident decode mesh. Raises when fewer devices are
    attached than the two pools need. Shared by
    :class:`repro.launch.workers.DisaggExecutor` and its degraded-mode
    rebuilds, so a restarted worker always lands on the same carve."""
    devs = list(devices) if devices is not None else list(jax.devices())
    if prefill + decode > len(devs):
        raise ValueError(
            f"disaggregated serving wants {prefill}+{decode} devices but "
            f"only {len(devs)} are attached")
    return devs[:prefill], devs[prefill:prefill + decode]


def make_serving_mesh(shape: Sequence[int] = (1, 1), *, devices=None):
    """Serving mesh over ``('data', 'model')`` (or ``('pod', 'data',
    'model')`` for 3 axes) on the first ``prod(shape)`` devices.

    Raises when fewer devices are attached than the shape needs: a
    smaller mesh would serve on fewer chips than the caller asked for.
    ``devices`` narrows the pool to an explicit device list (the
    disaggregated server carves prefill/decode pools this way).
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    n = math.prod(shape)
    if n > len(devs):
        raise ValueError(
            f"mesh {tuple(shape)} needs {n} devices but only {len(devs)} "
            f"are attached")
    axes = ("pod", "data", "model")[-len(shape):]
    return _auto_mesh(shape, axes, devices=devs[:n])
