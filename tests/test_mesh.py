"""Serving-mesh construction: mesh-spec parsing, and on a real
(forced-host) 4-device runtime the exact mesh or a raise — never a
smaller mesh than asked."""
import subprocess
import sys
import textwrap

import pytest
from conftest import cpu_subproc_env

from repro.launch.mesh import parse_mesh_spec


def test_parse_mesh_spec():
    assert parse_mesh_spec("2x2") == (2, 2)
    assert parse_mesh_spec("1x4") == (1, 4)
    assert parse_mesh_spec("2X2x2") == (2, 2, 2)
    assert parse_mesh_spec("4") == (4,)
    for bad in ("", "2x", "ax2", "2x2x2x2", "0x2", "-1x2"):
        with pytest.raises(ValueError):
            parse_mesh_spec(bad)


SUBPROC_FALLBACK = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import warnings
    import jax
    from jax.sharding import AxisType
    from repro.launch.mesh import carve_devices, make_serving_mesh

    # exact fit: no warning, requested shape honored, Auto axes
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        mesh = make_serving_mesh((2, 2))
    assert dict(mesh.shape) == {"data": 2, "model": 2}, mesh.shape
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)

    # oversubscribed: raises instead of serving on fewer devices
    try:
        make_serving_mesh((8, 8))
    except ValueError as e:
        assert "needs 64 devices but only 4" in str(e), e
    else:
        raise AssertionError("oversubscribed mesh did not raise")

    # the disaggregated carve raises too instead of overlapping pools
    p, d = carve_devices(2, 2)
    assert not set(p) & set(d) and len(p) == len(d) == 2
    try:
        carve_devices(3, 2)
    except ValueError as e:
        assert "3+2 devices but only 4" in str(e), e
    else:
        raise AssertionError("oversubscribed carve did not raise")

    # explicit device list narrows the pool (the disaggregated server
    # carves prefill/decode slices this way)
    devs = jax.devices()[2:]
    mesh = make_serving_mesh((1, 2), devices=devs)
    assert sorted(d.id for d in mesh.devices.ravel()) == \\
        sorted(d.id for d in devs)

    # 3-axis specs get the pod axis
    mesh = make_serving_mesh((1, 2, 2))
    assert mesh.axis_names == ("pod", "data", "model")
    print("MESH_FALLBACK_OK")
""")


def test_serving_mesh_fallback_4dev():
    res = subprocess.run([sys.executable, "-c", SUBPROC_FALLBACK],
                         capture_output=True, text=True, timeout=600,
                         env=cpu_subproc_env())
    assert "MESH_FALLBACK_OK" in res.stdout, res.stdout + res.stderr
