"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

Interpret mode runs a kernel body as plain JAX, so it cannot see what the
chip's compiler refuses: block shapes off the (8, 128) tiling, primitives
Mosaic cannot lower, scalar reads from vector memory, VMEM overflow. Here
each kernel compiles with ``interpret=False`` for one chip of a described
``v5e:2x2`` topology (no chip attached), at the widths the main path
serves: smollm-360m's ``full()`` projections at a decode batch and one
prefill batch, the CAM lookup of the retrieval server's README deployment
and the coding server's default array code.

The topology is described inside a fixture, never at import time: only
the process that describes it loads the TPU compiler library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import load_arch
from repro.core.formats import packed_width
from repro.gf2.ldpc import make_array_ldpc
from repro.kernels.binary_mvp.kernel import binary_matmul_packed
from repro.kernels.bitserial_mvp.kernel import (
    bitserial_matmul_packed,
    bitserial_matmul_sliced,
)
from repro.kernels.gf2_tiled.kernel import gf2_matmul_packed
from repro.kernels.hamming_topk.kernel import hamming_topk_packed

_CFG = load_arch("smollm_360m").full()
_D, _F = _CFG.d_model, _CFG.d_ff                    # 960, 2560
_QKV = (_CFG.n_heads + 2 * _CFG.n_kv_heads) * _CFG.head_dim   # 1600
# resident projections (in features, out rows) of one decoder layer, with
# the grouped wq|wk|wv and wi|wg containers the server builds
_PROJ = {"wqkv": (_D, _QKV), "wo": (_CFG.n_heads * _CFG.head_dim, _D),
         "wig": (_D, 2 * _F), "wd": (_F, _D)}
_DECODE_B, _PREFILL_B = 8, 4 * 256   # 8 slots; 4 prompts x 256 rows
_K_BITS, _L_BITS = 4, 8              # --weight-bits 4, act_bits 8
_CAM_M, _CAM_BITS, _CAM_K = 65536, 256, 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's programs cannot be read back from the persistent
    # cache without the chip, so keep them out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


U32, I32 = jnp.uint32, jnp.int32


@pytest.mark.parametrize("batch", [_DECODE_B, _PREFILL_B])
@pytest.mark.parametrize("proj", sorted(_PROJ))
def test_bitserial_sliced_compiles(one_chip, proj, batch):
    """The decode fast path: level codes bit-sliced inside the kernel."""
    n, m = _PROJ[proj]
    w = packed_width(n)
    fn = jax.jit(functools.partial(bitserial_matmul_sliced, l_bits=_L_BITS,
                                   pop_a=False, pop_x=False, const=False))
    _compile(fn, one_chip, ((32, batch, w), U32), ((_K_BITS, m, w), U32),
             ((_K_BITS + 1, _L_BITS + 1), I32))


@pytest.mark.parametrize("proj", ["wig", "wd"])
def test_bitserial_packed_offset_compiles(one_chip, proj):
    """Packed planes with an oddint mask plane: every extended term on."""
    n, m = _PROJ[proj]
    w = packed_width(n)
    fn = jax.jit(functools.partial(bitserial_matmul_packed, pop_a=True,
                                   pop_x=True, const=True))
    _compile(fn, one_chip, ((_L_BITS, _DECODE_B, w), U32),
             ((_K_BITS + 1, m, w), U32), ((_K_BITS + 2, _L_BITS + 1), I32))


@pytest.mark.parametrize("op", ["xor", "and"])
@pytest.mark.parametrize("proj", ["wqkv", "wd"])
def test_binary_compiles(one_chip, proj, op):
    n, m = _PROJ[proj]
    w = packed_width(n)
    fn = jax.jit(functools.partial(binary_matmul_packed, op=op))
    _compile(fn, one_chip, ((_DECODE_B, w), U32), ((m, w), U32))


def test_hamming_topk_compiles(one_chip):
    w = packed_width(_CAM_BITS)
    fn = jax.jit(functools.partial(hamming_topk_packed, n=_CAM_BITS,
                                   k=_CAM_K))
    _compile(fn, one_chip, ((_DECODE_B, w), U32), ((_CAM_M, w), U32),
             ((_CAM_M,), I32))


def test_gf2_compiles(one_chip):
    """The coding server's syndrome product (default 32x32 array code)."""
    code = make_array_ldpc(32, 32)
    w = packed_width(code.n)
    _compile(jax.jit(gf2_matmul_packed), one_chip, ((64, w), U32),
             ((code.n_chk, w), U32))
