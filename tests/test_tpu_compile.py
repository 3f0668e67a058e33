"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

Interpret mode runs a kernel body as plain JAX, so it cannot see what the
chip's compiler refuses: block shapes off the (8, 128) tiling, primitives
Mosaic cannot lower, scalar reads from vector memory, VMEM overflow. Here
each kernel compiles with ``interpret=False`` for one chip of a described
``v5e:2x2`` topology (no chip attached), at the widths the main path
serves: smollm-360m's ``full()`` projections at a decode batch and one
prefill batch, the CAM lookup of the retrieval server's README deployment
and the coding server's default array code. The serving decode step
compiles whole at the decode-backlog cell's size, to show that it
updates the resident KV cache in place.

The topology is described inside a fixture, never at import time: only
the process that describes it loads the TPU compiler library.
"""
import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import load_arch
from repro.core.formats import packed_width
from repro.gf2.ldpc import make_array_ldpc
from repro.kernels.binary_mvp.kernel import binary_matmul_packed
from repro.kernels.bitserial_mvp.kernel import (
    MXU_KERNEL_NAME,
    bitserial_matmul_packed,
    bitserial_matmul_sliced,
)
from repro.kernels.bitserial_mvp.ops import plane_factors
from repro.kernels.gf2_tiled.kernel import gf2_matmul_packed
from repro.kernels.hamming_topk.kernel import hamming_topk_packed
from repro.models import lm
from repro.serve.step import (
    convert_params_for_serving,
    make_decode_select_step,
)

_CFG = load_arch("smollm_360m").full()
_D, _F = _CFG.d_model, _CFG.d_ff                    # 960, 2560
_QKV = (_CFG.n_heads + 2 * _CFG.n_kv_heads) * _CFG.head_dim   # 1600
# resident projections (in features, out rows) of one decoder layer, with
# the grouped wq|wk|wv and wi|wg containers the server builds
_PROJ = {"wqkv": (_D, _QKV), "wo": (_CFG.n_heads * _CFG.head_dim, _D),
         "wig": (_D, 2 * _F), "wd": (_F, _D)}
_DECODE_B, _PREFILL_B = 8, 4 * 256   # 8 slots; 4 prompts x 256 rows
_SLOTS_B = 64                        # the decode batch of 64 serving slots
_CACHE_ROWS = 2048                   # and each slot's cache rows
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


# a projection launch as the compiled module shows it: the int32 result,
# then the level-code stack and the resident plane stack, in that order
_LAUNCH = re.compile(r"%(\S+) = s32\[(\d+),(\d+)\]\S* custom-call\(.*"
                     r"custom_call_target=\"tpu_custom_call\"")
_U32_3D = re.compile(r"u32\[(\d+),(\d+),(\d+)\]")


@pytest.mark.parametrize("batch", [_DECODE_B, _SLOTS_B, _PREFILL_B])
@pytest.mark.parametrize("proj", sorted(_PROJ))
def test_bitserial_sliced_compiles(one_chip, proj, batch):
    """The decode fast path: level codes bit-sliced inside the kernel, on
    the vector-unit body (a plain plane-pair matrix) and on the MXU body
    (the int/int plane factors the server passes). The MXU launch keeps
    the signature a device trace finds projections by: one custom call,
    s32[rows, m] out, u32[32, rows, w] level codes then u32[k, m, w]
    planes in."""
    n, m = _PROJ[proj]
    w = packed_width(n)
    shapes = (((32, batch, w), U32), ((_K_BITS, m, w), U32),
              ((_K_BITS + 1, _L_BITS + 1), I32))
    kw = dict(l_bits=_L_BITS, pop_a=False, pop_x=False, const=False)
    _compile(jax.jit(functools.partial(bitserial_matmul_sliced, **kw)),
             one_chip, *shapes)
    factors = plane_factors("int", _K_BITS, "int", _L_BITS)
    text = _compile(jax.jit(functools.partial(
        bitserial_matmul_sliced, plane_factors=factors, **kw)),
        one_chip, *shapes).as_text()
    calls = [ln for ln in text.splitlines() if _LAUNCH.search(ln)]
    assert len(calls) == 1, calls
    name, rows, mp = _LAUNCH.search(calls[0]).groups()
    assert name.startswith(MXU_KERNEL_NAME)
    stack, planes = _U32_3D.findall(calls[0])[:2]
    assert int(rows) >= batch and int(mp) >= m
    assert stack == ("32", rows, planes[2])
    assert planes[:2] == (str(_K_BITS), mp) and int(planes[2]) >= w


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


# an instruction, the type of its result and its opcode
_INST = re.compile(r"^(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"\b[a-z]+\d+\[([\d,]*)\](\{[^}]*\})?")  # bf16[64,5]{1,0}
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$")
# opcodes that move data: anywhere, never on a whole layer's cache leaf
_MOVES = ("copy", "copy-start", "copy-done", "scatter", "transpose")
# opcodes of an unfused instruction that pass a buffer on without moving it
_PASS = ("parameter", "get-tuple-element", "tuple", "bitcast", "while",
         "conditional", "call")


def _holds_cache_layer(dims: str, layer) -> bool:
    """Whether a result shape holds a whole layer's cache leaf: every
    one of its dims, in any order (a relayout, a restack)."""
    have = [int(d) for d in dims.split(",") if d]
    for d in layer:
        if d not in have:
            return False
        have.remove(d)
    return True


def _instructions(text: str):
    """(unfused, result, opcode, line) of every instruction of an
    optimized program; an instruction is fused when its computation is
    a fusion's body."""
    fused = set(re.findall(r" fusion\(.*?calls=(%[\w.\-]+)", text))
    comp = None
    for ln in text.splitlines():
        if (c := _COMPUTATION.match(ln)):
            comp = c.group(1)
        elif (m := _INST.match(ln.strip())):
            yield comp not in fused, m.group(1), m.group(2), ln.strip()


def _cache_moves(text: str, layer, stack: str, resident: str):
    """Instructions that copy, transpose or scatter a whole layer's cache
    leaf (in any layout, fused or not), and unfused instructions that
    make a buffer holding one other than by passing a buffer on or by
    writing the ``stack`` in place in its ``resident`` layout."""
    out = []
    for unfused, result, op, ln in _instructions(text):
        held = [(dims, lay) for dims, lay in _SHAPE.findall(result)
                if _holds_cache_layer(dims, layer)]
        if not held or (unfused and op in _PASS):
            continue
        in_place = (op in ("fusion", "dynamic-update-slice")
                    and all(h == (stack, resident) for h in held))
        if op in _MOVES or (unfused and not in_place):
            out.append(ln[:160])
    return out


def test_decode_step_updates_cache_in_place(one_chip):
    """The serving decode step for smollm-360m at its published widths,
    64 slots of 2048 rows, a bf16 cache and 4-bit served projections
    reads and writes the donated cache where it lies: temporaries under
    one layer's K leaf; no copy, transpose or scatter of a layer's cache
    leaf (in any layout, fused or not) anywhere in the optimized
    program, and no unfused instruction that makes a buffer holding one
    but the in-place write of the stack in its resident layout; every
    cache argument aliased to an output."""
    cfg = dataclasses.replace(
        _CFG, dtype="bfloat16", kv_dtype="bfloat16",
        ppac=dataclasses.replace(_CFG.ppac, enabled=True,
                                 weight_bits=_K_BITS, act_bits=_L_BITS,
                                 weight_format="int", act_format="int",
                                 min_features=32, backend="pallas"))
    params = jax.eval_shape(lambda k: convert_params_for_serving(
        lm.init(cfg, k)[0], cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: lm.init_cache(cfg, _SLOTS_B, _CACHE_ROWS)[0])
    on_chip = functools.partial(jax.tree.map, lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip))
    compiled = make_decode_select_step(cfg, mode="serve").lower(
        on_chip(params), *on_chip((
            jax.ShapeDtypeStruct((_SLOTS_B, 1), I32), cache,
            jax.ShapeDtypeStruct((2,), U32)))).compile()
    k = cache["layers"]["k"]
    layer_bytes = math.prod(k.shape[1:]) * k.dtype.itemsize   # 83.9 MB
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < layer_bytes, (temp, layer_bytes)
    text = compiled.as_text()
    entry = next(ln for ln in text.splitlines() if ln.startswith("ENTRY"))
    stack = ",".join(map(str, k.shape))
    resident = {lay for _, res, op, _ in _instructions(text)
                if op == "parameter" for dims, lay in _SHAPE.findall(res)
                if dims == stack}
    assert len(resident) == 1, resident
    moves = _cache_moves(text, k.shape[1:], stack, resident.pop())
    assert not moves, moves
    aliased = {int(i) for i in re.findall(
        r"\{[\d,]*\}: \((\d+),", text.split("\n", 1)[0])}
    names = re.findall(r"[(,] ?([\w.]+): ", entry)
    cached = [i for i, n in enumerate(names) if n.startswith("cache")]
    assert len(cached) == len(jax.tree.leaves(cache)), names
    assert set(cached) <= aliased, (cached, aliased)
