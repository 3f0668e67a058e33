"""Pallas kernels vs pure-jnp oracles: shape/dtype/format sweeps + hypothesis.

All kernels run in interpret mode on CPU (TPU is the lowering target);
results must be bit-exact (integer arithmetic — the property the paper
claims over mixed-signal PIM)."""
import numpy as np
import pytest
from conftest import given, settings, st  # hypothesis or skip-shim

from repro.core import formats as F
from repro.kernels.binary_mvp.kernel import binary_matmul_packed
from repro.kernels.binary_mvp.ops import (
    and_dot,
    cam_match,
    gf2_matmul,
    hamming_similarity,
    inner_product_pm1,
    pla_eval,
)
from repro.kernels.binary_mvp.ref import binary_matmul_packed_ref
from repro.kernels.bitserial_mvp.kernel import bitserial_matmul_packed
from repro.kernels.bitserial_mvp.ops import build_planes_and_weights, ppac_matmul
from repro.kernels.bitserial_mvp.ref import bitserial_matmul_packed_ref


@pytest.mark.parametrize("b,m,n", [(1, 1, 1), (3, 5, 7), (8, 16, 32),
                                   (9, 33, 100), (64, 128, 256),
                                   (17, 130, 513)])
@pytest.mark.parametrize("op", ["xor", "and"])
def test_binary_kernel_shapes(rng, b, m, n, op):
    x = F.pack_bits(rng.integers(0, 2, (b, n)))
    a = F.pack_bits(rng.integers(0, 2, (m, n)))
    got = np.asarray(binary_matmul_packed(x, a, op=op, interpret=True))
    ref = np.asarray(binary_matmul_packed_ref(x, a, op=op))
    assert np.array_equal(got, ref)


@pytest.mark.parametrize("blocks", [(8, 8, 128), (16, 32, 128),
                                    (64, 128, 256)])
def test_binary_kernel_block_sweep(rng, blocks):
    bb, bm, bw = blocks
    x = F.pack_bits(rng.integers(0, 2, (21, 300)))
    a = F.pack_bits(rng.integers(0, 2, (50, 300)))
    got = np.asarray(binary_matmul_packed(
        x, a, op="xor", block_b=bb, block_m=bm, block_w=bw, interpret=True))
    ref = np.asarray(binary_matmul_packed_ref(x, a, op="xor"))
    assert np.array_equal(got, ref)


@pytest.mark.parametrize("backend", ["pallas", "ref", "mxu"])
def test_mode_ops_vs_ground_truth(rng, backend):
    b, m, n = 5, 24, 70
    xb = rng.integers(0, 2, (b, n))
    ab = rng.integers(0, 2, (m, n))
    xp, ap = F.pack_bits(xb), F.pack_bits(ab)
    hs = np.asarray(hamming_similarity(xp, ap, n=n, backend=backend))
    assert np.array_equal(hs, (xb[:, None, :] == ab[None, :, :]).sum(-1))
    ip = np.asarray(inner_product_pm1(xp, ap, n=n, backend=backend))
    assert np.array_equal(ip, (2 * xb - 1) @ (2 * ab - 1).T)
    ad = np.asarray(and_dot(xp, ap, n=n, backend=backend))
    assert np.array_equal(ad, xb @ ab.T)
    g2 = np.asarray(gf2_matmul(xp, ap, n=n, backend=backend))
    assert np.array_equal(g2, (xb @ ab.T) % 2)


def test_cam_and_pla_ops(rng):
    n = 64
    ab = rng.integers(0, 2, (32, n))
    x = ab[3:4].copy()
    xp, ap = F.pack_bits(x), F.pack_bits(ab)
    match = np.asarray(cam_match(xp, ap, n=n))
    assert match[0, 3]
    # PLA: row 0 of bank 0 = AND of first 4 variables
    a2 = np.zeros((16, n), np.uint8)
    a2[0, :4] = 1
    nvars = np.full((16,), n + 1, np.int32)
    nvars[0] = 4
    x_on = np.zeros((1, n), np.uint8)
    x_on[0, :4] = 1
    out = np.asarray(pla_eval(F.pack_bits(x_on), F.pack_bits(a2), nvars, n=n))
    assert out[0, 0] == 1
    x_off = x_on.copy()
    x_off[0, 0] = 0
    out = np.asarray(pla_eval(F.pack_bits(x_off), F.pack_bits(a2), nvars, n=n))
    assert out[0, 0] == 0


@pytest.mark.parametrize("fmt_a", ["uint", "int", "oddint"])
@pytest.mark.parametrize("fmt_x", ["uint", "int", "oddint"])
@pytest.mark.parametrize("backend", ["pallas", "ref", "mxu"])
def test_ppac_matmul_formats(rng, fmt_a, fmt_x, backend):
    k, l, b, m, n = 4, 3, 4, 20, 40
    la, ha = F.value_range(fmt_a, k)
    lx, hx = F.value_range(fmt_x, l)
    a = rng.choice(np.arange(la, ha + 1, 2 if fmt_a == "oddint" else 1),
                   size=(m, n))
    x = rng.choice(np.arange(lx, hx + 1, 2 if fmt_x == "oddint" else 1),
                   size=(b, n))
    got = np.asarray(ppac_matmul(x, a, k_bits=k, l_bits=l, fmt_a=fmt_a,
                                 fmt_x=fmt_x, backend=backend))
    assert np.array_equal(got, x @ a.T), (fmt_a, fmt_x, backend)


def test_bitserial_kernel_vs_ref(rng):
    xp = rng.integers(0, 2**32, (3, 6, 4), dtype=np.uint32)
    ap = rng.integers(0, 2**32, (2, 10, 4), dtype=np.uint32)
    w = rng.integers(-8, 8, (2, 3)).astype(np.int32)
    got = np.asarray(bitserial_matmul_packed(xp, ap, w, interpret=True))
    ref = np.asarray(bitserial_matmul_packed_ref(xp, ap, w))
    assert np.array_equal(got, ref)


@given(st.integers(1, 4), st.integers(1, 4), st.integers(1, 12),
       st.integers(1, 24), st.integers(1, 66),
       st.sampled_from(["uint", "int", "oddint"]),
       st.sampled_from(["uint", "int", "oddint"]),
       st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_ppac_matmul_hypothesis(k, l, b, m, n, fmt_a, fmt_x, seed):
    rng = np.random.default_rng(seed)
    la, ha = F.value_range(fmt_a, k)
    lx, hx = F.value_range(fmt_x, l)
    a = rng.choice(np.arange(la, ha + 1, 2 if fmt_a == "oddint" else 1),
                   size=(m, n))
    x = rng.choice(np.arange(lx, hx + 1, 2 if fmt_x == "oddint" else 1),
                   size=(b, n))
    got = np.asarray(ppac_matmul(x, a, k_bits=k, l_bits=l, fmt_a=fmt_a,
                                 fmt_x=fmt_x, backend="ref"))
    assert np.array_equal(got, x @ a.T)


@given(st.integers(1, 40), st.integers(1, 40), st.integers(1, 129),
       st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_binary_kernel_hypothesis(b, m, n, seed):
    rng = np.random.default_rng(seed)
    xb = rng.integers(0, 2, (b, n))
    ab = rng.integers(0, 2, (m, n))
    xp, ap = F.pack_bits(xb), F.pack_bits(ab)
    got = np.asarray(binary_matmul_packed(xp, ap, op="xor", interpret=True))
    assert np.array_equal(got, (xb[:, None, :] ^ ab[None, :, :]).sum(-1))


def test_plane_weight_construction_offsets(rng):
    """oddint offsets fold into the extended weight matrix (eqs. 2/3
    analogue as in-kernel popcount coefficients + a constant) — the
    operands themselves never grow mask planes (zero-repack invariant)."""
    n = 10
    x = rng.choice([-3, -1, 1, 3], size=(2, n))
    a = rng.choice([-3, -1, 1, 3], size=(4, n))
    xp, ap, w, (pop_a, pop_x, const) = build_planes_and_weights(
        x, a, 2, 2, "oddint", "oddint")
    assert xp.shape[0] == 2 and ap.shape[0] == 2  # value planes only
    assert w.shape == (3, 3)                      # extended [K+1, L+1]
    assert pop_a and pop_x and const
    # oddint(2): w_l = {2, 4}, c = -3  ->  corner = c*c*n
    assert int(w[2, 2]) == 9 * n
    assert np.array_equal(np.asarray(w[:2, 2]), [-6, -12])  # wa_k * cx
    assert np.array_equal(np.asarray(w[2, :2]), [-6, -12])  # ca * wx_l
