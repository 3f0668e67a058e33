"""LDPC encode + iterative bit-flipping decode on PPAC GF(2)/and-dot ops.

Forward error correction is the paper's second §III-D workload: syndrome
computation s = H·c is a GF(2) MVP, and the inner step of a Gallager
bit-flipping decoder — counting, per code bit, how many unsatisfied checks
it participates in — is an integer and-dot (mode III-B2) of the syndrome
against Hᵀ.  Both run as PPAC array operations here, with per-iteration
emulated-cycle accounting priced by the geometry rules of
``core.cost_model`` / ``gf2.ops.gf2_cycles``, plus the §IV-B
compute-cache baseline (``cycles_compute_cache_inner_product``) for the
same work.

Codes
-----
* :func:`make_random_ldpc` — random sparse H = [P | L] with L
  unit-lower-triangular (always invertible over GF(2)); systematic.
* :func:`make_array_ldpc` — the r×c array (product) code: one parity
  check per grid row and per grid column.  Every bit lies in exactly 2
  checks (γ=2) and any two bits share at most one check (λ=1), so
  bit-flipping provably corrects t = ⌊γ/2λ⌋ = 1 error per word in one
  iteration; the decode matrix keeps the one redundant check on purpose
  (majority-logic decoding wants the full orthogonal check set), while
  encoding uses the full-rank triangular subset.

Encoding is systematic: c = [m, p] with L·p = P·m, solved once at code
construction by forward substitution on the unit-lower-triangular L
(host-side setup, like loading the latch array), after which every encode
is a single PPAC GF(2) MVP p = (L⁻¹P)·m.

Decoding flips every bit whose unsatisfied-check count passes a strict
per-bit majority, 2·votes > γ_j, and stops early (per word) as soon as
the syndrome clears: a cleared word has zero votes everywhere, so extra
iterations are natural no-ops and the fixed-trip-count jax loop stays
bit-identical to an early-exit host loop — and to the row-sharded
``shard_map`` path in ``gf2.sharded``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.cost_model import est_latency_us
from ..core.formats import pack_bits, unpack_bits
from ..core.ppac import CycleCounter, PPACConfig, cycles_compute_cache_inner_product
from ..kernels.binary_mvp.ops import and_dot
from ..kernels.gf2_tiled.ops import gf2_matmul_tiled
from .ops import gf2_cycles, resolve_backend


def solve_unit_lower(l_mat, rhs) -> np.ndarray:
    """Solve L·X = B over GF(2) for unit-lower-triangular L by forward
    substitution.  l_mat [p, p], rhs [p, q] -> X [p, q]."""
    l_mat = np.asarray(l_mat, np.uint8)
    x = np.array(np.atleast_2d(np.asarray(rhs, np.uint8)) % 2)
    p = l_mat.shape[0]
    assert l_mat.shape == (p, p) and np.all(np.diag(l_mat) == 1)
    assert not np.any(np.triu(l_mat, 1)), "L must be lower-triangular"
    for i in range(p):
        # x[i] -= L[i, :i] @ x[:i]  (over GF(2))
        if i:
            x[i] ^= (l_mat[i, :i] @ x[:i]) % 2
    return x


@dataclasses.dataclass(frozen=True)
class LDPCCode:
    """A binary linear code with a systematic encoder and a decode matrix.

    ``h`` is the parity-check matrix used for decoding (it may carry
    redundant rows — majority-logic decoding wants every orthogonal
    check).  ``h_enc`` = [P | L] is a full-rank subset with L
    unit-lower-triangular over the last n-k columns, used for encoding.
    """

    h: np.ndarray        # [n_chk, n] uint8
    h_enc: np.ndarray    # [n - k, n] uint8
    k: int
    gen_parity: np.ndarray = dataclasses.field(init=False)  # [n-k, k]

    def __post_init__(self):
        n = self.h.shape[1]
        r = n - self.k
        assert self.h_enc.shape == (r, n), (self.h_enc.shape, r, n)
        p_part = self.h_enc[:, : self.k]
        l_part = self.h_enc[:, self.k:]
        gen = solve_unit_lower(l_part, p_part)     # L⁻¹ P, [r, k]
        object.__setattr__(self, "gen_parity", gen.astype(np.uint8))
        # every h_enc row must be in h's row space for decode to accept
        # encoded words; we require the stronger (and simpler) subset check
        hs = {r_.tobytes() for r_ in np.asarray(self.h, np.uint8)}
        assert all(r_.tobytes() in hs for r_ in self.h_enc), \
            "h_enc rows must appear among the decode checks h"

    @property
    def n(self) -> int:
        return self.h.shape[1]

    @property
    def n_chk(self) -> int:
        return self.h.shape[0]

    @property
    def rate(self) -> float:
        return self.k / self.n

    @property
    def col_weight(self) -> np.ndarray:
        """γ_j: number of decode checks each bit participates in."""
        return np.asarray(self.h, np.int64).sum(axis=0)

    @property
    def max_overlap(self) -> int:
        """λ: max number of checks shared by any two distinct bits."""
        ov = np.asarray(self.h, np.int64).T @ np.asarray(self.h, np.int64)
        np.fill_diagonal(ov, 0)
        return int(ov.max())

    @property
    def guaranteed_t(self) -> int:
        """Errors per word the majority bit-flip rule provably corrects
        (in one iteration): ⌊γ_min / 2λ⌋ — see the decode analysis in the
        module docstring."""
        lam = max(1, self.max_overlap)
        return int(self.col_weight.min()) // (2 * lam)

    def encode(self, msgs, *, backend: str = "auto",
               counter: Optional[CycleCounter] = None,
               config: Optional[PPACConfig] = None) -> np.ndarray:
        """Systematic encode [B, k] -> [B, n]: c = [m, (L⁻¹P)·m]."""
        msgs = np.atleast_2d(np.asarray(msgs, np.uint8))
        assert msgs.shape[1] == self.k, (msgs.shape, self.k)
        parity = gf2_matmul_tiled(pack_bits(msgs), pack_bits(self.gen_parity),
                                  n=self.k, backend=resolve_backend(backend))
        if counter is not None:
            counter.tick(gf2_cycles(msgs.shape[0], self.n - self.k, self.k,
                                    config) + counter.pipeline_latency)
        return np.concatenate([msgs, np.asarray(parity, np.uint8)], axis=1)

    def syndrome(self, words, *, backend: str = "auto") -> np.ndarray:
        """s = H·c over GF(2): [B, n] -> [B, n_chk]."""
        words = np.atleast_2d(np.asarray(words, np.uint8))
        return np.asarray(gf2_matmul_tiled(
            pack_bits(words), pack_bits(self.h), n=self.n,
            backend=resolve_backend(backend)))


def make_random_ldpc(n: int, k: int, *, rng, col_weight: int = 3,
                     lower_density: float = 0.1) -> LDPCCode:
    """Random sparse systematic code: H = [P | L], P with fixed column
    weight, L = I ⊕ sparse strict-lower.  Decode matrix = encode matrix."""
    r = n - k
    assert 0 < k < n and col_weight <= r
    p = np.zeros((r, k), np.uint8)
    for j in range(k):
        p[rng.choice(r, size=col_weight, replace=False), j] = 1
    l_mat = (np.tril((rng.random((r, r)) < lower_density), -1)
             | np.eye(r, dtype=bool)).astype(np.uint8)
    h = np.concatenate([p, l_mat], axis=1)
    return LDPCCode(h=h, h_enc=h, k=k)


def make_array_ldpc(r: int, c: int) -> LDPCCode:
    """The r×c array code: bits on a grid, checks = row + column parities.

    Bit order: interior (message, row-major, (r-1)(c-1) bits), then the
    last-column parities (r-1), last-row parities (c-1), and the corner.
    Decode matrix: all r+c grid checks (γ=2, λ=1 ⇒ guaranteed_t = 1);
    encode matrix: the r+c-1 independent checks, which in this bit order
    are exactly [P | L] with L unit-lower-triangular.
    """
    assert r >= 2 and c >= 2
    n = r * c
    k = (r - 1) * (c - 1)

    def bit(i: int, j: int) -> int:
        """Grid position -> systematic bit index."""
        if i < r - 1 and j < c - 1:
            return i * (c - 1) + j                       # interior
        if j == c - 1 and i < r - 1:
            return k + i                                 # last col
        if i == r - 1 and j < c - 1:
            return k + (r - 1) + j                       # last row
        return n - 1                                     # corner

    h = np.zeros((r + c, n), np.uint8)
    for i in range(r):
        for j in range(c):
            h[i, bit(i, j)] = 1          # row checks
            h[r + j, bit(i, j)] = 1      # column checks
    # independent subset in triangular order: rows 0..r-2, cols 0..c-2,
    # then the last row check (corner on the diagonal)
    h_enc = np.concatenate(
        [h[: r - 1], h[r: r + c - 1], h[r - 1: r]], axis=0)
    return LDPCCode(h=h, h_enc=h_enc, k=k)


# ---------------------------------------------------------------------------
# Bit-flipping decoder
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DecodeResult:
    """Decoded words plus the emulated hardware cost of producing them."""

    codewords: np.ndarray   # [B, n] uint8 (best-effort when not ok)
    ok: np.ndarray          # [B] bool: syndrome cleared
    iters: np.ndarray       # [B] int32: flip iterations until clean
    k: int
    stats: Dict[str, float]

    @property
    def msgs(self) -> np.ndarray:
        """Systematic message bits of the decoded words."""
        return self.codewords[:, : self.k]


@functools.partial(jax.jit,
                   static_argnames=("n", "n_chk", "max_iters", "backend"))
def bitflip_decode_packed(y_packed, h_packed, ht_packed, gamma, *, n: int,
                          n_chk: int, max_iters: int, backend: str):
    """Fixed-trip-count bit-flip decode on packed words [B, W].

    Per iteration: syndrome s = H·c (GF(2) MVP), votes v = Hᵀ·s (integer
    and-dot), flip all bits with 2·v_j > γ_j.  Words whose syndrome is
    already clear have zero votes and never flip — iterating past
    convergence is the identity, which is what makes this loop
    shard-order- and batch-composition-invariant.
    Returns (c_packed, ok [B] bool, iters [B] int32).
    """
    b = y_packed.shape[0]
    gamma = jnp.asarray(gamma, jnp.int32)

    def syndrome(c):
        return gf2_matmul_tiled(c, h_packed, n=n, backend=backend)

    def step(t, carry):
        c, iters = carry
        syn = syndrome(c)                                        # [B, n_chk]
        clean = jnp.sum(syn.astype(jnp.int32), axis=1) == 0
        iters = jnp.where(clean, jnp.minimum(iters, t), iters)
        votes = and_dot(pack_bits(syn), ht_packed, n=n_chk,
                        backend=backend)                         # [B, n]
        flip = (2 * votes > gamma[None, :]).astype(jnp.uint8)
        return c ^ pack_bits(flip), iters

    init = (jnp.asarray(y_packed, jnp.uint32),
            jnp.full((b,), max_iters, jnp.int32))
    c, iters = lax.fori_loop(0, max_iters, step, init)
    ok = jnp.sum(syndrome(c).astype(jnp.int32), axis=1) == 0
    iters = jnp.where(ok, jnp.minimum(iters, max_iters), max_iters)
    return c, ok, iters


class BitFlipDecoder:
    """Batched LDPC bit-flip decoder with emulated PPAC cycle accounting."""

    def __init__(self, code: LDPCCode, *,
                 config: Optional[PPACConfig] = None,
                 backend: str = "auto", max_iters: int = 20,
                 parallel_arrays: Optional[int] = None):
        self.code = code
        self.config = config or PPACConfig()
        self.backend = resolve_backend(backend)
        self.max_iters = max_iters
        self.parallel_arrays = parallel_arrays
        self.counter = CycleCounter()
        self._h_packed = jnp.asarray(pack_bits(code.h))
        self._ht_packed = jnp.asarray(pack_bits(code.h.T))
        self._gamma = jnp.asarray(code.col_weight, jnp.int32)

    # -- cycle model ---------------------------------------------------------

    def cycles_per_word_iteration(self) -> int:
        """One decode iteration of one word: syndrome MVP (H, XOR-tree
        merge) + vote and-dot (Hᵀ, adder-tree merge).  The flip decision is
        the row ALU's threshold comparison and is free, like the CAM sign
        bit."""
        code, cfg, pa = self.code, self.config, self.parallel_arrays
        syn = gf2_cycles(1, code.n_chk, code.n, cfg, pa)
        votes = gf2_cycles(1, code.n, code.n_chk, cfg, pa)
        return syn + votes

    def compute_cache_cycles_per_word_iteration(self) -> int:
        """The same iteration under the §IV-B compute-cache model [3,4]:
        one N-dim 1-bit inner product per matrix, rows in parallel."""
        code = self.code
        return (cycles_compute_cache_inner_product(1, code.n)
                + cycles_compute_cache_inner_product(1, code.n_chk))

    def _stats(self, b: int, iters_exec: int, shards: int) -> Dict[str, float]:
        cpwi = self.cycles_per_word_iteration()
        total = b * iters_exec * cpwi + self.counter.pipeline_latency
        self.counter.tick(total)
        cc = b * iters_exec * self.compute_cache_cycles_per_word_iteration()
        stats = dict(words=b, iterations=iters_exec,
                     cycles_per_word_iteration=cpwi, total_cycles=total,
                     compute_cache_cycles=cc,
                     speedup_vs_compute_cache=cc / total if total else 0.0,
                     shards=shards, backend=self.backend)
        lat = est_latency_us(total, self.config, shards)
        if lat is not None:
            stats["est_latency_us"] = lat
        return stats

    # -- decode --------------------------------------------------------------

    def decode(self, words=None, *, words_packed=None, mesh=None,
               shard_axis: str = "data") -> DecodeResult:
        """Decode noisy words [B, n] {0,1} (or packed [B, W] uint32).

        With a ``mesh``, the block of codewords row-shards over
        ``shard_axis`` (each device decodes its rows; H replicated) —
        bit-identical to the single-device path.
        """
        code = self.code
        if words_packed is not None:
            y = jnp.asarray(words_packed, jnp.uint32)
        else:
            wb = np.atleast_2d(np.asarray(words, np.uint8))
            assert wb.shape[1] == code.n, (wb.shape, code.n)
            y = jnp.asarray(pack_bits(wb))
        b = y.shape[0]

        if mesh is None:
            shards = 1
            c, ok, iters = bitflip_decode_packed(
                y, self._h_packed, self._ht_packed, self._gamma,
                n=code.n, n_chk=code.n_chk, max_iters=self.max_iters,
                backend=self.backend)
        else:
            from .sharded import sharded_bitflip_decode

            shards = int(mesh.shape[shard_axis])
            pad = (-b) % shards
            if pad:  # repeat the tail word to a shardable multiple
                y = jnp.concatenate([y, jnp.repeat(y[-1:], pad, axis=0)])
            c, ok, iters = sharded_bitflip_decode(
                y, self._h_packed, self._ht_packed, self._gamma,
                n=code.n, n_chk=code.n_chk, max_iters=self.max_iters,
                backend=self.backend, mesh=mesh, axis=shard_axis)
            # the outputs are sharded over the mesh: crop the tail padding
            # on the host
            c, ok, iters = (np.asarray(t)[:b] for t in (c, ok, iters))

        ok = np.asarray(ok)
        iters = np.asarray(iters, np.int32)
        iters_exec = int(iters.max()) if b else 0
        stats = self._stats(b, iters_exec, shards)
        return DecodeResult(
            codewords=np.asarray(unpack_bits(c, code.n), np.uint8),
            ok=ok, iters=iters, k=code.k, stats=stats)


def bsc_flip(codewords, n_errors: int, rng) -> np.ndarray:
    """Flip exactly ``n_errors`` distinct random bits per word (a worst-case
    binary symmetric channel draw)."""
    out = np.array(np.atleast_2d(np.asarray(codewords, np.uint8)))
    for row in out:
        if n_errors:
            row[rng.choice(out.shape[1], size=n_errors, replace=False)] ^= 1
    return out
