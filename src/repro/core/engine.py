"""PPAC engine: the paper's technique as a first-class projection substrate.

A ``PPACLinear`` projection can run in three regimes:

  * ``float``  — plain bf16 matmul (baseline path).
  * ``qat``    — training-time fake quantization into the PPAC number
                 formats (Table I) with straight-through gradients; the
                 network learns weights executable on the PPAC engine.
  * ``serve``  — weights are *stored* quantized (the PPAC premise: the
                 matrix A is resident in low precision while vectors
                 stream, §IV-A) and the matmul is exact integer arithmetic.

Serving weight containers (memory-roofline lever, see EXPERIMENTS.md §Perf):

  bf16     : [in, out] bf16                       (baseline)
  int8     : [in, out] int8 + scale               (K<=8; MXU dot)
  packed4  : [K1, out, in/32] uint32 bitplanes    (K<=4; fused bit-serial
             kernel — the resident layout IS the kernel operand; offset
             formats store their all-ones mask plane as the K+1-th plane)
  packed1  : [out, in/32] uint32 bitplanes        (K=1; ±1 plane)

The zero-repack invariant: everything a lowering consumes is materialized
ONCE at load time ("writing the latch array") and a serving call only
streams activations. The packed kinds execute through the unified kernel
engine's ``mvp_multibit_resident`` mode — activations are bit-sliced
*inside* the Pallas body; nothing is ever concatenated onto or broadcast
over the resident planes at call time. Off-TPU, the MXU lowering consumes
an int8 *shadow* of the same integers, also built at load time (the
per-lowering analogue of loading the array), so no backend unpacks the
resident weight per call. All integer paths are bit-true (int32
accumulation) — the property the paper holds over mixed-signal PIM
(§III-D) — and bit-identical across the 'pallas'/'ref'/'mxu' backends.

Grouped containers (``splits``) stack several projections that share an
input (wq/wk/wv, wi/wg) column-wise into ONE resident container; per-
output-channel quantization makes the stacked container bit-identical to
the per-projection ones, while a decode step launches one fat kernel per
group instead of one per projection (``serve_dense_grouped``).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from ..kernels.engine import ppac_matmul
from ..obs import ledger as _flight
from .formats import fmt as _fmt
from .formats import pack_bits, to_bitplanes
from .quant import binarize_levels, binarize_pm1, fake_quant, quantize


@jax.tree_util.register_pytree_node_class
class QuantContainer:
    """Resident quantized weight: arrays are pytree children; ``kind`` plus
    the quantization metadata (``bits``, ``fmt``, logical ``n_in``, the
    grouped-projection ``splits``) are static aux data, so jit specializes
    on the container format. ``shadow`` is the optional load-time int8
    resident for the MXU lowering (None on TPU, where the packed planes
    are the native operand).

    A container may additionally carry a resident *draft rung*: a packed1
    view of the same logical weight (``dwq``/``dscale``/``dshadow``),
    built once at load time alongside the target rung. The draft rung is
    what self-speculative decoding drafts with — same weights, 1-bit
    bit-serial cost — and :meth:`draft_view` exposes it as an ordinary
    packed1 container so every serving path prices and executes it
    exactly like a standalone 1-bit conversion."""

    def __init__(self, kind: str, wq, scale, *, bits: Optional[int] = None,
                 fmt: Optional[str] = None, n_in: Optional[int] = None,
                 shadow=None, splits: Optional[Tuple[int, ...]] = None,
                 dwq=None, dscale=None, dshadow=None):
        self.kind = kind
        self.wq = wq
        self.scale = scale
        self.bits = bits
        self.fmt = fmt
        self.n_in = n_in
        self.shadow = shadow
        self.splits = tuple(splits) if splits else None
        self.dwq = dwq
        self.dscale = dscale
        self.dshadow = dshadow

    def tree_flatten(self):
        return ((self.wq, self.scale, self.shadow, self.dwq, self.dscale,
                 self.dshadow),
                (self.kind, self.bits, self.fmt, self.n_in, self.splits))

    @classmethod
    def tree_unflatten(cls, aux, children):
        kind, bits, fmt, n_in, splits = aux
        wq, scale, shadow, dwq, dscale, dshadow = children
        return cls(kind, wq, scale, bits=bits, fmt=fmt, n_in=n_in,
                   shadow=shadow, splits=splits, dwq=dwq, dscale=dscale,
                   dshadow=dshadow)

    def with_children(self, wq, scale, shadow=None, dwq=None, dscale=None,
                      dshadow=None) -> "QuantContainer":
        """Same kind/metadata, different payloads (sharding specs etc.)."""
        return QuantContainer(self.kind, wq, scale, bits=self.bits,
                              fmt=self.fmt, n_in=self.n_in, shadow=shadow,
                              splits=self.splits, dwq=dwq, dscale=dscale,
                              dshadow=dshadow)

    @property
    def has_draft(self) -> bool:
        return self.dwq is not None

    def draft_view(self) -> "QuantContainer":
        """The resident packed1 rung as a standalone container.

        Falls back to the container itself when no draft rung was packed
        (packed1 already IS the cheapest rung; a draft-less container
        drafts with the target, making the drafter exact).
        """
        if self.dwq is None:
            return self
        return QuantContainer("packed1", self.dwq, self.dscale, bits=1,
                              fmt="pm1", n_in=self.n_in, shadow=self.dshadow,
                              splits=self.splits)

    def __repr__(self):
        return (f"QuantContainer({self.kind}, bits={self.bits}, "
                f"wq={getattr(self.wq, 'shape', None)}"
                + (f", splits={self.splits}" if self.splits else "")
                + (", shadow" if self.shadow is not None else "")
                + (", draft" if self.dwq is not None else "") + ")")


def qat_dense(x, w, *, weight_bits: int, act_bits: int,
              weight_format: str = "int", act_format: str = "int"):
    """Fake-quantized matmul with STE gradients (training path)."""
    if weight_bits == 1:
        wq, ws = binarize_pm1(w.astype(jnp.float32), axis=0)
        wq = wq * ws
    else:
        wq = fake_quant(w.astype(jnp.float32), weight_bits, weight_format, axis=0)
    xq = fake_quant(x.astype(jnp.float32), act_bits, act_format, axis=-1)
    return jnp.einsum("...i,io->...o", xq, wq).astype(x.dtype)


def _row_major(tree):
    """Pin every array of ``tree`` to the row-major layout."""
    return jax.tree.map(lambda a: with_layout_constraint(
        a, Layout(tuple(range(a.ndim)))), tree)


def _want_shadow(store_shadow: Optional[bool]) -> bool:
    """Shadow policy: explicit wins; default stores the int8 resident only
    off-TPU (on TPU the packed planes are what the kernels eat)."""
    if store_shadow is not None:
        return store_shadow
    return jax.default_backend() != "tpu"


def _format_has_offset(weight_format: str) -> bool:
    from ..kernels.bitserial_mvp.ops import format_needs_mask
    return format_needs_mask(_fmt(weight_format))


def _pack_pm1(w, store_shadow: Optional[bool]):
    """One ±1 bitplane of a float [in, out] weight: (packed [out, in/32]
    u32, scale [out], optional int8 shadow [in, out])."""
    levels, q, s = binarize_levels(w, axis=0)
    packed = pack_bits(levels.T)
    shadow = q.astype(jnp.int8) if _want_shadow(store_shadow) else None
    return packed, s[0], shadow


def pack_weight_for_serving(w, *, weight_bits: int,
                            weight_format: str = "int",
                            splits: Optional[Sequence[int]] = None,
                            store_shadow: Optional[bool] = None,
                            draft: bool = False) -> QuantContainer:
    """Offline conversion of a float [in, out] weight to a resident
    quantized container (run once at model load, like writing the PPAC
    latch array).

    1-bit weights become one packed ±1 plane; 2..4-bit weights become K
    packed logical bitplanes [K, out, in/32] — the exact operand layout of
    the fused bit-serial kernel — plus a constant all-ones mask plane when
    the format carries an affine offset (oddint), so the serving kernels
    never synthesize one at call time. Off-TPU an int8 shadow of the same
    integers is stored for the MXU lowering (zero per-call unpacking on
    every backend). 5..8 bits fall back to int8 rows (MXU dot); wider
    requests keep bf16. ``splits`` records grouped-projection output
    widths (see ``serve_dense_grouped``).

    ``draft=True`` additionally packs the 1-bit rung of the SAME weight
    into the container's draft slots (``dwq``/``dscale``/``dshadow``) —
    bit-identical to a standalone ``weight_bits=1`` conversion — so
    self-speculative decoding drafts from the resident container with no
    re-conversion and no second model.
    """
    n_in = w.shape[0]
    splits = tuple(splits) if splits else None
    w = w.astype(jnp.float32)
    draft_kw = {}
    if draft and weight_bits > 1:
        dwq, dscale, dshadow = _pack_pm1(w, store_shadow)
        draft_kw = dict(dwq=dwq, dscale=dscale, dshadow=dshadow)
    if weight_bits == 1:
        packed, s0, shadow = _pack_pm1(w, store_shadow)  # [out, in/32] u32
        return QuantContainer("packed1", packed, s0, bits=1, fmt="pm1",
                              n_in=n_in, shadow=shadow, splits=splits)
    if weight_bits > 8:
        return QuantContainer("bf16", w.astype(jnp.bfloat16),
                              jnp.ones((w.shape[1],), jnp.float32),
                              bits=16, fmt="float", n_in=n_in, splits=splits,
                              **draft_kw)
    q, s = quantize(w, weight_bits, weight_format, axis=0)  # s [1, out]
    if weight_bits <= 4:
        a_int = q.T.astype(jnp.int32)               # [out, in] exact ints
        planes = to_bitplanes(a_int, weight_bits, weight_format)
        if _format_has_offset(weight_format):
            # resident all-ones mask plane: the affine-offset cross terms
            # (eqs. (2)/(3) generalized) ride an ordinary K+1-th plane
            # instead of a per-call concatenation
            mask = jnp.ones((1,) + a_int.shape, jnp.uint8)
            planes = jnp.concatenate([planes, mask], axis=0)
        packed = pack_bits(planes)                  # [K1, out, in/32] u32
        shadow = q.astype(jnp.int8) if _want_shadow(store_shadow) else None
        return QuantContainer("packed4", packed, s[0], bits=weight_bits,
                              fmt=weight_format, n_in=n_in, shadow=shadow,
                              splits=splits, **draft_kw)
    return QuantContainer("int8", q.astype(jnp.int8), s[0], bits=weight_bits,
                          fmt=weight_format, n_in=n_in, splits=splits,
                          **draft_kw)


def serve_dense_acc(xf, container: QuantContainer, *, act_bits: int,
                    act_format: str = "int", backend: str = "mxu"):
    """Exact integer accumulations for a packed/int container.

    xf: [B, in] float32 activations. Returns (acc [B, out] int32,
    act_scale [B, 1] float32) — the raw PPAC row-ALU results before
    dequantization, bit-identical across backends for the packed kinds.
    Packed kinds run the zero-repack resident mode: in-kernel activation
    bit-slicing on 'pallas', the load-time int8 shadow on 'mxu'.
    """
    kind = container.kind
    n = xf.shape[-1]

    def resident_mvp(xq, xs, planes, **kw):
        # The integer region is fenced: optimization barriers stop fusion
        # across it and row-major layout constraints stop layout choices
        # from leaking out of it. XLA otherwise fuses and lays out the
        # float program around a jnp oracle differently than around a
        # kernel call, and on TPU a float reduction over another layout
        # rounds differently. Fenced, the float program compiles the same
        # on every backend, so served tokens stay bit-identical.
        xi, xs = _row_major(jax.lax.optimization_barrier(
            _row_major((xq.astype(jnp.int32), xs))))
        acc = ppac_matmul(xi, planes, mode="mvp_multibit_resident", n=n,
                          a_int8=container.shadow, backend=backend, **kw)
        return _row_major(jax.lax.optimization_barrier(_row_major(acc))), xs

    if kind == "packed1":
        xq, xs = binarize_pm1(xf, axis=-1)          # {±1} activations
        # ±1 ≡ oddint(1): the packed1 plane serves through the same fused
        # resident kernel as packed4, with a 1x1 plane-pair schedule
        return resident_mvp(xq, xs, container.wq[None], k_bits=1, l_bits=1,
                            fmt_a="oddint", fmt_x="oddint")
    xq, xs = quantize(xf, act_bits, act_format, axis=-1)
    if kind == "packed4":
        a_has_mask = container.wq.shape[-3] == (container.bits or 0) + 1
        return resident_mvp(xq, xs, container.wq, k_bits=container.bits,
                            l_bits=act_bits, fmt_a=container.fmt,
                            fmt_x=act_format, a_has_mask=a_has_mask)
    if kind == "int8":
        if _flight.active():
            # the int8 MXU fallback bypasses ppac_matmul; record it at its
            # would-be K-bit-serial PPAC cost so ledger totals stay in
            # lockstep with serving_cycle_report across every kind
            _flight.record_launch(
                "mvp_int8_mxu", backend, batch=int(xq.shape[0]),
                m_rows=int(container.wq.shape[-1]), n_bits=n,
                k_bits=container.bits or 8, l_bits=act_bits,
                x_shape=tuple(xq.shape), a_shape=tuple(container.wq.shape),
                traced=isinstance(xq, jax.core.Tracer))
        acc = jax.lax.dot_general(
            xq.astype(jnp.int8), container.wq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return acc, xs
    raise ValueError(f"no integer path for container kind {kind!r}")


def serve_dense(x, container: QuantContainer, *, act_bits: int,
                act_format: str = "int", backend: str = "mxu",
                rung: str = "target"):
    """Exact-integer projection against a resident quantized weight.

    ``rung="draft"`` serves the container's resident packed1 rung (the
    1-bit bit-serial cost class) instead of the target rung; containers
    without a packed draft rung fall back to the target rung, so a
    draft-routed forward is always well-defined.
    """
    if rung == "draft":
        container = container.draft_view()
    elif rung != "target":
        raise ValueError(f"unknown serving rung {rung!r}")
    scale = container.scale
    lead = x.shape[:-1]
    xf = x.reshape((-1, x.shape[-1])).astype(jnp.float32)

    if container.kind == "bf16":
        y = (xf.astype(jnp.bfloat16) @ container.wq).astype(jnp.float32)
        y = y * scale[None, :]
    else:
        acc, xs = serve_dense_acc(xf, container, act_bits=act_bits,
                                  act_format=act_format, backend=backend)
        y = acc.astype(jnp.float32) * xs * scale[None, :]
    return y.reshape(lead + (y.shape[-1],)).astype(x.dtype)


def serve_dense_grouped(x, container: QuantContainer, *, act_bits: int,
                        act_format: str = "int", backend: str = "mxu",
                        rung: str = "target"):
    """One fused projection for a grouped container, split back into the
    member projections' outputs.

    The container stacks several same-input projections column-wise
    (``splits`` records the member output widths): activations quantize
    ONCE and one fat kernel launch covers the whole group — halving decode
    launches for wq/wk/wv (+ wi/wg) — while per-output-channel scales keep
    each slice bit-identical to its standalone projection.
    """
    if not container.splits:
        raise ValueError("serve_dense_grouped needs a container with splits")
    y = serve_dense(x, container, act_bits=act_bits, act_format=act_format,
                    backend=backend, rung=rung)
    outs, off = [], 0
    for width in container.splits:
        outs.append(jax.lax.slice_in_dim(y, off, off + width, axis=-1))
        off += width
    return tuple(outs)


# ---------------------------------------------------------------------------
# Resident-container integrity: CRC tags, scrub, shadow repair
# ---------------------------------------------------------------------------
#
# The PPAC premise stores the matrix *in memory* — so the serving stack
# treats resident bitplane corruption as a first-class failure mode. Each
# container's target planes (``wq``) get a GF(2) CRC tag at load time
# (computed through the repo's own CRC-as-MVP ops — detection lives on
# the memory path, per the near-memory-crypto direction in PAPERS.md);
# a scrub pass recomputes and compares. Packed kinds with a load-time
# int8 ``shadow`` repair in place by re-packing the planes from the
# shadow (the same deterministic pipeline as ``pack_weight_for_serving``,
# so the repaired container is bit-identical to the original). The draft
# rung is deliberately untagged: a corrupted drafter only lowers the
# speculative accept rate — the target-rung verify keeps outputs exact.

def _is_container(x) -> bool:
    return isinstance(x, QuantContainer)


def _container_items(params):
    """[(path_str, container)] over every QuantContainer leaf, in the
    stable flatten order (the tag-dict key space)."""
    leaves = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=_is_container)[0]
    return [(jax.tree_util.keystr(kp), x) for kp, x in leaves
            if _is_container(x)]


def container_tag(c: QuantContainer) -> int:
    """GF(2) CRC tag over the container's resident target planes."""
    from ..gf2.ops import crc_tag as _crc_tag
    return _crc_tag(np.asarray(c.wq))


def container_tags(params) -> Dict[str, int]:
    """path -> CRC tag for every resident container (run once at load)."""
    return {path: container_tag(c) for path, c in _container_items(params)}


def repack_from_shadow(c: QuantContainer) -> QuantContainer:
    """Rebuild a packed container's target planes from its load-time int8
    shadow — the corruption-repair path. Returns a container bit-identical
    to the original packing; raises for kinds with no redundant resident
    (int8/bf16 store exactly one copy)."""
    if c.shadow is None or c.kind not in ("packed1", "packed4"):
        raise ValueError(f"container kind {c.kind!r} "
                         f"{'without a shadow ' if c.shadow is None else ''}"
                         f"has no redundant resident to repair from")
    shadow = jnp.asarray(c.shadow)

    def repack2d(sh):  # one layer: shadow [in, out] -> resident planes
        if c.kind == "packed1":
            return pack_bits(((sh + 1) // 2).astype(jnp.uint8).T)
        a_int = sh.T.astype(jnp.int32)
        planes = to_bitplanes(a_int, c.bits, c.fmt)
        if c.wq.shape[-3] == (c.bits or 0) + 1:  # resident mask plane
            mask = jnp.ones((1,) + a_int.shape, jnp.uint8)
            planes = jnp.concatenate([planes, mask], axis=0)
        return pack_bits(planes)

    # stacked (scan) containers carry a leading layer axis: repack each
    # layer exactly as the vmapped load-time packer did
    wq = (repack2d(shadow) if shadow.ndim == 2
          else jax.vmap(repack2d)(shadow))
    assert wq.shape == c.wq.shape and wq.dtype == c.wq.dtype, \
        (wq.shape, c.wq.shape)
    return c.with_children(wq, c.scale, shadow=c.shadow, dwq=c.dwq,
                           dscale=c.dscale, dshadow=c.dshadow)


def scrub_params(params, tags: Dict[str, int]):
    """One integrity pass over the resident containers.

    Recomputes every container's CRC tag against ``tags`` (from
    :func:`container_tags` at load). Mismatching containers with a shadow
    are repaired via :func:`repack_from_shadow`; shadow-less mismatches
    are reported irreparable (the caller fails loudly rather than serving
    wrong weights). Returns ``(params', report)`` where report maps path
    -> 'clean' | 'repaired' | 'corrupt'.
    """
    leaves, treedef = jax.tree_util.tree_flatten(params,
                                                 is_leaf=_is_container)
    paths = iter([p for p, _ in _container_items(params)])
    report: Dict[str, str] = {}
    out = []
    for leaf in leaves:
        if not _is_container(leaf):
            out.append(leaf)
            continue
        path = next(paths)
        if container_tag(leaf) == tags.get(path):
            report[path] = "clean"
            out.append(leaf)
        elif leaf.shadow is not None and leaf.kind in ("packed1", "packed4"):
            fixed = repack_from_shadow(leaf)
            assert container_tag(fixed) == tags.get(path), \
                f"shadow repair of {path} did not restore the tagged planes"
            report[path] = "repaired"
            out.append(fixed)
        else:
            report[path] = "corrupt"
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out), report


def flip_container_bit(params, *, index: int = 0, bit: int = 0):
    """Fault injection: XOR one bit of the ``index``-th container's
    resident planes (host round-trip — chaos-test path only)."""
    leaves, treedef = jax.tree_util.tree_flatten(params,
                                                 is_leaf=_is_container)
    ks = [i for i, x in enumerate(leaves) if _is_container(x)]
    if not ks:
        raise ValueError("no QuantContainer leaves to corrupt")
    i = ks[index % len(ks)]
    c = leaves[i]
    wq = np.array(np.asarray(c.wq))
    flat = np.frombuffer(wq.tobytes(), np.uint8).copy()
    j = (bit // 8) % flat.size
    flat[j] ^= np.uint8(1 << (bit % 8))
    wq = np.frombuffer(flat.tobytes(), wq.dtype).reshape(wq.shape)
    leaves[i] = c.with_children(jnp.asarray(wq), c.scale, shadow=c.shadow,
                                dwq=c.dwq, dscale=c.dscale,
                                dshadow=c.dshadow)
    return jax.tree_util.tree_unflatten(treedef, leaves)
