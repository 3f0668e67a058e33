"""Attention variants: GQA (+QKV bias, sliding window), MLA (DeepSeek-style).

Memory-efficient chunked attention: queries are processed in chunks via
``lax.scan`` (peak activation = one [chunk × kv] score tile) with optional
remat of the chunk body — required for the 32k prefill shapes on a real
chip and for bounded compile-time memory on the dry-run.

KV caches are plain pytrees: {"k": [B,T,Hkv,D], "v": [B,T,Hkv,Dv]} with a
*per-sequence* write position ``pos: [B]`` — mixed-progress batches (the
continuous-batching server admits new prompts mid-flight) decode with one
fused step. Sliding-window attention uses a rolling (ring) cache of size
``window`` for decode: position ``p`` always lives at slot ``p % window``,
in prefill and decode alike, so decode can roll straight out of any
prefill length (bounds long-context memory). MLA caches the compressed
(kv_lora + rope) stream and decodes via the absorbed-projection trick —
the KV-memory win that makes it the natural PPAC companion for decode
shapes. Decode writes are batched scatters (per-sequence slots), which
lower in place when the cache pytree is donated (serve/step.py jits every
decode entry point with ``donate_argnums`` on the cache).

Named scopes mark the ops of each part in the compiled program's op
metadata, and so in a device trace: ``proj/wqkv`` and ``proj/wo`` (the
projections), ``kv_write`` (every cache write: contiguous update slice,
scatter, paged scatter) and ``attn`` (the attention over the cache or
the prompt).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig
from ..sharding.rules import constrain
from .layers import (
    dense_apply,
    dense_init,
    grouped_dense_apply,
    rmsnorm_apply,
    rmsnorm_init,
    rope,
)

NEG_INF = -1e9
_scope = jax.named_scope


def _attend_chunk(qc, k, v, q_pos, k_valid, *, window: int, scale: float,
                  causal: bool, rules=None, scores_dtype=None):
    """qc: [B,C,H,D]; k: [B,T,Hkv,D]; v: [B,T,Hkv,Dv]; q_pos: [C] int32.

    Returns [B,C,H,Dv]. GQA keys/values are repeated to the full head
    count and every head-indexed tensor is explicitly constrained to the
    'model' axis: without the constraints GSPMD replicates the quadratic
    score einsums whenever heads don't divide the axis (observed 16x
    redundant compute on smollm — EXPERIMENTS.md §Perf iteration 1).
    """
    b, c, h, d = qc.shape
    t, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)   # [B,T,H,D]
        v = jnp.repeat(v, rep, axis=2)
    if rules is not None:
        qc = constrain(qc, rules, "batch", None, "act_heads", None)
        k = constrain(k, rules, "batch", None, "act_heads", None)
        v = constrain(v, rules, "batch", None, "act_heads", None)
    return _attend_prepped(qc, k, v, q_pos, k_valid, window=window,
                           scale=scale, causal=causal, rules=rules,
                           scores_dtype=scores_dtype)


def _attend_prepped(qc, k, v, q_pos, k_valid, *, window, scale, causal,
                    rules=None, scores_dtype=None):
    """Like _attend_chunk but assumes k/v are already head-expanded and
    constrained (hoisted out of chunk loops so GSPMD gathers once, not
    once per chunk — §Perf llava iteration 3b)."""
    b, c, h, d = qc.shape
    t = k.shape[1]
    # fp32 ACCUMULATION without materializing fp32 copies of q/k/v
    # (input .astype(f32) casts were ~half the HBM traffic — §Perf it.2)
    scores = jnp.einsum("bchd,bthd->bhct", qc, k,
                        preferred_element_type=jnp.float32) * scale
    if rules is not None:
        scores = constrain(scores, rules, "batch", "act_heads", None, None)
    k_pos = jnp.arange(t)
    mask = k_pos[None, :] < k_valid  # valid cache entries
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
    if window:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    scores = jnp.where(mask[None, None, :, :], scores, NEG_INF)
    if scores_dtype is not None:
        # bf16 probability boundary (softmax max-subtracts internally;
        # bf16 keeps f32's exponent range) — halves the [C,T] HBM tensors
        scores = scores.astype(scores_dtype)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhct,bthv->bchv", w.astype(qc.dtype), v,
                     preferred_element_type=jnp.float32)
    if rules is not None:
        out = constrain(out, rules, "batch", None, "act_heads", None)
    return out


def chunked_attention(q, k, v, *, q_offset=0, k_valid=None, causal=True,
                      window: int = 0, q_chunk: int = 512,
                      scale: Optional[float] = None, remat: bool = True,
                      rules=None, blocking: str = "scan",
                      scores_dtype=None):
    """q: [B,S,H,D] against k/v: [B,T,Hkv,D*] -> [B,S,H,Dv]."""
    b, s, h, d = q.shape
    t = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    k_valid = t if k_valid is None else k_valid
    k_valid = jnp.asarray(k_valid, jnp.int32)

    if s <= q_chunk:
        q_pos = q_offset + jnp.arange(s)
        return _attend_chunk(q, k, v, q_pos, k_valid, window=window,
                             scale=scale, causal=causal, rules=rules,
                             scores_dtype=scores_dtype)

    c = q_chunk
    pad = (-s) % c
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = q.shape[1] // c

    if blocking == "triangle" and causal and t == s and not window:
        # Unrolled triangular blocking: chunk i only attends to keys
        # [0, (i+1)*c) — statically sliced, so the fully-masked half of
        # the [C, T] score work (and its HBM traffic) never exists.
        # K/V head expansion + sharding constraints are hoisted OUT of
        # the loop (inside it, GSPMD re-gathers per chunk).
        h_full = q.shape[2]
        rep = h_full // k.shape[2]
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        if rules is not None:
            k = constrain(k, rules, "batch", None, "act_heads", None)
            v = constrain(v, rules, "batch", None, "act_heads", None)
        outs = []

        def chunk_fn(qc, ki, vi, q_pos):
            if rules is not None:
                qc = constrain(qc, rules, "batch", None, "act_heads", None)
            return _attend_prepped(qc, ki, vi, q_pos, ki.shape[1],
                                   window=0, scale=scale, causal=True,
                                   rules=rules, scores_dtype=scores_dtype)

        fn = jax.checkpoint(chunk_fn) if remat else chunk_fn
        for i in range(nq):
            hi = min((i + 1) * c, t)
            qc = q[:, i * c:(i + 1) * c]
            q_pos = q_offset + i * c + jnp.arange(c)
            outs.append(fn(qc, k[:, :hi], v[:, :hi], q_pos))
        out = jnp.concatenate(outs, axis=1)
        return out[:, :s]

    qs = q.reshape(b, nq, c, h, d).transpose(1, 0, 2, 3, 4)  # [nq,B,C,H,D]

    def body(_, xs):
        qc, idx = xs
        q_pos = q_offset + idx * c + jnp.arange(c)
        out = _attend_chunk(qc, k, v, q_pos, k_valid, window=window,
                            scale=scale, causal=causal, rules=rules,
                            scores_dtype=scores_dtype)
        return None, out

    fn = jax.checkpoint(body) if remat else body
    _, ys = lax.scan(fn, None, (qs, jnp.arange(nq)))
    out = ys.transpose(1, 0, 2, 3, 4).reshape(b, nq * c, h, v.shape[-1])
    return out[:, :s]


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

def gqa_init(key, cfg: ModelConfig):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p, a = {}, {}
    p["wq"], a["wq"] = dense_init(ks[0], d, h * hd, ("embed", "heads"),
                                  bias=cfg.qkv_bias)
    p["wk"], a["wk"] = dense_init(ks[1], d, hkv * hd, ("embed", "kv_heads"),
                                  bias=cfg.qkv_bias)
    p["wv"], a["wv"] = dense_init(ks[2], d, hkv * hd, ("embed", "kv_heads"),
                                  bias=cfg.qkv_bias)
    p["wo"], a["wo"] = dense_init(ks[3], h * hd, d, ("heads", "embed"))
    return p, a


def gqa_cache_init(cfg: ModelConfig, batch: int, max_seq: int,
                   dtype=jnp.bfloat16):
    t = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
    shape = (batch, t, cfg.n_kv_heads, cfg.hd)
    if cfg.kv_dtype == "int8":
        # per-(token, head) max-scaled int8 store — 2x smaller cache, the
        # decode memory-roofline lever paired with PPAC resident weights
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.zeros(shape[:3] + (1,), jnp.bfloat16),
                "vs": jnp.zeros(shape[:3] + (1,), jnp.bfloat16)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_cache_axes(cfg: ModelConfig):
    ax = ("batch", "kv_seq", "kv_heads", None)
    if cfg.kv_dtype == "int8":
        return {"k": ax, "v": ax, "ks": ax, "vs": ax}
    return {"k": ax, "v": ax}


# -- paged KV pools -----------------------------------------------------------
#
# A paged cache virtualizes the per-slot [T, ...] token axis onto a bounded
# physical pool of fixed-size pages: leaves are [pool_pages, page_size, ...]
# and an int32 page table [slots, T / page_size] maps each slot's logical
# page to a physical one. Reads gather rows through the table (the same
# take-based trick as ``_ring_rows``), writes scatter through it — both
# lower in place under donation, so thousands of logical slots can share a
# pool sized by *live tokens*. Which physical pages back which slot (free
# list, refcounts, copy-on-write, prefix sharing) is host-side policy in
# ``launch.paging`` / ``launch.serve_lm``; the model layer only follows the
# table it is handed.


def gqa_paged_cache_init(cfg: ModelConfig, pool_pages: int, page_size: int,
                         dtype=jnp.bfloat16):
    shape = (pool_pages, page_size, cfg.n_kv_heads, cfg.hd)
    if cfg.kv_dtype == "int8":
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.zeros(shape[:3] + (1,), jnp.bfloat16),
                "vs": jnp.zeros(shape[:3] + (1,), jnp.bfloat16)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_paged_cache_axes(cfg: ModelConfig):
    ax = (None, None, "kv_heads", None)
    if cfg.kv_dtype == "int8":
        return {"k": ax, "v": ax, "ks": ax, "vs": ax}
    return {"k": ax, "v": ax}


def paged_view(pool, table):
    """Gather a pool [P, psz, ...] through table [B, n] -> [B, n*psz, ...].

    The per-slot logical view decode/suffix attention runs against —
    identical, row for row, to what a contiguous [B, T, ...] cache would
    hold (unallocated table entries read page 0; those rows sit beyond
    every validity/causality mask, so their values never contribute)."""
    b, n = table.shape
    rows = jnp.take(pool, table, axis=0, mode="clip")   # [B, n, psz, ...]
    return rows.reshape((b, n * pool.shape[1]) + pool.shape[2:])


def paged_scatter(pool, table, rows, row_idx, valid=None):
    """Write rows [B, S, ...] at logical rows ``row_idx`` [B, S] through
    the table. Invalid (right-pad) rows are routed to an out-of-range page
    and dropped — pads must never reach a page another slot may own."""
    p, psz = pool.shape[0], pool.shape[1]
    b, s = row_idx.shape
    page = jnp.take_along_axis(
        table, jnp.clip(row_idx // psz, 0, table.shape[1] - 1), axis=1)
    off = row_idx % psz
    if valid is not None:
        page = jnp.where(valid, page, p)                # OOB -> mode="drop"
    flat = rows.reshape((b * s,) + rows.shape[2:]).astype(pool.dtype)
    return pool.at[page.reshape(-1), off.reshape(-1)].set(flat, mode="drop")


def _attend_causal_rows(q, k, v, q_pos, *, scale, rules=None,
                        scores_dtype=None):
    """Per-sequence causal attention for suffix prefill: q [B,S,H,D] rows
    at absolute positions ``q_pos`` [B,S] against an assembled history
    view k/v [B,T,H,*]. Mirrors ``_attend_prepped`` (same einsums, same
    NEG_INF masking, same probability-boundary cast) so a 1-token suffix
    reproduces cold prefill's last-row attention bit for bit when the
    cached rows store exact values; the only change is the [B,S,T] mask
    (per-sequence positions instead of one shared chunk offset)."""
    b, s, h, d = q.shape
    t, hk = k.shape[1], k.shape[2]
    rep = h // hk
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if rules is not None:
        q = constrain(q, rules, "batch", None, "act_heads", None)
        k = constrain(k, rules, "batch", None, "act_heads", None)
        v = constrain(v, rules, "batch", None, "act_heads", None)
    scores = jnp.einsum("bchd,bthd->bhct", q, k,
                        preferred_element_type=jnp.float32) * scale
    if rules is not None:
        scores = constrain(scores, rules, "batch", "act_heads", None, None)
    mask = jnp.arange(t)[None, None, :] <= q_pos[:, :, None]   # [B,S,T]
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    if scores_dtype is not None:
        scores = scores.astype(scores_dtype)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhct,bthv->bchv", w.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    if rules is not None:
        out = constrain(out, rules, "batch", None, "act_heads", None)
    return out


GQA_CACHE_AXES = {"k": ("batch", "kv_seq", "kv_heads", None),
                  "v": ("batch", "kv_seq", "kv_heads", None)}


def _q8_kv(x):
    """x [B,S,Hkv,D] -> (int8 values, bf16 scales [B,S,Hkv,1])."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def as_pos_vector(pos, batch: int):
    """Normalize a write position (python int / scalar / [B]) to [B] int32."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (batch,))
    return pos


def _scatter_rows(cache_leaf, rows, slot):
    """Write rows [B,1,...] at per-sequence slots [B] of cache [B,T,...]."""
    b = cache_leaf.shape[0]
    return cache_leaf.at[jnp.arange(b), slot].set(
        rows[:, 0].astype(cache_leaf.dtype), mode="drop")


def _scatter_rows_multi(cache_leaf, rows, row_idx):
    """Write rows [B,S,...] at per-sequence rows [B,S] of cache [B,T,...].
    Out-of-range rows (a verify window running past the cache) drop."""
    b = cache_leaf.shape[0]
    return cache_leaf.at[jnp.arange(b)[:, None], row_idx].set(
        rows.astype(cache_leaf.dtype), mode="drop")


def _ring_rows(stream, lengths, t: int):
    """Ring-layout a per-position stream into rolling-cache rows.

    stream: [B,S,...] (positions 0..S-1, right-padded past ``lengths``);
    returns [B,t,...] where slot ``s`` holds the *latest* valid position
    ``p < lengths`` with ``p % t == s`` (zeros for never-written slots).
    This is exactly the layout decode's ``slot = pos % t`` writes produce,
    so decode rolls seamlessly out of any prefill length — including
    lengths that are neither multiples of nor smaller than the window.
    """
    b = stream.shape[0]
    ln = lengths[:, None]                              # [B,1]
    s_idx = jnp.arange(t)[None, :]                     # [1,t]
    p = ln - 1 - jnp.mod(ln - 1 - s_idx, t)            # [B,t]
    valid = (p >= 0) & (ln > 0)
    idx = jnp.clip(p, 0, stream.shape[1] - 1)
    rows = jnp.take_along_axis(
        stream, idx.reshape((b, t) + (1,) * (stream.ndim - 2)), axis=1)
    return jnp.where(valid.reshape((b, t) + (1,) * (stream.ndim - 2)),
                     rows, jnp.zeros((), stream.dtype))


def _decode_attend_q8(q, cache, k_valid, *, scale, rules=None):
    """(Optionally quantized) cache decode attention, GQA-grouped (NO
    key/value repeat: repeating a seq-sharded cache forces GSPMD into
    involuntary full rematerialization — replicate + repartition of the
    whole cache per layer; XLA emits a warning and ~800 GiB of phantom
    copies).

    ``k_valid: [B]`` — per-sequence count of valid cache slots (mixed-
    progress batches decode at different positions in one fused step).
    The per-(t,g) scales factor out of both einsums, so no dequantized
    [B,T,G,D] tensor is materialized:
        scores = (q · ki) * ks ;  out = ((w*vs) · vi)
    Like ``_attend_prepped``, every head-indexed einsum is constrained to
    the 'model' axis (the grouped dim g carries the kv-head sharding).
    """
    b, s, h, d = q.shape          # s == 1 decode; s > 1 verifies a window
    ki, vi = cache["k"], cache["v"]
    ks, vs = cache.get("ks"), cache.get("vs")
    t, g = ki.shape[1], ki.shape[2]
    rep = h // g
    qg = q.reshape(b, s, g, rep, d)
    if rules is not None:
        qg = constrain(qg, rules, "batch", None, "act_heads", None, None)
    scores = jnp.einsum("bsgrd,btgd->bgrst", qg, ki.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    if rules is not None:
        scores = constrain(scores, rules, "batch", "act_heads", None, None,
                           None)
    if ks is not None:
        scores = scores * ks[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    kv = jnp.asarray(k_valid, jnp.int32)
    kv = kv[:, None] if kv.ndim == 1 else kv           # [B,S] counts
    mask = jnp.arange(t)[None, None, :] < kv[:, :, None]   # [B,S,T]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    wv = w.astype(q.dtype)
    if vs is not None:
        wv = wv * vs[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    out = jnp.einsum("bgrst,btgv->bsgrv", wv, vi.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    if rules is not None:
        out = constrain(out, rules, "batch", None, "act_heads", None, None)
    return out.reshape(b, s, h, -1).astype(q.dtype)


def _verify_attend_views(q, views, k_valid, *, scale, rules=None):
    """``_decode_attend_q8`` against per-query cache views: leaves are
    [B,S,T,g,*] — query i sees its OWN snapshot of the ring (slots a later
    window row will overwrite still hold their pre-window content). Same
    einsum contractions, scale ordering and count masking as decode, with
    one extra query-indexed key axis, so each row of the window reproduces
    the decode step it replaces bit for bit up to key order (which the
    view construction preserves: slot order)."""
    b, s, h, d = q.shape
    ki, vi = views["k"], views["v"]
    ks, vs = views.get("ks"), views.get("vs")
    t, g = ki.shape[2], ki.shape[3]
    rep = h // g
    qg = q.reshape(b, s, g, rep, d)
    if rules is not None:
        qg = constrain(qg, rules, "batch", None, "act_heads", None, None)
    scores = jnp.einsum("bsgrd,bstgd->bgrst", qg, ki.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
    if rules is not None:
        scores = constrain(scores, rules, "batch", "act_heads", None, None,
                           None)
    if ks is not None:
        scores = scores * ks[..., 0].transpose(0, 3, 1, 2)[:, :, None, :, :]
    mask = jnp.arange(t)[None, None, :] < k_valid[:, :, None]   # [B,S,T]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    wv = w.astype(q.dtype)
    if vs is not None:
        wv = wv * vs[..., 0].transpose(0, 3, 1, 2)[:, :, None, :, :]
    out = jnp.einsum("bgrst,bstgv->bsgrv", wv, vi.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    if rules is not None:
        out = constrain(out, rules, "batch", None, "act_heads", None, None)
    return out.reshape(b, s, h, -1).astype(q.dtype)


def _ring_query_views(ext, j0, n_q: int, t: int):
    """Per-query ring views from extended leaves [B, t+S, ...]: query i's
    slot s reads window row ``j0[b,s]`` (appended at t+j0) once that row
    exists for i (``j0 <= i`` — covering both in-window replacement and
    window expiry of the slot's old content), else the untouched ring row.
    Returns leaves [B, n_q, t, ...]."""
    b = j0.shape[0]
    qi = jnp.arange(n_q, dtype=jnp.int32)[None, :, None]         # [1,S,1]
    idx = jnp.where(j0[:, None, :] <= qi, t + j0[:, None, :],
                    jnp.arange(t, dtype=jnp.int32)[None, None, :])  # [B,S,t]
    flat = idx.reshape(b, n_q * t)
    out = {}
    for kk, leaf in ext.items():
        rows = jnp.take_along_axis(
            leaf, flat.reshape((b, n_q * t) + (1,) * (leaf.ndim - 2)),
            axis=1)
        out[kk] = rows.reshape((b, n_q, t) + leaf.shape[2:])
    return out


def gqa_apply(p, x, cfg: ModelConfig, *, positions, cache=None, pos=None,
              lengths=None, mode: str = "float", rules=None, table=None,
              history=False, verify=False):
    """x: [B,S,d]. Train/prefill when cache is None or S>1 (writes cache
    at positions [0, lengths) — right-padded ragged prompts supported);
    decode (S==1) updates the rolling/linear cache at per-sequence
    ``pos: [B]`` (scalars are broadcast).

    With ``table`` [B, n_pages] the cache leaves are paged pools
    ([P, psz, ...]) and all reads/writes route through the table.
    ``history=True`` is the suffix-prefill path for prefix-reuse hits:
    ``positions`` [B,S] are absolute rows past an already-populated
    history (shared pages), written through the table and attended via
    the gathered per-slot view under a per-sequence causal mask.

    ``verify=True`` is the speculative-verify path: the S tokens sit at
    per-sequence positions ``pos + i`` PAST the populated cache, and
    every row runs the exact decode-step compute (same einsums, same
    count masking) so row i's logits bit-match the decode step it
    replaces. All S rows' target-rung K/V are written; rejected rows are
    masked by ``pos`` afterwards (linear/paged) or rolled back by the
    caller (ring — see ``models.lm.rollback_ring_cache``)."""
    dtype = jnp.dtype(cfg.dtype)
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    with _scope("proj/wqkv"):
        if "wqkv" in p:  # fused q/k/v group (serving fast path)
            q, k, v = grouped_dense_apply(p["wqkv"], x, ppac=cfg.ppac,
                                          mode=mode)
        else:
            q = dense_apply(p["wq"], x, ppac=cfg.ppac, mode=mode, dtype=dtype)
            k = dense_apply(p["wk"], x, ppac=cfg.ppac, mode=mode, dtype=dtype)
            v = dense_apply(p["wv"], x, ppac=cfg.ppac, mode=mode, dtype=dtype)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    q = rope(q, positions, theta=cfg.rope_theta)
    k = rope(k, positions, theta=cfg.rope_theta)

    sdt = (jnp.bfloat16 if cfg.scores_dtype == "bfloat16" else None)
    paged = table is not None and cache is not None
    new_cache = cache
    if cache is None:
        with _scope("attn"):
            attn = chunked_attention(q, k, v, causal=True,
                                     window=cfg.sliding_window,
                                     q_chunk=cfg.q_chunk,
                                     remat=cfg.remat != "none", rules=rules,
                                     blocking=cfg.attn_blocking,
                                     scores_dtype=sdt)
    elif history:  # paged suffix prefill after a prefix-cache hit
        assert paged and not cfg.sliding_window
        t = table.shape[1] * cache["k"].shape[1]
        ln = (jnp.full((b,), s, jnp.int32) if lengths is None
              else as_pos_vector(lengths, b))
        row_idx = positions.astype(jnp.int32)               # [B,S] absolute
        valid = (jnp.arange(s)[None, :] < ln[:, None]) & (row_idx < t)
        with _scope("kv_write"):
            if "ks" in cache:
                kq, ksc = _q8_kv(k)
                vq, vsc = _q8_kv(v)
                leaves = {"k": kq, "v": vq, "ks": ksc, "vs": vsc}
            else:
                leaves = {"k": k, "v": v}
            new_cache = {kk: paged_scatter(cache[kk], table, vv, row_idx,
                                           valid)
                         for kk, vv in leaves.items()}
        with _scope("attn"):
            view = {kk: paged_view(vv, table)
                    for kk, vv in new_cache.items()}
            kf = view["k"].astype(q.dtype)
            vf = view["v"].astype(q.dtype)
            if "ks" in view:
                kf = kf * view["ks"].astype(q.dtype)
                vf = vf * view["vs"].astype(q.dtype)
            attn = _attend_causal_rows(q, kf, vf, row_idx, scale=hd ** -0.5,
                                       rules=rules, scores_dtype=sdt)
    elif verify:  # speculative verify: S decode-equivalent rows at pos+i
        pos = as_pos_vector(pos, b)
        row_idx = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        with _scope("kv_write"):
            if "ks" in cache:            # quantized store (same as decode)
                kq, ksc = _q8_kv(k)
                vq, vsc = _q8_kv(v)
                leaves = {"k": kq, "v": vq, "ks": ksc, "vs": vsc}
            else:
                leaves = {"k": k, "v": v}
        if cfg.sliding_window:
            # ring: window rows ride as S appended keys; each query reads
            # a per-query slot-ordered view, so softmax sums in the same
            # key order as the decode steps being replaced.
            assert not paged, "spec verify: paged ring caches unsupported"
            t = cache["k"].shape[1]
            assert s <= t, "verify window must fit the sliding window"
            slot = row_idx % t
            j0 = jnp.mod(jnp.arange(t, dtype=jnp.int32)[None, :]
                         - pos[:, None], t)             # [B,t]
            with _scope("attn"):
                ext = {kk: jnp.concatenate(
                    [cache[kk], vv.astype(cache[kk].dtype)], axis=1)
                    for kk, vv in leaves.items()}
                views = _ring_query_views(ext, j0, s, t)
                k_valid = jnp.minimum(row_idx + 1, t)
                attn = _verify_attend_views(q, views, k_valid,
                                            scale=hd ** -0.5, rules=rules)
            with _scope("kv_write"):
                new_cache = {kk: _scatter_rows_multi(cache[kk], vv, slot)
                             for kk, vv in leaves.items()}
        elif paged:
            t = table.shape[1] * cache["k"].shape[1]
            # rows past the slot's allocation hit sentinel table entries
            # and drop; rows past the logical extent drop explicitly
            valid = row_idx < t
            with _scope("kv_write"):
                new_cache = {kk: paged_scatter(cache[kk], table, vv,
                                               row_idx, valid)
                             for kk, vv in leaves.items()}
            with _scope("attn"):
                attend = {kk: paged_view(vv, table)
                          for kk, vv in new_cache.items()}
                attn = _decode_attend_q8(q, attend, row_idx + 1,
                                         scale=hd ** -0.5, rules=rules)
        else:
            with _scope("kv_write"):
                new_cache = {kk: _scatter_rows_multi(cache[kk], vv, row_idx)
                             for kk, vv in leaves.items()}
            with _scope("attn"):
                attn = _decode_attend_q8(q, new_cache, row_idx + 1,
                                         scale=hd ** -0.5, rules=rules)
    elif s > 1:  # prefill into cache (cold: no history in the cache yet)
        psz = cache["k"].shape[1]
        t = table.shape[1] * psz if paged else cache["k"].shape[1]
        ln = (jnp.full((b,), s, jnp.int32) if lengths is None
              else as_pos_vector(lengths, b))
        with _scope("kv_write"):
            if cfg.sliding_window:
                # ring layout: position p at slot p % t, per-seq lengths
                kw, vw = _ring_rows(k, ln, t), _ring_rows(v, ln, t)
            else:
                kw, vw = k, v
            if "ks" in cache:
                kq, ksc = _q8_kv(kw)
                vq, vsc = _q8_kv(vw)
                leaves = {"k": kq, "v": vq, "ks": ksc, "vs": vsc}
            else:
                leaves = {"k": kw, "v": vw}
            if paged:
                sw = kw.shape[1]       # t for ring layout, s for linear
                row_idx = jnp.broadcast_to(
                    jnp.arange(sw, dtype=jnp.int32)[None, :], (b, sw))
                # ring writes all t ring rows (never-written slots hold
                # zeros, and every ring page is privately allocated);
                # linear drops right-pad rows so they cannot land in
                # shareable pages.
                valid = (None if cfg.sliding_window
                         else (row_idx < ln[:, None]) & (row_idx < t))
                new_cache = {kk: paged_scatter(cache[kk], table, vv,
                                               row_idx, valid)
                             for kk, vv in leaves.items()}
            else:
                new_cache = {kk: lax.dynamic_update_slice(
                    cache[kk], vv.astype(cache[kk].dtype),
                    (0,) * cache[kk].ndim) for kk, vv in leaves.items()}
        with _scope("attn"):
            attn = chunked_attention(q, k, v, causal=True,
                                     window=cfg.sliding_window,
                                     q_chunk=cfg.q_chunk,
                                     remat=cfg.remat != "none", rules=rules,
                                     blocking=cfg.attn_blocking,
                                     scores_dtype=sdt)
    else:  # decode, S == 1, per-sequence positions
        t = (table.shape[1] * cache["k"].shape[1] if paged
             else cache["k"].shape[1])
        pos = as_pos_vector(pos, b)
        if cfg.sliding_window:
            slot = pos % t           # rolling (ring) cache
            k_valid = jnp.minimum(pos + 1, t)
        else:
            slot = pos               # linear cache
            k_valid = pos + 1
        with _scope("kv_write"):
            if "ks" in cache:            # quantized store
                kq, ksc = _q8_kv(k)
                vq, vsc = _q8_kv(v)
                leaves = {"k": kq, "v": vq, "ks": ksc, "vs": vsc}
            else:
                leaves = {"k": k, "v": v}
            if paged:
                # vacant slots carry all-pad table rows, so their writes
                # drop instead of landing in pages another sequence owns.
                new_cache = {kk: paged_scatter(cache[kk], table, vv,
                                               slot[:, None])
                             for kk, vv in leaves.items()}
            else:
                new_cache = {kk: _scatter_rows(cache[kk], vv, slot)
                             for kk, vv in leaves.items()}
        with _scope("attn"):
            attend = ({kk: paged_view(vv, table)
                       for kk, vv in new_cache.items()} if paged
                      else new_cache)
            # rolling-cache entries are unordered but all within the
            # window, so the validity mask alone is the correct mask.
            attn = _decode_attend_q8(q, attend, k_valid, scale=hd ** -0.5,
                                     rules=rules)
    attn = attn.reshape(b, s, h * hd).astype(dtype)
    with _scope("proj/wo"):
        y = dense_apply(p["wo"], attn, ppac=cfg.ppac, mode=mode, dtype=dtype)
    if cache is not None and rules is not None:
        # Pin the updated leaves to the fitted resident-cache placement:
        # left to propagation, GSPMD pushes the projection shardings onto
        # the outputs, the output sharding diverges from the donated
        # input's, and strict aliasing degrades to a buffer donation
        # (a device-local cache-sized copy every step).
        cax = ((None, None, "kv_heads", None) if paged
               else GQA_CACHE_AXES["k"])
        new_cache = {kk: constrain(vv, rules, *cax)
                     for kk, vv in new_cache.items()}
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA block (DeepSeek-V2-style multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 6)
    p, a = {}, {}
    p["w_dkv"], a["w_dkv"] = dense_init(ks[0], d, m.kv_lora_rank,
                                        ("embed", "kv_lora"))
    p["norm_kv"], a["norm_kv"] = rmsnorm_init(m.kv_lora_rank, ("kv_lora",))
    p["w_kr"], a["w_kr"] = dense_init(ks[1], d, m.qk_rope_head_dim,
                                      ("embed", None))
    p["w_q"], a["w_q"] = dense_init(
        ks[2], d, h * (m.qk_nope_head_dim + m.qk_rope_head_dim),
        ("embed", "heads"))
    p["w_uk"], a["w_uk"] = dense_init(ks[3], m.kv_lora_rank,
                                      h * m.qk_nope_head_dim,
                                      ("kv_lora", "heads"))
    p["w_uv"], a["w_uv"] = dense_init(ks[4], m.kv_lora_rank,
                                      h * m.v_head_dim, ("kv_lora", "heads"))
    p["wo"], a["wo"] = dense_init(ks[5], h * m.v_head_dim, d,
                                  ("heads", "embed"))
    return p, a


def mla_cache_init(cfg: ModelConfig, batch: int, max_seq: int,
                   dtype=jnp.bfloat16):
    m = cfg.mla
    return {"kv_c": jnp.zeros((batch, max_seq, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_seq, m.qk_rope_head_dim), dtype)}


MLA_CACHE_AXES = {"kv_c": ("batch", "kv_seq", None),
                  "k_rope": ("batch", "kv_seq", None)}


def mla_paged_cache_init(cfg: ModelConfig, pool_pages: int, page_size: int,
                         dtype=jnp.bfloat16):
    m = cfg.mla
    return {"kv_c": jnp.zeros((pool_pages, page_size, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((pool_pages, page_size, m.qk_rope_head_dim),
                                dtype)}


MLA_PAGED_CACHE_AXES = {"kv_c": (None, None, None),
                        "k_rope": (None, None, None)}


def mla_apply(p, x, cfg: ModelConfig, *, positions, cache=None, pos=None,
              lengths=None, mode: str = "float", rules=None, table=None,
              history=False, verify=False):
    m = cfg.mla
    dtype = jnp.dtype(cfg.dtype)
    b, s, d = x.shape
    h = cfg.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    scale = (dn + dr) ** -0.5

    kv_c = dense_apply(p["w_dkv"], x, dtype=dtype)
    kv_c = rmsnorm_apply(p["norm_kv"], kv_c, eps=cfg.norm_eps, dtype=dtype)
    k_r = dense_apply(p["w_kr"], x, dtype=dtype).reshape(b, s, 1, dr)
    k_r = rope(k_r, positions, theta=cfg.rope_theta).reshape(b, s, dr)

    q = dense_apply(p["w_q"], x, ppac=cfg.ppac, mode=mode, dtype=dtype)
    q = q.reshape(b, s, h, dn + dr)
    q_n, q_r = q[..., :dn], q[..., dn:]
    q_r = rope(q_r, positions, theta=cfg.rope_theta)

    paged = table is not None and cache is not None
    sdt = (jnp.bfloat16 if cfg.scores_dtype == "bfloat16" else None)
    if history:
        # Paged suffix prefill after a prefix-cache hit: scatter the
        # compressed suffix through the table, then regenerate K/V over
        # the gathered per-slot view (history pages included).
        assert paged
        t = table.shape[1] * cache["kv_c"].shape[1]
        ln = (jnp.full((b,), s, jnp.int32) if lengths is None
              else as_pos_vector(lengths, b))
        row_idx = positions.astype(jnp.int32)               # [B,S] absolute
        valid = (jnp.arange(s)[None, :] < ln[:, None]) & (row_idx < t)
        with _scope("kv_write"):
            ckp = paged_scatter(cache["kv_c"], table, kv_c, row_idx, valid)
            crp = paged_scatter(cache["k_rope"], table, k_r, row_idx, valid)
        new_cache = {"kv_c": ckp, "k_rope": crp}
        with _scope("attn"):
            ckv = paged_view(ckp, table).astype(dtype)      # [B,T,lora]
            crv = paged_view(crp, table).astype(dtype)      # [B,T,dr]
            k_n = dense_apply(p["w_uk"], ckv,
                              dtype=dtype).reshape(b, t, h, dn)
            vv = dense_apply(p["w_uv"], ckv,
                             dtype=dtype).reshape(b, t, h, dv)
            k_full = jnp.concatenate(
                [k_n, jnp.broadcast_to(crv[:, :, None, :], (b, t, h, dr))],
                -1)
            q_full = jnp.concatenate([q_n, q_r], -1)
            attn = _attend_causal_rows(q_full, k_full, vv, row_idx,
                                       scale=scale, rules=rules,
                                       scores_dtype=sdt)
    elif cache is None or (s > 1 and not verify):
        # Non-absorbed (train/prefill) path: materialize K/V.
        k_n = dense_apply(p["w_uk"], kv_c, dtype=dtype).reshape(b, s, h, dn)
        v = dense_apply(p["w_uv"], kv_c, dtype=dtype).reshape(b, s, h, dv)
        k_full = jnp.concatenate(
            [k_n, jnp.broadcast_to(k_r[:, :, None, :], (b, s, h, dr))], -1)
        q_full = jnp.concatenate([q_n, q_r], -1)
        with _scope("attn"):
            attn = chunked_attention(q_full, k_full, v, causal=True,
                                     q_chunk=cfg.q_chunk, scale=scale,
                                     remat=cfg.remat != "none", rules=rules,
                                     blocking=cfg.attn_blocking,
                                     scores_dtype=sdt)
        new_cache = cache
        if paged:
            t = table.shape[1] * cache["kv_c"].shape[1]
            ln = (jnp.full((b,), s, jnp.int32) if lengths is None
                  else as_pos_vector(lengths, b))
            row_idx = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
            valid = (row_idx < ln[:, None]) & (row_idx < t)
            with _scope("kv_write"):
                new_cache = {
                    "kv_c": paged_scatter(cache["kv_c"], table, kv_c,
                                          row_idx, valid),
                    "k_rope": paged_scatter(cache["k_rope"], table, k_r,
                                            row_idx, valid),
                }
        elif cache is not None:
            with _scope("kv_write"):
                new_cache = {
                    "kv_c": lax.dynamic_update_slice(
                        cache["kv_c"], kv_c.astype(cache["kv_c"].dtype),
                        (0, 0, 0)),
                    "k_rope": lax.dynamic_update_slice(
                        cache["k_rope"], k_r.astype(cache["k_rope"].dtype),
                        (0, 0, 0)),
                }
    else:
        # Absorbed decode: score against the compressed cache directly,
        # at per-sequence write positions. The same path serves the
        # S-token speculative verify window (rows at pos+i, per-row
        # causal masks) — the absorbed einsums are S-generic, so every
        # verify row reproduces its decode step's float op order.
        pos = as_pos_vector(pos, b)
        row_idx = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        if paged:
            t = table.shape[1] * cache["kv_c"].shape[1]
            valid = row_idx < t    # unallocated pages drop via sentinel
            with _scope("kv_write"):
                ckp = paged_scatter(cache["kv_c"], table, kv_c, row_idx,
                                    valid)
                crp = paged_scatter(cache["k_rope"], table, k_r, row_idx,
                                    valid)
            new_cache = {"kv_c": ckp, "k_rope": crp}
            with _scope("attn"):
                ck = paged_view(ckp, table)
                cr = paged_view(crp, table)
        else:
            with _scope("kv_write"):
                ck = _scatter_rows_multi(cache["kv_c"], kv_c, row_idx)
                cr = _scatter_rows_multi(cache["k_rope"], k_r, row_idx)
            new_cache = {"kv_c": ck, "k_rope": cr}
        t = ck.shape[1]
        with _scope("attn"):
            w_uk = p["w_uk"]["w"].astype(dtype).reshape(
                m.kv_lora_rank, h, dn)
            # absorb: q' = q_n @ w_uk^T  -> [B,S,H,lora]
            q_abs = jnp.einsum("bshd,lhd->bshl", q_n, w_uk)
            scores = (jnp.einsum("bshl,btl->bhst", q_abs, ck,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bshd,btd->bhst", q_r, cr,
                                   preferred_element_type=jnp.float32)) * scale
            k_pos = jnp.arange(t)
            mask = k_pos[None, None, :] <= row_idx[:, :, None]     # [B,S,T]
            scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
            wts = jax.nn.softmax(scores, axis=-1)
            ctx = jnp.einsum("bhst,btl->bshl", wts.astype(ck.dtype), ck,
                             preferred_element_type=jnp.float32)
            w_uv = p["w_uv"]["w"].astype(jnp.float32).reshape(
                m.kv_lora_rank, h, dv)
            attn = jnp.einsum("bshl,lhv->bshv", ctx, w_uv)

    attn = attn.reshape(b, s, h * dv).astype(dtype)
    with _scope("proj/wo"):
        y = dense_apply(p["wo"], attn, ppac=cfg.ppac, mode=mode, dtype=dtype)
    if cache is not None and rules is not None:
        # Same strict-aliasing contract as the GQA path (see gqa_apply).
        cax = ((None, None, None) if paged else MLA_CACHE_AXES["kv_c"])
        new_cache = {kk: constrain(vv, rules, *cax)
                     for kk, vv in new_cache.items()}
    return y, new_cache
