"""Unified causal LM covering all ten assigned architectures.

One parameterized model: token/frontend embeddings -> N blocks -> norm ->
logits. Block flavor is dispatched on cfg.family:

  dense/audio/vlm : [GQA|MLA] attention + SwiGLU MLP (pre-norm)
  moe             : attention + (dense MLP for leading layers | MoE)
  ssm             : Mamba2 (SSD) block
  hybrid          : Mamba2 backbone + one *shared* attention+MLP block
                    applied every cfg.hybrid.shared_every layers (Zamba2)

Identical layers are stacked and executed with ``lax.scan`` (small HLO —
essential for the 80-layer dry-runs); heterogeneous prefixes (MoE leading
dense layers) and the hybrid's shared block are handled outside/inside the
scan respectively. Remat policy per cfg.remat.

Entry points:
  init(cfg, key)                      -> (params, axes)
  forward(params, cfg, batch)         -> (logits, aux)        train/eval
  init_cache(cfg, batch, max_seq)     -> (cache, cache_axes)
  prefill(params, cfg, batch, cache)  -> (logits, cache)
  decode_step(params, cfg, tokens, pos, cache) -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig
from ..sharding.rules import ShardingRules, constrain
from . import attention as attn_mod
from . import mamba2 as ssm_mod
from . import moe as moe_mod
from .layers import (
    dense_apply,
    dense_init,
    embed_apply,
    embed_init,
    mlp_apply,
    mlp_init,
    rmsnorm_apply,
    rmsnorm_init,
    unembed_apply,
)

def AUX0():
    return {"lb_loss": jnp.zeros((), jnp.float32),
            "z_loss": jnp.zeros((), jnp.float32)}


def _is_axes(x):
    return x is None or (isinstance(x, tuple)
                         and all(a is None or isinstance(a, str) for a in x))


def _stack_init(init_fn, key, n: int):
    """Stack n i.i.d. block inits along a leading 'layers' axis.

    Axes (static strings) are captured through a side channel so this
    remains traceable under jax.eval_shape (abstract init for the dry-run).
    """
    keys = jax.random.split(key, n)
    box = {}

    def one(k):
        p, a = init_fn(k)
        box["a"] = a
        return p

    p = jax.vmap(one)(keys)
    a = jax.tree.map(lambda ax: ("layers",) + tuple(ax) if ax else ("layers",),
                     box["a"], is_leaf=_is_axes)
    return p, a


def abstract_init(cfg: ModelConfig, key=None):
    """(param ShapeDtypeStructs, axes) without allocating anything."""
    key = key if key is not None else jax.random.PRNGKey(0)
    box = {}

    def f(k):
        p, a = init(cfg, k)
        box["a"] = a
        return p

    shapes = jax.eval_shape(f, key)
    return shapes, box["a"]


# -- block definitions --------------------------------------------------------

def _attn_init(key, cfg: ModelConfig):
    return (attn_mod.mla_init(key, cfg) if cfg.mla
            else attn_mod.gqa_init(key, cfg))


def _attn_apply(p, x, cfg, **kw):
    return (attn_mod.mla_apply(p, x, cfg, **kw) if cfg.mla
            else attn_mod.gqa_apply(p, x, cfg, **kw))


def _dense_block_init(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    k1, k2 = jax.random.split(key)
    p, a = {}, {}
    p["ln1"], a["ln1"] = rmsnorm_init(cfg.d_model)
    p["attn"], a["attn"] = _attn_init(k1, cfg)
    p["ln2"], a["ln2"] = rmsnorm_init(cfg.d_model)
    p["mlp"], a["mlp"] = mlp_init(k2, cfg.d_model, d_ff or cfg.d_ff)
    return p, a


def _dense_block_apply(p, x, cfg, *, positions, cache=None, pos=None,
                       lengths=None, mode="float", rules=None, table=None,
                       history=False, verify=False):
    h = rmsnorm_apply(p["ln1"], x, eps=cfg.norm_eps, dtype=jnp.dtype(cfg.dtype))
    att, new_cache = _attn_apply(p["attn"], h, cfg, positions=positions,
                                 cache=cache, pos=pos, lengths=lengths,
                                 mode=mode, rules=rules, table=table,
                                 history=history, verify=verify)
    x = x + att
    x = constrain(x, rules, "batch", "seq", None) if rules else x
    h = rmsnorm_apply(p["ln2"], x, eps=cfg.norm_eps, dtype=jnp.dtype(cfg.dtype))
    x = x + mlp_apply(p["mlp"], h, cfg, mode=mode)
    return x, new_cache, AUX0()


def _moe_block_init(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    p, a = {}, {}
    p["ln1"], a["ln1"] = rmsnorm_init(cfg.d_model)
    p["attn"], a["attn"] = _attn_init(k1, cfg)
    p["ln2"], a["ln2"] = rmsnorm_init(cfg.d_model)
    p["moe"], a["moe"] = moe_mod.moe_init(k2, cfg)
    return p, a


def _moe_block_apply(p, x, cfg, *, positions, cache=None, pos=None,
                     lengths=None, mode="float", rules=None, table=None,
                     history=False, verify=False):
    h = rmsnorm_apply(p["ln1"], x, eps=cfg.norm_eps, dtype=jnp.dtype(cfg.dtype))
    att, new_cache = _attn_apply(p["attn"], h, cfg, positions=positions,
                                 cache=cache, pos=pos, lengths=lengths,
                                 mode=mode, rules=rules, table=table,
                                 history=history, verify=verify)
    x = x + att
    x = constrain(x, rules, "batch", "seq", None) if rules else x
    h = rmsnorm_apply(p["ln2"], x, eps=cfg.norm_eps, dtype=jnp.dtype(cfg.dtype))
    y, aux = moe_mod.moe_apply(p["moe"], h, cfg, mode=mode)
    return x + y, new_cache, aux


def _ssm_block_init(key, cfg: ModelConfig):
    p, a = {}, {}
    p["ln"], a["ln"] = rmsnorm_init(cfg.d_model)
    p["mamba"], a["mamba"] = ssm_mod.mamba2_init(key, cfg)
    return p, a


def _ssm_block_apply(p, x, cfg, *, positions=None, cache=None, pos=None,
                     lengths=None, mode="float", rules=None, table=None,
                     history=False, verify=False):
    assert not verify, "SSM blocks have no token-indexed cache to verify into"
    h = rmsnorm_apply(p["ln"], x, eps=cfg.norm_eps, dtype=jnp.dtype(cfg.dtype))
    y, new_cache = ssm_mod.mamba2_apply(p["mamba"], h, cfg, cache=cache,
                                        mode=mode)
    return x + y, new_cache, AUX0()


# -- model --------------------------------------------------------------------

def _block_fns(cfg: ModelConfig):
    if cfg.family == "ssm" or cfg.family == "hybrid":
        return _ssm_block_init, _ssm_block_apply
    if cfg.family == "moe":
        return _moe_block_init, _moe_block_apply
    return _dense_block_init, _dense_block_apply


def init(cfg: ModelConfig, key) -> Tuple[Dict, Dict]:
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {}
    a: Dict[str, Any] = {}
    p["embed"], a["embed"] = embed_init(ks[0], cfg.vocab, cfg.d_model)
    binit, _ = _block_fns(cfg)

    n_scan = cfg.n_layers
    if cfg.moe and cfg.moe.first_dense_layers:
        nd = cfg.moe.first_dense_layers
        p["dense_layers"], a["dense_layers"] = _stack_init(
            lambda k: _dense_block_init(k, cfg, d_ff=cfg.moe.d_ff_dense
                                        or cfg.d_ff), ks[1], nd)
        n_scan = cfg.n_layers - nd
    p["layers"], a["layers"] = _stack_init(
        lambda k: binit(k, cfg), ks[2], n_scan)

    if cfg.family == "hybrid":
        hp, ha = _dense_block_init(ks[3], cfg, d_ff=cfg.hybrid.shared_d_ff)
        p["shared"], a["shared"] = hp, ha

    if cfg.frontend == "vision":
        p["patch_proj"], a["patch_proj"] = dense_init(
            ks[4], cfg.d_model, cfg.d_model, ("embed", None))

    p["final_norm"], a["final_norm"] = rmsnorm_init(cfg.d_model)
    if not cfg.tie_embeddings:
        p["lm_head"], a["lm_head"] = dense_init(ks[5], cfg.d_model, cfg.vocab,
                                                ("embed", "vocab"))
    return p, a


def _embed_inputs(params, cfg: ModelConfig, batch, rules=None):
    """Merge token/frontend inputs into [B,S,d] activations."""
    dtype = jnp.dtype(cfg.dtype)
    parts = []
    if "embeds" in batch:  # audio frontend stub: precomputed frame embeddings
        parts.append(batch["embeds"].astype(dtype))
    if "patches" in batch:  # vision frontend stub: precomputed patch embeds
        pe = batch["patches"].astype(dtype)
        if "patch_proj" in params:
            pe = dense_apply(params["patch_proj"], pe, dtype=dtype)
        parts.append(pe)
    if "tokens" in batch:
        parts.append(embed_apply(params["embed"], batch["tokens"], dtype=dtype))
    h = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if rules:
        h = constrain(h, rules, "batch", "seq", None)
    return h


def _run_layers(params, cfg: ModelConfig, h, *, positions, caches=None,
                pos=None, lengths=None, mode="float", rules=None,
                layer_offset=0, table=None, history=False, verify=False):
    """Scan (or unroll, for hybrid) the stacked blocks; returns
    (h, new_caches, aux). ``table`` (paged caches) is shared by every
    layer, so it rides as a closure capture, not a scan input.

    The stacked caches ride in the scan's carry, with the layer index as
    the scan input: each layer reads ``stack[l]`` and writes back into
    the stack at ``l``, so the donated buffer is updated in place (as
    ``xs``/``ys`` the scan would slice each layer out and restack it
    into a second whole-cache buffer)."""
    _, bapply = _block_fns(cfg)
    aux = AUX0()

    def body(carry, xs):
        hh, ax, stack = carry
        if caches is None:
            lp, lc = xs, None
        else:
            lp, l = xs
            lc = jax.tree.map(
                lambda c: lax.dynamic_index_in_dim(c, l, keepdims=False),
                stack)
        hh, nc, a2 = bapply(lp, hh, cfg, positions=positions, cache=lc,
                            pos=pos, lengths=lengths, mode=mode, rules=rules,
                            table=table, history=history, verify=verify)
        ax = {k: ax[k] + a2[k] for k in ax}
        if caches is not None:
            with jax.named_scope("kv_write"):
                stack = jax.tree.map(
                    lambda c, n: lax.dynamic_update_index_in_dim(c, n, l, 0),
                    stack, nc)
        return (hh, ax, stack), None

    if cfg.remat == "dots":
        body_fn = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots)
    elif cfg.remat != "none":
        body_fn = jax.checkpoint(body)
    else:
        body_fn = body

    if cfg.family == "hybrid":
        # unrolled: interleave the shared attention block. Each block is
        # individually rematerialized (the unrolled path bypasses the scan
        # body checkpoint).
        def shared_fn(sp, hh, sc):
            return _dense_block_apply(sp, hh, cfg, positions=positions,
                                      cache=sc, pos=pos, lengths=lengths,
                                      mode=mode, rules=rules, table=table,
                                      history=history)

        def block_fn(lp, hh, lc):
            return bapply(lp, hh, cfg, positions=positions, cache=lc,
                          pos=pos, lengths=lengths, mode=mode, rules=rules,
                          table=table, history=history)

        if cfg.remat != "none":
            shared_fn = jax.checkpoint(shared_fn)
            block_fn = jax.checkpoint(block_fn)

        n = jax.tree.leaves(params["layers"])[0].shape[0]
        new_caches = {"layers": [], "shared": []}
        sh_i = 0
        for i in range(n):
            lp = jax.tree.map(lambda t: t[i], params["layers"])
            lc = (jax.tree.map(lambda t: t[i], caches["layers"])
                  if caches is not None else None)
            if i % cfg.hybrid.shared_every == 0:
                sc = (jax.tree.map(lambda t: t[sh_i], caches["shared"])
                      if caches is not None else None)
                h, nsc, a2 = shared_fn(params["shared"], h, sc)
                aux = {k: aux[k] + a2[k] for k in aux}
                if caches is not None:
                    new_caches["shared"].append(nsc)
                sh_i += 1
            h, nc, a2 = block_fn(lp, h, lc)
            aux = {k: aux[k] + a2[k] for k in aux}
            if caches is not None:
                new_caches["layers"].append(nc)
        if caches is not None:
            stack = lambda lst: jax.tree.map(lambda *t: jnp.stack(t), *lst)
            return h, {"layers": stack(new_caches["layers"]),
                       "shared": stack(new_caches["shared"])}, aux
        return h, None, aux

    if caches is None:
        (h, aux, _), _ = lax.scan(body_fn, (h, aux, None), params["layers"])
        return h, None, aux
    n = jax.tree.leaves(params["layers"])[0].shape[0]
    (h, aux, stack), _ = lax.scan(
        body_fn, (h, aux, caches["layers"]),
        (params["layers"], jnp.arange(n, dtype=jnp.int32)))
    if rules is not None:
        # the carry's placement is left to propagation, which may split a
        # paged pool otherwise than the donated input: pin it
        axes = _layer_cache_axes(cfg, paged=table is not None)
        stack = {k: constrain(v, rules, "layers", *axes[k])
                 for k, v in stack.items()}
    return h, {"layers": stack}, aux


def _lm_head(params, cfg: ModelConfig, h):
    """Logits (fp32) of the final hidden states, under the named scope
    ``lm_head``: the tied embedding or the head projection."""
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            return unembed_apply(params["embed"], h,
                                 dtype=jnp.dtype(cfg.dtype))
        return dense_apply(params["lm_head"], h,
                           dtype=jnp.dtype(cfg.dtype)).astype(jnp.float32)


def forward(params, cfg: ModelConfig, batch, *, mode: str = "float",
            rules: Optional[ShardingRules] = None):
    """Training/eval forward: batch -> (logits [B,S,V] fp32, aux)."""
    h = _embed_inputs(params, cfg, batch, rules)
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    aux = AUX0()

    if "dense_layers" in params:
        for i in range(jax.tree.leaves(params["dense_layers"])[0].shape[0]):
            lp = jax.tree.map(lambda t: t[i], params["dense_layers"])
            h, _, a2 = _dense_block_apply(lp, h, cfg, positions=positions,
                                          mode=mode, rules=rules)
            aux = {k: aux[k] + a2[k] for k in aux}

    h, _, a2 = _run_layers(params, cfg, h, positions=positions, mode=mode,
                           rules=rules)
    aux = {k: aux[k] + a2[k] for k in aux}
    h = rmsnorm_apply(params["final_norm"], h, eps=cfg.norm_eps,
                      dtype=jnp.dtype(cfg.dtype))
    logits = _lm_head(params, cfg, h)
    if rules:
        logits = constrain(logits, rules, "batch", None, "vocab")
    return logits, aux


# -- caches -------------------------------------------------------------------

def _layer_cache_axes(cfg: ModelConfig, paged: bool = False):
    """Logical axes of one layer's cache leaves (a scanned block's)."""
    if cfg.family in ("ssm", "hybrid"):
        return ssm_mod.MAMBA2_CACHE_AXES
    if paged:
        return (attn_mod.MLA_PAGED_CACHE_AXES if cfg.mla
                else attn_mod.gqa_paged_cache_axes(cfg))
    return attn_mod.MLA_CACHE_AXES if cfg.mla else attn_mod.gqa_cache_axes(cfg)


def _layer_cache_init(cfg: ModelConfig, batch: int, max_seq: int, dtype):
    if cfg.family in ("ssm", "hybrid"):
        single = ssm_mod.mamba2_cache_init(cfg, batch, dtype)
    elif cfg.mla:
        single = attn_mod.mla_cache_init(cfg, batch, max_seq, dtype)
    else:
        single = attn_mod.gqa_cache_init(cfg, batch, max_seq, dtype)
    return single, _layer_cache_axes(cfg)


def paged_extent(cfg: ModelConfig, max_seq: int) -> int:
    """Logical per-slot token extent a paged table must cover (the
    sliding window bounds it for ring caches)."""
    if cfg.sliding_window:
        return min(max_seq, cfg.sliding_window)
    return max_seq


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16, *, page_size: Optional[int] = None,
               pool_pages: Optional[int] = None):
    """Returns (cache, cache_axes). Layer-stacked; hybrid adds shared-attn
    caches (one per shared-block application).

    With ``page_size`` the KV leaves become paged pools: every layer
    stack holds ``[n_layers, pool_pages, page_size, ...]`` and one shared
    ``table: [batch, extent/page_size]`` int32 maps each slot's logical
    pages to physical ones (a single page id addresses the same page in
    every stack). Fresh tables are filled with the out-of-range sentinel
    ``pool_pages`` — unmapped reads clip (and sit beyond every attention
    mask), unmapped writes drop. ``pool_pages`` defaults to full backing
    (batch * pages_per_slot); smaller pools oversubscribe the slots and
    rely on the server's page allocator."""
    first_dense = cfg.moe.first_dense_layers if cfg.moe else 0
    n_scan = cfg.n_layers - first_dense
    paged = page_size is not None
    if paged:
        if cfg.family in ("ssm", "hybrid"):
            raise ValueError("paged caches are token-indexed; SSM/hybrid "
                             "state caches have no token axis to page")
        extent = paged_extent(cfg, max_seq)
        if extent % page_size:
            raise ValueError(f"page_size={page_size} must divide the "
                             f"logical cache extent {extent}")
        n_pages = extent // page_size
        if pool_pages is None:
            pool_pages = batch * n_pages
        init1 = (attn_mod.mla_paged_cache_init if cfg.mla
                 else attn_mod.gqa_paged_cache_init)
        single = init1(cfg, pool_pages, page_size, dtype)
        axes1 = _layer_cache_axes(cfg, paged=True)
    else:
        single, axes1 = _layer_cache_init(cfg, batch, max_seq, dtype)

    def stack(t, n):
        return jax.tree.map(lambda x: jnp.zeros((n,) + x.shape, x.dtype), t)

    cache = {"layers": stack(single, n_scan)}
    axes = {"layers": jax.tree.map(
        lambda ax: ("layers",) + tuple(ax), axes1, is_leaf=_is_axes)}
    if first_dense:
        if paged:
            dsingle, daxes = single, axes1
        else:
            dsingle = attn_mod.mla_cache_init(cfg, batch, max_seq, dtype) \
                if cfg.mla else attn_mod.gqa_cache_init(cfg, batch,
                                                        max_seq, dtype)
            daxes = attn_mod.MLA_CACHE_AXES if cfg.mla \
                else attn_mod.gqa_cache_axes(cfg)
        cache["dense_layers"] = stack(dsingle, first_dense)
        axes["dense_layers"] = jax.tree.map(
            lambda ax: ("layers",) + tuple(ax), daxes, is_leaf=_is_axes)
    if cfg.family == "hybrid":
        n_shared = (cfg.n_layers + cfg.hybrid.shared_every - 1) \
            // cfg.hybrid.shared_every
        sh = attn_mod.gqa_cache_init(cfg, batch, max_seq, dtype)
        cache["shared"] = stack(sh, n_shared)
        axes["shared"] = jax.tree.map(
            lambda ax: ("layers",) + tuple(ax), attn_mod.GQA_CACHE_AXES,
            is_leaf=_is_axes)
    if paged:
        cache["table"] = jnp.full((batch, n_pages), pool_pages, jnp.int32)
        axes["table"] = ("batch", None)
    # per-sequence decode positions: mixed-progress batches (continuous
    # batching) decode with one fused step
    cache["pos"] = jnp.zeros((batch,), jnp.int32)
    axes["pos"] = ("batch",)
    return cache, axes


def _split_pos(cache):
    c = {k: v for k, v in cache.items() if k not in ("pos", "table")}
    return c, cache["pos"], cache.get("table")


def prefill(params, cfg: ModelConfig, batch, cache, *, lengths=None,
            mode: str = "float", rules: Optional[ShardingRules] = None,
            start=None, history: bool = False, table=None, slot_ids=None):
    """Run the full prompt, filling caches. Returns (logits, cache).

    ``lengths: [B]`` (optional) — per-sequence prompt lengths for
    *right-padded* ragged batches: the returned logits are taken at each
    sequence's last real token, ``cache['pos']`` starts each sequence at
    its own length, and attention-family caches mask the padded tail
    (causal attention makes right-pad bit-exact; SSM state accumulation
    has no position mask, so ragged prefill is attention-only — SSM
    prompts must arrive unpadded).

    Paged caches (a ``table`` leaf) route all KV writes through the page
    table. ``history=True`` is suffix prefill after a prefix-cache hit:
    ``batch['tokens']`` holds only the un-cached suffix, ``start: [B]``
    its absolute offset (shared pages already populate rows [0, start)),
    and attention runs over the full gathered history.

    ``table: [B, n_pages]`` (optional) overrides the cache's own table —
    the group-prefill path prefills B admitted sequences straight into
    the shared pools through their slots' table rows while the resident
    cache keeps all slots' rows; ``slot_ids: [B]`` then scatters the
    end positions into the resident ``pos`` vector."""
    caches, pos0, tbl = _split_pos(cache)
    if table is None:
        table = tbl
    h = _embed_inputs(params, cfg, batch, rules)
    b, s, _ = h.shape
    if start is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    else:
        start = jnp.asarray(start, jnp.int32)
        positions = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    ln = (jnp.full((b,), s, jnp.int32) if lengths is None
          else jnp.asarray(lengths, jnp.int32))
    aux = AUX0()
    new = dict(cache)
    if "dense_layers" in params:
        ncs = []
        for i in range(jax.tree.leaves(params["dense_layers"])[0].shape[0]):
            lp = jax.tree.map(lambda t: t[i], params["dense_layers"])
            lc = jax.tree.map(lambda t: t[i], caches["dense_layers"])
            h, nc, _ = _dense_block_apply(lp, h, cfg, positions=positions,
                                          cache=lc, lengths=ln, mode=mode,
                                          rules=rules, table=table,
                                          history=history)
            ncs.append(nc)
        new["dense_layers"] = jax.tree.map(lambda *t: jnp.stack(t), *ncs)
    h, ncaches, _ = _run_layers(params, cfg, h, positions=positions,
                                caches={k: caches[k] for k in ("layers", "shared")
                                        if k in caches},
                                lengths=ln, mode=mode, rules=rules,
                                table=table, history=history)
    new.update(ncaches)
    h = rmsnorm_apply(params["final_norm"], h, eps=cfg.norm_eps,
                      dtype=jnp.dtype(cfg.dtype))
    h_last = jnp.take_along_axis(h, (ln - 1)[:, None, None], axis=1)
    logits = _lm_head(params, cfg, h_last)
    end = ln if start is None else start + ln
    with jax.named_scope("kv_write"):
        if slot_ids is None:
            new["pos"] = end
        else:
            new["pos"] = pos0.at[jnp.asarray(slot_ids, jnp.int32)].set(
                end, mode="drop")
    return logits, new


def decode_step(params, cfg: ModelConfig, tokens, cache, *,
                mode: str = "float", rules: Optional[ShardingRules] = None):
    """One decode step: tokens [B,1] -> (logits [B,1,V], cache).
    ``cache['pos']`` is a per-sequence [B] vector (mixed-progress batches
    from the continuous-batching server decode in one fused step)."""
    caches, pos, table = _split_pos(cache)
    h = embed_apply(params["embed"], tokens, dtype=jnp.dtype(cfg.dtype))
    b = h.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos[:, None]
    new = dict(cache)
    if "dense_layers" in params:
        ncs = []
        for i in range(jax.tree.leaves(params["dense_layers"])[0].shape[0]):
            lp = jax.tree.map(lambda t: t[i], params["dense_layers"])
            lc = jax.tree.map(lambda t: t[i], caches["dense_layers"])
            h, nc, _ = _moe_or_dense_decode(lp, h, cfg, positions, lc, pos,
                                            mode, rules, dense=True,
                                            table=table)
            ncs.append(nc)
        new["dense_layers"] = jax.tree.map(lambda *t: jnp.stack(t), *ncs)
    h, ncaches, _ = _run_layers(params, cfg, h, positions=positions,
                                caches={k: caches[k] for k in ("layers", "shared")
                                        if k in caches},
                                pos=pos, mode=mode, rules=rules, table=table)
    new.update(ncaches)
    h = rmsnorm_apply(params["final_norm"], h, eps=cfg.norm_eps,
                      dtype=jnp.dtype(cfg.dtype))
    logits = _lm_head(params, cfg, h)
    with jax.named_scope("kv_write"):
        new["pos"] = pos + 1
    return logits, new


def _moe_or_dense_decode(lp, h, cfg, positions, lc, pos, mode, rules, *,
                         dense: bool, table=None, verify=False):
    if dense:
        return _dense_block_apply(lp, h, cfg, positions=positions, cache=lc,
                                  pos=pos, mode=mode, rules=rules,
                                  table=table, verify=verify)
    return _moe_block_apply(lp, h, cfg, positions=positions, cache=lc,
                            pos=pos, mode=mode, rules=rules, table=table,
                            verify=verify)


def verify(params, cfg: ModelConfig, tokens, cache, *, mode: str = "float",
           rules: Optional[ShardingRules] = None):
    """Speculative-verify forward: tokens [B,S] at per-sequence positions
    ``pos + i`` -> (logits [B,S,V] fp32 at EVERY row, cache).

    Row ``i`` runs the exact decode-step compute at position ``pos + i``
    (decode's einsums, masks and KV quantization — see the ``verify``
    branches in models.attention), so its logits bit-match the decode
    step that would consume ``tokens[:, i]`` there. All S rows' target-
    rung K/V are written to the cache and ``pos`` advances by S; the
    caller rewinds ``pos`` to the accepted prefix (linear/paged caches
    need nothing else — rows past ``pos`` sit beyond every mask and are
    overwritten later; ring caches additionally need
    :func:`rollback_ring_cache`). Writes past a paged slot's allocation
    hit the table's out-of-range sentinel and drop."""
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError("speculative verify needs a token-indexed cache; "
                         "SSM/hybrid recurrent state cannot rewind")
    caches, pos, table = _split_pos(cache)
    h = embed_apply(params["embed"], tokens, dtype=jnp.dtype(cfg.dtype))
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    new = dict(cache)
    if "dense_layers" in params:
        ncs = []
        for i in range(jax.tree.leaves(params["dense_layers"])[0].shape[0]):
            lp = jax.tree.map(lambda t: t[i], params["dense_layers"])
            lc = jax.tree.map(lambda t: t[i], caches["dense_layers"])
            h, nc, _ = _moe_or_dense_decode(lp, h, cfg, positions, lc, pos,
                                            mode, rules, dense=True,
                                            table=table, verify=True)
            ncs.append(nc)
        new["dense_layers"] = jax.tree.map(lambda *t: jnp.stack(t), *ncs)
    h, ncaches, _ = _run_layers(params, cfg, h, positions=positions,
                                caches={k: caches[k] for k in ("layers", "shared")
                                        if k in caches},
                                pos=pos, mode=mode, rules=rules, table=table,
                                verify=True)
    new.update(ncaches)
    h = rmsnorm_apply(params["final_norm"], h, eps=cfg.norm_eps,
                      dtype=jnp.dtype(cfg.dtype))
    logits = _lm_head(params, cfg, h)
    with jax.named_scope("kv_write"):
        new["pos"] = pos + s
    return logits, new


def rollback_ring_cache(cfg: ModelConfig, prev, cache, start, new_pos,
                        window: int):
    """Undo a ring cache's rejected verify rows.

    A verify over rows ``start + i`` wrote ALL its window's rows into
    the ring (slot ``(start+i) % t``); rows at positions >= ``new_pos``
    (the accepted end) were rejected, and — unlike linear/paged caches,
    where stale rows sit beyond every mask — their slots must get their
    pre-round content back (``prev``, the cache snapshot from before
    drafting: draft-rung KV writes polluted the same slots). Restores
    the rejected slots of every KV leaf ([L,B,Hkv,*,t], position minor)
    with one select and sets ``pos = new_pos``.
    """
    start = jnp.asarray(start, jnp.int32)
    new_pos = jnp.asarray(new_pos, jnp.int32)
    s = window
    row = start[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # [B,S]
    rejected = row >= new_pos[:, None]

    def one(pv, nv):
        if pv.ndim < 4:          # pos [B] / table [B,n] leaves
            return nv
        t = pv.shape[-1]
        back = jnp.any(rejected[:, :, None]
                       & (row[:, :, None] % t == jnp.arange(t)), axis=1)
        return jnp.where(back[None, :, None, None, :], pv, nv)   # [B,t]

    out = jax.tree.map(one, prev, dict(cache))
    out["pos"] = new_pos
    return out
