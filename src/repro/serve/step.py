"""Serving steps: donated prefill/decode/generation + PPAC weight conversion.

Generation is *device-resident*: every jitted entry point donates the KV
cache pytree (``donate_argnums``), so per-step cache writes lower to
in-place selects, update slices or (paged) scatters instead of
whole-cache copies —
the data-movement tax the paper's weight-stationary premise (§III) exists
to avoid, and exactly the invariant tests/test_generate.py asserts on the
lowered HLO (every cache leaf carries an aliasing attribute). On top of
the per-step path, :func:`generate_scan` fuses N decode steps *and* the
sampling (greedy / temperature / top-k) into one ``lax.scan`` program —
one dispatch for the whole generation instead of one per token.

``convert_params_for_serving`` is the PPAC load path: projection weights
become resident quantized containers (int8 / packed4 / packed1), exactly
the paper's weight-stationary premise — the decode memory-roofline lever
measured in EXPERIMENTS.md §Perf. ``serving_cycle_report`` prices the
converted model in emulated PPAC cycles per decoded token (the §III-C
K·L accounting aggregated over every projection of a step).
"""
from __future__ import annotations

import functools
import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..configs.base import ModelConfig
from ..core.cost_model import ProjectionCost, ServingCycleReport
from ..core.engine import QuantContainer, pack_weight_for_serving
from ..core.ppac import PPACConfig
from ..obs import ledger as _flight
from ..models import lm
from ..sharding.rules import ShardingRules


# Every serving program keeps each bf16 rounding the model writes. XLA may
# otherwise skip a bf16 round trip inside a fusion, and how it fuses
# depends on the batch shape: on TPU a row then rounds differently in a
# batch of 8 than in a batch of 4, so a data-parallel mesh (B/n rows per
# device) would serve other tokens than one device.
SERVE_COMPILER_OPTIONS = {"xla_allow_excess_precision": False}


def _serve_jit(fn, donate_argnums=()):
    return jax.jit(fn, donate_argnums=donate_argnums,
                   compiler_options=SERVE_COMPILER_OPTIONS)


def _maybe_cached(factory):
    """lru-cache a jitted-entry-point factory on its hashable args.

    jax.jit caches traces by function identity: a fresh wrapper per call
    would retrace (and recompile) every generation. ModelConfig is a
    frozen dataclass, so (cfg, mode, ...) keys are hashable; unhashable
    ``rules`` objects fall through to an uncached build (sharded callers
    hold on to the returned function themselves)."""
    cached = functools.lru_cache(maxsize=128)(factory)

    @functools.wraps(factory)
    def build(*args):
        try:
            return cached(*args)
        except TypeError:  # unhashable arg (e.g. ShardingRules)
            return factory(*args)
    return build


@_maybe_cached
def _prefill_step_cached(cfg, rules, mode, donate):
    def prefill_step(params, batch, cache, lengths=None):
        return lm.prefill(params, cfg, batch, cache, lengths=lengths,
                          mode=mode, rules=rules)
    return _serve_jit(prefill_step, donate_argnums=(2,) if donate else ())


def make_prefill_step(cfg: ModelConfig, rules: Optional[ShardingRules] = None,
                      mode: str = "float", *, jit: bool = True,
                      donate: bool = True):
    """(params, batch, cache, lengths=None) -> (logits, cache).

    Jitted with the cache donated by default: prefill writes the whole
    prompt into a zero cache, so the input buffers are dead on return.
    ``jit=False`` returns the raw function (the dry-run wraps it in its
    own sharded jit)."""
    if not jit:
        def prefill_step(params, batch, cache, lengths=None):
            return lm.prefill(params, cfg, batch, cache, lengths=lengths,
                              mode=mode, rules=rules)
        return prefill_step
    return _prefill_step_cached(cfg, rules, mode, donate)


@_maybe_cached
def _decode_step_cached(cfg, rules, mode, donate):
    def decode_step(params, tokens, cache):
        return lm.decode_step(params, cfg, tokens, cache, mode=mode,
                              rules=rules)
    return _serve_jit(decode_step, donate_argnums=(2,) if donate else ())


def make_decode_step(cfg: ModelConfig, rules: Optional[ShardingRules] = None,
                     mode: str = "float", *, jit: bool = True,
                     donate: bool = True):
    """(params, tokens, cache) -> (logits, cache), cache donated.

    Donation is what makes the per-layer cache update an in-place
    write: without it XLA must copy every cache leaf per layer per
    token to preserve the (dead) input buffers."""
    if not jit:
        def decode_step(params, tokens, cache):
            return lm.decode_step(params, cfg, tokens, cache, mode=mode,
                                  rules=rules)
        return decode_step
    return _decode_step_cached(cfg, rules, mode, donate)


# -- fused sampling ------------------------------------------------------------

def _scale_logits(logits, *, temperature: float, top_k: int):
    """Sampling pre-scale: temperature division + optional top-k mask.
    Shared by the fused sampler and speculative accept/reject, which must
    see the *same* distributions the sampler draws from."""
    scaled = logits.astype(jnp.float32) / temperature
    if top_k:
        kth = lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    return scaled


def sample_tokens(logits, key, *, temperature: float = 0.0, top_k: int = 0):
    """logits [B,V] -> tokens [B] int32, on device.

    temperature == 0 -> greedy argmax (key unused); otherwise softmax
    sampling at ``temperature``, optionally restricted to the ``top_k``
    highest-scoring tokens. Static python knobs: each setting is its own
    compiled program, fused into the decode step / scan body, under the
    named scope ``sample``."""
    with jax.named_scope("sample"):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = _scale_logits(logits, temperature=temperature, top_k=top_k)
        return jax.random.categorical(key, scaled,
                                      axis=-1).astype(jnp.int32)


@_maybe_cached
def _decode_select_cached(cfg, rules, mode, temperature, top_k, donate):
    def step(params, tokens, cache, key):
        logits, cache = lm.decode_step(params, cfg, tokens, cache,
                                       mode=mode, rules=rules)
        nxt = sample_tokens(logits[:, -1], key, temperature=temperature,
                            top_k=top_k)
        return nxt, cache
    return _serve_jit(step, donate_argnums=(2,) if donate else ())


def make_decode_select_step(cfg: ModelConfig,
                            rules: Optional[ShardingRules] = None,
                            mode: str = "float", *,
                            temperature: float = 0.0, top_k: int = 0,
                            donate: bool = True):
    """(params, tokens [B,1], cache, key) -> (next [B] int32, cache).

    One fused, cache-donating dispatch per token: decode + token
    selection stay on device — the host never sees logits, only the [B]
    token ids it actually needs (EOS/retirement decisions)."""
    return _decode_select_cached(cfg, rules, mode, temperature, top_k,
                                 donate)


@_maybe_cached
def _prefill_select_cached(cfg, rules, mode, temperature, top_k, paged,
                           history, donate):
    if not paged:
        def step(params, tokens, lengths, cache, key):
            logits, cache = lm.prefill(params, cfg, {"tokens": tokens},
                                       cache, lengths=lengths, mode=mode,
                                       rules=rules)
            tok = sample_tokens(logits[:, -1], key, temperature=temperature,
                                top_k=top_k)
            return tok, cache
        return _serve_jit(step, donate_argnums=(3,) if donate else ())

    def step(params, tokens, lengths, starts, slot_ids, table_rows, cache,
             key):
        logits, cache = lm.prefill(
            params, cfg, {"tokens": tokens}, cache, lengths=lengths,
            mode=mode, rules=rules, start=starts if history else None,
            history=history, table=table_rows, slot_ids=slot_ids)
        tok = sample_tokens(logits[:, -1], key, temperature=temperature,
                            top_k=top_k)
        return tok, cache
    return _serve_jit(step, donate_argnums=(6,) if donate else ())


def make_prefill_select_step(cfg: ModelConfig,
                             rules: Optional[ShardingRules] = None,
                             mode: str = "float", *,
                             temperature: float = 0.0, top_k: int = 0,
                             paged: bool = False, history: bool = False,
                             donate: bool = True):
    """Fused prefill + first-token selection, cache donated.

    Contiguous (``paged=False``):
        (params, tokens, lengths, cache, key) -> (tok0 [B], cache)
    prefills a scratch cache whose rows the server copies into resident
    slots.

    Paged (``paged=True``): the cache IS the resident pool pytree —
        (params, tokens, lengths, starts, slot_ids, table_rows, cache,
         key) -> (tok0 [B], cache)
    writes the admitted group's KV straight through ``table_rows``
    [B, n_pages] into the shared pools (no scratch cache, no copy) and
    scatters end positions at ``slot_ids``. ``history=True`` compiles
    the suffix variant for prefix-cache hits: ``tokens`` hold only the
    un-cached suffix and ``starts`` its absolute offsets."""
    return _prefill_select_cached(cfg, rules, mode, temperature, top_k,
                                  paged, history, donate)


def greedy_generate(params, cfg: ModelConfig, batch, *, steps: int,
                    max_seq: int, mode: str = "float"):
    """Reference per-step generation loop (prefill + greedy decode).

    Legacy path kept as the scan baseline: still one jitted dispatch per
    token, but token selection is fused into the decode step and the
    cache is donated — nothing round-trips to the host between steps
    (the [B, steps] token matrix transfers once, at the end)."""
    b = jax.tree.leaves(batch)[0].shape[0]
    cache, _ = lm.init_cache(cfg, b, max_seq)
    prefill = make_prefill_step(cfg, mode=mode)
    decode = make_decode_select_step(cfg, mode=mode)
    key = jax.random.PRNGKey(0)  # greedy: unused, fixed shape
    logits, cache = prefill(params, batch, cache)
    tok = sample_tokens(logits[:, -1], key)
    out = []
    for _ in range(steps):
        out.append(tok)
        tok, cache = decode(params, tok[:, None], cache, key)
    return jnp.stack(out, axis=1)


@_maybe_cached
def _generate_scan_cached(cfg, steps, rules, mode, temperature, top_k,
                          donate):

    def gen(params, logits, cache, key):
        key, k0 = jax.random.split(key)
        tok0 = sample_tokens(logits[:, -1], k0, temperature=temperature,
                             top_k=top_k)

        def body(carry, _):
            tok, cache, key = carry
            logits, cache = lm.decode_step(params, cfg, tok[:, None], cache,
                                           mode=mode, rules=rules)
            key, ks = jax.random.split(key)
            nxt = sample_tokens(logits[:, -1], ks, temperature=temperature,
                                top_k=top_k)
            return (nxt, cache, key), tok

        (last, cache, _), toks = lax.scan(body, (tok0, cache, key), None,
                                          length=steps)
        return jnp.moveaxis(toks, 0, 1), cache
    return _serve_jit(gen, donate_argnums=(2,) if donate else ())


def make_generate_scan(cfg: ModelConfig, *, steps: int,
                       rules: Optional[ShardingRules] = None,
                       mode: str = "float", temperature: float = 0.0,
                       top_k: int = 0, donate: bool = True):
    """One on-device program for the whole generation tail.

    (params, logits [B,1,V], cache, key) -> (tokens [B, steps], cache):
    samples the first token from the prefill logits, then runs ``steps``
    decode steps inside a single ``lax.scan`` with sampling fused in.
    The cache is donated and scan-carried, so every per-layer cache
    update is an in-place write — no cache-sized copy anywhere in the
    program — and the host pays one dispatch for N tokens."""
    return _generate_scan_cached(cfg, steps, rules, mode, temperature,
                                 top_k, donate)


def generate_scan(params, cfg: ModelConfig, batch, *, steps: int,
                  max_seq: int, mode: str = "float",
                  temperature: float = 0.0, top_k: int = 0, key=None,
                  rules: Optional[ShardingRules] = None,
                  return_cache: bool = False):
    """Device-resident generation: prefill + one fused N-step scan.

    Semantics match :func:`greedy_generate` at temperature 0 (token i is
    sampled from the logits *before* decode step i), with temperature /
    top-k sampling available via the fused sampler. Returns [B, steps]
    int32 tokens (and the final cache with ``return_cache``)."""
    b = jax.tree.leaves(batch)[0].shape[0]
    cache, _ = lm.init_cache(cfg, b, max_seq)
    prefill = make_prefill_step(cfg, rules, mode)
    gen = make_generate_scan(cfg, steps=steps, rules=rules, mode=mode,
                             temperature=temperature, top_k=top_k)
    logits, cache = prefill(params, batch, cache)
    key = _default_key(key, temperature, "generate_scan")
    toks, cache = gen(params, logits, cache, key)
    return (toks, cache) if return_cache else toks


def _default_key(key, temperature: float, where: str):
    """PRNG-key hygiene for the generation entry points: greedy decoding
    never consumes the key, but at ``temperature > 0`` a silently shared
    default key makes every call return identical samples — warn loudly
    instead of handing back deterministic 'randomness'."""
    if key is not None:
        return key
    if temperature > 0.0:
        warnings.warn(
            f"{where}: temperature={temperature} > 0 with no PRNG key — "
            "falling back to jax.random.PRNGKey(0), so every call returns "
            "IDENTICAL samples. Pass an explicit key= to sample.",
            stacklevel=3)
    return jax.random.PRNGKey(0)


# -- self-speculative decoding on the precision ladder -------------------------
#
# The same resident QuantContainer serves two rungs of the paper's
# precision ladder: the packed1 rung (one 8-cycle XNOR pass, §III-C) and
# the multi-bit target rung (K·L bit-plane-pair passes, e.g. 8x that for
# packed4/int8 inputs). A speculative round drafts k tokens with the cheap
# rung via
# the existing fused decode scan, then verifies all k+1 positions in ONE
# batched target-rung launch — the fused kernels are batch-oblivious, so
# verification prices like a single wide MVP, not k+1 decode steps — and
# accepts the longest matching prefix on device. Greedy outputs are
# bit-identical to target-rung-only decoding; at temperature > 0 the
# standard speculative rejection-sampling rule keeps the output
# distribution exactly the target rung's.


def _spec_round(params, cfg, tok, cache, key, *, draft_k: int, mode: str,
                rules, temperature: float, top_k: int):
    """One fused draft -> verify -> accept round.

    tok: [B] pending tokens (already emitted; logits not yet computed) at
    positions ``cache['pos']``. Returns ``(emitted [B, draft_k+1],
    n_emit [B] in [1, draft_k+1], cache)``: ``emitted[:, :n_emit]`` are
    this round's new tokens and ``emitted[b, n_emit[b]-1]`` the next
    pending token. The draft phase runs on a functional branch of the
    cache (its packed1-rung KV writes are discarded); verify writes all
    k+1 positions' target-rung KV and the accept step rewinds ``pos`` to
    the accepted prefix (ring caches also restore rejected slots).
    """
    b = tok.shape[0]
    k = draft_k
    start = jnp.broadcast_to(jnp.asarray(cache["pos"], jnp.int32), (b,))
    kd, ka, kc = jax.random.split(key, 3)

    draft_toks = draft_scaled = None
    if k:
        def dbody(carry, ks):
            t, c = carry
            with _flight.phase("draft", window=1):
                logits, c = lm.decode_step(params, cfg, t[:, None], c,
                                           mode="draft", rules=rules)
            lg = logits[:, -1]
            if temperature <= 0.0:
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                return (nxt, c), (nxt, lg)
            sc = _scale_logits(lg, temperature=temperature, top_k=top_k)
            nxt = jax.random.categorical(ks, sc, axis=-1).astype(jnp.int32)
            return (nxt, c), (nxt, sc)

        _, (dt, dsc) = lax.scan(dbody, (tok, cache),
                                jax.random.split(kd, k))
        draft_toks = jnp.moveaxis(dt, 0, 1)          # [B, k]
        draft_scaled = jnp.moveaxis(dsc, 0, 1)       # [B, k, V]
        window = jnp.concatenate([tok[:, None], draft_toks], axis=1)
    else:
        window = tok[:, None]

    with _flight.phase("verify", window=k + 1):
        vlogits, vcache = lm.verify(params, cfg, window, cache, mode=mode,
                                    rules=rules)

    if temperature <= 0.0:
        # exact greedy match: accept drafts while d_j == argmax(p_{j-1})
        g = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)   # [B, k+1]
        if k:
            match = (draft_toks == g[:, :k]).astype(jnp.int32)
            a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # [B] in [0,k]
        else:
            a = jnp.zeros((b,), jnp.int32)
        correction = jnp.take_along_axis(g, a[:, None], axis=1)
    else:
        vsc = _scale_logits(vlogits, temperature=temperature, top_k=top_k)
        p = jax.nn.softmax(vsc, axis=-1)                     # [B, k+1, V]
        if k:
            q = jax.nn.softmax(draft_scaled, axis=-1)        # [B, k, V]
            pd = jnp.take_along_axis(p[:, :k], draft_toks[..., None],
                                     axis=-1)[..., 0]        # p_{j-1}(d_j)
            qd = jnp.take_along_axis(q, draft_toks[..., None],
                                     axis=-1)[..., 0]        # q_{j-1}(d_j)
            u = jax.random.uniform(ka, (b, k))
            acc = (u * qd < pd).astype(jnp.int32)            # u < p/q
            a = jnp.sum(jnp.cumprod(acc, axis=1), axis=1)
            q_ext = jnp.concatenate(
                [q, jnp.zeros_like(p[:, :1])], axis=1)       # bonus: q = 0
        else:
            a = jnp.zeros((b,), jnp.int32)
            q_ext = jnp.zeros_like(p)
        # first rejected (or bonus) slot: sample the residual max(p-q, 0)
        p_row = jnp.take_along_axis(p, a[:, None, None], axis=1)[:, 0]
        q_row = jnp.take_along_axis(q_ext, a[:, None, None], axis=1)[:, 0]
        r = jnp.maximum(p_row - q_row, 0.0)
        tot = jnp.sum(r, axis=-1, keepdims=True)
        r = jnp.where(tot > 0.0, r, p_row)    # p <= q pointwise: fall back
        correction = jax.random.categorical(
            kc, jnp.log(r), axis=-1).astype(jnp.int32)[:, None]

    n_emit = a + 1
    if k:
        ext_d = jnp.concatenate(
            [draft_toks, jnp.zeros((b, 1), jnp.int32)], axis=1)
        emitted = jnp.where(
            jnp.arange(k + 1, dtype=jnp.int32)[None, :] == a[:, None],
            correction, ext_d)
    else:
        emitted = correction

    new_pos = start + n_emit
    if cfg.sliding_window and "table" not in cache:
        # ring caches: rejected verify rows landed in slots whose old
        # content is still in-window for later steps — restore them from
        # the pre-round snapshot (the functional `cache` value)
        vcache = lm.rollback_ring_cache(cfg, cache, vcache, start, new_pos,
                                        k + 1)
    else:
        vcache = dict(vcache)
        vcache["pos"] = new_pos
    return emitted, n_emit, vcache


@_maybe_cached
def _speculative_decode_step_cached(cfg, rules, mode, draft_k, temperature,
                                    top_k, donate):
    def step(params, tok, cache, key):
        return _spec_round(params, cfg, tok, cache, key, draft_k=draft_k,
                           mode=mode, rules=rules, temperature=temperature,
                           top_k=top_k)
    return _serve_jit(step, donate_argnums=(2,) if donate else ())


def make_speculative_decode_step(cfg: ModelConfig,
                                 rules: Optional[ShardingRules] = None,
                                 mode: str = "float", *, draft_k: int = 4,
                                 temperature: float = 0.0, top_k: int = 0,
                                 donate: bool = True):
    """(params, tok [B], cache, key) -> (emitted [B, k+1], n_emit [B],
    cache) — one speculative round as a single fused, cache-donating
    dispatch, the continuous-batching server's unit of work under
    ``--spec-decode``: the host pays one dispatch and retires up to
    ``draft_k + 1`` tokens per slot (variable per round, ``n_emit``)."""
    return _speculative_decode_step_cached(cfg, rules, mode, draft_k,
                                           temperature, top_k, donate)


@_maybe_cached
def _speculative_scan_cached(cfg, steps, draft_k, rules, mode, temperature,
                             top_k, donate):
    width = steps + draft_k + 1

    def gen(params, logits, cache, key):
        key, k0 = jax.random.split(key)
        tok0 = sample_tokens(logits[:, -1], k0, temperature=temperature,
                             top_k=top_k)
        b = tok0.shape[0]
        out = jnp.zeros((b, width), jnp.int32).at[:, 0].set(tok0)
        off = jnp.ones((b,), jnp.int32)

        def cond(carry):
            return jnp.min(carry[4]) < steps

        def body(carry):
            tok, cache, key, out, off = carry
            key, kr = jax.random.split(key)
            emitted, n_emit, cache = _spec_round(
                params, cfg, tok, cache, kr, draft_k=draft_k, mode=mode,
                rules=rules, temperature=temperature, top_k=top_k)
            idx = jnp.arange(draft_k + 1, dtype=jnp.int32)[None, :]
            col = jnp.where(idx < n_emit[:, None], off[:, None] + idx,
                            width)                   # rejected/past: drop
            out = out.at[jnp.arange(b)[:, None], col].set(emitted,
                                                          mode="drop")
            tok = jnp.take_along_axis(emitted, (n_emit - 1)[:, None],
                                      axis=1)[:, 0]
            return (tok, cache, key, out, off + n_emit)

        _, cache, _, out, _ = lax.while_loop(cond, body,
                                             (tok0, cache, key, out, off))
        return out[:, :steps], cache
    return _serve_jit(gen, donate_argnums=(2,) if donate else ())


def make_speculative_scan(cfg: ModelConfig, *, steps: int, draft_k: int = 4,
                          rules: Optional[ShardingRules] = None,
                          mode: str = "float", temperature: float = 0.0,
                          top_k: int = 0, donate: bool = True):
    """One on-device program for a speculative generation tail.

    (params, logits [B,1,V], cache, key) -> (tokens [B, steps], cache):
    samples the first token from the prefill logits, then loops
    draft(k, packed1 rung) -> verify(k+1, one batched target launch) ->
    accept rounds in a ``lax.while_loop`` until every sequence holds
    ``steps`` tokens. Fixed shapes throughout: each round scatters its
    variable-length accepted prefix into the [B, steps + k + 1] output
    buffer (rejected slots route out of range and drop). The cache is
    donated and loop-carried; outputs match :func:`make_generate_scan`
    on the target rung exactly (bit-identical at temperature 0,
    distribution-identical above)."""
    return _speculative_scan_cached(cfg, steps, draft_k, rules, mode,
                                    temperature, top_k, donate)


def speculative_generate(params, cfg: ModelConfig, batch, *, steps: int,
                         max_seq: int, draft_k: int = 4,
                         mode: str = "float", temperature: float = 0.0,
                         top_k: int = 0, key=None,
                         rules: Optional[ShardingRules] = None,
                         return_cache: bool = False):
    """Device-resident speculative generation: prefill + one fused
    draft/verify/accept loop. Drop-in for :func:`generate_scan` — same
    [B, steps] output (bit-identical at temperature 0), fewer target-rung
    sequential steps when the packed1 drafts keep being accepted."""
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError("speculative decoding needs a token-indexed KV "
                         "cache; SSM/hybrid state cannot rewind")
    b = jax.tree.leaves(batch)[0].shape[0]
    cache, _ = lm.init_cache(cfg, b, max_seq)
    prefill = make_prefill_step(cfg, rules, mode)
    gen = make_speculative_scan(cfg, steps=steps, draft_k=draft_k,
                                rules=rules, mode=mode,
                                temperature=temperature, top_k=top_k)
    logits, cache = prefill(params, batch, cache)
    key = _default_key(key, temperature, "speculative_generate")
    toks, cache = gen(params, logits, cache, key)
    return (toks, cache) if return_cache else toks


# -- PPAC serving conversion ---------------------------------------------------

_PPAC_ELIGIBLE = ("wq", "wk", "wv", "wo", "wi", "wg", "w_q", "w_uk", "w_uv",
                  "in_proj", "out_proj")

# Same-input projections fused into ONE resident container per layer (the
# grouped serving fast path): attention's q/k/v and the SwiGLU up/gate pair.
_PPAC_GROUPS = (("wqkv", ("wq", "wk", "wv")), ("wig", ("wi", "wg")))


def convert_params_for_serving(params, cfg: ModelConfig, *,
                               group: bool = True,
                               store_shadow: Optional[bool] = None,
                               draft: bool = False):
    """Replace large projection weights with resident PPAC containers.

    Only 2-D weight leaves under eligible projection names are converted
    (norms, SSD internals stay float). Works on stacked (scan) params by
    vmapping the packer over the layer axis. The embedding table is
    stored in the compute dtype: both of its readers (the token lookup
    and the tied head) cast it there first, so the served values are the
    same, and no step writes a compute-dtype copy of the whole table.

    With ``group`` (the default), same-input projection trios/pairs
    (wq/wk/wv -> ``wqkv``, wi/wg -> ``wig``) whose members are ALL
    individually eligible and bias-free are column-concatenated and packed
    as one grouped container (``splits`` records the member widths) —
    halving decode-step kernel launches while staying bit-identical to the
    per-projection containers (quantization scales are per output
    channel). ``group=False`` keeps the per-projection layout, e.g. for
    sharding-spec trees that must mirror the init-time param structure.
    ``store_shadow`` forwards to :func:`pack_weight_for_serving`.

    With ``draft`` each multi-bit container also carries a resident
    packed1 (binarized) rung of the SAME weight — the cheap end of the
    precision ladder — enabling self-speculative decoding
    (:func:`make_speculative_scan`) with zero extra conversions at serve
    time.
    """
    ppac = cfg.ppac
    if not ppac.enabled:
        return params

    pack = functools.partial(pack_weight_for_serving,
                             weight_bits=ppac.weight_bits,
                             weight_format=ppac.weight_format,
                             store_shadow=store_shadow, draft=draft)

    def eligible(leaf):
        ndim = getattr(leaf, "ndim", 0)
        if ndim == 2:
            return min(leaf.shape) >= ppac.min_features
        if ndim == 3:  # stacked over layers
            return min(leaf.shape[1:]) >= ppac.min_features
        return False

    def pack_leaf(leaf, splits=None):
        p = functools.partial(pack, splits=splits)
        return p(leaf) if leaf.ndim == 2 else jax.vmap(p)(leaf)

    def groupable(sub):
        """A bias-free {'w': float leaf} projection dict."""
        return (isinstance(sub, dict) and set(sub) == {"w"}
                and not isinstance(sub["w"], QuantContainer)
                and eligible(sub["w"]))

    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {k: walk(v) for k, v in node.items()}
        if group:
            for gname, members in _PPAC_GROUPS:
                subs = [out.get(m) for m in members]
                if not all(groupable(s) for s in subs):
                    continue
                ws = [s["w"] for s in subs]
                if (len({w.ndim for w in ws}) != 1
                        or len({w.shape[:-1] for w in ws}) != 1):
                    continue  # mismatched in-dims / stacking: keep separate
                splits = tuple(int(w.shape[-1]) for w in ws)
                wcat = jnp.concatenate(ws, axis=-1)
                out[gname] = {"w": pack_leaf(wcat, splits=splits)}
                for m in members:
                    del out[m]
        for k, v in out.items():
            if (k in _PPAC_ELIGIBLE and isinstance(v, dict)
                    and not isinstance(v.get("w"), QuantContainer)
                    and eligible(v.get("w"))):
                out[k] = {**v, "w": pack_leaf(v["w"])}
        return out

    out = walk(params)
    if "embed" in out:
        out["embed"] = {**out["embed"], "table": out["embed"]["table"]
                        .astype(jnp.dtype(cfg.dtype))}
    return out


# -- tile-plan autotuning ------------------------------------------------------

def autotune_serving_plans(params, cfg: ModelConfig, *, batch: int,
                           verbose: bool = False):
    """Measure-and-persist tile plans for every distinct packed projection
    shape of a converted model (refresh with a different decode batch by
    re-running; keyed on shape × platform in the plan cache).

    Only the 'pallas' lowering consults tile plans, so this is meaningful
    on TPU (off-TPU it still runs — interpret-mode timings — and exercises
    the cache plumbing). Returns {(mode, b, m, w): blocks}.
    """
    from ..core.formats import packed_width
    from ..kernels import tiling
    from ..kernels.bitserial_mvp.ops import ppac_matmul_resident

    flat, _ = jax.tree_util.tree_flatten(
        params, is_leaf=lambda x: isinstance(x, QuantContainer))
    shapes = {}
    for leaf in flat:
        if not isinstance(leaf, QuantContainer) \
                or leaf.kind not in ("packed1", "packed4"):
            continue
        base, d_out, d_in = _container_geometry(leaf)
        if leaf.kind == "packed1":
            k_bits, l_bits, fa, fx = 1, 1, "oddint", "oddint"
        else:
            k_bits, l_bits = leaf.bits, cfg.ppac.act_bits
            fa, fx = leaf.fmt, cfg.ppac.act_format
        has_mask = leaf.kind == "packed4" and \
            leaf.wq.shape[-3] == (leaf.bits or 0) + 1
        shapes[(d_out, d_in, k_bits, l_bits, fa, fx, has_mask)] = None

    tuned = {}
    for (d_out, d_in, k_bits, l_bits, fa, fx, has_mask) in shapes:
        w = packed_width(d_in)
        key = ("bitserial_sliced", batch, d_out, w)
        if key in tuned:
            continue
        x = jnp.zeros((batch, d_in), jnp.int32)
        planes = jnp.zeros((k_bits + has_mask, d_out, w), jnp.uint32)

        def run(plan, x=x, planes=planes, n=d_in, k=k_bits, l=l_bits,
                fa=fa, fx=fx, hm=has_mask):
            return ppac_matmul_resident(
                x, planes, n=n, k_bits=k, l_bits=l, fmt_a=fa, fmt_x=fx,
                a_has_mask=hm, backend="pallas", **plan.blocks)

        plan = tiling.autotune_plan(
            "bitserial_sliced", batch, d_out, w, run,
            candidates=tiling.quick_candidates(batch, d_out, w,
                                               "bitserial_sliced"), reps=2)
        tuned[key] = plan.blocks
        if verbose:
            print(f"autotuned bitserial_sliced b={batch} m={d_out} w={w} "
                  f"-> {plan.blocks}")
    return tuned


# -- PPAC cycle accounting -----------------------------------------------------

def _container_geometry(c: QuantContainer):
    """(base_ndim, d_out, d_in) of one (possibly layer-stacked) container."""
    wq = c.wq
    if c.kind == "packed1":
        base, d_out = 2, wq.shape[-2]
        d_in = c.n_in or wq.shape[-1] * 32
    elif c.kind == "packed4":
        base, d_out = 3, wq.shape[-2]
        d_in = c.n_in or wq.shape[-1] * 32
    else:  # int8 / bf16: [in, out] rows
        base, d_out = 2, wq.shape[-1]
        d_in = c.n_in or wq.shape[-2]
    return base, d_out, d_in


def serving_cycle_report(params, cfg: ModelConfig, *,
                         config: Optional[PPACConfig] = None,
                         parallel_arrays: Optional[int] = None
                         ) -> ServingCycleReport:
    """Per-token PPAC cycle accounting over every quantized projection.

    Each K-bit container costs K·L tile-grid cycles per streamed token
    (packed1: K=L=1, one XNOR pass), aggregated across (possibly
    layer-stacked) projections — a full LM decode step priced in the
    paper's §III-C accounting. Grouped containers (wqkv/wig) are priced
    at their *fused* [sum(out), in] shape — one virtualized tile-grid
    scan for the whole group, which is exactly what the fast path
    launches (and ≤ the per-projection sum, since row tiles amortize
    across members). int8 containers run on the MXU fallback, not the
    fused kernels; they are reported with ``fused=False`` at their
    would-be K=8 bit-serial cost. bf16 containers are not PPAC-executable
    and are skipped.

    The accounting is a *ledger replay*: each projection synthesizes the
    exact LaunchRecord (``obs.ledger.record_for``, batch=1) that one
    streamed token emits through the instrumented dispatch chokepoint, so
    this static estimate and a recorded flight ledger share one costing
    function and cannot diverge (tests/test_obs.py asserts bit-exact
    agreement across every container kind).
    """
    hw = config or PPACConfig()
    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, QuantContainer))
    entries = []
    for path, leaf in flat:
        if not isinstance(leaf, QuantContainer) or leaf.kind == "bf16":
            continue
        name = "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                        for p in path)
        base, d_out, d_in = _container_geometry(leaf)
        if leaf.kind == "packed1":
            k_bits, l_bits = 1, 1
        else:
            k_bits = leaf.bits or 8
            l_bits = cfg.ppac.act_bits
        count = (int(np.prod(leaf.wq.shape[: leaf.wq.ndim - base]))
                 if leaf.wq.ndim > base else 1)
        mode = ("mvp_int8_mxu" if leaf.kind == "int8"
                else "mvp_multibit_resident")
        rec = _flight.record_for(
            mode, "replay", batch=1, m_rows=d_out, n_bits=d_in,
            k_bits=k_bits, l_bits=l_bits, config=hw,
            parallel_arrays=parallel_arrays)
        entries.append(ProjectionCost(
            name=name, kind=leaf.kind, d_in=d_in, d_out=d_out,
            k_bits=k_bits, l_bits=l_bits, count=count,
            cycles=count * rec.cycles,
            fused=leaf.kind in ("packed1", "packed4"),
            energy_nj=count * rec.energy_nj))
    return ServingCycleReport(projections=tuple(entries), config=hw)
