"""Seeded traffic for the chip benchmark, driven by one traffic file.

A traffic file (``traffic/<mix>.json``) gives the serving shape (slots,
cache rows, prefill and admission buckets), the length and arrival
distributions, and how many requests the output comparison reads. Every seed serves the same lengths and inter-arrival
gaps in the same order: stratified quantiles of the stated
distributions, in an order fixed by the traffic file alone. The seed
draws the prompt tokens (and, in ``weights.py``, the weights) and which
starting occupant carries which remaining length. So every seed does
the same work: a window never sees a luckier mix of short prompts.

Two loops:

* ``closed_backlog``: the queue never empties. The first ``slots``
  requests are the starting occupants and carry only the *remaining*
  part of their output (a uniform fraction of a drawn length), so that
  retirements start at the steady rate.
* ``open_poisson``: independent users arrive at ``rate_rps`` on a
  Poisson schedule that starts ``lead_in_s`` before the measured window.
"""
from __future__ import annotations

import dataclasses
import json
import os
from statistics import NormalDist
from typing import Dict, Iterator, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# fixed stream ids, so that adding a stream never shifts another
_STREAM_ORDER, _STREAM_TOKENS, _STREAM_RESIDUAL = 1, 2, 3


def load_traffic(name: str) -> dict:
    """``traffic/<name>.json``; a name with a slash is a path under this
    directory (test traffic)."""
    rel = f"{name}.json" if "/" in name else os.path.join("traffic",
                                                          f"{name}.json")
    with open(os.path.join(HERE, rel)) as f:
        spec = json.load(f)
    if spec["prompt"]["min"] < 2:
        raise ValueError("prompts are at least 2 tokens: a length-1 row is "
                         "the server's batch padding")
    return spec


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def stratified_lognormal(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a clipped lognormal, as ints.

    ``spec``: median, sigma, min, max. Quantile i sits at probability
    (i + 0.5) / n, so the multiset depends on ``n`` alone."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    v = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(v, spec["min"], spec["max"]).astype(np.int64)


def poisson_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` stratified exponential inter-arrival gaps at ``rate`` / s."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


@dataclasses.dataclass
class Item:
    """One generated request: prompt token ids, output length, and the
    time it is due relative to the window start (open loop only)."""
    idx: int
    prompt: np.ndarray
    max_new: int
    due_s: Optional[float] = None


class Traffic:
    """Seeded request stream for one traffic file."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec, self.seed, self.vocab = spec, int(seed), int(vocab)
        n = int(spec["pool"])
        order = rng_for(0, _STREAM_ORDER)  # the same for every seed
        self.prompt_lens = order.permutation(
            stratified_lognormal(spec["prompt"], n))
        self.output_lens = order.permutation(
            stratified_lognormal(spec["output"], n))
        self._tokens = rng_for(seed, _STREAM_TOKENS)

    @property
    def slots(self) -> int:
        return int(self.spec["slots"])

    def _item(self, i: int, max_new: int, due_s=None) -> Item:
        """Request ``i``; its output is cut where prompt and output would
        pass ``max_seq``, the cache rows (at most the model's context)."""
        plen = int(self.prompt_lens[i % len(self.prompt_lens)])
        prompt = self._tokens.integers(0, self.vocab, plen, dtype=np.int32)
        max_new = min(int(max_new), int(self.spec["max_seq"]) - plen)
        return Item(i, prompt, max_new, due_s)

    # -- closed backlog ----------------------------------------------------

    def starting_occupants(self) -> List[Item]:
        """The requests that fill every slot before the window: each has
        its drawn length cut to a residual ceil(u * length), with the u of
        the slots stratified over (0, 1) and paired with the lengths in a
        fixed order, so every seed starts from the same residuals."""
        s = self.slots
        u = (np.arange(s) + 0.5) / s
        base = np.sort(stratified_lognormal(self.spec["output"], s))
        pair = np.random.default_rng(0).permutation(s)
        residual = np.maximum(1, np.ceil(u * base[pair])).astype(np.int64)
        # held under the cache rows whatever prompt the seed pairs it with
        residual = np.minimum(residual, int(self.spec["max_seq"])
                              - int(self.spec["prompt"]["max"]))
        residual = rng_for(self.seed, _STREAM_RESIDUAL).permutation(residual)
        return [self._item(i, residual[i]) for i in range(s)]

    def backlog(self, start: int) -> Iterator[Item]:
        """Endless requests after the starting occupants."""
        i = start
        while True:
            yield self._item(i, self.output_lens[i % len(self.output_lens)])
            i += 1

    # -- open loop ------------------------------------------------------------

    def schedule(self, window_s: float) -> List[Item]:
        """Poisson arrivals from ``-lead_in_s`` to the window's end:
        stratified gaps in a fixed order, rescaled so the arrivals span
        exactly ``lead_in_s + window_s``."""
        rate = float(self.spec["rate_rps"])
        span = float(self.spec["lead_in_s"]) + float(window_s)
        n = max(1, int(round(rate * span)))
        gaps = poisson_gaps(rate, n)
        gaps = rng_for(0, _STREAM_ORDER + 16).permutation(gaps)
        gaps *= span / gaps.sum()
        due = np.cumsum(gaps) - gaps[0] - float(self.spec["lead_in_s"])
        return [self._item(i, self.output_lens[i % len(self.output_lens)],
                           float(due[i])) for i in range(n)]


def lateness_report(due: List[float], sent: List[float]) -> Dict[str, float]:
    """How late the generator submitted open-loop requests (seconds)."""
    late = np.maximum(0.0, np.asarray(sent) - np.asarray(due))
    if late.size == 0:
        return {"n": 0, "mean_s": 0.0, "max_s": 0.0}
    return {"n": int(late.size), "mean_s": float(late.mean()),
            "max_s": float(late.max())}

