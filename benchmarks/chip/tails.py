"""End-to-end metric arithmetic over a run's raw token timestamps.

Every output token carries the harness's clock at the return of the
``tick()`` that delivered it, plus the server's prefill-batch counter read
at that moment. From those alone:

* ``output_tok_s``: every token delivered inside the window over the
  window's whole length;
* ``itl_p50_ms``, ``itl_p95_ms``, ``itl_p99_ms``: the median, 95th and
  99th percentiles of every gap between consecutive tokens of a request
  with both tokens inside the window (a gap that straddles the window's
  start is left out);
* ``ttft_p95_s``: the 95th percentile, over every request due inside the
  window, of first token time minus due time. A request with no first
  token by the end of the drain (failed, shed or unserved) counts as
  missing: its time is taken as at least the drain's end, and it is
  counted in ``missing``;
* ``prefill_stall_share``: the share of those gaps during which a
  prefill batch ran.

Percentiles are nearest-rank over the raw values, never bucketed.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank ``p``-th percentile (the smallest value with at least
    p% of the values at or below it)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    k = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[k - 1]


@dataclasses.dataclass
class Track:
    """One request as the harness saw it."""
    rid: int
    due: Optional[float] = None          # open loop: when it was due
    sent: Optional[float] = None         # when submit() was called
    tokens: List[Tuple[float, int]] = dataclasses.field(default_factory=list)
    outcome: Optional[str] = None        # completed | shed | failed | None

    def add(self, n: int, t: float, prefills: int) -> None:
        self.tokens.extend([(t, prefills)] * n)


def tokens_in(tracks, t0: float, t1: float) -> int:
    return sum(1 for tr in tracks for t, _ in tr.tokens if t0 <= t <= t1)


def gaps_in(tracks, t0: float, t1: float) -> List[Tuple[float, bool]]:
    """(gap seconds, a prefill ran in it) for each consecutive-token gap
    of a request whose earlier and later tokens both lie in [t0, t1]."""
    out = []
    for tr in tracks:
        for (ta, ca), (tb, cb) in zip(tr.tokens, tr.tokens[1:]):
            if t0 <= ta and tb <= t1:
                out.append((tb - ta, cb > ca))
    return out


def ttfts(tracks, t0: float, t1: float, drain_end: float
          ) -> Tuple[List[float], int]:
    """(first token time - due time for every request due in [t0, t1),
    number of those that had no first token: they count at drain_end)."""
    vals, missing = [], 0
    for tr in tracks:
        if tr.due is None or not (t0 <= tr.due < t1):
            continue
        if tr.tokens and tr.outcome != "failed":
            vals.append(tr.tokens[0][0] - tr.due)
        else:
            missing += 1
            vals.append(drain_end - tr.due)
    return vals, missing


def window_metrics(tracks, t0: float, t1: float, *,
                   drain_end: Optional[float] = None) -> Dict[str, float]:
    """The end-to-end numbers of one window [t0, t1] plus the counts
    they rest on."""
    gaps = gaps_in(tracks, t0, t1)
    out: Dict[str, float] = {
        "output_tok_s": tokens_in(tracks, t0, t1) / (t1 - t0),
        "n_gaps": len(gaps),
    }
    if gaps:
        g = [x for x, _ in gaps]
        out["itl_p50_ms"] = percentile(g, 50) * 1e3
        out["itl_p95_ms"] = percentile(g, 95) * 1e3
        out["itl_p99_ms"] = percentile(g, 99) * 1e3
        out["prefill_stall_share"] = \
            100.0 * sum(s for _, s in gaps) / len(gaps)
    if drain_end is not None:
        vals, missing = ttfts(tracks, t0, t1, drain_end)
        out["n_due"], out["n_missing"] = len(vals), missing
        if vals:
            out["ttft_p95_s"] = percentile(vals, 95)
    return out
