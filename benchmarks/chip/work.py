"""Operations and bytes of the served model, computed from its shapes.

Two counts, kept apart:

* the work a projection launch *needs* at its launched shape (the
  roofline's numerator): an int8 x 4-bit product of ``rows`` activation
  rows against an [n, m] weight is 2*rows*n*m operations and moves the
  4-bit weight planes, the per-channel float32 scales, the 8-bit
  activations and the int32 outputs. Never the bit-planes or passes a
  particular kernel happens to run, so a later implementation is read
  against the same work;
* the *useful* model operations of a step (the MFU numerator): packed
  projections and the LM head for occupied rows or real prompt tokens
  only, plus attention over each row's live context, 2 per multiply-add.

Peaks come from ``peaks.json`` keyed by ``device_kind``; an unknown
device is an error, never a default.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def projections(m: Dict) -> List[Tuple[str, int, int]]:
    """The packed projection launches of one layer, in program order, as
    (name, n_in, m_out): the grouped q/k/v container, the attention
    output, the grouped up/gate container and the down projection."""
    d, hd = m["d_model"], m["head_dim"]
    nh, nkv, ff = m["n_heads"], m["n_kv_heads"], m["d_ff"]
    return [("wqkv", d, (nh + 2 * nkv) * hd), ("wo", nh * hd, d),
            ("wig", d, 2 * ff), ("down", ff, d)]


def launches_per_step(m: Dict) -> int:
    return len(projections(m)) * m["n_layers"]


def mvp_work(n: int, m_out: int, rows: int, *, weight_bits: int,
             act_bits: int) -> Tuple[float, float]:
    """(operations, bytes) one projection launch needs."""
    ops = 2.0 * rows * n * m_out
    nbytes = (n * m_out * weight_bits / 8 + 4 * m_out
              + rows * n * act_bits / 8 + 4 * rows * m_out)
    return ops, nbytes


def _proj_macs(m: Dict) -> int:
    return sum(n * mo for _, n, mo in projections(m)) * m["n_layers"]


def decode_useful_ops(m: Dict, contexts: Iterable[int]) -> float:
    """Useful operations of one decode step whose occupied rows attend
    over ``contexts`` keys each (their position + 1)."""
    per_row = 2 * (_proj_macs(m) + m["d_model"] * m["vocab"])
    att = 4 * m["n_heads"] * m["head_dim"] * m["n_layers"]
    return float(sum(per_row + att * int(c) for c in contexts))


def prefill_useful_ops(m: Dict, lengths: Iterable[int]) -> float:
    """Useful operations of one prefill batch over the real prompt
    tokens: projections per token, causal attention (each token over its
    own prefix), and the LM head once per prompt (the first token)."""
    att = 2 * m["n_heads"] * m["head_dim"] * m["n_layers"]
    total = 0.0
    for n in map(int, lengths):
        total += 2 * n * _proj_macs(m) + 2 * m["d_model"] * m["vocab"]
        total += att * n * (n + 1)
    return total
