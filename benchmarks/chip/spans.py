"""Program spans and device-op scopes from a profiler trace, and the
per-layer numbers that read them.

The program marks each phase of a scheduler tick and each executor
dispatch with a span on the profiler's clock: ``tick`` holds
``sched.admit`` (with ``prefill_batch`` > ``prefill.dispatch``,
``prefill.wait`` and ``write_slot.dispatch`` inside), ``decode.prepare``,
``decode_step`` > ``decode.dispatch``, ``decode.wait``, and
``decode.retire``; ``host.gc`` covers a garbage collection wherever it
falls. Its device programs carry named scopes in their op metadata:
``proj/wqkv``, ``proj/wo``, ``proj/wig``, ``proj/down``, ``attn``,
``kv_write``, ``lm_head``, ``sample``. ``trace_reduce.load_xplane`` keeps
neither; ``load`` reads both from the same ``.xplane.pb`` into a
:class:`Spans`: the normalized ``trace_reduce.Trace`` plus the program's
spans and each op event's scope path. The names are this file's own (the
benchmark imports nothing of the program). A program without these spans
and scopes gives a :class:`Spans` without them, and every number here then
reads None.

The harness's ``Recorder`` wraps the executor's ``decode`` and waits for
its tokens inside the wrapper, so in a benchmark run the device wait of a
decode step falls inside ``decode_step`` but outside ``decode.wait``. The
numbers here subtract whole executor-call spans (``prefill_batch``,
``decode_step``, ``spec_round``), so they hold either way.

The readers in ``metrics/`` get the harness's ``trace_reduce.Context``,
which does not carry the trace's path: :func:`for_context` takes the
newest ``chipbench-trace-*`` directory the harness made under the
temporary directory, and checks that its ``bench_window`` is the
context's.

``python spans.py --trim <trace dir> <out.json> [ms]`` writes the first
``ms`` (default 1000) of a window in this form, the test data; the same
file loads as a plain ``trace_reduce.Trace`` too.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import json
import os
import sys
import tempfile
from typing import Dict, List, Optional, Tuple

import trace_reduce as tr

PROGRAM_SPANS = (
    "tick", "sched.faults", "sched.scrub", "sched.admit",
    "prefill_batch", "decode_step", "spec_round",
    "decode.prepare", "decode.retire",
    "prefill.dispatch", "prefill.wait",
    "prefill_paged.dispatch", "prefill_paged.wait",
    "decode.dispatch", "decode.wait",
    "spec_round.dispatch", "spec_round.wait",
    "write_slot.dispatch", "write_slot.wait", "host.gc")
# executor calls: the device's work of a tick, and its wait for it
CALLS = ("prefill_batch", "decode_step", "spec_round")
SCOPES = ("proj/wqkv", "proj/wo", "proj/wig", "proj/down", "attn",
          "kv_write", "lm_head", "sample")
DEVICE = "/device:TPU:0"


@dataclasses.dataclass
class Spans:
    trace: tr.Trace
    spans: List[Tuple[str, int, int]]   # program spans: (name, start, dur)
    scopes: List[str]                   # scope path of each trace.ops event

    def to_json(self) -> dict:
        names = sorted(set(self.scopes))
        idx = {n: i for i, n in enumerate(names)}
        return {**self.trace.to_json(), "spans": self.spans,
                "scope_names": names,
                "scopes": [idx[s] for s in self.scopes]}

    @classmethod
    def from_json(cls, d: dict) -> "Spans":
        names = d.get("scope_names", [])
        return cls(tr.Trace.from_json(d),
                   [tuple(x) for x in d.get("spans", [])],
                   [names[i] for i in d.get("scopes", [])]
                   or [""] * len(d["ops"]))


# -- the op metadata: ProfileData shows an event's own stats, not those of
# its metadata, where the op's scope path (``tf_op``) is; so the device
# plane's metadata is read from the XSpace protobuf's wire format.

def _varint(b, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b, i: int, end: int):
    """(field number, value) of one message's fields in ``b[i:end]``: an
    int for a varint, (start, end) for a length-delimited field."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise tr.TraceError(f"wire type {wire} in the trace")
        yield key >> 3, v


def _text(b, v) -> str:
    return bytes(b[v[0]:v[1]]).decode("utf-8", "replace")


def _map_values(b, entries):
    """The values (field 2) of protobuf map entries."""
    for a, z in entries:
        for f, v in _fields(b, a, z):
            if f == 2:
                yield v


def op_scopes(path: str) -> Dict[str, str]:
    """Each device op's name (its HLO text, as ``ProfileData`` gives it)
    -> its scope path: the ``tf_op`` stat of its event metadata."""
    with open(xplane_path(path), "rb") as f:
        b = memoryview(f.read())
    for fn, plane in _fields(b, 0, len(b)):
        if fn != 1:                               # XSpace.planes
            continue
        fs = list(_fields(b, *plane))
        if DEVICE not in [_text(b, v) for f, v in fs if f == 2]:
            continue
        stat_names = {}
        for a, z in _map_values(b, [v for f, v in fs if f == 5]):
            meta = dict(_fields(b, a, z))         # XStatMetadata
            stat_names[meta.get(1, 0)] = _text(b, meta.get(2, (0, 0)))
        out: Dict[str, str] = {}
        for a, z in _map_values(b, [v for f, v in fs if f == 4]):
            name, scope = "", ""                  # XEventMetadata
            for f, v in _fields(b, a, z):
                if f == 2:
                    name = _text(b, v)
                elif f == 5:                      # XStat
                    st = dict(_fields(b, *v))
                    if stat_names.get(st.get(1)) == "tf_op":
                        scope = (_text(b, st[5]) if 5 in st
                                 else stat_names.get(st.get(7), ""))
            if scope:
                out.setdefault(name, scope)
        return out
    return {}


def xplane_path(path: str) -> str:
    if path.endswith(".pb"):
        return path
    found = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
    if not found:
        raise tr.TraceError(f"no .xplane.pb under {path}")
    return found[0]


def load(path: str) -> Spans:
    """The harness's and the program's host spans, and the first
    device's op events with their scopes, on one time line (ns)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(xplane_path(path))
    scopes = op_scopes(path)
    host, spans, ops, modules = [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    x = (ev.name, int(ev.start_ns), int(ev.duration_ns))
                    if ev.name in tr.HOST_SPANS:
                        host.append(x)
                    elif ev.name in PROGRAM_SPANS:
                        spans.append(x)
        elif plane.name == DEVICE:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((tr.op_key(ev.name), int(ev.start_ns),
                                int(ev.duration_ns), scopes.get(ev.name, ""))
                               for ev in line.events)
                elif line.name == "XLA Modules":
                    modules.extend((ev.name, int(ev.start_ns),
                                    int(ev.duration_ns))
                                   for ev in line.events)
    for x in (host, spans, ops, modules):
        x.sort(key=lambda e: e[1])
    return Spans(tr.Trace(host, [o[:3] for o in ops], modules), spans,
                 [o[3] for o in ops])


def overlap(a: int, b: int, merged) -> int:
    """ns of [a, b) covered by sorted disjoint [s, e] intervals."""
    return sum(max(0, min(b, e) - max(a, s)) for s, e in merged
               if s < b and e > a)


class Window:
    """The program's spans and scopes over the harness's window."""

    def __init__(self, sp: Spans):
        self.sp = sp
        win = [h for h in sp.trace.host if h[0] == "bench_window"]
        if len(win) != 1:
            raise tr.TraceError(f"{len(win)} bench_window spans")
        _, s, d = win[0]
        self.lo, self.hi = s, s + d
        inside = [x for x in sp.spans
                  if self.lo <= x[1] and x[1] + x[2] <= self.hi]
        self.ticks = [x for x in inside if x[0] == "tick"]
        self.calls = [x for x in inside if x[0] in CALLS]
        self.inside = inside
        _, self.calls_merged = tr.union_length(
            [(x[1], x[1] + x[2]) for x in self.calls], self.lo, self.hi)
        _, self.ticks_merged = tr.union_length(
            [(x[1], x[1] + x[2]) for x in self.ticks], self.lo, self.hi)
        _, self.busy = tr.union_length(
            [(o[1], o[1] + o[2]) for o in sp.trace.ops], self.lo, self.hi)
        self.offset, self.offset_range = self._offset()
        self.bounds, self.labels = self._timeline()

    def _offset(self) -> Tuple[int, int]:
        """host time = device time + offset: the middle of the range in
        which every executor call holds the device program it overlaps
        most (as ``trace_reduce.Reduction`` takes it from the harness's
        spans), and the width of that range (ns): a time moved onto the
        host's clock is known to about half of it."""
        lo_off, hi_off = [], []
        for _, cs, cd in self.calls:
            best, prog = 0, None
            for _, s, d in self.sp.trace.modules:
                if s > cs + cd:
                    break
                ov = min(s + d, cs + cd) - max(s, cs)
                if ov > best:
                    best, prog = ov, (s, s + d)
            if prog is not None:
                lo_off.append(cs - prog[0])
                hi_off.append(cs + cd - prog[1])
        if not lo_off:
            return 0, 0
        a, b = max(lo_off), min(hi_off)
        return ((a + b) // 2, b - a) if a <= b else (a, 0)

    def _timeline(self) -> Tuple[List[int], List[str]]:
        """The host time line cut where any program span or ``tick``
        starts or ends: between ``bounds[i]`` and ``bounds[i + 1]`` the
        innermost open span is ``labels[i]`` (the latest to start, the
        shorter of two that start together), ``outside_tick`` outside
        every ``tick``: the benchmark's client."""
        spans = sorted(self.sp.spans, key=lambda x: (x[1], -x[2]))
        bounds = sorted({t for x in spans for t in (x[1], x[1] + x[2])}
                        | {t for iv in self.ticks_merged for t in iv})
        tick_starts = [iv[0] for iv in self.ticks_merged]
        heap: List[Tuple[int, int, int, str]] = []
        labels, j = [], 0
        for t in bounds[:-1]:
            while j < len(spans) and spans[j][1] <= t:
                name, s, d = spans[j]
                heapq.heappush(heap, (-s, d, s + d, name))
                j += 1
            while heap and heap[0][2] <= t:
                heapq.heappop(heap)
            k = bisect.bisect_right(tick_starts, t)
            in_tick = k > 0 and t < self.ticks_merged[k - 1][1]
            labels.append(heap[0][3] if heap and in_tick else "outside_tick")
        return bounds, labels

    def idle(self) -> List[Tuple[int, int]]:
        """The device's idle stretches in the window, on the host clock."""
        edges = [self.lo] + [x for iv in self.busy for x in iv] + [self.hi]
        return [(a + self.offset, b + self.offset)
                for a, b in zip(edges[::2], edges[1::2]) if b > a]

    def innermost(self, t: int) -> str:
        """The innermost program span open at host time ``t``; time
        outside every ``tick`` is the benchmark's client."""
        i = bisect.bisect_right(self.bounds, t) - 1
        if 0 <= i < len(self.labels):
            return self.labels[i]
        return "outside_tick"

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds of the window by the innermost program span open
        over each part of an idle stretch, split where spans start and
        end. The idle stretches are moved onto the host's clock by
        ``offset``, known to about ``offset_range / 2``: labels of
        neighbouring spans trade that much time."""
        out: Dict[str, float] = {}
        for a, b in self.idle():
            i = bisect.bisect_right(self.bounds, a) - 1
            t = a
            while t < b:
                nxt = (self.bounds[i + 1] if i + 1 < len(self.bounds)
                       else b)
                e = min(b, max(nxt, t + 1))
                k = (self.labels[i] if 0 <= i < len(self.labels)
                     else "outside_tick")
                out[k] = out.get(k, 0.0) + (e - t) * 1e-9
                t, i = e, i + 1
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    def host_self_ms(self) -> Optional[float]:
        """Mean over the window's ticks of the tick's length less the
        parts its executor calls cover, in ms."""
        if not self.ticks:
            return None
        self_ns = []
        for _, s, d in self.ticks:
            self_ns.append(d - overlap(s, s + d, self.calls_merged))
        return 1e-6 * sum(self_ns) / len(self_ns)

    def scheduler_idle_share(self) -> Optional[float]:
        """Device idle time inside a tick but outside its executor calls,
        over the window's length (%)."""
        if not self.ticks:
            return None
        ns = sum(overlap(a, b, self.ticks_merged)
                 - overlap(a, b, self.calls_merged) for a, b in self.idle())
        return 100.0 * ns / (self.hi - self.lo)

    def programs(self, call: str) -> List[Tuple[int, int]]:
        """Device intervals of the programs run by ``call`` spans: each
        module whose middle, on the host clock, lies inside one."""
        spans = [(x[1], x[1] + x[2]) for x in self.calls if x[0] == call]
        out = []
        for _, s, d in self.sp.trace.modules:
            mid = s + d // 2 + self.offset
            if any(a <= mid < b for a, b in spans):
                out.append((s, s + d))
        return out

    def scope_share(self, scope: str, call: str) -> Optional[float]:
        """Device time of the ops of ``call``'s programs whose scope path
        holds ``scope``, over those programs' op time (%); ops that only
        contain other ops left out. 0 when those programs carry the
        program's named scopes (``SCOPES``) but no op holds ``scope``;
        None when they carry none, as a program without scopes."""
        progs = sorted(self.programs(call))
        starts = [a for a, _ in progs]
        ops = self.sp.trace.ops
        skip = tr.containers(ops)
        tot = hit = 0
        named = False
        for i, (_, s, d) in enumerate(ops):
            j = bisect.bisect_right(starts, s) - 1
            if i in skip or j < 0 or s >= progs[j][1]:
                continue
            tot += d
            path = f"/{self.sp.scopes[i]}/"
            if f"/{scope}/" in path:
                hit += d
            named = named or any(f"/{x}/" in path for x in SCOPES)
        if not named or tot == 0:
            return None
        return 100.0 * hit / tot

    def dispatches(self, call: str, child: str) -> List[int]:
        """The number of ``child`` spans inside each ``call`` span."""
        return [sum(1 for y in self.inside if y[0] == child
                    and x[1] <= y[1] and y[1] + y[2] <= x[1] + x[2])
                for x in self.calls if x[0] == call]

    def gc_in_window_ms(self) -> float:
        """ms of ``host.gc`` spans inside the window."""
        return 1e-6 * sum(x[2] for x in self.inside if x[0] == "host.gc")

    def slowest_tick(self) -> Optional[Dict]:
        """The window's longest tick and the spans inside it (ms from the
        tick's start, ms long)."""
        if not self.ticks:
            return None
        _, s, d = max(self.ticks, key=lambda x: x[2])
        kids = [[x[0], (x[1] - s) * 1e-6, x[2] * 1e-6] for x in self.sp.spans
                if s <= x[1] and x[1] + x[2] <= s + d and x[2] > 0.1 * d]
        return {"ms": d * 1e-6, "spans": kids}


# the program's process counters (metrics registry keys, labels cut off)
COUNTERS = ("lm_compiles", "lm_compile_s", "lm_gc_pause_s")

_WINDOWS: Dict[Tuple[int, int], Optional[Window]] = {}


def process_counters(ctx) -> Dict[str, float]:
    """The program's compile counters by span and its collection pause
    over the run so far, from the server's metrics registry; empty for a
    program without them."""
    try:
        snap = ctx.run.server.metrics.snapshot()
    except AttributeError:
        return {}
    return {k: v for k, v in snap.items() if k.split("{")[0] in COUNTERS}


def for_context(ctx) -> Optional[Window]:
    """The :class:`Window` of a reader's context, read once per window;
    None when its trace holds no program span."""
    key = (ctx.red.lo, ctx.red.hi)
    if key not in _WINDOWS:
        _WINDOWS[key] = _find(key)
        w = _WINDOWS[key]
        if w is not None:
            print("program spans: " + json.dumps({
                "idle_by_span": w.idle_by_span(),
                "clock_offset_range_us": w.offset_range * 1e-3,
                "decode_steps": len(w.dispatches("decode_step",
                                                 "decode.dispatch")),
                "decode_dispatches": sum(w.dispatches(
                    "decode_step", "decode.dispatch")),
                "gc_in_window_ms": w.gc_in_window_ms(),
                "slowest_tick": w.slowest_tick(),
                "process_counters": process_counters(ctx)}),
                file=sys.stderr, flush=True)
    return _WINDOWS[key]


def _find(key: Tuple[int, int]) -> Optional[Window]:
    dirs = glob.glob(os.path.join(tempfile.gettempdir(), "chipbench-trace-*"))
    for d in sorted(dirs, key=os.path.getmtime, reverse=True):
        try:
            sp = load(d)
        except tr.TraceError:
            continue
        win = [h for h in sp.trace.host if h[0] == "bench_window"]
        if len(win) == 1 and (win[0][1], win[0][1] + win[0][2]) == key:
            return Window(sp) if sp.spans else None
    return None


def trim(path: str, out: str, ms: float) -> None:
    """Keep the first ``ms`` of the window, in this file's form."""
    sp = load(path)
    win = [h for h in sp.trace.host if h[0] == "bench_window"][0]
    lo, hi = win[1], win[1] + int(ms * 1e6)

    def within(xs):
        return [x for x in xs if lo <= x[1] and x[1] + x[2] <= hi]
    host = within([h for h in sp.trace.host if h[0] != "bench_window"])
    spans = within(sp.spans)
    end = max(x[1] + x[2] for x in host + spans)
    host.append(("bench_window", lo, end - lo))
    keep = [i for i, o in enumerate(sp.trace.ops) if lo <= o[1] < end]
    modules = [x for x in sp.trace.modules if lo <= x[1] < end]
    trace = tr.Trace(sorted(host, key=lambda x: x[1]),
                     [sp.trace.ops[i] for i in keep], modules)
    with open(out, "w") as f:
        json.dump(Spans(trace, spans, [sp.scopes[i] for i in keep]
                        ).to_json(), f)


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "--trim":
        ms = float(sys.argv[4]) if len(sys.argv) > 4 else 1000.0
        trim(sys.argv[2], sys.argv[3], ms)
    else:
        print(__doc__)
        sys.exit(2)
