"""From a profiler trace of one window to per-layer numbers.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into a compact, normalized form (``Trace``): the harness's and the
server's host spans, and every op the device ran, on one time line in
nanoseconds. ``Reduction`` then takes from it:

* busy: the union of the device op intervals inside the window (the
  host span ``bench_window``), and the idle gaps between them, each
  labelled by the host span it fell in (``bench_prefill``,
  ``bench_decode``) or ``between ticks``. The device's clock runs a few
  milliseconds off the host's; the offset is taken as the middle of the
  range in which every device program lies inside the host call that
  waited for it;
* projection launches: every TPU custom call that returns the int32
  accumulator [rows, m] of a product against a resident uint32 plane
  stack [k, m, w]. Keyed on that signature, not on a kernel's name, so a
  kernel rewritten under the same resident layout is still found. Each
  launch is matched to the configuration's projection whose [n, m] fits
  its padded shape best, and belongs to the device program (an ``XLA
  Modules`` event) that contains it; a program belongs to the prefill or
  decode call (host span) it overlaps most;
* the device ops that took most time, ops that only contain other ops
  (a layer loop's ``while``) left out.

``python trace_reduce.py --trim <trace dir> <out.json> [ms]`` writes the
first ``ms`` (default 1000) of a window in the normalized form: the test
data. A traced run keeps its trace directory when the harness is called
as ``harness.execute(..., trace=True, keep_trace=<dir>)``.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import re
import sys
from typing import Dict, List, Optional, Tuple

import work

HOST_SPANS = ("bench_window", "bench_prefill", "bench_decode")
KIND_OF = {"bench_prefill": "prefill", "bench_decode": "decode"}
_INST = re.compile(r"%([^\s=]+)")
_RESULT = re.compile(r"=\s*s32\[(\d+),(\d+)\]")
_PLANES = re.compile(r"u32\[(\d+),(\d+),(\d+)\]")


class TraceError(Exception):
    """The trace does not hold what the metric needs."""


def op_key(hlo: str) -> str:
    """Compact name of a device op event: the HLO instruction name, and
    for a TPU custom call also its int32 result and plane operand."""
    m = _INST.match(hlo)
    inst = m.group(1) if m else hlo.split(" ")[0]
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return inst
    res = _RESULT.search(hlo)
    planes = [p for p in _PLANES.findall(hlo)]
    if not res or len(planes) < 2:
        return inst
    k, mm, w = planes[1]
    return f"{inst} = s32[{res.group(1)},{res.group(2)}] planes " \
           f"u32[{k},{mm},{w}]"


@dataclasses.dataclass
class Trace:
    host: List[Tuple[str, int, int]]     # (name, start_ns, dur_ns)
    ops: List[Tuple[str, int, int]]      # (op_key, start_ns, dur_ns)
    modules: List[Tuple[str, int, int]]  # device programs, same form

    def to_json(self) -> dict:
        return {"host": self.host, "ops": self.ops, "modules": self.modules}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(*[[tuple(x) for x in d[k]]
                     for k in ("host", "ops", "modules")])


def load_xplane(path: str) -> Trace:
    """Host spans of interest and the first device's op events."""
    import jax
    if not path.endswith(".pb"):
        found = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
        if not found:
            raise TraceError(f"no .xplane.pb under {path}")
        path = found[0]
    pd = jax.profiler.ProfileData.from_file(path)
    host, ops, modules = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
        elif plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((op_key(ev.name), int(ev.start_ns),
                                int(ev.duration_ns)) for ev in line.events)
                elif line.name == "XLA Modules":
                    modules.extend((ev.name, int(ev.start_ns),
                                    int(ev.duration_ns)) for ev in line.events)
    for x in (host, ops, modules):
        x.sort(key=lambda e: e[1])
    return Trace(host, ops, modules)


def union_length(intervals, lo: int, hi: int) -> Tuple[int, List]:
    """(covered ns inside [lo, hi], merged intervals) of (start, end)s."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def containers(ops) -> set:
    """Indices of op events that contain a later event of the line."""
    out, stack = set(), []
    for i, (_, s, d) in enumerate(ops):
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out.add(stack[-1])
        stack.append(i)
    return out


@dataclasses.dataclass
class Launch:
    kind: str          # prefill | decode
    proj: str          # the configuration's projection name
    rows: int
    n: int
    m: int
    dur_s: float


class Reduction:
    def __init__(self, trace: Trace, m: Optional[Dict] = None):
        self.trace, self.m = trace, m
        win = [h for h in trace.host if h[0] == "bench_window"]
        if len(win) != 1:
            raise TraceError(f"{len(win)} bench_window spans in the trace")
        _, s, d = win[0]
        self.lo, self.hi = s, s + d
        self.window_s = d * 1e-9
        busy, self.merged = union_length(
            [(o[1], o[1] + o[2]) for o in trace.ops], self.lo, self.hi)
        self.busy_s = busy * 1e-9
        self.calls = [h for h in trace.host if h[0] in KIND_OF
                      and self.lo <= h[1] and h[1] + h[2] <= self.hi]
        # each call's device program: the module it overlaps most
        self.programs = []   # (start, end, kind)
        lo_off, hi_off = [], []
        for name, cs, cd in self.calls:
            best, prog = 0, None
            for _, s, d in trace.modules:
                if s > cs + cd:
                    break
                ov = min(s + d, cs + cd) - max(s, cs)
                if ov > best:
                    best, prog = ov, (s, s + d)
            if prog is not None:
                self.programs.append((*prog, KIND_OF[name]))
                lo_off.append(cs - prog[0])
                hi_off.append(cs + cd - prog[1])
        # host time = device time + offset
        self.offset = 0
        if lo_off:
            a, b = max(lo_off), min(hi_off)
            self.offset = (a + b) // 2 if a <= b else a

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def span_at(self, t: int) -> str:
        for name, s, d in self.calls:
            if s <= t < s + d:
                return name
        return "between ticks"

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """(host span, seconds) of every idle stretch of the window."""
        edges = [self.lo] + [x for iv in self.merged for x in iv] + [self.hi]
        out = []
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                out.append((self.span_at((a + b) // 2 + self.offset),
                            (b - a) * 1e-9))
        return out

    def device_ops(self) -> List[Tuple[str, float]]:
        """(op, seconds) over the window, containers left out, grouped by
        the instruction name without its number."""
        skip = containers(self.trace.ops)
        tot: Dict[str, float] = {}
        for i, (key, s, d) in enumerate(self.trace.ops):
            if i in skip or not (self.lo <= s < self.hi):
                continue
            base = re.sub(r"\.\d+$", "", key.split(" ")[0])
            tot[base] = tot.get(base, 0.0) + d * 1e-9
        return sorted(tot.items(), key=lambda kv: -kv[1])

    def kind_at(self, t: int) -> Optional[str]:
        """The kind of the device program running at device time ``t``."""
        for s, e, kind in self.programs:
            if s <= t < e:
                return kind
        return None

    def launches(self) -> List[Launch]:
        """Projection launches inside prefill and decode programs."""
        projs = work.projections(self.m)
        out = []
        for key, s, d in self.trace.ops:
            if " planes " not in key:
                continue
            kind = self.kind_at(s)
            if kind is None:
                continue
            rows, mpad = map(int, re.search(r"s32\[(\d+),(\d+)\]",
                                            key).groups())
            wpad = int(re.search(r"u32\[\d+,\d+,(\d+)\]", key).group(1))
            fits = [(mpad - mo + 32 * wpad - n, name, n, mo)
                    for name, n, mo in projs
                    if mo <= mpad and n <= 32 * wpad]
            if not fits:
                raise TraceError(f"launch {key} fits no projection of "
                                 f"the configuration")
            _, name, n, mo = min(fits)
            out.append(Launch(kind, name, rows, n, mo, d * 1e-9))
        return out

    def roofline(self, kind: str, pk: Dict) -> Optional[float]:
        """Share (%) of the projection launches' device time that the
        chip's roofline needs for their work, or None when no call of
        that kind ran in the window. Raises when a call ran with fewer
        launches than the configuration has projections."""
        calls = [c for c in self.calls if KIND_OF[c[0]] == kind]
        if not calls:
            return None
        ls = [x for x in self.launches() if x.kind == kind]
        need = work.launches_per_step(self.m) * len(calls)
        if len(ls) < need:
            raise TraceError(
                f"{len(ls)} projection launches in {len(calls)} {kind} "
                f"calls; the configuration implies {need}")
        t_min = sum(max(o / pk["int8_op_s"], b / pk["hbm_byte_s"])
                    for o, b in (work.mvp_work(x.n, x.m, x.rows,
                                               weight_bits=self.m[
                                                   "weight_bits"],
                                               act_bits=self.m["act_bits"])
                                 for x in ls))
        t_dev = sum(x.dur_s for x in ls)
        return 100.0 * t_min / t_dev

    def breakdown(self, top: int = 10) -> Dict:
        gaps = sorted(self.idle_gaps(), key=lambda g: -g[1])[:top]
        return {"device_ops": [[k, v] for k, v in self.device_ops()[:top]],
                "idle_gaps": [[k, v] for k, v in gaps]}


class Context:
    """What a per-layer metric reader gets: the run, its window, the
    trace reduction and the chip's peaks."""

    def __init__(self, run, win, trace_dir: str, device_kind: str):
        self.run, self.win = run, win
        self.m = run.m
        self.peaks = work.peaks(device_kind)
        self.red = Reduction(load_xplane(trace_dir), run.m)
        self.busy_s, self.window_s = self.red.busy_s, self.red.window_s
        self.e2e = run.end_to_end(win)

    def calls(self, kind: str):
        """The harness's spans around the executor's ``kind`` calls that
        lie inside the window."""
        return self.run.rec.between(kind, self.win.t0, self.win.t1)

    def breakdown(self) -> Dict:
        return self.red.breakdown()


def trim(path: str, out: str, ms: float) -> None:
    """Keep the first ``ms`` of the window, in the normalized form."""
    tr = load_xplane(path)
    win = [h for h in tr.host if h[0] == "bench_window"][0]
    lo, hi = win[1], win[1] + int(ms * 1e6)
    host = [h for h in tr.host if h[0] != "bench_window"
            and lo <= h[1] and h[1] + h[2] <= hi]
    end = max(h[1] + h[2] for h in host)
    host.append(("bench_window", lo, end - lo))
    ops = [o for o in tr.ops if lo <= o[1] < end]
    modules = [x for x in tr.modules if lo <= x[1] < end]
    with open(out, "w") as f:
        json.dump(Trace(sorted(host, key=lambda x: x[1]), ops,
                        modules).to_json(), f)


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "--trim":
        ms = float(sys.argv[4]) if len(sys.argv) > 4 else 1000.0
        trim(sys.argv[2], sys.argv[3], ms)
    else:
        print(__doc__)
        sys.exit(2)
