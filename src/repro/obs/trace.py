"""The program's one span API, on the profiler's clock, with a
Chrome-trace (``chrome://tracing`` / Perfetto) sink.

``span(name, **args)`` brackets a region as a
``jax.profiler.TraceAnnotation``: inside a profiler capture it lands in
the same ``.xplane.pb`` as the device ops, on the profiler's clock, so a
device idle gap can be put down to the host span that was open over it.
Args are encoded only while a capture is active
(``TraceAnnotation.is_enabled()``): with tracing off a span is one
annotation enter and exit (about a microsecond) and builds no strings.
Each thread keeps a stack of its open span names (``current()``).

A :class:`TraceBuilder` is a sink of the same spans: attached to a
thread (``attached(builder)``, as ``LMServer(trace=...)`` does around
each tick), every span also becomes a complete ("ph": "X") event on its
``server`` track, timed on ``perf_counter``; instruction-ledger launch
events (cycles / energy / tile plan) interleave on another track
(``add_ledger``). The written file loads in Perfetto
(https://ui.perfetto.dev) or chrome://tracing.

``install(registry)`` (``LMServer`` calls it with its own registry)
registers two process hooks, once per process, and records their counts
into every registry installed and still alive, so they reach the
server's ``--metrics-out`` snapshot and Prometheus dump:

* ``host.gc``: a span over every garbage collection (``gc.callbacks``),
  and the counter ``lm_gc_pause_s``;
* ``lm_compiles`` / ``lm_compile_s``: JAX's backend compiles and their
  seconds (its ``backend_compile_duration`` monitoring event), labelled
  ``span=`` the innermost span open on the compiling thread
  (``none`` outside any), so a compile is put down to the step that
  triggered it.

The events are the process's: a registry counts every collection and
compile while it is installed, whichever server caused it.
"""
from __future__ import annotations

import contextlib
import gc
import json
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation

from .metrics import MetricsRegistry

_PID = 1
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_tls = threading.local()


def _stack() -> List[str]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
        _tls.sink = None
    return st


def current() -> Optional[str]:
    """The innermost span open on this thread, or None."""
    st = _stack()
    return st[-1] if st else None


def _encode(v):
    # the profiler reads annotation args as "k=v,k=v": a sequence is
    # joined with spaces so its commas cannot split it
    if isinstance(v, (list, tuple)):
        return " ".join(str(x) for x in v)
    return v


class span:
    """Context manager: one span named ``name``, with ``args`` shown in a
    profiler capture and on an attached :class:`TraceBuilder`."""

    __slots__ = ("name", "args", "_ann", "_sink", "_t0")

    def __init__(self, name: str, **args):
        self.name, self.args = name, args

    def __enter__(self):
        if self.args and TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(
                self.name, **{k: _encode(v) for k, v in self.args.items()})
        else:
            self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        _stack().append(self.name)
        self._sink = _tls.sink
        if self._sink is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._sink is not None:
            self._sink.event(self.name, track="server", t_start=self._t0,
                             dur_s=time.perf_counter() - self._t0,
                             args=self.args or None)
        _tls.stack.pop()
        self._ann.__exit__(*exc)
        return False


@contextlib.contextmanager
def attached(sink: Optional["TraceBuilder"]):
    """Send this thread's spans to ``sink`` (None: to no builder) for the
    duration of the block."""
    _stack()
    prev, _tls.sink = _tls.sink, sink
    try:
        yield
    finally:
        _tls.sink = prev


# -- process hooks -----------------------------------------------------------

_installed = False
_gc_open: List[Any] = []   # [annotation, perf_counter at start]
_targets: "weakref.WeakSet[MetricsRegistry]" = weakref.WeakSet()


def _count(name: str, value: float, **labels) -> None:
    for reg in list(_targets):
        reg.counter(name, **labels).inc(value)


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    if phase == "start":
        ann = (TraceAnnotation("host.gc", generation=info["generation"])
               if TraceAnnotation.is_enabled()
               else TraceAnnotation("host.gc"))
        ann.__enter__()
        _gc_open[:] = [ann, time.perf_counter()]
    elif _gc_open:
        ann, t0 = _gc_open
        del _gc_open[:]
        ann.__exit__(None, None, None)
        _count("lm_gc_pause_s", time.perf_counter() - t0)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        where = current() or "none"
        _count("lm_compiles", 1, span=where)
        _count("lm_compile_s", duration, span=where)


def install(registry: MetricsRegistry) -> None:
    """Record the ``host.gc`` and compile hooks' counts into ``registry``
    from now on; the hooks are registered on the first call."""
    global _installed
    _targets.add(registry)
    if _installed:
        return
    _installed = True
    gc.callbacks.append(_on_gc)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


# -- the Chrome-trace sink ---------------------------------------------------

class TraceBuilder:
    """Accumulates trace events; serializes Trace Event Format JSON."""

    def __init__(self):
        self._events: List[dict] = []   # with absolute t_start seconds
        self._tids: Dict[str, int] = {}

    def _tid(self, track: str) -> int:
        tid = self._tids.get(track)
        if tid is None:
            tid = self._tids[track] = len(self._tids) + 1
        return tid

    def event(self, name: str, *, track: str, t_start: float, dur_s: float,
              args: Optional[Dict[str, Any]] = None) -> None:
        """One complete event; ``t_start`` is a ``perf_counter`` reading."""
        self._events.append(dict(name=name, track=track, t=t_start,
                                 dur=max(dur_s, 1e-7), args=args or {}))

    def add_ledger(self, ledger, *, track: str = "ppac") -> None:
        """Interleave every ledger launch record as one event on ``track``
        (cycles / energy / plan ride in the event args)."""
        for rec in ledger.records:
            name = f"{rec.mode}[{rec.backend}]"
            if rec.traced:
                name += " (traced)"
            self.event(name, track=track, t_start=rec.t_start,
                       dur_s=rec.dur_s, args=rec.as_dict())

    @property
    def num_events(self) -> int:
        return len(self._events)

    def to_dict(self) -> dict:
        """Trace Event Format: metadata naming each track, then the events
        sorted by timestamp (ts in microseconds, earliest event at 0)."""
        base = min((e["t"] for e in self._events), default=0.0)
        out: List[dict] = []
        for e in self._events:  # assign tids in first-seen track order
            self._tid(e["track"])
        for track in self._tids:
            out.append(dict(name="thread_name", ph="M", pid=_PID,
                            tid=self._tid(track),
                            args=dict(name=track)))
        for e in sorted(self._events, key=lambda e: e["t"]):
            out.append(dict(name=e["name"], ph="X", pid=_PID,
                            tid=self._tid(e["track"]),
                            ts=(e["t"] - base) * 1e6,
                            dur=e["dur"] * 1e6, args=e["args"]))
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1)
