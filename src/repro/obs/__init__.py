"""PPAC flight recorder: instruction ledger, serving metrics, trace export.

Always available, off by default: opening a :class:`Ledger` turns on
per-launch recording at the kernel dispatch chokepoint; a
:class:`MetricsRegistry` rides inside every server; ``span`` is the one
span API, on the profiler's clock, and a :class:`TraceBuilder` is its
sink that serializes spans and ledger launches into one
Perfetto-loadable trace.
"""
from .ledger import LaunchRecord, Ledger, launch_cost, record_for
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .trace import TraceBuilder, span

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "LaunchRecord",
    "Ledger",
    "MetricsRegistry",
    "TraceBuilder",
    "launch_cost",
    "record_for",
    "span",
]
