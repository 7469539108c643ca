"""Device idle time put on the engine's own layers, from its host spans.

The engine opens a ``jax.profiler.TraceAnnotation`` at each layer boundary,
named ``repro.<layer>`` (``repro.api.profile``'s ``SPAN_*``).  The spans sit
on the host line that holds the window span, on the clock the device events
are aligned to, and they nest: an execute holds its prepare, lower and
schedule; the schedule holds its units and the merge; a unit holds the
building of its operands.

Each device idle gap of the window is put on the innermost engine span that
covers the gap's midpoint, so a span's idle time is its self time: a
unit's idle leaves out what its operands child covers.  Idle under no
engine span (the benchmark's own loop, the plan's building) is put on none.
The names are written here, not imported, so that the readers run on a
program that has no spans and then report nothing.
"""

from __future__ import annotations

import collections
from typing import Iterable

from chipbench.trace_reduce import Span, Trace, gaps, union

PREFIX = "repro."
PREPARE = "repro.prepare"
LOWER = "repro.lower"
SCHEDULE = "repro.schedule"
UNIT = "repro.unit"
OPERANDS = "repro.operands"
MERGE = "repro.merge"


def idle_by_span(trace: Trace, lo: float, hi: float) -> dict[str | None, float]:
    """Device idle ns in [lo, hi] by the innermost engine span over each gap's
    midpoint (None: no engine span), mean over devices; {} where the trace
    holds no engine span."""
    spans = sorted((s for s in trace.host if s.name.startswith(PREFIX)),
                   key=lambda s: (s.start_ns, -s.end_ns))
    if not spans:
        return {}
    acc: collections.Counter = collections.Counter()
    for d in trace.devices:
        idle = gaps(union(d.ops, lo, hi), lo, hi)
        names = innermost(spans, ((a + b) / 2 for a, b in idle))
        for (a, b), name in zip(idle, names):
            acc[name] += b - a
    k = max(len(trace.devices), 1)
    return {name: ns / k for name, ns in acc.items()}


def innermost(spans: list[Span], times: Iterable[float]) -> list[str | None]:
    """For each of ascending ``times``, the name of the innermost span that
    covers it, or None.

    ``spans`` are sorted by start (outer first on a tie) and nest, as the
    spans of one thread do.  One sweep keeps the stack of open spans, so
    a span stays found however many others start inside it.
    """
    out: list[str | None] = []
    stack: list[Span] = []
    i = 0
    for t in times:
        while i < len(spans) and spans[i].start_ns <= t:
            while stack and stack[-1].end_ns < spans[i].start_ns:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1].end_ns < t:
            stack.pop()
        out.append(stack[-1].name if stack else None)
    return out


def idle_ms_per_iteration(w, names: tuple[str, ...]) -> float | None:
    """Device idle ms per iteration under ``names`` (self time of each).

    ``w`` is a :class:`chipbench.run.TracedWindow`.  None where the trace
    holds no engine span (a program without them) or no iteration ended.
    """
    by = idle_by_span(w.trace, w.lo, w.hi)
    if not by or w.iterations <= 0:
        return None
    return sum(by.get(n, 0.0) for n in names) * 1e-6 / w.iterations
