"""Reduce a profiler trace of the measured window to device metrics.

The trace is read into plain interval lists first (:func:`from_profile`),
so every reduction below is arithmetic on ``Span`` lists and is tested on
synthetic events without a chip:

* busy time: the union of the device's op intervals, clipped to the window;
* idle share: 1 - busy / window, averaged over the cell's devices;
* program time: summed durations of the device's program (module) events,
  filtered by the largest array that the program's ops name;
* the breakdown: the ops that took most device time, and the device's idle
  time grouped by what the benchmark's thread was doing meanwhile.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed op and their ``XLA Modules`` line one per program run.
The host's events (the benchmark's ``TraceAnnotation`` spans and the
runtime's own) are on the line of ``/host:CPU`` that holds the window span.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Callable, Iterable

WINDOW = "chipbench.window"
ITERATION = "chipbench.iteration"

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_HOST_PLANE = "/host:CPU"
#: "jit_fold(12)": the program's name, then a fingerprint of its build
_INSTANCE = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Device:
    name: str
    ops: list[Span]
    modules: list[Span]


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    host: list[Span]   # the benchmark thread's events, sorted by start

    # -- the window ---------------------------------------------------------

    def window(self) -> tuple[float, float]:
        """(start, end) of the benchmark's window span on the host clock."""
        spans = [s for s in self.host if s.name == WINDOW]
        if len(spans) != 1:
            raise ValueError(f"expected one {WINDOW!r} span, found {len(spans)}")
        return spans[0].start_ns, spans[0].end_ns

    # -- device time ----------------------------------------------------------

    def busy_ns(self, lo: float, hi: float) -> float:
        """Union of op intervals in [lo, hi], averaged over devices."""
        return _mean(total(union(d.ops, lo, hi)) for d in self.devices)

    def idle_share(self, lo: float, hi: float) -> float:
        """1 - busy / window, averaged over devices (a fraction)."""
        return 1.0 - self.busy_ns(lo, hi) / (hi - lo)

    def module_ns(self, lo: float, hi: float, keep: Callable[[int | None], bool]) -> float:
        """Clipped durations of the programs that ``keep`` admits, mean per device.

        ``keep`` sees the elements of the largest array that any op of the
        program names (:func:`largest_array`), or None where no op of it
        names an array: it judges a program by the sizes it works on, never
        by its name.
        """
        return _mean(
            sum(_clipped(m, lo, hi) for m, largest in _programs(d) if keep(largest))
            for d in self.devices
        )

    # -- breakdown ------------------------------------------------------------

    def top_ops(self, lo: float, hi: float, n: int = 10) -> list[list]:
        """[[program/op, seconds]] of the ops with most device time, mean per device."""
        acc: collections.Counter = collections.Counter()
        for d in self.devices:
            mods = sorted(d.modules, key=lambda s: s.start_ns)
            starts = [m.start_ns for m in mods]
            for op in d.ops:
                t = _clipped(op, lo, hi)
                if t > 0:
                    acc[f"{_enclosing(mods, starts, op)}/{op_label(op.name)}"] += t
        k = max(len(self.devices), 1)
        return [[name, ns * 1e-9 / k] for name, ns in acc.most_common(n)]

    def idle_by_host(self, lo: float, hi: float, n: int = 10) -> list[list]:
        """[[host activity, seconds]]: device idle time grouped by what the
        benchmark's thread was doing at each gap's midpoint, mean per device."""
        iters = [s for s in self.host if s.name == ITERATION]
        others = [s for s in self.host if s.name not in (WINDOW, ITERATION)]
        iter_starts = [s.start_ns for s in iters]
        other_starts = [s.start_ns for s in others]
        acc: collections.Counter = collections.Counter()
        for d in self.devices:
            for a, b in gaps(union(d.ops, lo, hi), lo, hi):
                t = (a + b) / 2
                i = bisect.bisect_right(iter_starts, t) - 1
                outer = "iteration" if i >= 0 and iters[i].end_ns >= t else "between"
                acc[f"{outer}/{_innermost(others, other_starts, t)}"] += b - a
        k = max(len(self.devices), 1)
        return [[name, ns * 1e-9 / k] for name, ns in acc.most_common(n)]


def _innermost(spans: list[Span], starts: list[float], t: float, reach: int = 512) -> str:
    """Name of the latest-starting span that covers ``t`` (spans nest), or "-"."""
    i = bisect.bisect_right(starts, t)
    for s in reversed(spans[max(0, i - reach):i]):
        if s.end_ns >= t:
            return _INSTANCE.sub("", s.name)
    return "-"


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def _clipped(s: Span, lo: float, hi: float) -> float:
    return max(0.0, min(s.end_ns, hi) - max(s.start_ns, lo))


def union(spans: Iterable[Span], lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged, sorted intervals covered by ``spans`` inside [lo, hi]."""
    ivs = sorted(
        (max(s.start_ns, lo), min(s.end_ns, hi)) for s in spans if s.end_ns > lo and s.start_ns < hi
    )
    out: list[list[float]] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(ivs: list[tuple[float, float]]) -> float:
    return sum(b - a for a, b in ivs)


def gaps(ivs: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of merged intervals ``ivs`` inside [lo, hi]."""
    out, at = [], lo
    for a, b in ivs:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def _mean(values: Iterable[float]) -> float:
    values = list(values)
    return sum(values) / len(values) if values else 0.0


def _enclosing_index(mods: list[Span], starts: list[float], op: Span, reach: int = 16) -> int:
    """Index of the program an op ran in (programs can overlap, so look a few
    back), or -1."""
    i = bisect.bisect_right(starts, op.start_ns)
    for j in range(i - 1, max(0, i - reach) - 1, -1):
        if mods[j].end_ns >= op.end_ns:
            return j
    return -1


def _enclosing(mods: list[Span], starts: list[float], op: Span) -> str:
    j = _enclosing_index(mods, starts, op)
    return _INSTANCE.sub("", mods[j].name) if j >= 0 else "-"


def _programs(d: Device) -> list[tuple[Span, int | None]]:
    """Each program run of ``d`` with the largest array its ops name (None: none)."""
    mods = sorted(d.modules, key=lambda s: s.start_ns)
    starts = [m.start_ns for m in mods]
    largest: list[int | None] = [None] * len(mods)
    for op in d.ops:
        j = _enclosing_index(mods, starts, op)
        n = largest_array(op.name)
        if j >= 0 and n is not None:
            largest[j] = max(largest[j] or 0, n)
    return list(zip(mods, largest))


#: an array type in HLO text: ``f32[156250,20]{1,0:T(8,128)}``, ``pred[]``
_ARRAY = re.compile(r"\b(?:pred|bf16|[fsuc]\d+(?:e\d+m\d+\w*)?)\[([0-9,]*)\]")


def largest_array(text: str) -> int | None:
    """Elements of the largest array that an op's HLO text names, result or
    operand; None where it names none."""
    sizes = [
        _product(int(x) for x in dims.split(",") if x) for dims in _ARRAY.findall(text)
    ]
    return max(sizes) if sizes else None


def _product(values: Iterable[int]) -> int:
    out = 1
    for v in values:
        out *= v
    return out


def parse_op(text: str) -> tuple[str, str, str]:
    """(instruction, opcode, result shape) of an op event.

    TPU traces name an op by its HLO text, ``%name = shape opcode(operands),
    ...``; other names pass through as the instruction.
    """
    if not text.startswith("%") or " = " not in text:
        return text, "", ""
    name, rest = text[1:].split(" = ", 1)
    if rest.startswith("("):   # a tuple shape: up to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = rest[: i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    return name, rest.split("(", 1)[0], shape


def op_label(text: str) -> str:
    """A short name of an op: instruction, opcode and result shape."""
    name, opcode, shape = parse_op(text)
    if not opcode:
        return name
    return f"{name} {opcode} {shape if len(shape) <= 60 else shape[:57] + '...'}"


# ---------------------------------------------------------------------------
# reading the profiler's XSpace
# ---------------------------------------------------------------------------


def from_profile(profile, devices: int) -> Trace:
    """A :class:`Trace` of the first ``devices`` TPU planes of a ``ProfileData``."""
    found: dict[int, Device] = {}
    host: list[Span] = []
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < devices:
            dev = Device(plane.name, [], [])
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    dev.ops = _spans(line.events)
                elif line.name == _MODULES_LINE:
                    dev.modules = _spans(line.events)
            found[int(m.group(1))] = dev
        elif plane.name == _HOST_PLANE:
            for line in plane.lines:
                spans = _spans(line.events)
                if any(s.name == WINDOW for s in spans):
                    host = sorted(spans, key=lambda s: s.start_ns)
    if len(found) != devices:
        raise ValueError(f"trace holds TPU planes {sorted(found)}, expected {devices}")
    if not host:
        raise ValueError(f"no host line of the trace holds the {WINDOW!r} span")
    return Trace(devices=[found[i] for i in sorted(found)], host=host)


def _spans(events) -> list[Span]:
    return [Span(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)) for e in events]
