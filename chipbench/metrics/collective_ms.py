"""Device ms per iteration of the collective ops that merge across chips
(all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute,
and their asynchronous start and done halves), mean over the cell's
devices.  None where no collective op ran in the window.

An op is told by the opcode of its HLO text (``%all-gather.3 = ...
all-gather(...)``) or, where an event names only its instruction, by that
name.
"""

import re

from chipbench.trace_reduce import parse_op

_COLLECTIVE = re.compile(
    r"^(?:all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?(?:[.\d]*)$"
)


def is_collective(text: str) -> bool:
    name, opcode, _ = parse_op(text)
    return bool(_COLLECTIVE.match(opcode or name))


def read(w):
    if w.iterations <= 0 or not w.trace.devices:
        return None
    inside = [
        min(op.end_ns, w.hi) - max(op.start_ns, w.lo)
        for d in w.trace.devices for op in d.ops
        if op.end_ns > w.lo and op.start_ns < w.hi and is_collective(op.name)
    ]
    if not inside:
        return None
    return sum(inside) / len(w.trace.devices) * 1e-6 / w.iterations
