"""Device idle ms per iteration while the engine builds a unit's operands,
the stacking copy (self time of its ``repro.operands`` spans; see
``chipbench/spans.py``)."""

from chipbench.spans import OPERANDS, idle_ms_per_iteration


def read(w):
    return idle_ms_per_iteration(w, (OPERANDS,))
