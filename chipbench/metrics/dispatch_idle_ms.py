"""Device idle ms per iteration while the engine schedules and launches its
units (self time of its ``repro.schedule`` and ``repro.unit`` spans, the
operands apart; see ``chipbench/spans.py``)."""

from chipbench.spans import SCHEDULE, UNIT, idle_ms_per_iteration


def read(w):
    return idle_ms_per_iteration(w, (SCHEDULE, UNIT))
