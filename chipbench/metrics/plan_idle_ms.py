"""Device idle ms per iteration while the engine prepares the placement and
lowers the plan (self time of its ``repro.prepare`` and ``repro.lower``
spans; see ``chipbench/spans.py``)."""

from chipbench.spans import LOWER, PREPARE, idle_ms_per_iteration


def read(w):
    return idle_ms_per_iteration(w, (PREPARE, LOWER))
