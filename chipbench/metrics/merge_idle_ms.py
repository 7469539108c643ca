"""Device idle ms per iteration while the engine merges the partials (self
time of its ``repro.merge`` spans: the stack of partials and the fold's
launch; see ``chipbench/spans.py``)."""

from chipbench.spans import MERGE, idle_ms_per_iteration


def read(w):
    return idle_ms_per_iteration(w, (MERGE,))
