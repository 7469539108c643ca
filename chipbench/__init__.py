"""On-chip benchmark of the task engine: one cell of ``BENCHMARK.json`` per run.

``run.py`` is the entry point.  Everything that belongs to one
configuration, traffic mix or per-layer metric lives in a file of its own,
found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` — sizes, guarantees and limits of a dataset;
  its ``app`` key names ``apps/<app>.py``, which makes the data from the
  seed, drives the timed iteration, and holds the plain reference and the
  comparison that decides ``correct``;
* ``traffic/<traffic>.json`` — how the user drives the engine (policy,
  backend, loop);
* ``metrics/<metric>.py`` — a reader of one per-layer metric.

The yardstick (peaks, trace reduction, pass rooflines) is shared:
``peaks.py``, ``trace_reduce.py``, ``roofline.py``.
"""
