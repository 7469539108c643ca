"""Benchmark smoke job:  PYTHONPATH=src python -m benchmarks.smoke

Runs every benchmark suite at toy size — the policy×executor grid per app —
and emits one ``BENCH_<app>.json`` artifact each (wall, dispatches, merges,
traces, bytes_moved, granularity, retunes per row).

The perf trajectory is *committed*: the canonical ``BENCH_<app>.json``
baselines live in the repo root and CI re-runs the grid on every push,
diffing the **structural** columns (dispatches / merges / traces /
bytes_moved — exact on any single-device host) against the committed
baseline via ``--baseline .``.  Wall-clock is indicative only and never
diffed.  Rows of autotuned policies (``*_auto``) are compared by presence
only: their steady-state granularity follows *measured* wall times, so
their structural columns are legitimately host-dependent.

Baseline files are written with ``--write-baseline DIR`` and contain ONLY
the row identity + structural columns — no wall times, no tuner outputs —
so the committed artifact is deterministic and regenerating it on any
host produces an empty git diff unless something structural actually
changed.  Full rows (wall, granularity, retunes) always go to ``--out``
for the CI artifact upload.

Exits non-zero if any suite fails or the baseline diff is non-empty, so a
regression that breaks an app at toy size — or silently changes the
execution layer's dispatch/traffic behaviour — fails the job rather than
slipping through.  After an *intentional* change, regenerate and commit:
``PYTHONPATH=src python -m benchmarks.smoke --write-baseline .``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

#: columns that must match the committed baseline exactly (deterministic on
#: any single-device host); wall_s and the autotuner outputs are excluded.
#: ``bytes_moved`` is the steady-state (cache-warm) traffic, ``prep_bytes``
#: the first call's one-time prepare traffic — both diffed, so regressions
#: in either the per-iteration or the preparation path are caught.
#: ``remote_dispatches`` and ``retries`` pin the cluster rows: how much of
#: each plan crosses the IPC boundary is structural, and a non-zero retry
#: count in a no-fault smoke run is a bug.  ``shm_bytes`` pins the cluster
#: data plane: exact block bytes copied into shared-memory segments (raw
#: array sizes, not serialized forms — deterministic), so a regression
#: that silently re-routes payloads back onto the pipes (shm_bytes → 0)
#: or re-copies cached exports (shm_bytes inflated) fails the diff.
#: ``ipc_bytes`` is excluded — serialized sizes may drift across
#: pickle/numpy versions.  ``jobs`` and ``resumes`` pin the JobServer
#: rows: how many submissions one app run multiplexes is structural, and
#: a non-zero resume count in a no-kill smoke run is a bug.
#: ``overlapped_launches`` pins the pipelined rows (DESIGN.md §14): the
#: overlap count is frozen at submit time — a pure function of the app's
#: call order, not of host speed — so a regression that silently stops
#: iterations from overlapping (count → 0) fails the diff.
#: ``p2p_bytes`` and ``driver_merge_bytes`` pin the peer-exchange path
#: (DESIGN.md §16): both are exact — every member partial is consumed
#: exactly once, and with p2p off (the default at smoke partial sizes)
#: ``p2p_bytes`` must be exactly 0 on every grid row, so an auto gate
#: that silently flips (or a fold that double-bills) fails the diff.
#: The ``cluster-p2p`` kmeans row pins the collapse itself: one merged
#: partial per location, asserted in-suite at ≥4×.
#: ``steals`` and ``scale_events`` pin the elastic rows (DESIGN.md §15):
#: both must be exactly 0 on every non-elastic row (stealing defaults
#: off, so any non-zero count here is an accounting leak).  The elastic
#: straggler rows themselves (executor ``cluster-elastic``) are
#: presence-only, like ``*_auto`` policies: which units get stolen
#: follows measured load, so their structural columns are legitimately
#: host-dependent.
STRUCTURAL = (
    "dispatches",
    "merges",
    "traces",
    "bytes_moved",
    "prep_bytes",
    "remote_dispatches",
    "shm_bytes",
    "p2p_bytes",
    "driver_merge_bytes",
    "retries",
    "jobs",
    "resumes",
    "overlapped_launches",
    "steals",
    "scale_events",
)


def _row_key(row: dict) -> tuple:
    return (row.get("policy"), row.get("executor"))


def diff_rows(app: str, rows: list[dict], baseline_rows: list[dict]) -> list[str]:
    """Human-readable structural mismatches of one suite vs its baseline."""
    got = {_row_key(r): r for r in rows}
    want = {_row_key(r): r for r in baseline_rows}
    problems = []
    for key in sorted(set(want) - set(got)):
        problems.append(f"{app}: row {key} missing (present in baseline)")
    for key in sorted(set(got) - set(want)):
        problems.append(f"{app}: row {key} new (absent from baseline — "
                        "regenerate with --write-baseline . and commit)")
    for key in sorted(set(got) & set(want)):
        policy = key[0] or ""
        executor = key[1] or ""
        if "_auto" in policy:
            continue  # measured-granularity rows: presence-only
        if "elastic" in executor:
            continue  # measured-load steal rows: presence-only
        for col in STRUCTURAL:
            g, w = got[key].get(col), want[key].get(col)
            if g != w:
                problems.append(f"{app}: row {key} {col} = {g}, baseline {w}")
    return problems


def _baseline_row(row: dict) -> dict:
    """Strip a row to its deterministic identity + structural columns."""
    keep = {"policy": row.get("policy"), "executor": row.get("executor")}
    if "_auto" not in (row.get("policy") or "") and "elastic" not in (
        row.get("executor") or ""
    ):
        keep.update({col: row.get(col) for col in STRUCTURAL})
    return keep


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--out",
        default=os.environ.get("REPRO_BENCH_DIR", "results/bench"),
        help="directory for the full BENCH_<app>.json artifacts",
    )
    ap.add_argument(
        "--baseline",
        default=None,
        help="directory holding committed BENCH_<app>.json files; structural "
        "columns are diffed and mismatches fail the run",
    )
    ap.add_argument(
        "--write-baseline",
        default=None,
        metavar="DIR",
        help="also write structural-only baseline files (deterministic; "
        "commit these — canonically the repo root)",
    )
    ap.add_argument("--suite", action="append", default=None,
                    choices=["histogram", "kmeans", "svm", "knn", "trainer"],
                    help="subset of suites (default: all)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_histogram,
        bench_kmeans,
        bench_knn,
        bench_svm,
        bench_trainer,
    )

    suites = {
        "histogram": bench_histogram,
        "kmeans": bench_kmeans,
        "svm": bench_svm,
        "knn": bench_knn,
        "trainer": bench_trainer,
    }
    selected = args.suite or list(suites)
    os.makedirs(args.out, exist_ok=True)

    problems: list[str] = []
    t_all = time.perf_counter()
    for name in selected:
        t0 = time.perf_counter()
        rows = suites[name].smoke()
        elapsed = time.perf_counter() - t0
        path = os.path.join(args.out, f"BENCH_{name}.json")
        with open(path, "w") as f:
            json.dump(
                {"app": name, "elapsed_s": round(elapsed, 2), "rows": rows},
                f,
                indent=1,
            )
        if args.write_baseline is not None:
            os.makedirs(args.write_baseline, exist_ok=True)
            base_out = os.path.join(args.write_baseline, f"BENCH_{name}.json")
            with open(base_out, "w") as f:
                json.dump(
                    {"app": name, "rows": [_baseline_row(r) for r in rows]},
                    f,
                    indent=1,
                )
                f.write("\n")
        print(f"[{name}] {len(rows)} rows in {elapsed:.1f}s → {path}", flush=True)
        for r in rows:
            print(
                f"  {r['policy']:<16} {r['executor']:<9} "
                f"wall={r['wall_s']:<9} disp={r['dispatches']:<5} "
                f"traces={r['traces']:<3} bytes={r['bytes_moved']}"
                + (f" ppl={r['granularity']} retunes={r['retunes']}"
                   if r.get("granularity") else "")
            )
        if args.baseline is not None:
            base_path = os.path.join(args.baseline, f"BENCH_{name}.json")
            if not os.path.exists(base_path):
                problems.append(f"{name}: no committed baseline {base_path}")
            else:
                with open(base_path) as f:
                    baseline_rows = json.load(f)["rows"]
                problems.extend(diff_rows(name, rows, baseline_rows))
    print(f"smoke done in {time.perf_counter() - t_all:.1f}s")

    if args.baseline is not None:
        if problems:
            print(f"\nbaseline diff: {len(problems)} structural mismatch(es):")
            for p in problems:
                print(f"  {p}")
            sys.exit(1)
        print("baseline diff: clean")


if __name__ == "__main__":
    main()
