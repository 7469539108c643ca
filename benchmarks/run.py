"""Benchmark entry point:  PYTHONPATH=src python -m benchmarks.run

One benchmark per paper table/figure (Figs 9–21, Table 1) plus the
framework-level benches (trainer accumulation modes, dispatch overhead).
Results print as tables and persist to results/bench/*.json.

``--full`` uses larger datasets / more repeats (paper-scale shapes);
default sizes finish in a few minutes on one CPU core.
"""

from __future__ import annotations

import argparse
import time

SUITES = ("histogram", "kmeans", "svm", "knn", "trainer")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--suite", action="append", choices=SUITES, default=None)
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--out", default="results/bench")
    args = ap.parse_args()
    suites = args.suite or list(SUITES)
    quick = not args.full

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_histogram,
        bench_kmeans,
        bench_knn,
        bench_svm,
        bench_trainer,
    )

    mods = {
        "histogram": bench_histogram,
        "kmeans": bench_kmeans,
        "svm": bench_svm,
        "knn": bench_knn,
        "trainer": bench_trainer,
    }

    t_all = time.perf_counter()
    for name in suites:
        t0 = time.perf_counter()
        tables = mods[name].bench(quick=quick)
        for tbl in tables:
            tbl.show()
            tbl.save(args.out)
        print(f"[{name}] done in {time.perf_counter() - t0:.1f}s "
              f"→ {args.out}/*.json", flush=True)
    print(f"\nall suites done in {time.perf_counter() - t_all:.1f}s")


if __name__ == "__main__":
    main()
