#!/usr/bin/env python3
"""Read the numbers a cell compares, on the chip, for the limits in its
configuration: the control's and the program's.

    python3 chipbench/control.py --workload kmeans.spliter --seeds 5 6 7
    python3 chipbench/control.py --workload kmeans.spliter --seeds 5 6 7 --program-seconds 4

Without ``--program-seconds`` it reads the control: for each seed it makes
the cell's data at the cell's own size, takes as many answers as a run
compares from the plain reference put in the engine's place, computed one
precision below the configuration's, and prints the compared numbers
beside the limits as one JSON line.  Every seed has to come out not
correct; the smallest reading is the upper end a limit is set below.

With it, each seed is a whole run of the cell (set-up, a closed loop of
that many seconds, the comparison), all in this one process, so that a
dozen seeds pay the chip's start-up once; the largest reading is the lower
end a limit is set above (``PERF.md``).  The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import run  # noqa: E402


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program-seconds", type=float, default=0.0,
                    help="read the program's runs of this window instead of the control")
    args = ap.parse_args(argv)

    cell = run.resolve(args.workload)
    run._import_program()
    devices = run.tpu_devices(cell.chips)
    app = importlib.import_module(f"chipbench.apps.{cell.app_name}")
    limits = cell.config["limits"]
    for seed in args.seeds:
        if args.program_seconds:
            result = run.run_cell(cell, seed, args.program_seconds, False, devices)
            line = {"of": min(run.SAMPLE, result["attempted"]), "failed": result["failed"],
                    "readings": {k: c["value"] for k, c in result["checks"].items()}}
        else:
            data = app.make_data(cell.config, seed)
            answers = app.control_answers(cell.config, data, seed, run.SAMPLE)
            worst, failed = app.check(cell.config, data, answers)
            line = {"of": len(answers), "failed": failed, "readings": worst}
            del data, answers
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "read": "program" if args.program_seconds else "control",
                          **line, "limits": limits}), flush=True)


if __name__ == "__main__":
    main()
