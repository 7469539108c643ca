#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip and print its result line.

    python3 chipbench/run.py --workload kmeans.spliter --seed 7 --seconds 51 --trace 0

From the root of a checkout.  The run makes the cell's data on the device
from ``--seed``, warms up the cell's own shapes (set-up), then drives the
cell's closed loop for ``--seconds``: one user, the next ``compute()`` sent
when the last has returned.  Each iteration is timed from the call to
``block_until_ready`` of its answer.  Once the window has closed it reads
the peak device memory, closes the engine, and compares a sample of the
window's answers, drawn from the seed, with the configuration's plain
reference.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the window with the profiler and reports the per-layer metrics, the
device's busy and window seconds, and a breakdown.  The last line of
standard output is one JSON object; the numbers compared with the
reference, each beside its limit, are its last key and the last lines of
standard error.  Without a TPU holding the chips the cell asks for, the run
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import random  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "chipbench"
#: answers of the window compared with the reference, drawn from the seed
SAMPLE = 16
#: untimed iterations before the window: the first compiles (or loads) every
#: program the loop runs, the second shows nothing is left to compile
WARMUP = 2
_IDENT = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


class SetupError(SystemExit):
    """The run cannot start: a missing file, an unknown name, no chip."""

    def __init__(self, msg: str):
        super().__init__(f"chipbench: {msg}")


# ---------------------------------------------------------------------------
# resolving a cell from BENCHMARK.json
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    app_name: str
    end_to_end: list[dict]
    per_layer: list[dict]   # the per-layer metrics this cell reports


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SetupError(f"missing {path.relative_to(ROOT)}") from None


def resolve(workload: str) -> Cell:
    """The cell named ``workload``, with its configuration and traffic files read."""
    bench = _read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SetupError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(ROOT / configs[w["config"]]["file"])
    traffic = _read_json(BENCH / "traffic" / f"{w['traffic']}.json")
    app = config.get("app", "")
    if not _IDENT.match(app) or not (BENCH / "apps" / f"{app}.py").is_file():
        raise SetupError(f"configuration {w['config']!r} names no app under chipbench/apps")
    per_layer = [
        m for m in bench["per_layer"] if workload in m.get("workloads", [workload])
    ]
    for m in per_layer:
        if not (BENCH / "metrics" / f"{m['name']}.py").is_file():
            raise SetupError(f"per-layer metric {m['name']!r} has no chipbench/metrics file")
    return Cell(workload, w["chips"], config, traffic, app, bench["end_to_end"], per_layer)


def load_reader(metric: str):
    """The ``read(window)`` function of ``chipbench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _import_program() -> None:
    """Put the checkout's ``src`` and this directory's parent on the path."""
    if not (ROOT / "src" / "repro").is_dir():
        raise SetupError(f"no program under {ROOT / 'src'}; run from a checkout of the repo")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def tpu_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; exits where JAX finds fewer."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(
            f"JAX's devices are on platform {devs[0].platform!r}, not 'tpu'; "
            "this benchmark runs only on a TPU (no CPU fall-back)"
        )
    if len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def _policy(traffic: dict):
    import repro.api

    spec = dict(traffic["policy"])
    kind = spec.pop("kind")
    if kind not in ("Baseline", "SplIter"):
        raise SetupError(f"unknown policy kind {kind!r}")
    return getattr(repro.api, kind)(**spec)


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    start: float                 # perf_counter at the first timed call
    end: float                   # ... at the last answer's return
    times: list[float]           # per-iteration seconds
    dispatches: int              # engine dispatches over the window
    sample: list                 # answers drawn from the seed


def measure(loop, seconds: float, rng: random.Random) -> Window:
    import jax

    from chipbench.trace_reduce import ITERATION, WINDOW

    times: list[float] = []
    sample: list = []
    dispatches = 0
    with jax.profiler.TraceAnnotation(WINDOW):
        start = end = time.perf_counter()
        while end - start < seconds:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(ITERATION):
                answer, report = loop.call()
            end = time.perf_counter()
            times.append(end - t0)
            dispatches += report.dispatches
            # reservoir sample: every answer of the window equally likely
            i = len(times) - 1
            if i < SAMPLE:
                sample.append(answer)
            else:
                j = rng.randrange(i + 1)
                if j < SAMPLE:
                    sample[j] = answer
            loop.carry(answer)
    return Window(start, end, times, dispatches, sample)


@dataclasses.dataclass
class TracedWindow:
    """What a per-layer metric reader sees of a traced window."""

    app: str
    chips: int
    peak: object                 # chipbench.peaks.Peak of the device
    pass_bytes: float            # one pass's work, from the shapes
    pass_flops: float
    partials: int                # elements of one partial per block
    block_elements: int          # elements of one block of the data
    iterations: int
    dispatches: int
    trace: object                # chipbench.trace_reduce.Trace
    lo: float                    # the window on the trace's clock (ns)
    hi: float


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # Python call tracing would dominate the host
    opts.host_tracer_level = 2     # runtime spans name what the host was doing
    return opts


def _read_trace(log_dir: str, chips: int):
    import jax

    from chipbench.trace_reduce import from_profile

    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane.pb under {log_dir}, found {len(files)}")
    return from_profile(jax.profiler.ProfileData.from_file(str(files[0])), chips)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices=None) -> dict:
    """Run ``cell`` and return its result object.

    ``devices`` defaults to :func:`tpu_devices`; only tests pass their own.
    """
    import jax

    from repro.api import engine
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # small programs too, so that a warm run compiles nothing at all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = devices if devices is not None else tpu_devices(cell.chips)
    app = importlib.import_module(f"chipbench.apps.{cell.app_name}")
    cfg, traffic = cell.config, cell.traffic
    if traffic.get("loop") != "closed":
        raise SetupError(f"traffic loop {traffic.get('loop')!r} is not supported")

    t_devices = time.perf_counter()
    data = app.make_data(cfg, seed)
    jax.block_until_ready(data.blocks)
    t_data = time.perf_counter()
    executor = engine(traffic["backend"], devices=tuple(devices))
    loop = app.Loop(cfg, data, _policy(traffic), executor, seed)
    for _ in range(WARMUP):
        answer, _ = loop.call()
        loop.carry(answer)
    t_warm = time.perf_counter()
    print(f"setup: to devices {t_devices - PROCESS_T0:.3f} s, data {t_data - t_devices:.3f} s, "
          f"warm-up {t_warm - t_data:.3f} s", file=sys.stderr, flush=True)

    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(log_dir, profiler_options=_profile_options())
    try:
        win = measure(loop, seconds, random.Random(seed))
    finally:
        if trace:
            jax.profiler.stop_trace()
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    executor.close()
    del loop, executor

    n = len(win.times)
    dev = devices[0]
    result: dict = {
        "correct": False,
        "attempted": n,
        "failed": 0,
        "metrics": {},
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": peak_bytes,
        },
    }
    if trace:
        try:
            tw = _traced_window(cell, app, win, _read_trace(log_dir, cell.chips), dev)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        result["device"]["busy_s"] = tw.trace.busy_ns(tw.lo, tw.hi) * 1e-9
        result["device"]["window_s"] = (tw.hi - tw.lo) * 1e-9
        for m in cell.per_layer:
            value = load_reader(m["name"])(tw)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": tw.trace.top_ops(tw.lo, tw.hi),
            "idle_gaps": tw.trace.idle_by_host(tw.lo, tw.hi),
        }
    else:
        e2e = {
            "iter_s": (win.end - win.start) / n,
            "iter_p95_s": statistics.quantiles(win.times, n=100, method="inclusive")[94]
            if n > 1 else win.times[0],
            "setup_s": win.start - PROCESS_T0,
        }
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    worst, failed = app.check(cfg, data, win.sample)
    limits = cfg["limits"]
    result["correct"] = failed == 0 and all(worst[k] <= limits[k] for k in limits)
    result["failed"] = failed
    result["checks"] = {k: {"value": worst[k], "limit": limits[k]} for k in limits}
    return result


def _traced_window(cell: Cell, app, win: Window, trace, dev) -> TracedWindow:
    from chipbench.peaks import peak_for

    lo, hi = trace.window()
    cfg = cell.config
    nbytes, flops = app.pass_work(cfg)
    blocks = cfg["locations"] * cfg["blocks_per_location"]
    return TracedWindow(
        app=cell.app_name, chips=cell.chips,
        peak=peak_for(dev.device_kind), pass_bytes=nbytes, pass_flops=flops,
        partials=blocks * app.partial_elements(cfg),
        block_elements=cfg["rows_per_block"] * cfg["d"],
        iterations=len(win.times), dispatches=win.dispatches, trace=trace, lo=lo, hi=hi,
    )


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="a workload name of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="seed of the data and the sample")
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the window and report the per-layer metrics")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SetupError(f"--seed must be >= 0, got {args.seed}")

    cell = resolve(args.workload)
    _import_program()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
