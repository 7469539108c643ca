"""Child process of ``chipbench/test_chipbench_mesh.py``: the four-chip
k-means app on four forced CPU devices.

Run as ``python chipbench/_mesh_child.py`` from the root of a checkout,
with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.  Every check
runs, each in its own ``try``; the last line of standard output is one
JSON object mapping each check's name to ``"ok"`` or its failure.
"""

from __future__ import annotations

import contextlib
import json
import sys
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

SEED = 2**31 + 11
WORKLOAD = "kmeans.gigantic.mesh4"
FAULTS = ["none", "stale", "half", "altered", "one_rank"]


def _cfg(**sizes) -> dict:
    cfg = json.loads((ROOT / "chipbench" / "configs" / "kmeans-hibench-gigantic.json").read_text())
    cfg.update(sizes)
    return cfg


def check_make_data_places_and_matches_one_device():
    import jax
    import numpy as np

    from chipbench.apps import kmeans_mesh
    from chipbench.datagen import uniform_blocked

    cfg = _cfg(blocks_per_location=2, rows_per_block=1000)
    data = kmeans_mesh.make_data(cfg, SEED)
    one = uniform_blocked(SEED, kmeans_mesh.DATA_TAG, cfg)
    devs = jax.devices()
    assert list(data.placements) == list(one.placements)
    for b, (x, y) in enumerate(zip(data.blocks, one.blocks)):
        assert x.devices() == {devs[data.placements[b]]}, (b, x.devices())
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _engine_answers(cfg, data, n):
    import jax

    from chipbench import run
    from chipbench.apps import kmeans_mesh
    from repro.api import engine

    traffic = run.resolve(WORKLOAD).traffic
    answers = []
    with engine(traffic["backend"], devices=tuple(jax.devices())) as ex:
        loop = kmeans_mesh.Loop(cfg, data, run._policy(traffic), ex, SEED)
        for _ in range(n):
            answer, _ = loop.call()
            answers.append(answer)
            loop.carry(answer)
    return answers


def check_reference_passes_the_engine_and_refuses_the_control():
    from chipbench.apps import kmeans_mesh

    # about two million rows: the three-pass control moves rows across a
    # center's boundary only at that scale
    cfg = _cfg(blocks_per_location=2, rows_per_block=262_144)
    data = kmeans_mesh.make_data(cfg, SEED)
    worst, failed = kmeans_mesh.check(cfg, data, _engine_answers(cfg, data, 3))
    assert failed == 0, worst
    control = kmeans_mesh.control_answers(cfg, data, SEED, 3)
    worst, failed = kmeans_mesh.check(cfg, data, control)
    assert failed > 0, worst
    assert worst["moved_ppm"] > cfg["limits"]["moved_ppm"], worst


@contextlib.contextmanager
def _one_rank():
    """The exchange between chips left out: each rank's own partial is
    taken for the merged answer."""
    from repro.api import mesh_executor

    original = mesh_executor._sharded_program

    def program(fn, combine, n_data, per_rank, mesh, axis, n_extras):
        import jax
        from jax.sharding import PartitionSpec as P

        return jax.shard_map(fn, mesh=mesh, in_specs=(P(axis),) * n_data + (P(),) * n_extras,
                             out_specs=P(), check_vma=False)

    mesh_executor._sharded_program = program
    try:
        yield
    finally:
        mesh_executor._sharded_program = original


def _fault_run(fault: str) -> None:
    import jax

    from chipbench import run
    from chipbench.test_chipbench_faults import TINY, planted

    cell = run.resolve(WORKLOAD)
    cell.config.update(TINY)
    ctx = _one_rank() if fault == "one_rank" else planted(fault)
    with ctx:
        result = run.run_cell(cell, SEED, 0.3, False, devices=jax.devices()[: cell.chips])
    assert result["attempted"] > 0
    assert result["correct"] is (fault == "none"), (fault, result["checks"])


CHECKS = {
    "make_data_places_and_matches_one_device": check_make_data_places_and_matches_one_device,
    "reference_passes_the_engine_and_refuses_the_control":
        check_reference_passes_the_engine_and_refuses_the_control,
    **{f"fault_{f}": (lambda f=f: _fault_run(f)) for f in FAULTS},
}

if __name__ == "__main__":
    import jax

    assert jax.device_count() == 4, jax.device_count()
    results = {}
    for name, check in CHECKS.items():
        try:
            check()
            results[name] = "ok"
        except Exception:  # noqa: BLE001 — reported to the parent, check by check
            results[name] = traceback.format_exc()
    print(json.dumps(results))
