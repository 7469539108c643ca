"""Child process of tests/test_mesh_placement.py: blocks placed on the
devices their locations name, and the mesh backend reading them in place.

Runs under ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` set by
the parent, so the main pytest process keeps seeing one device.  Every
check runs, each in its own ``try``; the last line of standard output is
one JSON object mapping each check's name to ``"ok"`` or its failure.
"""

import json
import pathlib
import sys
import tempfile
import traceback
import warnings

import numpy as np

LOCATIONS, PER_LOCATION, ROWS, D, K = 4, 4, 1000, 20, 10
BLOCKS = LOCATIONS * PER_LOCATION
WINDOW = "mesh_child.window"


def _blocks():
    import jax
    import jax.numpy as jnp

    key = jax.random.key(7)
    return [jax.random.uniform(jax.random.fold_in(key, b), (ROWS, D), jnp.float32)
            for b in range(BLOCKS)]


def _data(placed: bool):
    import jax

    from repro.core.blocked import BlockedArray, round_robin_placement

    data = BlockedArray.from_blocks(
        _blocks(), round_robin_placement(BLOCKS, LOCATIONS), LOCATIONS
    )
    return data.placed_on(jax.devices()) if placed else data


def _centers():
    import jax
    import jax.numpy as jnp

    return jax.random.uniform(jax.random.key(3), (K, D), jnp.float32)


def _compute(data, executor, ppl=1, fusion="pallas"):
    from repro.api import Collection, SplIter
    from repro.core.apps.kmeans import _combine, partial_sum_block

    return (
        Collection.from_blocked(data)
        .split(SplIter(ppl, fusion=fusion))
        .map_blocks(partial_sum_block, extra_args=(_centers(),))
        .reduce(_combine)
        .compute(executor=executor)
    )


def _numpy_step(data):
    """One Lloyd step in float64 NumPy: (sums (K, D), counts (K,))."""
    x = np.concatenate([np.asarray(b, np.float64) for b in data.blocks])
    c = np.asarray(_centers(), np.float64)
    assign = ((x[:, None, :] - c[None]) ** 2).sum(-1).argmin(1)
    sums = np.stack([x[assign == i].sum(0) for i in range(K)])
    return sums, np.bincount(assign, minlength=K).astype(np.float64)


def _spans(run):
    """Run ``run()`` under the profiler; the engine's spans' stats by name."""
    import jax

    log_dir = tempfile.mkdtemp(prefix="mesh-child-")
    with jax.profiler.trace(log_dir):
        with jax.profiler.TraceAnnotation(WINDOW):
            out = run()
    (path,) = pathlib.Path(log_dir).rglob("*.xplane.pb")
    profile = jax.profiler.ProfileData.from_file(str(path))
    found: dict[str, list[dict]] = {}
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = list(line.events)
            if any(e.name == WINDOW for e in events):
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore", DeprecationWarning)
                    for e in events:
                        if e.name.startswith("repro."):
                            found.setdefault(e.name, []).append(dict(e.stats))
    return out, found


def check_placement():
    import jax

    from repro.core.blocked import location_device

    devs = jax.devices()
    assert [location_device(l, 4, devs) for l in range(4)] == devs
    assert [location_device(l, 8, devs) for l in range(8)] == [
        devs[0], devs[0], devs[1], devs[1], devs[2], devs[2], devs[3], devs[3]
    ]
    data = _data(placed=True)
    for b, block in enumerate(data.blocks):
        assert block.devices() == {devs[data.placements[b]]}, (b, block.devices())
    # a block already on its device is kept, not copied
    again = data.placed_on(devs)
    for a, b in zip(data.blocks, again.blocks):
        assert a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()


def check_in_place():
    import jax

    from repro.api import engine
    from repro.api.mesh_executor import MeshExecutor

    data = _data(placed=True)
    seen = []
    original = MeshExecutor._rank_operands

    def spy(self, mesh, tasks, per_task):
        out = original(self, mesh, tasks, per_task)
        seen.append(out)
        return out

    MeshExecutor._rank_operands = spy
    try:
        with engine("mesh", devices=jax.devices()) as ex:
            res, spans = _spans(lambda: _compute(data, ex))
    finally:
        MeshExecutor._rank_operands = original
    (operands, placed, copied, moved), = seen
    assert (placed, copied, moved) == (BLOCKS, 0, 0), (placed, copied, moved)
    (ops,) = spans["repro.operands"]
    assert (ops["copied"], ops["placed"]) == (0, BLOCKS), ops
    (unit,) = spans["repro.unit"]
    assert (unit["kind"], unit["ranks"]) == ("sharded", 4), unit
    assert res.report.dispatches == 1, res.report
    # only the collective is billed: 3 ranks receive each partial
    partial = (K * D + K) * 4
    assert res.report.bytes_moved == 3 * partial, res.report.bytes_moved
    # the p-th array of the one data operand holds each location's p-th
    # block, and each rank's shard is that block's buffer itself
    (blocks,) = operands
    for p, column in enumerate(blocks):
        for shard in column.addressable_shards:
            r = shard.device.id
            want = data.blocks[data.blocks_at(r)[p]]
            assert shard.data.unsafe_buffer_pointer() == want.unsafe_buffer_pointer(), (p, r)


def _agrees(data, **kw):
    import jax

    from repro.api import engine

    with engine("mesh", devices=jax.devices()) as ex:
        mesh = _compute(data, ex, **kw)
    # the local backend runs on one device: the same blocks, unplaced
    with engine("local") as ex:
        local = _compute(_data(placed=False), ex, **kw)
    for a, b in zip(mesh.value, local.value):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-4)
    sums, counts = _numpy_step(data)
    np.testing.assert_array_equal(np.asarray(mesh.value[1]), counts)
    np.testing.assert_allclose(np.asarray(mesh.value[0]), sums, rtol=1e-5, atol=1e-3)
    return mesh.report


def check_agrees_in_place():
    _agrees(_data(placed=True))


def check_fallback_blocks_elsewhere():
    import jax

    from repro.api import engine

    data = _data(placed=False)   # every block on the first device
    report = _agrees(data)
    with engine("mesh", devices=jax.devices()) as ex:
        _, spans = _spans(lambda: _compute(data, ex))
    (ops,) = spans["repro.operands"]
    # the first rank's blocks lie where it runs; the other 12 are moved
    assert (ops["copied"], ops["placed"]) == (12, 4), ops
    partial = (K * D + K) * 4
    assert report.bytes_moved == 3 * partial + 12 * ROWS * D * 4, report.bytes_moved


def check_fallback_ranks_stack_their_tasks():
    import jax

    from repro.api import engine

    data = _data(placed=True)
    _agrees(data, ppl=2)         # 8 tasks: each rank stacks its two
    with engine("mesh", devices=jax.devices()) as ex:
        _, spans = _spans(lambda: _compute(data, ex, ppl=2))
    (ops,) = spans["repro.operands"]
    assert (ops["copied"], ops["placed"]) == (BLOCKS, 0), ops


def check_scan_lowering_in_place():
    _agrees(_data(placed=True), fusion="scan")


CHECKS = {
    "placement": check_placement,
    "in_place": check_in_place,
    "agrees_in_place": check_agrees_in_place,
    "fallback_blocks_elsewhere": check_fallback_blocks_elsewhere,
    "fallback_ranks_stack_their_tasks": check_fallback_ranks_stack_their_tasks,
    "scan_lowering_in_place": check_scan_lowering_in_place,
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
    sys.exit(0)
