"""A whole run, the chip's look skipped, with the timed path broken underneath:
``correct`` has to come out false for every fault the cell can have, and
true with nothing broken.

Faults, each planted in the engine that the window drives:

* ``stale``: every ``compute()`` returns the first answer it gave (a loop
  whose state never moves);
* ``half``: half of the blocks left out of the plan, the answer taken over
  the rest;
* ``altered``: the merged answer changed where the engine produces it.

No cell runs across chips, so no exchange between chips can be left out.
"""

from __future__ import annotations

import contextlib
import dataclasses

import pytest

SEED = 3_000_000_017
TINY = dict(locations=4, blocks_per_location=2, rows_per_block=512)
FAULTS = {
    "kmeans.spliter": ["none", "stale", "half", "altered"],
    "kmeans.baseline": ["none", "stale", "half", "altered"],
}


@contextlib.contextmanager
def planted(fault: str):
    """Break the engine's timed path with ``fault`` for the duration."""
    import jax
    import jax.numpy as jnp

    from repro.api import executors, lowering

    patches = []

    def patch(obj, name, value):
        patches.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    if fault == "stale":
        first = {}
        execute = executors._PlanExecutor.execute

        def stale_execute(self, plan):
            if "result" not in first:
                first["result"] = execute(self, plan)
            return first["result"]

        patch(executors._PlanExecutor, "execute", stale_execute)
    elif fault == "half":
        prepare = executors._PlanExecutor._prepare

        def half_prepare(self, inputs, policy, report):
            p = prepare(self, inputs, policy, report)
            groups = list(p.groups)
            if all(len(g.block_ids) == 1 for g in groups):
                groups = groups[::2]
            else:
                groups = [
                    lowering.PlacedGroup(g.location, g.block_ids[: max(1, len(g.block_ids) // 2)])
                    for g in groups
                ]
            return dataclasses.replace(p, groups=groups)

        patch(executors._PlanExecutor, "_prepare", half_prepare)
    elif fault == "altered":
        merge = executors._merge_partials

        def altered_merge(*args, **kwargs):
            out = merge(*args, **kwargs)
            leaves, tree = jax.tree.flatten(out)
            first = tuple([0] * leaves[0].ndim)
            leaves[0] = leaves[0].at[first].add(jnp.asarray(leaves[0][first] + 1, leaves[0].dtype))
            return jax.tree.unflatten(tree, leaves)

        patch(executors, "_merge_partials", altered_merge)
    elif fault != "none":
        raise ValueError(fault)
    try:
        yield
    finally:
        for obj, name, value in reversed(patches):
            setattr(obj, name, value)


def run_tiny(workload: str, fault: str) -> dict:
    """One whole run of ``workload`` at a tiny size on the CPU devices."""
    import jax

    from chipbench import run

    cell = run.resolve(workload)
    cell.config.update(TINY)
    with planted(fault):
        return run.run_cell(cell, SEED, 0.3, False, devices=jax.devices()[: cell.chips])


@pytest.mark.parametrize(
    "workload, fault", [(w, f) for w, faults in FAULTS.items() for f in faults]
)
def test_fault_makes_the_run_incorrect(workload, fault, tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    result = run_tiny(workload, fault)
    assert result["attempted"] > 0
    assert result["correct"] is (fault == "none"), result["checks"]
    assert list(result)[-1] == "checks"
