"""MeshExecutor — sharded scheduling of a TaskGraph over a JAX device mesh.

The third backend of the execution layer (DESIGN.md §5.4): logical
*locations* are mapped onto a 1-D device mesh and all partition tasks of a
location group execute as ONE sharded dispatch.  Where LocalExecutor emits
one host dispatch per task and ThreadedExecutor overlaps them with
threads, MeshExecutor deals the same-signature tasks of a lowered
:class:`~repro.api.lowering.TaskGraph` over the mesh ranks in contiguous
runs, in graph order, and runs them as one :func:`jax.shard_map` program:
each rank runs its own tasks over its own shard of every data operand,
then the ranks merge with a psum-style collective (all-gather + fold, the
all-reduce of an arbitrary associative monoid — plain ``psum`` when the
combine is a sum).

Operands are built from the rank shards where they lie
(:func:`jax.make_array_from_single_device_arrays`).  A rank that holds one
task gets that task's blocks themselves as its shard: where every block
lies on its rank's device — the device
:func:`~repro.core.blocked.location_device` names for its location, where
:meth:`~repro.core.blocked.BlockedArray.placed_on` puts it — nothing is
stacked, copied or moved, and a fused Pallas kernel reads the blocks in
place on every chip.  A block elsewhere is moved to its rank's device
first; a rank that holds several tasks stacks them on its own device and
folds them with the plan's combine before the merge.

Accounting maps onto the existing :class:`~repro.core.engine.EngineReport`:

* ``dispatches`` — sharded calls (one per same-signature task run), not
  per-task invocations; still bounded by C1.
* ``bytes_moved`` — the collective traffic estimate: each of the M mesh
  ranks receives the other M-1 partial pytrees, so one cross-rank merge
  bills ``(M - 1) × partial_nbytes`` (the per-rank ring volume); plus the
  bytes of every block moved to its rank's device.
* ``merges`` — cross-rank collective merges (plus the plan-order fold over
  distinct task runs, e.g. ragged tails, exactly as on the other backends).

Tasks that cannot be sharded — ``map_partitions`` views, un-reduced maps,
singleton runs — fall back to per-task dispatch, so every plan the other
backends accept runs here too, and results agree up to float reassociation
(C4).

Ordering: buckets preserve graph task order, so the fold visits partials
in plan order whenever task signatures don't interleave (uniform blocks —
the common case).  With interleaved signatures (a ragged run between
uniform ones) partials are folded bucket-by-bucket, which REASSOCIATES AND
REORDERS the combine relative to LocalExecutor: combines must be
commutative up to float reassociation (true of every reduction in the
paper's apps) for this backend.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api.executors import _PlanExecutor, _Unit
from repro.api.lowering import (
    Task,
    TaskGraph,
    _partition_body,
    program_name,
    stacked_fold,
)
from repro.api.profile import SPAN_OPERANDS
from repro.core.blocked import location_device
from repro.core.engine import TaskEngine

__all__ = ["MeshExecutor"]


def _tree_nbytes(tree: Any) -> int:
    return sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree.leaves(tree)
        if hasattr(x, "shape")
    )


class MeshExecutor(_PlanExecutor):
    """Execute location groups as sharded dispatches over a device mesh.

    Args:
      engine: shared :class:`TaskEngine` (accounting + jit cache).
      devices: devices backing the mesh; defaults to ``jax.devices()``.
        The mesh axis size for a run of G stacked tasks is the largest
        divisor of G not exceeding the device count (1 on a single-device
        host — still one sharded dispatch, with zero collective traffic).
      axis_name: mesh axis name the location dimension shards over.
    """

    def __init__(
        self,
        engine: TaskEngine | None = None,
        *,
        devices=None,
        axis_name: str = "loc",
    ):
        super().__init__(engine)
        self._devices = tuple(devices) if devices is not None else None
        self.axis_name = axis_name
        self._meshes: dict[int, Mesh] = {}

    # -- mesh plumbing ---------------------------------------------------------

    def _device_count(self) -> int:
        return len(self._devices) if self._devices is not None else len(jax.devices())

    def _mesh(self, size: int) -> Mesh:
        m = self._meshes.get(size)
        if m is None:
            devs = self._devices if self._devices is not None else tuple(jax.devices())
            m = self._meshes[size] = Mesh(np.array(devs[:size]), (self.axis_name,))
        return m

    @staticmethod
    def _axis_size(n_tasks: int, n_devices: int) -> int:
        """Largest mesh size that evenly tiles the stacked task dimension."""
        for m in range(min(n_tasks, max(n_devices, 1)), 0, -1):
            if n_tasks % m == 0:
                return m
        return 1

    # -- scheduling ------------------------------------------------------------

    def _plan_dispatches(self, graph: TaskGraph) -> list[_Unit]:
        """Bucketed dispatch units for the shared scheduler core.

        Tasks with the same dispatch signature — same jit key + same
        per-task data shapes — stack into ONE sharded unit, PRESERVING
        graph task order, so within a bucket the fold visits partials in
        plan order (lowering emits partition tasks location-major, which is
        what maps contiguous location groups onto contiguous mesh ranks).
        Operands stay lazy here: buckets form from Task.data_shapes
        metadata and each bucket materializes its stacks only at its own
        dispatch.  Views, un-reduced maps and singleton buckets fall back
        to per-task units (the LocalExecutor path).
        """
        if graph.merge is None or not graph.tasks or any(
            not t.counted for t in graph.tasks
        ):
            return super()._plan_dispatches(graph)

        buckets: dict[tuple, list[Task]] = {}
        for t in graph.tasks:
            buckets.setdefault((t.key, t.data_shapes), []).append(t)

        units: list[_Unit] = []
        for tasks in buckets.values():
            if len(tasks) == 1:
                t = tasks[0]
                units.append(
                    _Unit(index=len(units), location=t.location, tasks=(t,),
                          run=self._bind(t), kind=t.kind)
                )
            else:
                units.append(
                    _Unit(
                        index=len(units),
                        location=-1,
                        tasks=tuple(tasks),
                        run=functools.partial(self._sharded_dispatch, graph, tasks),
                        kind="sharded",
                        ranks=self._axis_size(len(tasks), self._device_count()),
                    )
                )
        return units

    def _sharded_dispatch(self, graph: TaskGraph, tasks: list[Task]) -> Any:
        t0 = tasks[0]
        m = self._axis_size(len(tasks), self._device_count())
        mesh = self._mesh(m)
        with TraceAnnotation(SPAN_OPERANDS) as span:
            per_task = [t.operands() for t in tasks]
            data, placed, copied, moved = self._rank_operands(mesh, tasks, per_task)
            # extras are plan-wide, shared by every task of the signature:
            # replicated
            extras = jax.device_put(per_task[0][t0.n_data:], NamedSharding(mesh, P()))
            del per_task
            span.set_metadata(copied=copied, placed=placed)

        # the key carries the merge identity too: the same map fn reduced by
        # a different combine must not reuse this compiled fold
        key = ("mesh", t0.key, graph.merge.key, m, t0.data_shapes, len(tasks))
        program = self.engine.task(
            _sharded_program(t0.fn, graph.merge.combine, t0.n_data, len(tasks) // m,
                             mesh, self.axis_name, len(extras)),
            key=key,
            name=program_name("sharded", t0.kernel_name or t0.fn),
        )
        value = program(*data, *extras)
        self.engine.report.bytes_moved += moved
        if m > 1:
            self.engine.report.merges += 1
            self.engine.report.bytes_moved += (m - 1) * _tree_nbytes(value)
        return value

    def _rank_operands(
        self, mesh: Mesh, tasks: list[Task], per_task: list[tuple]
    ) -> tuple[tuple, int, int, int]:
        """Each data operand as one array split over the mesh's ranks.

        Task ``i`` of the run goes to the rank of device
        :func:`~repro.core.blocked.location_device` ``(i, tasks, devices)``
        (contiguous runs of tasks, as contiguous runs of locations share a
        device), and every array of its operand (a block, a stacked run, or
        each block of a Pallas run) becomes that rank's shard.  A rank with
        one task passes the array itself; one with several stacks them
        along a new group axis on its own device.  An array that does not
        lie on its rank's device is moved there first.  Returns the
        operands, the blocks passed where they lie, the blocks copied
        (stacked, concatenated or moved), and the bytes moved between
        devices.
        """
        devices = list(mesh.devices.flat)
        ranks = [location_device(i, len(tasks), devices) for i in range(len(tasks))]
        per_rank = len(tasks) // len(devices)
        sharding = NamedSharding(mesh, P(self.axis_name))
        total = sum(t.n_data * len(t.block_ids) for t in tasks)
        copied = sum(t.copied for t in tasks)
        moved = 0
        data = []
        for j in range(tasks[0].n_data):
            leaves = [jax.tree.leaves(ops[j]) for ops in per_task]
            columns = []
            for p in range(len(leaves[0])):
                runs: dict = {dev: [] for dev in devices}
                for task, dev, arrays in zip(tasks, ranks, leaves):
                    x = arrays[p]
                    if _lies_on(x, dev):
                        # a task's own stack already counts its blocks
                        copied += per_rank > 1 and not task.copied
                    else:
                        x = jax.device_put(x, dev)
                        moved += x.nbytes
                        copied += not task.copied
                    runs[dev].append(x)
                shards = [run[0] if per_rank == 1 else jnp.stack(run) for run in runs.values()]
                shape = (len(devices) * shards[0].shape[0], *shards[0].shape[1:])
                columns.append(jax.make_array_from_single_device_arrays(shape, sharding, shards))
            data.append(jax.tree.unflatten(jax.tree.structure(per_task[0][j]), columns))
        return tuple(data), total - copied, copied, moved


def _lies_on(x: Any, device) -> bool:
    return isinstance(x, jax.Array) and x.devices() == {device}


def _sharded_program(
    fn, combine, n_data: int, per_rank: int, mesh: Mesh, axis: str, n_extras: int
):
    """The one program of a sharded unit.

    Each rank runs ``fn`` over its shard of every data operand — directly
    where it holds one task, folded over the group axis with ``combine``
    (the generic partition body) where it holds ``per_rank`` > 1 — then the
    ranks all-gather their partials and fold them in rank order with the
    same :func:`~repro.api.lowering.stacked_fold` the host-side merge task
    runs, so the two merge paths cannot drift apart.
    """
    local = fn if per_rank == 1 else _partition_body(fn, combine, n_data)

    def fused(*ops):
        acc = local(*ops)
        gathered = jax.tree.map(
            lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=False), acc
        )
        return stacked_fold(combine)(gathered)

    return jax.shard_map(
        fused,
        mesh=mesh,
        in_specs=(P(axis),) * n_data + (P(),) * n_extras,
        out_specs=P(),
        check_vma=False,
    )
