"""MeshExecutor — sharded scheduling of a TaskGraph over a JAX device mesh.

The third backend of the execution layer (DESIGN.md §5.4): logical
*locations* are mapped onto a 1-D device mesh and all partition tasks of a
location group execute as ONE sharded dispatch.  Where LocalExecutor emits
one host dispatch per task and ThreadedExecutor overlaps them with
threads, MeshExecutor stacks the same-signature tasks of a lowered
:class:`~repro.api.lowering.TaskGraph` along a leading axis, shards that
axis over the mesh with :func:`jax.shard_map`, folds
each rank's local tasks with the plan's combine, and merges across ranks
with a psum-style collective (all-gather + fold, the all-reduce of an
arbitrary associative monoid — plain ``psum`` when the combine is a sum).

Accounting maps onto the existing :class:`~repro.core.engine.EngineReport`:

* ``dispatches`` — sharded calls (one per same-signature task run), not
  per-task invocations; still bounded by C1.
* ``bytes_moved`` — the collective traffic estimate: each of the M mesh
  ranks receives the other M-1 partial pytrees, so one cross-rank merge
  bills ``(M - 1) × partial_nbytes`` (the per-rank ring volume).
* ``merges`` — cross-rank collective merges (plus the plan-order fold over
  distinct task runs, e.g. ragged tails, exactly as on the other backends).

Tasks that cannot be stacked — ``map_partitions`` views, un-reduced maps,
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
    Capabilities,
    Task,
    TaskGraph,
    _partition_body,
    stacked_fold,
)
from repro.api.profile import SPAN_OPERANDS
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

    @property
    def capabilities(self) -> Capabilities:
        return Capabilities(
            name=type(self).__name__,
            prefer_pallas=jax.default_backend() == "tpu",
            grouped_dispatch=True,
        )

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
                    )
                )
        return units

    def _sharded_dispatch(self, graph: TaskGraph, tasks: list[Task]) -> Any:
        t0 = tasks[0]
        n_data = t0.n_data
        combine = graph.merge.combine
        g = len(tasks)
        m = self._axis_size(g, self._device_count())
        mesh = self._mesh(m)
        axis = self.axis_name

        # stack each per-task data operand (each block of a run passed in
        # place) along a new leading (group) axis and place it split over the
        # mesh; extras are plan-wide, shared by every task of the signature,
        # and replicated
        copied = sum(t.copied + t.n_data * len(t.block_ids) for t in tasks)
        with TraceAnnotation(SPAN_OPERANDS, copied=copied):
            per_task = [t.operands() for t in tasks]
            stacked = tuple(
                jax.device_put(
                    jax.tree.map(
                        lambda *xs: jnp.stack(xs, axis=0), *(ops[j] for ops in per_task)
                    ),
                    NamedSharding(mesh, P(axis)),
                )
                for j in range(n_data)
            )
            extras = jax.device_put(per_task[0][n_data:], NamedSharding(mesh, P()))
            del per_task

        # local fold over the rank's tasks = the generic partition body over
        # the group axis (one source of truth for the first/scan fold)
        local_fold = _partition_body(t0.fn, combine, n_data)

        def fused(*ops):
            acc = local_fold(*ops)
            # psum-style cross-rank merge: all-gather the rank partials and
            # fold in rank order (all-reduce for an arbitrary monoid) — the
            # same stacked_fold the host-side merge task runs, so the two
            # merge paths cannot drift apart.
            gathered = jax.tree.map(
                lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=False), acc
            )
            return stacked_fold(combine)(gathered)

        sharded = jax.shard_map(
            fused,
            mesh=mesh,
            in_specs=(P(axis),) * n_data + (P(),) * len(extras),
            out_specs=P(),
            check_vma=False,
        )
        # the key carries the merge identity too: the same map fn reduced by
        # a different combine must not reuse this compiled fold
        key = ("mesh", t0.key, graph.merge.key, m, t0.data_shapes, g)
        value = self.engine.task(sharded, key=key)(*stacked, *extras)
        if m > 1:
            self.engine.report.merges += 1
            self.engine.report.bytes_moved += (m - 1) * _tree_nbytes(value)
        return value
