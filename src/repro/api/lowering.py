"""Lowering pass — (plan spec, prepared placement, capabilities) → TaskGraph.

This is the first half of the execution layer's two-stage split
(DESIGN.md §5): *lowering* turns a validated
:class:`~repro.api.plan.MapReduceSpec` plus the prepared placement (the
policy-derived task groups) into a frozen :class:`TaskGraph` of placed,
keyed :class:`Task` descriptors; *scheduling* (the executor backends) then
decides where and when each descriptor runs.  Everything execution-strategy
dependent — fusion level, task keys, operand construction — is decided
here, once, so a new backend is "implement scheduling over TaskGraph"
rather than another fork of the task-construction logic.

Fusion levels for a reduced ``map_blocks`` under ``SplIter``:

``partition_scan``
    The generic fusion (paper Listing 5): one task per same-shape run of a
    partition's blocks, ``lax.scan`` carrying the partition-local reduction.
``partition_pallas``
    A registered fused kernel (``repro.api.kernels``): one ``pallas_call``
    whose grid iterates the run's blocks while the accumulator stays in
    VMEM.  Chosen by the policy's ``fusion`` knob ("pallas", or "auto" on
    backends that prefer it) with automatic fallback to the scan when no
    kernel is registered, the kernel rejects the shapes, or the plan has
    multiple inputs.

Task *keys* are stable across plan rebuilds: :func:`stable_task_key`
derives a key from code objects, closures and ``functools.partial``
statics, so an app that recreates its lambdas every call (the historical
``("merge", combine)`` bug) still hits the engine's jit cache instead of
re-tracing per call.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import pickle
from typing import Any, Callable, Hashable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.chunkstore import ChunkRef, resolve_chunk
from repro.api.fnref import encode_fn
from repro.api.futures import Deferred, resolve_deferred
from repro.api.kernels import PartitionKernel, kernel_ref, partition_kernel_for
from repro.api.plan import MapReduceSpec
from repro.api.policy import SplIter
from repro.core.blocked import BlockedArray

__all__ = [
    "Capabilities",
    "PartitionView",
    "PlacedGroup",
    "Task",
    "TaskSpec",
    "key_summary",
    "MergeSpec",
    "TaskGraph",
    "cross_iteration_edges",
    "fold_plan",
    "planned_fold",
    "lower",
    "inputs_signature",
    "partition_key",
    "plan_fingerprint",
    "program_name",
    "stable_task_key",
    "stacked_fold",
]


# ---------------------------------------------------------------------------
# stable task keys (jit-cache identity that survives plan rebuilds)
# ---------------------------------------------------------------------------


def stable_task_key(fn: Callable) -> Hashable:
    """A hashable identity for ``fn`` stable across re-creations.

    App-level lambdas and ``functools.partial`` wrappers are rebuilt on
    every call (``histogram()`` makes a fresh ``partial`` and a fresh merge
    lambda each time); keying the engine's jit cache on the *object* made
    every call re-trace.  Two callables get the same key iff they share the
    same code object, the same default arguments, the same closure cell
    values, and (for partials) the same statics — i.e. they compute the
    same function.  Anything non-hashable falls back to the object itself
    (identity keying, the previous behaviour).
    """
    if isinstance(fn, functools.partial):
        inner = stable_task_key(fn.func)
        try:
            statics = (tuple(fn.args), tuple(sorted(fn.keywords.items())))
            hash(statics)
        except TypeError:
            return fn
        return ("partial", inner, statics)
    code = getattr(fn, "__code__", None)
    if code is None:
        return fn  # builtins / callables: identity is the best we can do
    # id(__globals__) guards against identical bytecode resolving different
    # global bindings (two modules defining the same-looking fn): the module
    # dict outlives its functions, so the id is stable across re-creations
    # within a module but distinct across modules.
    parts: list[Any] = [code, id(getattr(fn, "__globals__", None))]
    defaults = getattr(fn, "__defaults__", None)
    cells = getattr(fn, "__closure__", None)
    try:
        if defaults:
            hash(defaults)
            parts.append(defaults)
        if cells:
            vals = tuple(c.cell_contents for c in cells)
            hash(vals)
            parts.append(vals)
    except (TypeError, ValueError):  # unhashable default/cell, or empty cell
        return fn
    return ("fn", *parts)


def program_name(kind: str, fn: Callable | str) -> str:
    """``repro_<kind>_<name>``: the name a task's program runs under.

    ``fn`` is the task's function (``functools.partial`` layers peeled) or
    a kernel's registered name.  A profile then shows the engine's
    programs as ``jit_repro_…`` whatever wrappers build them, so a trace
    reduction finds them after a refactor.

    >>> program_name("merge", stacked_fold(lambda a, b: a + b))
    'repro_merge_fold'
    """
    if not isinstance(fn, str):
        while isinstance(fn, functools.partial):
            fn = fn.func
        fn = getattr(fn, "__name__", type(fn).__name__)
    return f"repro_{kind}_{fn}"


# ---------------------------------------------------------------------------
# plan fingerprints (cross-request identity for shared server assets)
# ---------------------------------------------------------------------------


def inputs_signature(arrays: tuple) -> tuple:
    """The geometry identity of a set of inputs, independent of object ids.

    Two submissions over equal-geometry datasets (same blocking, dtypes and
    placements) share this signature even when the arrays are distinct
    objects — e.g. two tenants loading the same dataset, or a journal-
    rebuilt array after a server restart.  It deliberately excludes buffer
    *contents* (hashing them would force chunk loads), so it is a
    cache/tuner sharing key, not a proof of data equality.
    """
    return tuple(
        (
            tuple(int(r) for r in a.block_rows),
            tuple(a.row_shape),
            str(a.dtype),
            int(a.num_locations),
            tuple(int(p) for p in a.placements),
        )
        for a in arrays
    )


def plan_fingerprint(spec: MapReduceSpec, policy=None) -> str:
    """A stable hex digest identifying a plan across processes and restarts.

    Combines the plan shape (kind, fn/combine references via
    :func:`~repro.api.fnref.encode_fn`, extra-arg bytes), the policy and
    the :func:`inputs_signature`.  The JobServer journals it per
    submission: equal fingerprints mean "the same work", which is what
    lets shared assets (profiles, tuner state) accumulate across tenants
    and a restarted server match journal records to rebuilt plans.
    Unencodable callables degrade to their qualified name, so the
    fingerprint always exists — it is an identity, not a replay payload.
    """

    def fn_part(fn):
        if fn is None:
            return None
        ref = encode_fn(fn)
        if ref is not None:
            return ref
        return getattr(fn, "__qualname__", repr(type(fn)))

    parts = (
        spec.kind,
        repr(policy if policy is not None else spec.policy),
        fn_part(spec.fn),
        fn_part(spec.combine),
        tuple(
            # Deferred operands (pipelined iteration) have no geometry until
            # their source execute resolves; fingerprinting must not force —
            # or worse, block on — that resolution, so they degrade to a
            # marker.  Loop-carried deferreds share geometry across
            # iterations anyway, so the identity stays useful.
            ("deferred",)
            if isinstance(e, Deferred)
            else (tuple(np.asarray(e).shape), str(np.asarray(e).dtype))
            for e in spec.extra_args
        ),
        inputs_signature(spec.inputs),
    )
    return hashlib.sha256(pickle.dumps(parts)).hexdigest()[:32]


# ---------------------------------------------------------------------------
# backend capabilities
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Capabilities:
    """What an executor backend can (and wants to) run.

    Attributes:
      name: backend label (diagnostics only).
      prefer_pallas: under ``fusion="auto"`` pick the Pallas kernel when one
        is registered.  Backends where the kernel runs compiled (TPU) prefer
        it; interpret-mode backends (CPU tests) keep the scan, which is the
        per-backend granularity trade-off of Bora et al. (arXiv:2202.11464).
      out_of_core: backend streams chunk-backed blocks under a residency
        budget (StreamExecutor).  Lowering then attaches each task's
        :class:`~repro.api.chunkstore.ChunkRef` operands to the descriptor
        (``Task.chunk_refs``) so the scheduler can pin/prefetch/release
        them around dispatch without materializing operands; non-streaming
        backends skip the bookkeeping (refs still resolve lazily inside
        ``operands()``).
      remote: backend dispatches tasks to other processes (ClusterExecutor).
        Lowering then attaches a picklable function reference
        (``Task.fn_ref``, built via :mod:`repro.api.fnref` and the named
        kernel registry) plus a raw-operand builder, so :meth:`Task.spec`
        can project the descriptor into a :class:`TaskSpec` that crosses a
        process boundary.  Tasks whose code cannot be referenced (driver
        views, unpicklable closures) keep ``fn_ref=None`` and the backend
        runs them in-process.
      pipelined: backend overlaps consecutive ``execute_async`` submissions
        (DESIGN.md §14): iteration *k+1*'s units are gated on their
        same-partition *k* predecessors via :func:`cross_iteration_edges`
        instead of a global drain.  Non-pipelined backends run
        ``execute_async`` as a synchronous execute returning an
        already-completed future — same results, no overlap.
      exporter: dispatch-time block exporter of the shared-memory data
        plane (``callable(block) -> ShmBlockRef | None``), or None.  When
        set, operand builders hand large blocks off as shm descriptors
        instead of raw ndarray payloads; a ``None`` return falls back to
        inline bytes.  Excluded from equality/hash so caches keyed on
        capabilities don't fragment on executor identity.
    """

    name: str = "local"
    prefer_pallas: bool = False
    out_of_core: bool = False
    remote: bool = False
    pipelined: bool = False
    exporter: Any = dataclasses.field(default=None, compare=False, repr=False)


# ---------------------------------------------------------------------------
# prepared placement + partition views
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlacedGroup:
    """One policy-derived task group: which blocks one task consumes, where."""

    location: int
    block_ids: tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class PartitionView:
    """A single-location group of aligned blocks, as seen by map_partitions.

    Generalizes :class:`~repro.core.spliter.Partition` to multi-input plans
    (e.g. Cascade SVM's aligned points+labels) and to the Baseline policy,
    where every block is its own single-block partition.
    """

    arrays: tuple[BlockedArray, ...]
    location: int
    block_ids: tuple[int, ...]

    @property
    def blocks(self) -> list[jax.Array]:
        """Blocks of the first (or only) input array."""
        return self.blocks_of(0)

    def blocks_of(self, i: int) -> list[jax.Array]:
        return [self.arrays[i].block(b) for b in self.block_ids]

    @property
    def num_rows(self) -> int:
        return int(sum(self.arrays[0].block_rows[b] for b in self.block_ids))

    @property
    def item_indexes(self) -> np.ndarray:
        """Global row ids of every element (paper §4.1 ``get_item_indexes``)."""
        x = self.arrays[0]
        offs = x.row_offsets()
        rows = x.block_rows
        return np.concatenate(
            [np.arange(offs[b], offs[b] + rows[b], dtype=np.int64) for b in self.block_ids]
        )

    @property
    def materialized(self) -> tuple[jax.Array, ...]:
        """Local concat of each input's blocks — intra-location copy only."""
        return tuple(
            jnp.concatenate(self.blocks_of(i), axis=0) for i in range(len(self.arrays))
        )


# ---------------------------------------------------------------------------
# the TaskGraph IR
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Task:
    """One placed, keyed task descriptor.

    ``operands()`` builds the operand tuple lazily (stacking/concatenating
    block buffers only when the task actually runs); the first ``n_data``
    operands are per-task data, the rest are plan-wide traced extras shared
    by every task of the same ``key`` — the distinction grouped backends
    (MeshExecutor) use to stack data across tasks while replicating extras.
    A ``partition_pallas`` task's data operand is the tuple of its run's
    block buffers themselves, uncopied.

    ``counted=False`` marks tasks that are *driver* work rather than engine
    dispatches (map_partitions views: the view callback itself dispatches
    engine tasks).
    """

    index: int
    location: int
    kind: str                # "block" | "partition_scan" | "partition_pallas"
                             # | "partition_materialized" | "partition_view"
    key: Hashable
    fn: Callable
    operands: Callable[[], tuple]
    block_ids: tuple[int, ...]
    n_data: int = 1
    counted: bool = True
    kernel_name: str | None = None
    #: name of the task's compiled program (:func:`program_name`)
    name: str | None = None
    #: ((shape, dtype_str), ...) of the per-task data operands — lets grouped
    #: backends bucket same-signature tasks WITHOUT materializing operands.
    #: A partition's run reads ``(nblocks, rows, *row)`` however it is passed.
    data_shapes: tuple = ()
    #: block buffers ``operands()`` copies (stacks or concatenates); 0 where
    #: the data goes in place
    copied: int = 0
    #: store-held chunk refs this task's operands resolve — populated only
    #: for out-of-core backends (``Capabilities.out_of_core``), which
    #: pin/prefetch/release them around dispatch.
    chunk_refs: tuple = ()
    #: picklable reference to this task's code (``Capabilities.remote``
    #: lowerings only): ``("fn", ref)``, ``("scan", fn_ref, combine_ref,
    #: n_in)`` or ``("kernel", kernel_ref)``.  None ⇒ not remotable.
    fn_ref: tuple | None = None
    #: nullary builder of the raw remote payload ``(data, extras)`` —
    #: per-input block payloads (ndarray or ChunkHandle) still UNstacked,
    #: so the worker performs the stack/concat and the float story matches
    #: the in-process lowering bit for bit.
    remote_operands: Callable[[], tuple] | None = None

    def spec(self) -> "TaskSpec":
        """Project this descriptor into its picklable :class:`TaskSpec`.

        Only valid on tasks lowered under ``Capabilities.remote`` with a
        resolvable ``fn_ref`` — the cluster backend checks ``fn_ref`` and
        schedules every other task in-process.
        """
        if self.fn_ref is None or self.remote_operands is None:
            raise ValueError(
                f"task {self.index} ({self.kind}) has no remote projection"
            )
        data, extras = self.remote_operands()
        return TaskSpec(
            index=self.index,
            location=self.location,
            kind=self.kind,
            key_repr=key_summary(self.key),
            fn_ref=self.fn_ref,
            block_ids=self.block_ids,
            n_data=self.n_data,
            data=data,
            extras=extras,
        )


@dataclasses.dataclass(frozen=True)
class TaskSpec:
    """The picklable projection of one :class:`Task` (DuctTeip-style cheap
    task descriptor): everything a worker process needs to replay the task
    — code reference, geometry, and per-block operand payloads that are
    either raw ``ndarray`` bytes or store-attached
    :class:`~repro.api.chunkstore.ChunkHandle`\\ s.

    Deterministic replay contract: running the same TaskSpec twice (on any
    worker) produces bit-identical partials, because the payloads are
    immutable snapshots and the worker rebuilds the exact stack/concat +
    fn the in-process lowering would have dispatched.
    """

    index: int
    location: int
    kind: str
    key_repr: str          # human-readable key digest (errors, worker logs)
    fn_ref: tuple
    block_ids: tuple
    n_data: int
    data: tuple            # per input: tuple of block payloads
    extras: tuple          # plan-wide traced extras, np-converted


def key_summary(key: Hashable) -> str:
    """Short, address-free rendering of a task key (errors / worker logs)."""
    if isinstance(key, tuple):
        return "(" + ", ".join(key_summary(k) for k in key) + ")"
    name = getattr(key, "co_name", None)
    if name is not None:
        return f"<code {name}>"
    r = repr(key)
    return r if len(r) <= 48 else r[:45] + "..."


@dataclasses.dataclass(frozen=True)
class MergeSpec:
    """The final fold over task partials (the paper's @reduction task)."""

    combine: Callable[[Any, Any], Any]
    key: Hashable


@dataclasses.dataclass(frozen=True)
class TaskGraph:
    """Frozen result of lowering: placed tasks + the merge contract.

    Executors consume this and nothing else: scheduling a TaskGraph must
    produce the per-task partials in ``tasks`` order (or a single
    already-merged value when the backend fuses the merge into its
    dispatch), then apply ``merge`` in plan order.
    """

    tasks: tuple[Task, ...]
    merge: MergeSpec | None
    spec: MapReduceSpec

    @property
    def locations(self) -> tuple[int, ...]:
        return tuple(sorted({t.location for t in self.tasks}))

    def by_location(self) -> dict[int, list[Task]]:
        out: dict[int, list[Task]] = {}
        for t in self.tasks:
            out.setdefault(t.location, []).append(t)
        return out

    def describe(self) -> str:
        """One line per task: index, placement, kind, key summary.

        Deliberately free of memory addresses and other run-varying detail
        so the output is golden-testable — a lowering regression shows up
        as a readable string diff (tests/test_api.py).
        """
        lines = []
        for t in self.tasks:
            extra = f" kernel={t.kernel_name}" if t.kernel_name else ""
            lines.append(
                f"[{t.index}] loc={t.location} {t.kind} blocks={t.block_ids}{extra}"
            )
        if self.merge is not None:
            c = self.merge.combine
            name = getattr(c, "__name__", type(c).__name__)
            lines.append(f"[merge] combine={name}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# cross-iteration dependency edges (pipelined iteration, DESIGN.md §14)
# ---------------------------------------------------------------------------


def partition_key(task: Task) -> tuple:
    """The stable identity of the data partition one task covers.

    ``(location, block_ids)`` — the versioned-key half of the pipelining
    contract: the same partition of the same dataset lowers to the same key
    every iteration (placement and grouping are policy-derived and the
    prepare cache reuses them), so "iteration *k*'s unit for this
    partition" is addressable without any global coordination.  Pipelined
    schedulers pair it with a per-partition version counter: version *v* of
    a key is that partition's unit in the *v*-th overlapped execute.
    """
    return (task.location, task.block_ids)


def cross_iteration_edges(prev: TaskGraph, nxt: TaskGraph) -> dict[int, tuple[int, ...]]:
    """Same-partition dependency edges from ``nxt``'s tasks to ``prev``'s.

    The inter-iteration half of the TaskGraph: for consecutive pipelined
    executes, each task of ``nxt`` depends on the ``prev`` tasks covering
    the same :func:`partition_key` — a partition's *k+1* unit may launch
    the moment its *k* unit completes, no global drain.  Keys are task
    indices in ``nxt``; values are matching task indices in ``prev``.

    Tasks with no same-partition predecessor (a granularity retune between
    submits re-partitioned the data) are absent from the mapping; the
    scheduler falls back to gating them on ``prev``'s merge, which is
    always correct — just barrier-shaped for that one boundary.
    """
    by_part: dict[tuple, list[int]] = {}
    for t in prev.tasks:
        by_part.setdefault(partition_key(t), []).append(t.index)
    out: dict[int, tuple[int, ...]] = {}
    for t in nxt.tasks:
        deps = by_part.get(partition_key(t))
        if deps:
            out[t.index] = tuple(deps)
    return out


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------


def stacked_fold(combine: Callable[[Any, Any], Any]) -> Callable[[Any], Any]:
    """Fold a stacked pytree of partials (leading axis) in index order.

    ``stacked_fold(c)(stacked)`` = ``c(c(s[0], s[1]), s[2]) ...`` as one
    ``lax.scan`` — the single source of truth for "reduce N partials with an
    associative combine": the host-side merge task (``_merge_partials`` in
    :mod:`repro.api.executors`) folds stacked task partials with it, and
    :class:`~repro.api.mesh_executor.MeshExecutor` folds the all-gathered
    per-rank partials with it inside the sharded program (the all-reduce of
    an arbitrary associative monoid).
    """

    def fold(stacked):
        first = jax.tree.map(lambda s: s[0], stacked)
        rest = jax.tree.map(lambda s: s[1:], stacked)
        acc, _ = jax.lax.scan(lambda a, p: (combine(a, p), None), first, rest)
        return acc

    return fold


def fold_plan(entries) -> tuple[tuple[int, tuple[int, ...]], ...]:
    """The canonical merge tree over ``(index, location)`` pairs.

    Returns ``((location, member_indices), ...)`` — one fold group per
    location, members in entry order, groups in first-appearance order of
    their location.  This is the merge association contract every backend
    folds by: each group's members reduce left-to-right (one
    :func:`stacked_fold` chain), then the per-group values reduce
    left-to-right in group order.  The shape is a pure function of the
    entry sequence — itself derived from stable task keys and the
    policy's placement — so a replayed, resumed, or peer-exchanged fold
    (DESIGN.md §16) re-derives the exact same tree and stays
    bit-identical.

    >>> fold_plan([(0, 1), (1, 1), (2, 0), (3, 0)])
    ((1, (0, 1)), (0, (2, 3)))
    >>> fold_plan([(0, -1)])
    ((-1, (0,)),)
    """
    groups: dict[int, list[int]] = {}
    order: list[int] = []
    for idx, loc in entries:
        if loc not in groups:
            groups[loc] = []
            order.append(loc)
        groups[loc].append(idx)
    return tuple((loc, tuple(groups[loc])) for loc in order)


def planned_fold(
    combine: Callable[[Any, Any], Any],
    groups: tuple[tuple[int, ...], ...],
) -> Callable[[Any], Any]:
    """Fold a stacked pytree of partials along a :func:`fold_plan` tree.

    ``planned_fold(c, groups)(stacked)`` reduces each group's members with
    the :func:`stacked_fold` chain, then chains the group values in group
    order — the same arithmetic, in the same order, as running each group
    chain worker-side and the root chain driver-side (the peer-exchange
    path), so the two routes produce bit-identical values.  Degenerates to
    ``stacked_fold(c)`` for a single group.  One jitted program, one
    dispatch — the merge keeps costing exactly one task however many
    groups the plan has.
    """
    chain = stacked_fold(combine)

    def fold(stacked):
        accs = []
        for members in groups:
            if len(members) == 1:
                accs.append(jax.tree.map(lambda s, i=members[0]: s[i], stacked))
            else:
                idx = jnp.asarray(members)
                accs.append(chain(jax.tree.map(lambda s, x=idx: s[x], stacked)))
        if len(accs) == 1:
            return accs[0]
        return chain(jax.tree.map(lambda *xs: jnp.stack(xs, 0), *accs))

    return fold


def _partition_body(block_fn: Callable, combine: Callable, n_in: int) -> Callable:
    """The fused per-partition task (paper Listing 5 as a ``lax.scan``)."""

    def partition_task(*operands):
        data, extra = operands[:n_in], operands[n_in:]

        def body(acc, blk):
            p = block_fn(*blk, *extra)
            return combine(acc, p), None

        first = block_fn(*jax.tree.map(lambda s: s[0], data), *extra)
        acc, _ = jax.lax.scan(body, first, jax.tree.map(lambda s: s[1:], data))
        return acc

    return partition_task


def _pick_fusion(
    policy,
    caps: Capabilities,
    kernel: PartitionKernel | None,
    stacked_shape: tuple,
    extra_args: tuple,
) -> str:
    """Resolve the SplIter ``fusion`` knob for one same-shape run."""
    mode = getattr(policy, "fusion", "auto")
    if mode == "scan":
        return "scan"
    if kernel is None or not kernel.supported(stacked_shape, extra_args):
        return "scan"  # automatic fallback: no kernel, or shapes rejected
    if mode == "pallas":
        return "pallas"
    return "pallas" if caps.prefer_pallas else "scan"


def lower(
    spec: MapReduceSpec,
    arrays: tuple[BlockedArray, ...],
    groups: list[PlacedGroup],
    caps: Capabilities,
) -> TaskGraph:
    """Lower a normalized plan over prepared placement into a TaskGraph.

    ``arrays``/``groups`` are the policy's prepared form (already rechunked
    for ``Rechunk``; the original arrays plus partition groups otherwise) —
    executors compute them once per ``(inputs, policy)`` and cache.
    """
    merge = (
        MergeSpec(spec.combine, key=("merge", stable_task_key(spec.combine)))
        if spec.combine is not None
        else None
    )

    if spec.kind == "map_partitions":
        tasks = _lower_partition_views(spec, arrays, groups, caps)
    else:
        tasks = _lower_map_blocks(spec, arrays, groups, caps)
    return TaskGraph(tasks=tuple(tasks), merge=merge, spec=spec)


def _refs_of(arrays, ids, caps: Capabilities) -> tuple:
    """The chunk refs a task over ``ids`` resolves — out-of-core backends only."""
    if not caps.out_of_core:
        return ()
    return tuple(
        a.blocks[i] for a in arrays for i in ids if isinstance(a.blocks[i], ChunkRef)
    )


def _block_payload(block, exporter=None):
    """One block as it crosses a process boundary.

    Cheapest transport first: store-held chunks covered by a manifest
    travel as tiny :class:`~repro.api.chunkstore.ChunkHandle` descriptors
    (the worker resolves them against its attached store); other blocks go
    through the backend's shared-memory ``exporter`` when one is set
    (:class:`Capabilities.exporter` — descriptors instead of bytes); only
    when both decline do raw ndarray bytes ship over the control channel.
    """
    if isinstance(block, ChunkRef):
        handle = getattr(block.store, "handle", None)
        if handle is not None:
            h = handle(block)
            if h is not None:
                return h
    if exporter is not None:
        ref = exporter(block)
        if ref is not None:
            return ref
    return np.asarray(resolve_chunk(block))


def _remote_operands_builder(arrays, ids, extra, exporter=None) -> Callable[[], tuple]:
    """Builder of a task's raw remote payload — evaluated at dispatch time."""

    def build():
        data = tuple(
            tuple(_block_payload(a.blocks[b], exporter) for b in ids) for a in arrays
        )
        extras = []
        for e in extra:
            e = resolve_deferred(e)  # pipelined loop-carried operand
            ref = exporter(e) if exporter is not None else None
            extras.append(ref if ref is not None else np.asarray(e))
        return data, tuple(extras)

    return build


def _lower_partition_views(spec, arrays, groups, caps: Capabilities) -> list[Task]:
    tasks = []
    for g in groups:
        view = PartitionView(arrays=arrays, location=g.location, block_ids=g.block_ids)
        tasks.append(
            Task(
                index=len(tasks),
                location=g.location,
                kind="partition_view",
                key=None,
                fn=spec.fn,
                operands=(lambda view=view: (view,)),
                block_ids=g.block_ids,
                n_data=1,
                counted=False,
                chunk_refs=_refs_of(arrays, g.block_ids, caps),
            )
        )
    return tasks


def _lower_map_blocks(spec, arrays, groups, caps: Capabilities) -> list[Task]:
    extra = spec.extra_args
    n_in = len(arrays)
    pol = spec.policy
    fn_key = stable_task_key(spec.fn)
    tasks: list[Task] = []

    # Remote code references (Capabilities.remote): computed once per plan,
    # shared by every task.  A None reference — unencodable fn/combine —
    # simply leaves the tasks in-process-only; lowering never fails on it.
    plain_ref = scan_ref = None
    if caps.remote:
        efn = encode_fn(spec.fn)
        plain_ref = ("fn", efn) if efn is not None else None
        if spec.combine is not None:
            ecomb = encode_fn(spec.combine)
            if efn is not None and ecomb is not None:
                scan_ref = ("scan", efn, ecomb, n_in)

    def remote_fields(fn_ref, ids):
        if not caps.remote or fn_ref is None:
            return {}
        return {
            "fn_ref": fn_ref,
            "remote_operands": _remote_operands_builder(
                arrays, ids, extra, caps.exporter
            ),
        }

    fused = isinstance(pol, SplIter) and not pol.materialize and spec.combine is not None
    if fused:
        # Fused iteration: ONE dispatch scanning (or pallas-gridding) the
        # partition's local blocks, carrying the partition-local reduction.
        # Ragged tails lower per same-shape run — at most one extra task per
        # tail, so C1's dispatch bound survives the fusion choice.
        kernel = partition_kernel_for(spec.fn) if n_in == 1 else None
        scan_fn = _partition_body(spec.fn, spec.combine, n_in)
        scan_key = ("part", fn_key, stable_task_key(spec.combine), n_in)
        pallas_ref = None
        if caps.remote and kernel is not None:
            kref = kernel_ref(spec.fn)
            pallas_ref = ("kernel", kref) if kref is not None else None
        for g in groups:
            by_shape: dict[tuple, list[int]] = {}
            for b in g.block_ids:
                by_shape.setdefault(arrays[0].blocks[b].shape, []).append(b)
            for shape, ids in by_shape.items():
                ids = tuple(ids)
                stacked_shape = (len(ids), *shape)
                choice = _pick_fusion(pol, caps, kernel, stacked_shape, extra)

                def operands(ids=ids, stack=choice == "scan"):
                    runs = (tuple(a.block(b) for b in ids) for a in arrays)
                    data = tuple(jnp.stack(r, axis=0) if stack else r for r in runs)
                    return data + tuple(resolve_deferred(e) for e in extra)

                if choice == "pallas":  # the kernel reads each block where it lies
                    task_fn, key, kname = kernel.fn, ("pallas", kernel.key), kernel.name
                else:
                    task_fn, key, kname = scan_fn, scan_key, None
                kind = f"partition_{choice}"
                tasks.append(
                    Task(
                        index=len(tasks),
                        location=g.location,
                        kind=kind,
                        key=key,
                        fn=task_fn,
                        operands=operands,
                        block_ids=ids,
                        n_data=n_in,
                        kernel_name=kname,
                        name=program_name(kind, kname or spec.fn),
                        copied=0 if choice == "pallas" else n_in * len(ids),
                        chunk_refs=_refs_of(arrays, ids, caps),
                        data_shapes=tuple(
                            (
                                (len(ids), *a.blocks[ids[0]].shape),
                                str(a.blocks[ids[0]].dtype),
                            )
                            for a in arrays
                        ),
                        **remote_fields(
                            pallas_ref if choice == "pallas" else scan_ref, ids
                        ),
                    )
                )
    elif isinstance(pol, SplIter) and pol.materialize:
        # Materialized partition (paper §7): local concat, one call.
        for g in groups:
            def operands(g=g):
                return tuple(
                    jnp.concatenate([a.block(b) for b in g.block_ids], axis=0)
                    for a in arrays
                ) + tuple(resolve_deferred(e) for e in extra)

            tasks.append(
                Task(
                    index=len(tasks),
                    location=g.location,
                    kind="partition_materialized",
                    key=("block", fn_key),
                    fn=spec.fn,
                    operands=operands,
                    block_ids=g.block_ids,
                    n_data=n_in,
                    name=program_name("partition_materialized", spec.fn),
                    copied=n_in * len(g.block_ids),
                    chunk_refs=_refs_of(arrays, g.block_ids, caps),
                    data_shapes=tuple(
                        (
                            (
                                sum(a.blocks[b].shape[0] for b in g.block_ids),
                                *a.blocks[g.block_ids[0]].shape[1:],
                            ),
                            str(a.blocks[g.block_ids[0]].dtype),
                        )
                        for a in arrays
                    ),
                    **remote_fields(plain_ref, g.block_ids),
                )
            )
    else:
        # Baseline / Rechunk (single-block groups), or an un-reduced SplIter
        # map: one task per block, in GLOBAL block order so an un-reduced
        # compute() returns partials aligned with the blocking regardless of
        # policy/partition layout.
        placed = sorted((b, g.location) for g in groups for b in g.block_ids)
        for b, loc in placed:
            def operands(b=b):
                return tuple(a.block(b) for a in arrays) + tuple(
                    resolve_deferred(e) for e in extra
                )

            tasks.append(
                Task(
                    index=len(tasks),
                    location=loc,
                    kind="block",
                    key=("block", fn_key),
                    fn=spec.fn,
                    operands=operands,
                    block_ids=(b,),
                    n_data=n_in,
                    name=program_name("block", spec.fn),
                    chunk_refs=_refs_of(arrays, (b,), caps),
                    data_shapes=tuple(
                        (a.blocks[b].shape, str(a.blocks[b].dtype)) for a in arrays
                    ),
                    **remote_fields(plain_ref, (b,)),
                )
            )
    return tasks
