"""Worker-process main loop for :class:`~repro.api.cluster_executor.ClusterExecutor`.

Each worker is a **spawn**-started process (fork is unsafe under JAX/XLA)
that owns one logical *location* of the cluster.  It drains a byte-framed
pickle protocol from its command connection and writes replies to its own
reply connection — per-worker pipes, NOT a shared queue, because a worker
that dies mid-write (exactly what fault injection does) must only be able
to corrupt *its own* channel: the parent reads the torn end as EOF and
buries that worker, while every other worker's replies keep flowing.  (A
shared ``multiprocessing.Queue`` fails this: a killed producer can leave
the common pipe locked/torn for everyone.)

parent → worker (either one bare message, or ``("batch", [messages])`` —
the parent coalesces a scheduling sweep's commands into one send)
    ``("attach", StoreManifest)`` — build an
    :class:`~repro.api.chunkstore.AttachedStore` so later units can
    resolve :class:`~repro.api.chunkstore.ChunkHandle` payloads; a second
    attach for the same store is a *delta* of a grown store and merges
    into the existing attach (bytes never transit the control channel);
    ``("unit", epoch, TaskSpec, attempt)`` — execute one task descriptor;
    ``("call", epoch, call_id, fn_ref, args, key)`` — execute one
    driver-level task RPC (the ``executor.task()`` path);
    ``("steal", token, ((epoch, index), ...))`` — a steal probe: grant
    every listed unit still sitting *unstarted* in the local queue back
    to the parent (reply ``steal_ok``); anything already started or
    finished is silently kept — exactly-once by construction;
    ``("stop",)`` — exit cleanly.

Work stealing (DESIGN.md §15): a batched send can park several units in
the worker's local queue, so the main loop keeps a pending deque and
polls the command channel between unit executions — that poll is where
steal probes are answered, bounding probe latency by one unit's wall
time.  A granted unit is removed from the queue *before* any of its
work runs, so a steal can never double-execute; the parent re-dispatches
granted units to the idle thief with their shared-memory descriptors
(a steal moves descriptors, not bytes).

worker → parent, over the worker's own reply connection (each message
pre-pickled so the parent can bill exact ``ipc_bytes``)
    ``("ready", wid, pid)``, ``("hb", wid, t)`` — liveness;
    ``("unit_done", wid, epoch, index, result, loaded, shm_wrote)`` /
    ``("unit_error", wid, epoch, index, err)`` — unit outcomes;
    ``("call_done", wid, epoch, call_id, result, shm_wrote)`` /
    ``("call_error", wid, epoch, call_id, err)`` — RPC outcomes.

The shared-memory data plane (:mod:`repro.api.shm`): operand payloads may
arrive as ``ShmBlockRef`` descriptors, resolved zero-copy against
read-only attachments of the parent's segments.  Results above
``result_min_bytes`` travel back the same way — packed into ONE fresh
segment per reply named ``<result_prefix><seq>`` (the parent unlinks it
on consume, or sweeps the prefix if this worker dies first);
``shm_wrote`` in the reply bills the copied bytes to the parent's
``EngineReport.shm_bytes``.

Determinism: the worker rebuilds exactly the stack/concat + function the
in-process lowering would have dispatched (same jnp ops, same fold order,
same host), so a replayed unit — or the same unit on a different worker —
produces bit-identical partials.  That is the Chunks-and-Tasks replay
story: fault tolerance is "run the pure task descriptor again".

Platform: a worker's JAX is pinned to the CPU (:data:`WORKER_PLATFORM`)
before its first JAX call.  An accelerator belongs to the one process that
drives it — the driver — and a worker that reached for it would either fail
on the runtime's lock or, with no platform set, quietly fall back to the
CPU anyway.

Fault injection (tests / the CI fault lane): ``kill_after`` makes the
worker ``os._exit`` on *receiving* its nth dispatch (the unit is lost
in-flight, exercising requeue); ``kill_on_retry`` does the same when it
receives an already-replayed unit (exercising retry exhaustion);
``mute_after`` silences heartbeats and hangs (exercising the
heartbeat-timeout detector while the process stays alive); ``slow_s``
sleeps before every unit execution — the deterministic straggler hook
the elastic bench and chaos harness slow one worker with.  Dispatch
counts are per unit/call message, so a fault keyed on "the nth dispatch"
fires identically whether the commands arrived batched or one by one.
"""

from __future__ import annotations

import collections
import os
import pickle
import threading
import time
import traceback

__all__ = ["worker_main"]

#: the JAX platform every worker runs on (the driver keeps the accelerator)
WORKER_PLATFORM = "cpu"

#: exit codes used by injected faults (visible in worker logs / waitpid)
KILLED_EXIT = 23
RETRY_KILLED_EXIT = 24


def _log_line(log, wid: int, msg: str) -> None:
    if log is not None:
        log.write(f"[w{wid} +{time.monotonic():.3f}] {msg}\n")
        log.flush()


def _resolve_fn(fn_ref: tuple, cache: dict):
    """Rehydrate + jit a task function from its picklable reference."""
    fn = cache.get(fn_ref)
    if fn is not None:
        return fn
    import jax

    from repro.api.fnref import decode_fn

    kind = fn_ref[0]
    if kind == "scan":
        from repro.api.lowering import _partition_body

        _, efn, ecomb, n_in = fn_ref
        body = _partition_body(decode_fn(efn), decode_fn(ecomb), n_in)
    elif kind == "fold":
        # A peer-exchange merge chain: the same stacked_fold program the
        # driver's merge task jits — separate jit, same HLO, same bits.
        from repro.api.lowering import stacked_fold

        body = stacked_fold(decode_fn(fn_ref[1]))
    elif kind == "kernel":
        from repro.api.kernels import kernel_from_ref

        kernel = kernel_from_ref(fn_ref[1])
        if kernel is None:
            raise RuntimeError(f"no registered kernel for {fn_ref[1]!r}")
        body = kernel.fn
    elif kind == "fn":
        body = decode_fn(fn_ref[1])
    else:
        raise RuntimeError(f"unknown fn_ref kind {kind!r}")
    fn = cache[fn_ref] = jax.jit(body)
    return fn


def _build_operands(kind: str, data: tuple, extras: tuple, stores: dict, shm_att):
    """Payloads → operand tuple, mirroring the in-process lowering exactly.

    ``partition_scan`` stacks the blocks on a new leading axis,
    ``partition_pallas`` passes them as a tuple (the kernel reads each in
    place), ``partition_materialized`` concatenates, ``block`` passes the
    single block through.  Returns the operands plus
    the chunk bytes read from spill files (billed upstream as
    ``bytes_loaded`` — shared-memory resolutions move no file bytes and
    bill nothing).
    """
    import jax.numpy as jnp

    from repro.api.chunkstore import ChunkHandle, ChunkStoreError
    from repro.api.shm import ShmBlockRef

    def resolve(b):
        nonlocal loaded
        if isinstance(b, ChunkHandle):
            store = stores.get(b.store_uid)
            if store is None:
                raise ChunkStoreError(f"store {b.store_uid} not attached")
            entry = store.manifest.chunks.get(b.chunk_id)
            if entry is not None and entry[0] == "file":
                loaded += b.nbytes
            return store.resolve(b)
        if isinstance(b, ShmBlockRef):
            return jnp.asarray(shm_att.view(b))  # zero-copy off the pipe
        return jnp.asarray(b)

    loaded = 0
    ops = []
    for blocks in data:
        arrs = [resolve(b) for b in blocks]
        if kind == "partition_scan":
            ops.append(jnp.stack(arrs, axis=0))
        elif kind == "partition_pallas":
            ops.append(tuple(arrs))
        elif kind == "partition_materialized":
            ops.append(jnp.concatenate(arrs, axis=0))
        else:
            ops.append(arrs[0])
    ops.extend(resolve(e) for e in extras)
    return tuple(ops), loaded


def worker_main(
    worker_id: int,
    location: int,
    conn,
    reply_conn,
    *,
    heartbeat_s: float = 0.2,
    kill_after: int | None = None,
    kill_on_retry: bool = False,
    mute_after: int | None = None,
    slow_s: float | None = None,
    log_path: str | None = None,
    result_prefix: str | None = None,
    result_min_bytes: int = 1024,
) -> None:
    """Entry point of one cluster worker process."""
    import jax

    jax.config.update("jax_platforms", WORKER_PLATFORM)
    log = open(log_path, "a") if log_path else None
    _log_line(log, worker_id, f"start pid={os.getpid()} location={location}")

    reply_lock = threading.Lock()  # main thread + heartbeat thread share the pipe

    def reply(msg) -> None:
        payload = pickle.dumps(msg)
        with reply_lock:
            reply_conn.send_bytes(payload)

    stop_beat = threading.Event()

    def beat() -> None:
        while not stop_beat.is_set():
            try:
                reply(("hb", worker_id, time.time()))
            except (OSError, ValueError):  # parent gone / pipe torn down
                return
            stop_beat.wait(heartbeat_s)

    threading.Thread(target=beat, name="hb", daemon=True).start()
    reply(("ready", worker_id, os.getpid()))

    import numpy as np  # deferred: keep the pre-ready window minimal

    from repro.api import shm as shm_mod

    shm_att = shm_mod.ShmAttachments()
    fns: dict = {}
    stores: dict = {}
    dispatches = 0
    reply_seq = 0

    def to_host(tree):
        import jax

        return jax.tree.map(np.asarray, tree)

    def pack(tree, *, publish=None):
        """Large reply leaves → one fresh segment; (tree, bytes_copied).

        ``publish`` overrides the segment name and drops the size floor to
        0: a published partial (peer exchange, DESIGN.md §16) must land at
        the deterministic name the driver derived — addressed by unit
        key/epoch/attempt, never by worker id, so replays and steals
        publish to the same place — and must pack EVERY leaf, because a
        sibling attaches the segment instead of reading the reply.
        """
        nonlocal reply_seq
        if result_prefix is None:
            return tree, 0
        if publish is not None:
            packed, _seg, wrote = shm_mod.pack_tree(tree, threshold=0, name=publish)
            return packed, wrote
        reply_seq += 1
        packed, _seg, wrote = shm_mod.pack_tree(
            tree,
            threshold=result_min_bytes,
            name=f"{result_prefix}{reply_seq}",
        )
        return packed, wrote

    #: unit/call messages received but not yet executed — the local queue
    #: steal probes are answered against.
    pending: collections.deque = collections.deque()

    def handle_steal(msg) -> None:
        """Grant every probed unit still unstarted in the local queue.

        Exactly-once hinges on ordering: a unit is granted only while its
        message is still in ``pending`` — removal here happens before any
        of its work runs, and a unit already popped (running or finished)
        is silently kept, so the parent's grant list and this worker's
        execution set can never overlap.
        """
        _, token, wants = msg
        want = set(wants)
        granted = []
        kept: collections.deque = collections.deque()
        for qm in pending:
            if qm[0] == "unit" and (qm[1], qm[2].index) in want:
                granted.append((qm[1], qm[2].index))
            elif qm[0] == "fold" and (qm[1], qm[2]) in want:
                granted.append((qm[1], qm[2]))
            else:
                kept.append(qm)
        pending.clear()
        pending.extend(kept)
        reply(("steal_ok", worker_id, token, tuple(granted)))
        _log_line(
            log,
            worker_id,
            f"steal probe token={token} wants={len(wants)} "
            f"granted={len(granted)}",
        )

    def handle(msg) -> bool:
        """Execute one unit/call message; False means exit the main loop."""
        nonlocal dispatches
        kind = msg[0]

        dispatches += 1
        if mute_after is not None and dispatches >= mute_after:
            _log_line(log, worker_id, "FAULT: muting heartbeats and hanging")
            stop_beat.set()
            while True:  # injected hang: only the parent's timeout saves us
                time.sleep(3600)
        if kill_after is not None and dispatches >= kill_after:
            _log_line(log, worker_id, f"FAULT: killing on dispatch #{dispatches}")
            os._exit(KILLED_EXIT)

        if kind == "unit":
            _, epoch, spec, attempt = msg[:4]
            publish = msg[4] if len(msg) > 4 else None
            if kill_on_retry and attempt > 0:
                _log_line(
                    log, worker_id, f"FAULT: killing on retried unit {spec.index}"
                )
                os._exit(RETRY_KILLED_EXIT)
            if slow_s:
                time.sleep(slow_s)  # injected straggler: 10×-ish per unit
            try:
                fn = _resolve_fn(spec.fn_ref, fns)
                ops, loaded = _build_operands(
                    spec.kind, spec.data, spec.extras, stores, shm_att
                )
                out, wrote = pack(to_host(fn(*ops)), publish=publish)
                reply(
                    ("unit_done", worker_id, epoch, spec.index, out, loaded, wrote)
                )
                _log_line(
                    log,
                    worker_id,
                    f"unit {spec.index} kind={spec.kind} blocks={spec.block_ids} "
                    f"attempt={attempt} ok"
                    + (f" published={publish}" if publish else ""),
                )
            except BaseException:
                err = traceback.format_exc()
                _log_line(log, worker_id, f"unit {spec.index} FAILED\n{err}")
                reply(("unit_error", worker_id, epoch, spec.index, err))
        elif kind == "fold":
            # Peer exchange (DESIGN.md §16): fold a sibling-published merge
            # chain in place.  The operands are packed ref trees the driver
            # forwarded — attach each published segment read-only, stack,
            # and run the SAME jitted stacked_fold chain the driver's merge
            # task would have run, so the partial is bit-identical however
            # the subtree was routed.  Unlink stays with the driver's lease.
            _, epoch, index, attempt, combine_ref, key_repr, trees = msg
            if kill_on_retry and attempt > 0:
                _log_line(log, worker_id, f"FAULT: killing on retried fold {index}")
                os._exit(RETRY_KILLED_EXIT)
            if slow_s:
                time.sleep(slow_s)
            try:
                import jax
                import jax.numpy as jnp

                fold = _resolve_fn(("fold", combine_ref), fns)
                partials = [
                    jax.tree.map(jnp.asarray, shm_mod.attach_tree(t, shm_att))
                    for t in trees
                ]
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *partials)
                out, wrote = pack(to_host(fold(stacked)))
                reply(("unit_done", worker_id, epoch, index, out, 0, wrote))
                _log_line(
                    log,
                    worker_id,
                    f"fold {index} key={key_repr} fan_in={len(trees)} "
                    f"attempt={attempt} ok",
                )
            except BaseException:
                err = traceback.format_exc()
                _log_line(log, worker_id, f"fold {index} FAILED\n{err}")
                reply(("unit_error", worker_id, epoch, index, err))
        elif kind == "call":
            _, epoch, call_id, fn_ref, args, key = msg
            try:
                fn = _resolve_fn(fn_ref, fns)
                import jax.numpy as jnp

                from repro.api.shm import ShmBlockRef

                ops = (
                    jnp.asarray(shm_att.view(a))
                    if isinstance(a, ShmBlockRef)
                    else jnp.asarray(a)
                    for a in args
                )
                out, wrote = pack(to_host(fn(*ops)))
                reply(("call_done", worker_id, epoch, call_id, out, wrote))
                _log_line(log, worker_id, f"call {call_id} key={key} ok")
            except BaseException:
                err = traceback.format_exc()
                _log_line(log, worker_id, f"call {call_id} key={key} FAILED\n{err}")
                reply(("call_error", worker_id, epoch, call_id, err))
        else:
            _log_line(log, worker_id, f"unknown message {kind!r}; ignoring")
        return True

    def ingest(payload) -> bool:
        """Route one received message; False means stop was seen.

        Control traffic (attach, steal probes, stop) is handled inline so
        it takes effect ahead of queued work; unit/call messages append to
        ``pending`` in arrival order — execution order equals receive
        order minus whatever a steal removed.
        """
        msg = pickle.loads(payload)
        for m in msg[1] if msg[0] == "batch" else (msg,):
            kind = m[0]
            if kind == "stop":
                _log_line(log, worker_id, "stop")
                return False
            if kind == "attach":
                manifest = m[1]
                from repro.api.chunkstore import AttachedStore

                store = stores.get(manifest.uid)
                if store is not None:
                    store.merge(manifest)  # a grown store's delta
                else:
                    stores[manifest.uid] = AttachedStore(manifest)
                _log_line(
                    log,
                    worker_id,
                    f"attach store={manifest.uid} chunks={len(manifest.chunks)}",
                )
            elif kind == "steal":
                handle_steal(m)
            else:
                pending.append(m)
        return True

    running = True
    while running:
        if pending:
            # Between units: drain whatever control traffic has arrived —
            # this is where steal probes are answered, so probe latency is
            # bounded by one unit's wall time.
            try:
                while running and conn.poll(0):
                    running = ingest(conn.recv_bytes())
            except (EOFError, OSError):
                _log_line(log, worker_id, "command channel closed; exiting")
                break
            if not running or not pending:
                continue
            if not handle(pending.popleft()):
                running = False
        else:
            try:
                payload = conn.recv_bytes()
            except EOFError:
                _log_line(log, worker_id, "command channel closed; exiting")
                break
            running = ingest(payload)

    stop_beat.set()
    shm_att.close()  # release our mappings; unlink stays the parent's job
    for store in stores.values():
        store.close()
    if log is not None:
        log.close()
