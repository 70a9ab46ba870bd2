"""Mesh gang stages: whole-stage SPMD execution over the device mesh.

This is the engine integration of :mod:`.mesh` (VERDICT.md round-1 item 3):
the reference routes EVERY cross-stage exchange through the disk+Flight
shuffle (``shuffle_writer.rs:142-292`` → ``flight_service.rs:80-118``); on
a TPU host, partitions of a mesh-resident stage are SHARDS, and the
partial-aggregate exchange collapses into ``psum``/``pmin``/``pmax`` over
ICI inside one jit-compiled ``shard_map`` program.

Mechanically: the distributed planner wraps an eligible stage subtree
(filter→project→partial-aggregate, the same shapes ``maybe_accelerate``
fuses) in a :class:`MeshGangExec` whose output partitioning is 1 — so the
scheduler naturally creates ONE task for the stage, and the executor that
receives it runs every input partition as a shard of a single mesh
program.  Nothing else in the graph/task machinery changes: recovery,
retries and stats see an ordinary one-task stage.  The reduced
[capacity]-sized states are the only thing that leaves the device.
"""

from __future__ import annotations

import contextlib
import ctypes
import dataclasses
import functools
import os
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from itertools import islice
from typing import Iterator, Optional

import numpy as np
import pyarrow as pa

from ..config import BATCH_SIZE
from ..exec.operators import ExecutionPlan, Partitioning, TaskContext
from ..obs import trace

# jitted shard_map step per (kernel signature, n_devices): reused across
# plan instances exactly like stage_compiler._KERNEL_CACHE
_MESH_STEP_CACHE: dict = {}


@functools.lru_cache(maxsize=1)
def _libc_sched_getcpu():
    try:
        return ctypes.CDLL(None).sched_getcpu
    except (OSError, AttributeError):
        return None


def _sched_cpu() -> int:
    """The core this thread runs on right now, from libc's
    ``sched_getcpu`` (Python's ``os`` has no such call); -1 where the
    platform cannot say.  Only traced gang stages ask."""
    fn = _libc_sched_getcpu()
    return int(fn()) if fn is not None else -1


def _usable_cores() -> int:
    """Cores this process may run on (its affinity mask, which a
    container or ``taskset`` narrows; the machine's count where the
    platform has no such call)."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:
        return os.cpu_count() or 1


# The gang stage's worker pool is never wider than this: on the chip host
# (13 cores; PERF.md, PR 29) q1's stage read 1004, 637, 445, 385, 323 ms
# at widths 1, 2, 3, 4, 6 and nothing steady past 6 (8 and 12 read inside
# 6's run-to-run range), q6's was flat from 4 -- the workers' scans, key
# hashing and copies meet in memory bandwidth and the GIL.
_MAX_GANG_WIDTH = 6


def _gang_width(ctx: TaskContext, n_parts: int) -> int:
    """How many partitions a gang stage prepares side by side: the cores
    the process can see less one for each OTHER task slot of the executor
    (a gang stage is its stage's only task; what shares the process with
    it is other stages' tasks, a thread each), at most one worker a
    partition, at least 1 -- and 1 is the same loop run inline.  Four
    slots each running a pool of 6 at once (24 threads on 13 cores) were
    no slower than four pools of 3 or four single threads (same sweep),
    so the rule does not divide the cores by the slots."""
    spare = _usable_cores() - (max(1, ctx.task_slots) - 1)
    return max(1, min(spare, n_parts, _MAX_GANG_WIDTH))


@dataclass
class _Prepared:
    """What a worker hands back for one partition: nothing in it is
    shared with another partition's."""

    rows: int = 0
    seg: Optional[np.ndarray] = None  # local segment ids (row -> local gid)
    cols: list = field(default_factory=list)  # numpy, tpu._flat_names order
    encoders: list = field(default_factory=list)  # the partition's own
    table: object = None  # the partition's own GroupTable


def _pull(it) -> tuple:
    """(next batch of a source iterator or None, ns inside the source)."""
    t0 = time.perf_counter_ns()
    batch = next(it, None)
    return batch, time.perf_counter_ns() - t0


def _close(it) -> None:
    """Close a source iterator that may have been left half read (a
    generator then runs its ``finally``: timers, open files)."""
    close = getattr(it, "close", None)
    if close is not None:
        close()


def _in_partition_order(
    prepare, n_parts: int, width: int, stop, name: str = "gang"
):
    """``prepare(p)`` for p = 0 .. n_parts-1, results in that order, up to
    ``width`` partitions in the making side by side (in flight or done
    and waiting their turn), one more being handed over.  An error of
    ``prepare(p)`` is raised when p's turn comes.  Closing the generator
    -- after the last result, an error or the consumer's own exit -- sets
    ``stop`` and joins every worker, so no thread outlives the stage.
    Width 1 runs ``prepare`` inline on the caller's thread.  The workers
    are named ``<name>_<n>`` after their caller.  What XLA compiled for a
    partition on a worker (a device stage below an exchange) is credited
    to the caller's thread at that partition's hand-over, where the task's
    compile counters are read."""
    if width <= 1:
        for p in range(n_parts):
            yield prepare(p)
        return
    from ..ops import xla_meter

    def metered(p: int) -> tuple:
        before = xla_meter.snapshot()
        return prepare(p), xla_meter.since(before)

    pool = ThreadPoolExecutor(width, thread_name_prefix=name)
    todo = iter(range(n_parts))
    pending: deque = deque()
    try:
        pending.extend(pool.submit(metered, p) for p in islice(todo, width))
        while pending:
            part, compiled = pending.popleft().result()
            xla_meter.credit(compiled)
            # top up before the hand-over, so no worker idles through it
            pending.extend(pool.submit(metered, p) for p in islice(todo, 1))
            yield part
    finally:
        stop.set()
        pool.shutdown(wait=True, cancel_futures=True)


def _mesh_width(n_devices: int, ctx: TaskContext) -> int:
    """Devices a mesh stage spreads over: the plan's count, else
    ``ballista.mesh.devices``, else all; never more than there are."""
    import jax

    n_dev = n_devices or ctx.config.mesh_devices or len(jax.devices())
    return max(1, min(n_dev, len(jax.devices())))


def gang_eligible(plan: ExecutionPlan) -> bool:
    """Structural check (no kernel build, no device touch — safe on the
    scheduler): does this stage subtree fuse into a partial-aggregate
    kernel whose states reduce with mesh collectives?"""
    from ..exec.aggregates import PARTIAL, HashAggregateExec
    from ..ops.stage_compiler import _flatten

    if not isinstance(plan, HashAggregateExec) or plan.mode != PARTIAL:
        return False
    if any(
        a.func == "count_distinct" or a.func.startswith("udaf:")
        for a in plan.aggs
    ):
        return False
    fused = _flatten(plan)
    # device-join stages run sequentially for now: the gang path would
    # need the build side replicated across shards
    return fused is not None and fused.join is None


class MeshGangExec(ExecutionPlan):
    """Runs a whole stage as one shard_map program over the mesh.

    Output partitioning is always 1: the scheduler sees a one-task stage.
    Execution accelerates the subtree (``maybe_accelerate``) and, when it
    fused, shards ALL input partitions over the mesh's data axis, reduces
    the per-device states over ICI and materializes the combined partial
    result.  Any fusion/capacity failure falls back to executing the input
    partitions sequentially inside the same task — still correct, just
    without the collective.
    """

    def __init__(self, input: ExecutionPlan, n_devices: int = 0):
        super().__init__()
        self.input = input
        self.n_devices = n_devices

    @property
    def schema(self) -> pa.Schema:
        return self.input.schema

    def output_partitioning(self) -> Partitioning:
        return Partitioning.unknown(1)

    def children(self) -> list[ExecutionPlan]:
        return [self.input]

    def with_new_children(self, children):
        return MeshGangExec(children[0], self.n_devices)

    def __str__(self) -> str:
        n = self.n_devices or "auto"
        return f"MeshGangExec: devices={n}"

    # ------------------------------------------------------------ execute
    def execute(
        self, partition: int, ctx: TaskContext
    ) -> Iterator[pa.RecordBatch]:
        assert partition == 0, "gang stages are single-task"
        from ..errors import ExecutionError
        from ..ops.stage_compiler import (
            Route,
            TpuStageExec,
            _CapacityExceeded,
            _JaxRuntimeError,
            maybe_accelerate,
            note_device_error,
        )

        inner = self.input
        if not isinstance(inner, TpuStageExec):
            inner = maybe_accelerate(inner, ctx.config)
        # fully materialized before yielding: a capacity fallback must
        # never follow already-emitted rows with a re-run
        batches = None
        if (
            isinstance(inner, TpuStageExec)
            and ctx.config.tpu_enable
            and inner.fused.join is None
        ):
            try:
                route, batches = self._execute_mesh(inner, ctx)
                if route is Route.KEYED:
                    # groups ~ rows: the KEYED reduction per shard (every
                    # device concurrently), merged on host, keeps the mesh
                    batches = list(self._execute_mesh_keyed(inner, ctx))
                elif route is Route.CPU_HASH:
                    # the sequential run hands each partition to the C++
                    # hash aggregate
                    self.metrics.add("mesh_fallback", 1)
            except (_CapacityExceeded, ExecutionError):
                # group capacity overflow or a type that slipped past
                # plan-time lowering: re-run sequentially (Cancelled is a
                # BallistaError sibling and still propagates)
                self.metrics.add("mesh_fallback", 1)
            except _JaxRuntimeError as e:
                # a DEVICE/COMPILE failure (chip, round 5, h2o: the
                # gang's shard_map compile got its tpu_compile_helper
                # SIGKILLed and the uncaught JaxRuntimeError killed the
                # whole query): a gang stage degrades to the sequential
                # path, loudly.  Only jax's runtime error is caught —
                # blanket RuntimeError would hide real bugs.
                note_device_error(self.metrics, str(self), e)
        if batches is None:
            batches = self._execute_sequential(inner, ctx)
        yield from batches

    def _execute_sequential(
        self, inner: ExecutionPlan, ctx: TaskContext
    ) -> Iterator[pa.RecordBatch]:
        for p in range(self.input.output_partitioning().n):
            yield from inner.execute(p, ctx)

    def _execute_mesh(self, tpu, ctx: TaskContext) -> tuple:
        """All input partitions → one sharded fused kernel + ICI reduce:
        ``(Route.GID, the output)``, or ``(another route, None)`` where
        the probe of the stage's first batch says to leave this path.

        A plain method (the caller materializes the result anyway), so the
        ``gang.*`` spans nest on the thread's span stack.  The stage's wall
        is ``mesh_stage_time_ns``; on THIS thread each part of it is
        counted to exactly one phase (``gang_wait_ns``: until the next
        partition in order is prepared; ``gang_merge_ns``,
        ``gang_upload_ns``, ``gang_assemble_ns``, ``gang_step_ns``,
        ``gang_materialize_ns``), and what is left is loop overhead.
        ``gang_scan_ns``, ``key_encode_time_ns`` and ``gang_convert_ns``
        are time inside those phases summed over the ``gang_workers``
        threads that prepare partitions side by side (at width 1, this
        thread, inside its wait).  ``gang_cpu_ns`` is this thread's CPU
        time over the same wall."""
        clock = time.perf_counter_ns
        wall0, cpu0 = clock(), time.thread_time_ns()
        n_dev = _mesh_width(self.n_devices, ctx)
        stage_span = trace.span("gang.stage", n_dev=n_dev)
        # one check a stage: no per-partition clock, cpu or attr work and
        # no span object when obs is off or the task is unsampled
        traced = stage_span is not trace.NOOP
        try:
            with stage_span:
                return self._mesh_phases(tpu, ctx, n_dev, stage_span, traced)
        finally:
            self.metrics.add("mesh_stage_time_ns", clock() - wall0)
            self.metrics.add("gang_cpu_ns", time.thread_time_ns() - cpu0)

    def _mesh_phases(
        self, tpu, ctx: TaskContext, n_dev: int, stage_span, traced: bool
    ) -> tuple:
        import jax

        from ..errors import Cancelled
        from ..ops import kernels as K
        from ..ops.bridge import (
            _concat_batches, make_key_encoder, merge_key_codes,
        )
        from ..ops.groups import GroupTable
        from ..ops.stage_compiler import Route
        from . import mesh as M

        clock = time.perf_counter_ns
        add = self.metrics.add
        fused = tpu.fused
        n_keys = len(fused.group_exprs)

        def new_key_encoders() -> list:
            return [
                make_key_encoder(tpu._schema.field(i).type)
                for i in range(n_keys)
            ]

        key_encoders = new_key_encoders()
        group_table = GroupTable(n_keys)
        n_rows = 0
        n_parts = fused.source.output_partitioning().n
        width = _gang_width(ctx, n_parts)
        add("gang_workers", width)
        # the counts read 0, not absent, on a stage that left on its probe
        for k in ("gang_batches", "gang_uploads", "gang_upload_bytes"):
            add(k, 0)
        # Partitions ARE the shards, and the partition is the unit of host
        # work.  PREPARE (a worker, up to `width` partitions side by side):
        # pull the partition's batches, coalesce them once at the Arrow
        # level, encode the group keys against the partition's OWN
        # encoders and group table, convert the columns to numpy.  Workers
        # share nothing mutable.  HAND OVER (this thread, in partition
        # order): map the partition's dictionaries and groups into the
        # stage's, rewrite its segment ids with one gather, and give its
        # columns to its device (round-robin) in ONE device_put (~250 us a
        # call whatever it carries).  Local codes and gids are in
        # first-appearance order and partitions merge in order, so the
        # stage's gids are those of one encoder fed every row in order.
        # Peak host memory is `width` + 1 partitions; the asynchronous
        # transfer overlaps the next hand-over.  The arrays handed over
        # are never written again and no buffer is reused: the CPU backend
        # may alias them and the TPU copies late.
        # Column order per device chunk: [seg, valid, *flat_names].
        names = ["__seg", "__valid"] + list(tpu._flat_names)
        mesh = M.make_mesh(n_dev)
        devices = list(mesh.devices.flatten())
        n_dev_chunks: list[list[list]] = [[] for _ in devices]  # [device][partition][column]
        stage_ctx = trace.current_context() if traced else None
        stop = threading.Event()
        # partition -> (its open iterator, its first non-empty batch or
        # None): what the route probe pulled before any worker started
        opened: dict = {}

        def prepare(p: int) -> _Prepared:
            part_span = trace.NOOP
            if traced:
                part_span = trace.span(
                    "gang.partition", parent=stage_ctx, partition=p,
                    device=p % n_dev,
                    worker=threading.current_thread().name,
                    cpu_start=_sched_cpu(),
                )
                part_cpu0 = time.thread_time_ns()
            # phase times of this partition: local integers, one
            # metrics.add each at its end (no timer object a batch)
            scan_ns = encode_ns = convert_ns = 0
            out = _Prepared()
            it, head = opened.pop(p, (None, None))
            batches = [] if head is None else [head]
            with part_span:
                try:
                    if it is None:
                        it = iter(fused.source.execute(p, ctx))
                    while True:
                        batch, ns = _pull(it)
                        scan_ns += ns
                        if batch is None:
                            break
                        ctx.check_cancelled()
                        if stop.is_set():
                            raise Cancelled("gang stage stopped")
                        if batch.num_rows:
                            batches.append(batch)
                    if batches:
                        t0 = clock()
                        whole = _concat_batches(batches)
                        out.rows = n = whole.num_rows
                        t1 = clock()
                        if n_keys:
                            out.encoders = new_key_encoders()
                            out.table = GroupTable(n_keys)
                            out.seg = tpu._encode_groups(
                                whole, out.encoders, out.table
                            )
                        t2 = clock()
                        env = K.build_env(whole, tpu.leaves, n)
                        out.cols = [env[nm] for nm in tpu._flat_names]
                        encode_ns = t2 - t1
                        convert_ns = (t1 - t0) + (clock() - t2)
                    return out
                finally:
                    _close(it)
                    add("gang_scan_ns", scan_ns)
                    add("key_encode_time_ns", encode_ns)
                    add("gang_convert_ns", convert_ns)
                    add("gang_batches", len(batches))
                    if traced:
                        for k, v in (
                            ("rows", out.rows), ("batches", len(batches)),
                            ("scan_ns", scan_ns), ("encode_ns", encode_ns),
                            ("convert_ns", convert_ns),
                            ("cpu_ns", time.thread_time_ns() - part_cpu0),
                            ("cpu_end", _sched_cpu()),
                        ):
                            part_span.set_attr(k, v)

        def hand_over(p: int, part: _Prepared) -> tuple:
            """Partition p into the stage's groups and onto its device:
            (merge ns, upload ns, arrays handed over, their bytes)."""
            t0 = clock()
            if n_keys:
                remap = tpu._assign_gids(
                    part.table.key_columns([
                        merge_key_codes(mine, theirs)
                        for mine, theirs in zip(key_encoders, part.encoders)
                    ]),
                    group_table,
                )
                seg = remap[part.seg]
            else:
                seg = np.zeros(part.rows, dtype=np.int32)
            host = [seg, np.ones(part.rows, dtype=bool)] + part.cols
            t1 = clock()
            n_dev_chunks[p % n_dev].append(
                jax.device_put(host, devices[p % n_dev])
            )
            return t1 - t0, clock() - t1, len(host), sum(a.nbytes for a in host)

        try:
            if n_keys:
                # the route decision keeps its input: the stage's first
                # non-empty batch, encoded alone, before a worker starts.
                # Time this thread spends before it has a partition to
                # hand over: the first wait.
                t0 = clock()
                try:
                    route = self._probe_route(
                        tpu, ctx, opened, new_key_encoders()
                    )
                finally:
                    add("gang_wait_ns", clock() - t0)
                if route is not Route.GID:
                    return route, None
            with contextlib.closing(
                _in_partition_order(prepare, n_parts, width, stop)
            ) as prepared:
                for p in range(n_parts):
                    hand_span = trace.NOOP
                    if traced:
                        hand_span = trace.span(
                            "gang.handover", partition=p, device=p % n_dev
                        )
                    wait_ns = merge_ns = upload_ns = uploads = upload_bytes = 0
                    with hand_span:
                        try:
                            t0 = clock()
                            part = next(prepared)
                            wait_ns = clock() - t0
                            if part.rows:
                                merge_ns, upload_ns, uploads, upload_bytes = (
                                    hand_over(p, part)
                                )
                                n_rows += part.rows
                        finally:
                            add("gang_wait_ns", wait_ns)
                            add("gang_merge_ns", merge_ns)
                            add("gang_upload_ns", upload_ns)
                            add("gang_uploads", uploads)
                            add("gang_upload_bytes", upload_bytes)
                            add("gang_partitions", 1)
                            if traced:
                                for k, v in (
                                    ("wait_ns", wait_ns),
                                    ("merge_ns", merge_ns),
                                    ("upload_ns", upload_ns),
                                    ("upload_bytes", upload_bytes),
                                ):
                                    hand_span.set_attr(k, v)
        finally:
            # workers are joined by now; what the probe opened and no
            # worker took over (an early exit) is closed here
            for it, _ in opened.values():
                _close(it)

        if n_rows == 0:
            return Route.GID, self._timed_materialize(
                tpu, None, key_encoders, group_table, 0, ctx
            )

        # same 4x capacity bucketing as the sequential device path —
        # segment ids beyond the table would be dropped silently
        cap = tpu.capacity
        while cap < group_table.n_groups:
            cap *= 4
        cap = min(cap, tpu.max_capacity)
        if cap > tpu.capacity:
            add("capacity_growths", 1)
        stage_span.set_attr("rows", n_rows)
        stage_span.set_attr("groups", group_table.n_groups)
        stage_span.set_attr("capacity", cap)

        t0 = clock()
        with trace.span("gang.assemble"):
            sharded = M.assemble_shards(mesh, n_dev_chunks, len(names))
        t1 = clock()
        with trace.span("gang.step") as step_span:
            compiles = tpu.metrics.values.get("kernel_compiles", 0)
            step_key = (tpu._sig, n_dev, cap) + K.algo_cache_token()
            step = _MESH_STEP_CACHE.get(step_key)
            if step is None:
                raw_kernel, _ = tpu._kernel_for(cap)
                step = M.make_distributed_agg_step(
                    raw_kernel, tpu.specs, mesh, cap, tpu._mode
                )
                _MESH_STEP_CACHE[step_key] = step
            # compile/execute attribution lands on the inner stage's
            # metrics, next to the sequential path's
            out = tpu._timed_jit(step)(*sharded)
            step_span.set_attr(
                "compiled",
                tpu.metrics.values.get("kernel_compiles", 0) > compiles,
            )
        with trace.span("gang.fetch"):
            # the packed fetch is the sync: one transfer, sliced to
            # the assigned groups (pow2 bucket)
            host_states = tpu._fetch_states(
                tuple(out),
                group_table.n_groups if fused.group_exprs else None,
            )
        t2 = clock()
        add("gang_assemble_ns", t1 - t0)
        add("gang_step_ns", t2 - t1)
        add("mesh_rows_in", n_rows)
        add("mesh_devices", n_dev)
        return Route.GID, self._timed_materialize(
            tpu, host_states, key_encoders, group_table, n_rows, ctx
        )

    def _probe_route(
        self, tpu, ctx: TaskContext, opened: dict, key_encoders: list
    ):
        """Open partitions in order up to the stage's first non-empty
        batch, encode that batch alone (against encoders of its own,
        thrown away) and have ``choose_route`` say, before anything else
        is read, encoded or uploaded, whether the stage stays on this
        path (``Route.GID``; also where no partition holds a row).  What
        was opened stays in ``opened`` for the partitions' workers."""
        from ..ops.groups import GroupTable
        from ..ops.stage_compiler import FirstBatch, Route, choose_route

        fused = tpu.fused
        scan_ns = encode_ns = 0
        try:
            for p in range(fused.source.output_partitioning().n):
                it = iter(fused.source.execute(p, ctx))
                opened[p] = (it, None)
                while True:
                    batch, ns = _pull(it)
                    scan_ns += ns
                    if batch is None:
                        break
                    ctx.check_cancelled()
                    if batch.num_rows == 0:
                        continue
                    opened[p] = (it, batch)
                    t0 = time.perf_counter_ns()
                    try:
                        table = GroupTable(len(key_encoders))
                        tpu._encode_groups(batch, key_encoders, table)
                    finally:
                        encode_ns = time.perf_counter_ns() - t0
                    # no join in a gang stage, and a stage that needs the
                    # keyed path runs these phases like any other; the
                    # keyed gang encodes its keys on the host and checks
                    # batch by batch that they fit
                    return choose_route(
                        highcard_mode=tpu.config.tpu_highcard_mode,
                        device_encode=False,
                        grouped=True,
                        needs_keyed=False,
                        folded_join=False,
                        max_capacity=tpu.max_capacity,
                        first=FirstBatch(
                            batch.num_rows,
                            fast_encoders=lambda: False,
                            keys_fit=lambda: True,
                            groups=lambda: table.n_groups,
                        ),
                    )
            return Route.GID
        finally:
            self.metrics.add("gang_scan_ns", scan_ns)
            self.metrics.add("key_encode_time_ns", encode_ns)

    def _timed_materialize(
        self, tpu, host_states, key_encoders, group_table, n_rows, ctx
    ) -> list[pa.RecordBatch]:
        t0 = time.perf_counter_ns()
        with trace.span("gang.materialize"):
            out = list(
                tpu._materialize(
                    host_states, key_encoders, group_table, n_rows, ctx, 0
                )
            )
        self.metrics.add("gang_materialize_ns", time.perf_counter_ns() - t0)
        return out

    def _execute_mesh_keyed(
        self, tpu, ctx: TaskContext
    ) -> Iterator[pa.RecordBatch]:
        """High-cardinality gang: per-shard KEYED reduction on every
        device CONCURRENTLY (async dispatch of the single-chip keyed
        kernels — sort by raw key codes, gids from key-change
        boundaries), then a [distinct]-sized vectorized host merge by
        key.  The O(rows) sort/scan work stays on the shards; only the
        per-shard (unique keys, states) cross to host.  An ICI
        tree-merge is the future optimization; the host merge is already
        orders of magnitude below row scale."""
        import jax
        import jax.numpy as jnp

        from ..errors import ExecutionError
        from ..ops import kernels as K
        from ..ops.bridge import make_key_encoder
        from ..ops.stage_compiler import _CapacityExceeded, _KeyedGroups
        from . import mesh as M

        fused = tpu.fused
        holder, _raw, prep = tpu._keyed_prep()
        key_encoders = [
            make_key_encoder(tpu._schema.field(pos).type)
            for pos, (kind, _s) in enumerate(tpu._group_plan)
            if kind == "enc"
        ]
        n_keys = tpu._n_encoded_groups
        n_dev = _mesh_width(self.n_devices, ctx)
        mesh = M.make_mesh(n_dev)
        devices = list(mesh.devices.flatten())
        per_dev_buf: list[list] = [[] for _ in devices]
        n_rows = 0
        with self.metrics.timer("mesh_stage_time_ns"):
            n_parts = fused.source.output_partitioning().n
            for p in range(n_parts):
                for batch in fused.source.execute(p, ctx):
                    ctx.check_cancelled()
                    n = batch.num_rows
                    if n == 0:
                        continue
                    with self.metrics.timer("key_encode_time_ns"):
                        codes = tpu._encode_codes(batch, key_encoders)
                    if tpu._mode == "x32":
                        for c in codes:
                            if len(c) and (
                                c.min() < -(1 << 31)
                                or c.max() >= (1 << 31)
                            ):
                                raise ExecutionError(
                                    "gang keys exceed i32"
                                )
                    n_pad = K.bucket_rows(n)
                    keys = tuple(
                        K._pad(K.coerce_host_values(c), n_pad)
                        for c in codes
                    )
                    valid = np.zeros(n_pad, dtype=bool)
                    valid[:n] = True
                    with self.metrics.timer("bridge_time_ns"):
                        # trivial-validity substitution is skipped here:
                        # the gang pins arrays to explicit mesh devices,
                        # and a default-device iota mask would break that
                        # placement
                        args, _ = tpu._kernel_args(batch, n, n_pad, None)
                    dev = devices[p % n_dev]
                    with self.metrics.timer("device_time_ns"):
                        keys_d = tuple(
                            jax.device_put(k, dev) for k in keys
                        )
                        valid_d = jax.device_put(valid, dev)
                        args_d = [jax.device_put(a, dev) for a in args]
                        per_dev_buf[p % n_dev].append(
                            prep(keys_d, valid_d, *args_d)
                        )
                    n_rows += n

            if n_rows == 0:
                yield from tpu._materialize(
                    None, key_encoders, _KeyedGroups([], 0), 0, ctx, 0
                )
                return

            with self.metrics.timer("device_time_ns"):
                # per-device concat + phase-1 sort (dispatches overlap
                # across devices; only the scalar fetches serialize)
                sort_out: list = []
                for buf in per_dev_buf:
                    if not buf:
                        sort_out.append(None)
                        continue
                    parts = list(zip(*buf))
                    if len(buf) == 1:
                        fields = [q[0] for q in parts]
                    else:
                        fields = [jnp.concatenate(q) for q in parts]
                    total = int(fields[0].shape[0])
                    n2 = K.bucket_rows(total)
                    if n2 != total:
                        fields = [
                            jnp.pad(f, (0, n2 - total)) for f in fields
                        ]
                    mask = fields[0]
                    keys_f = fields[1:1 + n_keys]
                    flat = fields[1 + n_keys:]
                    out = K.keyed_sort_kernel(n_keys)(mask, *keys_f)
                    sort_out.append((out, flat))
                counts = [
                    int(np.asarray(so[0][-1])) if so is not None else 0
                    for so in sort_out
                ]
                if max(counts, default=0) > tpu.max_capacity:
                    raise _CapacityExceeded()
                cap = K.bucket_rows(max(counts), floor=64)
                fetches = []
                for so, ng in zip(sort_out, counts):
                    if so is None:
                        continue
                    out, flat = so
                    s2, perm, sk = out[0], out[1], out[2:-1]
                    finish = K.keyed_finish_kernel(
                        holder["kinds"], holder["plan"], tpu.specs,
                        n_keys, cap, tpu._mode,
                    )
                    fetches.append(
                        (finish(s2, perm, tuple(sk), tuple(flat)), ng)
                    )
                per_dev = []
                for packed, ng in fetches:
                    host = np.asarray(packed)
                    states, kc = K.unpack_keyed_host(
                        tpu.specs, host, tpu._mode, n_keys
                    )
                    per_dev.append((states, kc, ng))
            merged_states, merged_keys, n_groups = K.merge_keyed_host(
                tpu.specs, tpu._mode, per_dev
            )
        self.metrics.add("mesh_rows_in", n_rows)
        self.metrics.add("mesh_devices", n_dev)
        self.metrics.add("mesh_keyed", 1)
        yield from tpu._materialize(
            merged_states, key_encoders,
            _KeyedGroups(merged_keys, n_groups), n_rows, ctx, 0,
        )


@dataclass
class _ExchangePart:
    """What a worker hands back for one input partition of an exchange:
    nothing in it is shared with another partition's."""

    rows: int = 0
    cols: list = field(default_factory=list)  # numpy, the exchange's order
    encoders: dict = field(default_factory=dict)  # field -> its own DictEncoder


# A worker coalesces at most this many bytes of Arrow batches with a string
# column into one batch before it flattens them: a string column's int32
# offsets end at 2 GiB, and past them ``Table.combine_chunks`` leaves
# several chunks, of which ``_concat_batches`` returns the first.  A
# partition under it, or with no string column, is coalesced once.
_COALESCE_BYTES = 1 << 30


def _byte_groups(batches: list, limit: Optional[int]) -> Iterator[list]:
    """``batches`` in order, cut into runs of at most ``limit`` bytes (a
    batch that is larger alone is a run of its own); one run where
    ``limit`` is None."""
    group: list = []
    size = 0
    for b in batches:
        if limit is not None:
            if group and size + b.nbytes > limit:
                yield group
                group, size = [], 0
            size += b.nbytes
        group.append(b)
    if group:
        yield group


# An exchange's workers pull an input of host operators this many rows a
# batch: the rows of a parquet row group as pyarrow's writer cuts one by
# default (its reader then fills a batch across row groups).  The
# worker coalesces its whole partition anyway, so the batch size only sets
# how often the scan and the filter return to Python, and each return waits
# to win the GIL back from every other thread of the process: at 8,192 rows
# a lineitem partition of 5M rows made 611 such trips, and the filter cost
# several times more side by side than alone.
_PULL_ROWS = 1 << 20


def _pull_context(ctx: TaskContext) -> TaskContext:
    """``ctx`` with a batch size of at least ``_PULL_ROWS`` rows, for an
    exchange's workers to pull an input of host operators with.  Only
    that pull: every other operator keeps the session's batch size, and
    over a device stage it is part of the stage's program signature."""
    if ctx.batch_size >= _PULL_ROWS:
        return ctx
    settings = {**ctx.config.settings, BATCH_SIZE: str(_PULL_ROWS)}
    return dataclasses.replace(
        ctx, config=dataclasses.replace(ctx.config, settings=settings)
    )


def _device_stages(plan: ExecutionPlan) -> list:
    """The operators of this subtree that run on the device."""
    from ..ops.stage_compiler import TpuStageExec
    from ..ops.window_compiler import TpuWindowExec

    found, stack = [], [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, (TpuStageExec, TpuWindowExec)):
            found.append(node)
        stack.extend(node.children())
    return found


def _exchange_width(
    plan: ExecutionPlan, ctx: TaskContext, n_parts: int, n_dev: int
) -> int:
    """How many of ``plan``'s partitions an exchange over ``n_dev`` chips
    prepares side by side: the gang stage's rule (``_gang_width``) over
    host operators; over device stages, no more than one a chip, and only
    where every one of them runs its partitions apart
    (``TpuStageExec.partitions_apart``: the build-row route, each partition
    on its own chip); 1 (inline) otherwise, and always on one chip."""
    from ..ops.stage_compiler import TpuStageExec

    stages = _device_stages(plan)
    if not stages:
        return _gang_width(ctx, n_parts)
    if n_dev > 1 and all(
        isinstance(s, TpuStageExec) and s.partitions_apart(ctx) for s in stages
    ):
        return min(n_dev, _gang_width(ctx, n_parts))
    return 1


class MeshExchangeError(Exception):
    """Exchange-specific failure (capacity ceiling, untransferable column):
    the owning writer falls back to the classic hash-split.  Deliberately
    NOT an ExecutionError so inner-plan execution errors propagate to the
    normal stage-retry machinery instead of being silently re-run."""


def exchange_supported(schema: pa.Schema) -> bool:
    """Can every field of this schema cross the ICI batch exchange?
    (numeric/bool/date/timestamp directly, strings as dictionary codes,
    i64 as lo/hi pairs — mesh.BatchExchanger's layout rules)."""
    from ..ops.bridge import _is_device_friendly

    for f in schema:
        t = f.type
        if not (
            pa.types.is_string(t)
            or pa.types.is_large_string(t)
            or _is_device_friendly(t)
        ):
            return False
    return True


class MeshRepartitionExec(ExecutionPlan):
    """Gang-form hash repartition: the stage's shuffle IS an ICI collective.

    The reference hash-splits every batch per input partition and writes
    n_in x n_out shuffle files (``shuffle_writer.rs:201-285``); when the
    stage's partitions are mesh-resident, this node runs ONE task that
    shards every input partition over the mesh, routes rows to their
    destination output partition with a single ``all_to_all``
    (:class:`..parallel.mesh.BatchExchanger`), and hands the owning
    :class:`ShuffleWriterExec` already-partitioned output batches — zero
    hash-split files, one memory write per output partition.

    Output partition ``p`` lives on device ``p % n_dev``.  The run a
    source shard sends a device holds that device's partitions one after
    the other, ascending, and a row's slot in it is its partition's offset
    (the shard's rows of the device's lower partitions) plus its rank
    within its partition, so each partition's rows arrive contiguous and
    in input order; the host, which counted the rows a (shard, partition)
    to size the capacity, slices them out (``execute_exchanged``: where
    the one-hot the ranking takes, 4 B a partition, fits in what a row
    already costs the program).

    The host side works a partition at a time: a small pool of workers
    (``_exchange_width``: the gang stage's rule over host operators; over a
    device stage on the build-row route, a worker a chip, each partition
    running on its own chip; inline over any other device stage, and on
    one chip) pulls the input partitions side by side, each coalescing,
    hashing and flattening its own partition once; the task thread takes
    them in partition order, concatenates them and makes the one device
    call, so the rows go up in the order a sequential read would give.
    A worker pulls an input of host operators a parquet row group's rows
    at a time (``_PULL_ROWS``), not at the session's batch size, which
    would only send the scan and the filter back through Python and the
    GIL every few thousand rows; over a device stage it pulls at the
    session's, which is part of the stage's program signature.
    The whole input is buffered on the host, so ``mesh.exchange_max_rows``
    bounds it: the workers sum the rows pulled at every batch and all stop
    pulling when the sum passes the ceiling (MeshExchangeError → writer
    fallback).

    ``output_partitioning()`` is 1 so the scheduler sees an ordinary
    one-task stage (same trick as :class:`MeshGangExec`); recovery and
    stats machinery are untouched.  Capacity follows the documented
    n_dropped contract: computed exactly from the shard layout, doubled
    and retried if the exchange still reports drops, ExecutionError (→
    writer fallback) past the ceiling.

    The largest it meets (q3 at SF10 on one chip, PR 34): stage 4 hands
    over 30,863,328 lineitem rows, 15.4M for each of the two output
    partitions.  On one device every row is that device's, so the exact
    count's bucket, 1 << 25 slots, is the capacity from the first call:
    above ``_CAP_CEILING``, which bounds only the doublings after drops,
    and the exact count leaves none (no growth on the chip).  The row
    ceiling, ``mesh.exchange_max_rows`` = 1 << 26, is twice the rows.
    """

    _CAP_CEILING = 1 << 24
    # process-wide observability: completed exchanges / writer fallbacks
    # (executor-side metrics are not reachable from cluster tests)
    exchanges_completed = 0

    def __init__(
        self, input: ExecutionPlan, partitioning: Partitioning,
        n_devices: int = 0,
    ):
        super().__init__()
        assert partitioning.kind == "hash"
        self.input = input
        self.partitioning = partitioning
        self.n_devices = n_devices

    @property
    def schema(self) -> pa.Schema:
        return self.input.schema

    def output_partitioning(self) -> Partitioning:
        return Partitioning.unknown(1)

    def children(self) -> list[ExecutionPlan]:
        return [self.input]

    def with_new_children(self, children):
        return MeshRepartitionExec(
            children[0], self.partitioning, self.n_devices
        )

    def __str__(self) -> str:
        return (
            f"MeshRepartitionExec: hash({self.partitioning.n}) "
            f"devices={self.n_devices or 'auto'}"
        )

    def execute(
        self, partition: int, ctx: TaskContext
    ) -> Iterator[pa.RecordBatch]:
        # direct execution (no writer): repartition does not change row
        # content, so pass every input partition through unchanged
        for p in range(self.input.output_partitioning().n):
            yield from self.input.execute(p, ctx)

    # -------------------------------------------------------- exchanged
    def _prepare_partitions(self, ctx: TaskContext, layout) -> tuple[list, int]:
        """The stage's input as the exchange's columns: a column list for
        each non-empty input partition, in partition order (the input's
        fields as ``layout`` lays them out, then each row's output
        partition), and the ns this thread spent mapping dictionaries.

        The partition is the unit of the host work.  PREPARE (a worker, up
        to ``width`` partitions side by side): pull the partition's
        batches from the input (scan, decode and filter run inside that
        pull; over host operators at ``_PULL_ROWS`` rows a batch, since
        the partition is coalesced whole anyway, and over a device stage
        at the session's batch size, part of the stage's program), coalesce
        them once at the Arrow level, hash the
        destinations once, flatten to numpy once, strings against the
        partition's OWN dictionaries.  Workers share nothing mutable but
        the rows pulled so far, a slot a partition; the width is
        ``_exchange_width``'s: the gang stage's rule over host operators, a
        worker a chip over a device stage whose partitions run apart (the
        build-row route: partition ``p`` on chip ``p % n_dev``, its states
        its own, the build side placed on each chip once under the stage's
        lock), and 1 (inline) over any other device stage and on one chip.
        HAND OVER (this thread, in partition order): the row ceiling on
        the running count, and the partition's dictionaries mapped into
        the layout's.  What is buffered: every prepared partition's numpy
        columns (the stage's whole input, as before) and, inside a worker,
        its partition's Arrow batches until they are flattened; the
        workers sum the rows pulled at every batch and all stop pulling
        when the sum passes the ceiling, so no more than the ceiling and a
        batch or two a worker (a row group each over host operators) is
        ever held.  ``exchange_pull_batches`` counts the non-empty batches
        the workers pulled, summed over them."""
        from ..errors import Cancelled, ExecutionError
        from ..ops.bridge import _concat_batches
        from ..shuffle.execution_plans import partition_indices

        add = self.metrics.add
        clock = time.perf_counter_ns
        n_out = self.partitioning.n
        exprs = list(self.partitioning.exprs)
        n_parts = self.input.output_partitioning().n
        n_dev = _mesh_width(self.n_devices, ctx)
        width = _exchange_width(self.input, ctx, n_parts, n_dev)
        add("exchange_workers", width)
        # over a device stage, the chip each input partition runs on
        on_chip = bool(_device_stages(self.input))
        pull_ctx = ctx if on_chip else _pull_context(ctx)
        # the exchange buffers the stage input in host memory (~2x resident
        # plus device staging): a row ceiling keeps huge shuffles on the
        # streaming hash-split path instead of OOMing this task
        max_rows = ctx.config.mesh_exchange_max_rows
        # rows pulled so far, a slot a partition: each is written by its own
        # worker alone (no lock for 18 threads to queue behind), and the
        # worker whose write comes last sees every other in its sum
        pulled = [0] * n_parts
        # the exchange.* spans hang under the task's current span, in the
        # recorder's trace and, while a profiler records, in its trace
        stage_ctx = trace.current_context()
        traced = stage_ctx is not None
        stop = threading.Event()

        def over_ceiling(rows: int) -> MeshExchangeError:
            return MeshExchangeError(
                f"stage exceeds mesh.exchange_max_rows ({rows} > {max_rows})"
            )

        def check_stop() -> None:
            if stop.is_set():
                # stopped for the ceiling (any worker's batch took the sum
                # past it): every partition still in the making says so,
                # whichever one's turn comes first
                if sum(pulled) > max_rows:
                    raise over_ceiling(sum(pulled))
                raise Cancelled("exchange stopped")

        def prepare(p: int) -> _ExchangePart:
            part_span = trace.NOOP
            if traced:
                part_span = trace.span(
                    "exchange.partition", parent=stage_ctx, partition=p,
                    worker=threading.current_thread().name,
                    **({"device": p % n_dev} if on_chip else {}),
                )
            pull_ns = hash_ns = convert_ns = 0
            out = _ExchangePart()
            batches: list[pa.RecordBatch] = []
            it = None
            with part_span:
                try:
                    check_stop()
                    it = iter(self.input.execute(p, pull_ctx))
                    while True:
                        batch, ns = _pull(it)
                        pull_ns += ns
                        if batch is None:
                            break
                        ctx.check_cancelled()
                        check_stop()
                        if batch.num_rows == 0:
                            continue
                        batches.append(batch)
                        out.rows += batch.num_rows
                        pulled[p] = out.rows
                        if sum(pulled) > max_rows:
                            stop.set()
                            check_stop()
                    out.encoders = layout.new_encoders()
                    flat: list[list] = []
                    # only a string column has offsets to overflow
                    limit = _COALESCE_BYTES if out.encoders else None
                    for group in _byte_groups(batches, limit):
                        t0 = clock()
                        whole = _concat_batches(group)
                        t1 = clock()
                        dest = partition_indices(whole, exprs, n_out).astype(
                            np.int32
                        )
                        t2 = clock()
                        try:
                            cols = layout.flatten(whole, out.encoders)
                        except ExecutionError as e:
                            # column didn't cross the bridge (dtype slipped
                            # past the plan-time check): an exchange
                            # failure, not a plan failure
                            raise MeshExchangeError(str(e)) from e
                        flat.append(cols + [dest])
                        hash_ns += t2 - t1
                        convert_ns += (t1 - t0) + (clock() - t2)
                    if len(flat) > 1:
                        t0 = clock()
                        flat = [[np.concatenate(one) for one in zip(*flat)]]
                        convert_ns += clock() - t0
                    if flat:
                        out.cols = flat[0]
                    return out
                finally:
                    _close(it)
                    add("exchange_pull_ns", pull_ns)
                    add("exchange_pull_batches", len(batches))
                    add("repart_time_ns", hash_ns)
                    add("exchange_convert_ns", convert_ns)
                    if traced:
                        for k, v in (
                            ("rows", out.rows), ("batches", len(batches)),
                            ("pull_ns", pull_ns), ("hash_ns", hash_ns),
                            ("convert_ns", convert_ns),
                        ):
                            part_span.set_attr(k, v)

        prepared: list = []
        rows_seen = merge_ns = 0
        with contextlib.closing(
            _in_partition_order(prepare, n_parts, width, stop, "exchange")
        ) as in_order:
            for p in range(n_parts):
                hand_span = trace.NOOP
                if traced:
                    hand_span = trace.span("exchange.handover", partition=p)
                with hand_span:
                    t0 = clock()
                    try:
                        part = next(in_order)
                    finally:
                        wait_ns = clock() - t0
                        add("exchange_wait_ns", wait_ns)
                        hand_span.set_attr("wait_ns", wait_ns)
                    if not part.rows:
                        continue
                    rows_seen += part.rows
                    if rows_seen > max_rows:
                        raise over_ceiling(rows_seen)
                    t0 = clock()
                    layout.adopt_codes(part.cols, part.encoders)
                    merge_ns += clock() - t0
                    prepared.append(part.cols)
        return prepared, merge_ns

    def execute_exchanged(
        self, ctx: TaskContext
    ) -> Iterator[tuple[int, pa.RecordBatch]]:
        """Yield (output_partition, batch) pairs after the mesh exchange:
        devices ascending, then their partitions ascending, each partition's
        rows in input order.

        The exchange is GROUPED where a row's one-hot over the output
        partitions (4 B a partition) costs no more than the row already
        takes in the program's inputs: the program ranks each row within
        its output partition and places it at its partition's offset in
        the (source shard, device) run plus that rank, so the decode
        slices every partition out of the received buffers at the bucket
        counts the encode made (``BatchExchanger.partition_batches``): no
        mask, sort or gather.  Past that width the program routes by
        device as before, the partition rides as a payload column
        (``__part``), and the decode slices each device's rows, sorts a
        uint8/uint16 copy of their partitions (a stable radix sort) and
        gathers.  Either way the batches are the same.

        Always-on counters: ``exchange_workers`` (the pool's width, 1 =
        inline), ``exchange_grouped`` (1 where the program placed rows by
        output partition, else 0), ``exchange_row_words`` (the 32-bit
        words a row takes in the program, ``mesh.exchange_row_words``); on
        the task thread ``exchange_wait_ns`` (blocked until the next
        partition IN ORDER is prepared),
        ``exchange_encode_ns`` (what is left of the encode there:
        dictionaries mapped at the hand-over, the final concatenate, the
        bucket count), ``device_time_ns`` (the exchange calls, capacity
        growths included) split exactly into ``exchange_pad_ns``,
        ``exchange_upload_ns``, ``exchange_program_ns`` and
        ``exchange_fetch_ns`` (``BatchExchanger.exchange``'s phases),
        ``exchange_decode_ns``; summed
        over the workers (at width 1 this thread, inside its wait)
        ``exchange_pull_ns`` (inside ``next()`` on the input),
        ``exchange_pull_batches`` (the non-empty batches pulled),
        ``repart_time_ns`` (destination hash), ``exchange_convert_ns``
        (coalesce + flatten)."""
        from ..ops import kernels as K
        from . import mesh as M

        n_out = self.partitioning.n
        n_dev = _mesh_width(self.n_devices, ctx)
        add = self.metrics.add
        clock = time.perf_counter_ns

        # the stage's wall stops before the first yield: the writer that
        # consumes the batches has its own timers
        with self.metrics.timer("mesh_stage_time_ns"):
            # the input's layout and dictionaries now, the program once
            # the rows have said what capacity it needs
            layout = M.ExchangeLayout(self.input.schema)
            prepared, merge_ns = self._prepare_partitions(ctx, layout)
            if not prepared:
                return

            mesh = M.make_mesh(n_dev)

            def exchanger(cap, share_from):
                # the program keys a row's slot as an i32: past that, the
                # writer's Flight shuffle
                if n_dev * cap >= M.EXCHANGE_MAX_SLOTS:
                    raise MeshExchangeError(
                        f"{n_dev} devices x {cap} slots pass the exchange's key"
                    )
                return M.BatchExchanger(
                    mesh, schema, cap, share_from=share_from, n_out=width
                )

            t0 = clock()
            with trace.span("exchange.encode"):
                cols = [np.concatenate(one) for one in zip(*prepared)]
                part = cols.pop()  # each row's output partition
                total = len(part)
                valid = np.ones(total, dtype=bool)
                grouped = 4 * n_out <= (
                    part.itemsize + valid.itemsize + sum(c.itemsize for c in cols)
                )
                schema, width, route = self.input.schema, n_out, part
                if not grouped:
                    # route by device; the partition rides along
                    schema = pa.schema(
                        list(schema) + [pa.field("__part", pa.int32())]
                    )
                    width, route = n_dev, (part % n_dev).astype(np.int32)
                    cols += [part, valid]
                # exact rows a (source shard, partition) of the PADDED
                # input's contiguous shards: the arrays go up at
                # exchange_rows(total) rows (the pad rows are invalid and
                # fill the last shards), so that the program's shapes do
                # not follow the data.  They size the capacity (a device's
                # run is its partitions') and bound the decode's slices.
                rows = M.exchange_rows(total, n_dev)
                per_shard = rows // n_dev
                counts = np.stack([
                    np.bincount(
                        route[s * per_shard:(s + 1) * per_shard], minlength=width
                    )
                    for s in range(n_dev)
                ])
                need = max(
                    int(counts[:, d::n_dev].sum(axis=1).max())
                    for d in range(min(n_dev, width))
                )
                cap = K.bucket_rows(need, floor=1)
                ex = exchanger(cap, layout)
            t1 = clock()
            # the call's phases split t1..t2 exactly; a growth's set-up
            # goes to the next pad, the span's close to the last fetch
            laps = M.PhaseClock(t1)
            growths = 0
            with trace.span(
                "exchange.device", rows=total, padded_rows=rows - total,
                grouped=int(grouped),
            ) as dev_span:
                while True:
                    recv_cols, recv_valid, n_dropped = ex.exchange(
                        route, valid, cols, laps
                    )
                    if n_dropped == 0:
                        break
                    cap *= 2  # grow-or-fallback contract (mesh.py docstring)
                    if cap > self._CAP_CEILING:
                        raise MeshExchangeError(
                            "mesh exchange capacity ceiling exceeded"
                        )
                    growths += 1
                    ex = exchanger(cap, ex)
                dev_span.set_attr("capacity", cap)
                dev_span.set_attr("growths", growths)
            t2 = laps.lap("fetch")
            with trace.span("exchange.decode"):
                received = ex.partition_batches(recv_cols, counts)
                out = (
                    list(received) if grouped
                    else [pb for _, recv in received for pb in _split(recv, n_out, n_dev)]
                )
            add("exchange_encode_ns", merge_ns + t1 - t0)
            add("device_time_ns", t2 - t1)
            for phase, ns in laps.ns.items():
                add(f"exchange_{phase}_ns", ns)
            add("exchange_decode_ns", clock() - t2)
            add("exchange_grouped", int(grouped))
            add("exchange_row_words", ex.row_words)
            if growths:
                add("capacity_growths", growths)
            add("mesh_exchange_rows", total)
            # what the program was handed, padding included (the
            # partition and validity arrays and every encoded column),
            # and what it handed back (devices x capacity slots a device)
            add("mesh_exchange_padded_rows", rows - total)
            add(
                "mesh_exchange_bytes",
                rows * (
                    route.itemsize + valid.itemsize
                    + sum(c.itemsize for c in cols)
                ),
            )
            add(
                "mesh_exchange_recv_bytes",
                recv_valid.nbytes + sum(c.nbytes for c in recv_cols),
            )
            add("mesh_devices", n_dev)
            MeshRepartitionExec.exchanges_completed += 1
        yield from out


def _split(recv: pa.RecordBatch, n_out: int, n_dev: int) -> list:
    """One device's received rows (input order; its partitions ride as the
    last column) as ``(p, batch)`` a non-empty partition, ascending: a
    stable radix sort of a uint8/uint16 copy of the partitions, one take."""
    last = recv.num_columns - 1
    small = np.asarray(recv.column(last)).astype(
        np.uint8 if n_out <= 1 << 8 else np.uint16 if n_out <= 1 << 16 else np.int64
    )
    order = np.argsort(small, kind="stable")
    shuffled = recv.select(range(last)).take(pa.array(order))
    bounds = np.concatenate([[0], np.cumsum(np.bincount(small, minlength=n_out))]).tolist()
    return [
        (p, shuffled.slice(bounds[p], bounds[p + 1] - bounds[p]))
        for p in range(n_out) if bounds[p + 1] > bounds[p]
    ]


def maybe_mesh(plan: ExecutionPlan, config) -> ExecutionPlan:
    """Physical-optimizer rule for the LOCAL engine (SessionContext): run
    an accelerated partial-aggregate under Repartition/Coalesce as one
    mesh gang so the local path exercises the same collectives as the
    distributed gang stages."""
    from ..exec.operators import CoalescePartitionsExec, RepartitionExec
    from ..ops.stage_compiler import TpuStageExec

    if not (config.mesh_enable and config.tpu_enable):
        return plan
    kids = plan.children()
    if kids:
        plan = plan.with_new_children([maybe_mesh(c, config) for c in kids])
    if isinstance(plan, (RepartitionExec, CoalescePartitionsExec)):
        child = plan.children()[0]
        if (
            isinstance(child, TpuStageExec)
            and child.fused.mode == "partial"
            and child.fused.source.output_partitioning().n > 1
        ):
            return plan.with_new_children(
                [MeshGangExec(child, config.mesh_devices)]
            )
    return plan
