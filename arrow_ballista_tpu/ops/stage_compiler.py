"""TPU stage compiler: swap eligible subtrees for fused XLA kernels.

This is the north-star component (BASELINE.json): the counterpart of a
DataFusion ``PhysicalOptimizerRule`` + extension ``ExecutionPlan`` that
intercepts eligible Filter→Project→HashAggregate subplans inside the stage
runner.  ``maybe_accelerate`` walks a physical plan and replaces each
eligible ``HashAggregateExec`` (plus its filter/projection chain) with a
:class:`TpuStageExec`; everything else stays on the CPU operator path, so
the TPU path is a pure operator-level plugin gated by session config
(``ballista.tpu.enable``) — the same role the reference's extension-codec
hook plays for third-party operators (``core/src/serde/mod.rs:82-95``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import enum
import functools
import itertools
import logging
import threading
import time
from typing import Callable, Iterator, Optional

import numpy as np
import pyarrow as pa

# jax is already imported by ops/__init__; .errors adds no backend init
import jax
from jax.errors import JaxRuntimeError as _JaxRuntimeError

from ..config import BallistaConfig
from ..errors import ExecutionError
from ..exec import expressions as pe
from ..exec.aggregates import PARTIAL, SINGLE, AggSpec, HashAggregateExec
from ..exec.operators import (
    ExecutionPlan,
    FilterExec,
    Partitioning,
    ProjectionExec,
    TaskContext,
)
from ..exec.planner import RenameSchemaExec
from ..obs import trace
from . import kernels as K

log = logging.getLogger(__name__)

# Counter for "the chip, its runtime or the XLA compiler refused this
# stage and it re-ran on the CPU operator path".  Kept apart from
# tpu_fallback / cpu_fallback / mesh_fallback, which count routes the
# DATA chose (capacity, row count, key types): a run that must prove it
# used the device asserts this one is zero.
DEVICE_ERROR = "device_error"


def note_device_error(metrics, where: str, exc: BaseException) -> None:
    """Record a device-error degradation: the query survives on the CPU
    operators, but never silently."""
    metrics.add(DEVICE_ERROR, 1)
    log.warning(
        "%s: device error, re-running on the CPU operator path",
        where,
        exc_info=exc,
    )


class _CapacityExceeded(Exception):
    pass


class _JoinIneligible(Exception):
    """The device join cannot run for THIS data (non-unique or
    i32-unrepresentable build keys): re-run with the join on CPU and only
    the aggregate on device (the pre-fold round-2 shape)."""


class _TrackingIter:
    """Iterator wrapper recording whether any item was actually yielded —
    lets the keyed fallback replay buffered batches + chain the tail when
    the failure happened before the live source was touched."""

    def __init__(self, it):
        self._it = iter(it)
        self.consumed = False

    def __iter__(self):
        return self

    def __next__(self):
        item = next(self._it)
        self.consumed = True
        return item


class _KeyedGroups:
    """GroupTable-shaped view over DEVICE-assigned groups: the fetched
    unique key codes (gid order = key-sorted order) satisfy the
    ``n_groups`` / ``codes_for`` surface ``_materialize`` reads."""

    def __init__(self, key_codes: list, n_groups: int):
        self._codes = key_codes
        self.n_groups = n_groups

    def codes_for(self, gids: np.ndarray, key: int) -> np.ndarray:
        return self._codes[key][gids]


class _BuildRows:
    """The groups of the build-row route: build row i, in key order, is
    group i, and its key is the build side's i-th sorted key."""

    def __init__(self, keys: np.ndarray):
        self._keys = keys
        self.n_groups = len(keys)

    def key_column(self, rows: np.ndarray, t: pa.DataType) -> pa.Array:
        v = self._keys[rows]
        if pa.types.is_date32(t):
            return pa.array(v.astype("datetime64[D]"), t)
        return pa.array(v, pa.int64()).cast(t)


class Route(enum.Enum):
    """How an aggregate stage runs, as :func:`choose_route` says."""

    GID = "gid"  # host-assigned group ids into the device segment table
    BUILD_ROWS = "build_rows"  # a folded join's matched build row is the group
    KEYED = "keyed"  # raw key codes sort on the device (``_run_keyed``)
    CPU_HASH = "cpu_hash"  # the C++ hash aggregate, pulled batches replayed
    CPU_SMALL = "cpu_small"  # source under tpu.min_rows: CPU operators
    NOJOIN = "nojoin"  # join on CPU, only the aggregate on the device


@dataclasses.dataclass(frozen=True)
class FirstBatch:
    """What :func:`choose_route` may ask about a stage's first non-empty
    batch.  The three questions cost host work (a range check, the key
    encode, the group-id assignment), so each is a call, made only where
    the route hangs on its answer."""

    rows: int
    # raw key columns the device encodes itself, with identity keys in range
    fast_encoders: Callable[[], bool]
    # the host-encoded key codes are i32 (or the stage computes in x64)
    keys_fit: Callable[[], bool]
    # groups in the gid table after this batch; None: the table refused it
    groups: Callable[[], Optional[int]]


# "groups ~ rows": a first batch with more groups than both bounds.  Below
# either, the gid table wins outright (chip, round 5: q1 SF10 35-40x the
# CPU operators).  Above both, the keyed device sort has won on no captured
# shape (chip, round 5: q3 SF10 keyed ran 0.036x CPU, ~130 s an iteration
# of stream-wide sort against the hash aggregate's 14 s, and 1.13x on the
# gid table in round 3; CPU platform, 1e5 rows: scatter 166M rows/s against
# the keyed sort's 2.6M), so only ``highcard_mode=device`` asks for it.
# Tests set small bounds to route small fixtures.
_HIGHCARD_MIN_GROUPS = 1 << 16
_HIGHCARD_RATIO = 0.05


def choose_route(
    *,
    highcard_mode: str,
    device_encode: bool,
    grouped: bool,
    needs_keyed: bool,
    folded_join: bool,
    max_capacity: int,
    small_input: bool = False,
    build_rows: bool = False,
    first: Optional[FirstBatch] = None,
) -> Optional[Route]:
    """The one place that says how an aggregate stage runs.

    Asked when the peek of the source ends (``small_input``: it ended
    under ``tpu.min_rows``) and, if that gave None, with the stage's
    ``first`` non-empty batch.  Pure: it reads its arguments and the two
    bounds above, and calls ``first``'s questions in the order of their
    cost.  ``highcard_mode`` is ``ballista.tpu.highcard_mode``; ``auto``
    is ``cpu`` for a stage without a folded join.  ``build_rows``: the
    stage folds a join whose groups are its build rows (its one
    host-encoded group key is the probe join key, no aggregate needs the
    keyed buffers) and the build side's bucket is within ``max_capacity``;
    such a stage needs no first batch, and a pinned mode (``device``,
    ``gid``) keeps its meaning.  A gang stage asks with what its probe
    knows (no join, keys taken as fitting: ``_execute_mesh_keyed`` checks
    them batch by batch)."""
    if small_input:
        return Route.CPU_SMALL
    if not grouped:
        return Route.GID  # every row into group 0: nothing to decide
    if build_rows and highcard_mode in ("auto", "cpu"):
        # the groups are bounded by the build rows, whatever number of
        # distinct keys the probe side holds (q3 SF10: 15M order keys,
        # 1.45M build rows): no gid table, no host key encode
        return Route.BUILD_ROWS
    if first is None:
        return None
    pinned_keyed = needs_keyed or highcard_mode == "device"
    if device_encode and pinned_keyed and first.fast_encoders():
        return Route.KEYED  # before any host encode
    if needs_keyed:
        # median/corr live on the keyed path at any cardinality
        return Route.KEYED if first.keys_fit() else Route.CPU_HASH
    groups = first.groups()
    if groups is not None and not (
        groups > _HIGHCARD_MIN_GROUPS and groups > _HIGHCARD_RATIO * first.rows
    ):
        return Route.GID
    if highcard_mode == "device" and first.keys_fit():
        return Route.KEYED
    if highcard_mode == "gid" and groups is not None:
        return Route.GID  # pinned (A/B)
    if not folded_join:
        return Route.CPU_HASH
    # A folded join whose groups are not its build rows pays the join on
    # either route, so it stays on the gid table while that can hold the
    # stage -- but the table keys on every distinct PROBE key before the
    # join filters, so a first batch that alone fills half the ceiling
    # says the stream will overflow it after the host has paid the
    # encode: leave for the join-on-CPU shape now.
    if groups is None or groups > max_capacity // 2:
        return Route.NOJOIN
    return Route.GID


# Whole-stage fusion (ballista.tpu.whole_stage_fusion): the widest operator
# run the planner packs into one traced segment, and the stage input rows
# under which a fused dispatch does not amortize its trace and launch.
_FUSION_MAX_OPS = 8
_FUSION_MIN_ROWS = 2048
# Build-key spans up to this many slots use the dense direct-probe join
# table ([span] i32 = 256 MiB HBM at the cap) instead of searchsorted's
# log2(m) sequential gather passes (chip, round 5, star join).
_DENSE_JOIN_SPAN_CAP = 1 << 26
# The fused single-dispatch runner unrolls one kernel body per retained
# batch; past this many entries the per-batch dispatch loop runs instead
# (an unbounded unroll compiles an XLA program linear in batch count —
# a compile cliff at the default 8k batch size).
_FUSED_MAX_ENTRIES = 32


@functools.partial(jax.jit, static_argnums=1)
def _dense_join_table(slots, span: int):
    """[span] i32 table of the dense join: row index + 1 at a build key's
    slot, 0 where there is no such key.  A slot >= ``span`` (the build
    side's pad rows) is out of bounds and dropped."""
    import jax.numpy as jnp

    return jnp.zeros(span, jnp.int32).at[slots].set(
        jnp.arange(1, slots.shape[0] + 1, dtype=jnp.int32), mode="drop"
    )


def _keep_bucket(n_groups: int) -> int:
    """Pow2 bucket of assigned-group slots a packed fetch moves (shared
    by the streamed and fused fetch paths so their trace keys agree)."""
    return K.bucket_rows(n_groups, floor=64)


class _ReadAhead:
    """Bounded background prefetch of source batches.

    Device stages alternate host-side work (scan/decode, key encode) with
    device dispatch; pulling the NEXT batch on a daemon thread overlaps
    the source's IO (pyarrow readers release the GIL in C++) with the
    current batch's device work.  The iterator is transparent: batches
    arrive in order, source exceptions re-raise at the consumer, and
    a CPU replay (``TpuStageExec._cpu_replay``) can keep consuming it —
    queued batches are still inside and will be yielded.

    ``close()`` stops the pump before a fallback re-runs the stage on
    CPU — otherwise the abandoned thread would keep consuming the old
    source concurrently with the re-run's fresh iterator (a double-read
    of e.g. a Flight stream) and then block on the bounded queue forever.
    Residual race: a pump already blocked INSIDE the source's read when
    ``close()`` lands cannot be interrupted and may consume ONE more item
    before it sees the flag (the item is dropped, never yielded); the
    double-read window is mitigated to that single in-flight read, not
    eliminated.
    """

    _DONE = object()

    def __init__(self, it, depth: int):
        import queue
        import threading

        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._closed = False
        self._exhausted = False

        def pump():
            try:
                for item in it:
                    if self._closed:
                        return  # drop: a fallback re-run owns the source
                    self._q.put(item)
                    if self._closed:
                        return
            except BaseException as e:  # re-raised on the consumer side
                self._q.put(e)
                return
            self._q.put(self._DONE)

        self._thread = threading.Thread(target=pump, daemon=True)
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted:
            raise StopIteration
        item = self._q.get()
        if item is self._DONE:
            self._exhausted = True
            raise StopIteration
        if isinstance(item, BaseException):
            # generator semantics: a terminal exception surfaces once,
            # then the iterator stays exhausted
            self._exhausted = True
            raise item
        return item

    def close(self, deadline_s: float = 1.0) -> None:
        """Stop the pump: drain the queue until the thread exits (freeing
        queue slots unblocks a pump stuck in put; the loop re-checks the
        flag after each put).  Bounded wait: a pump blocked inside the
        SOURCE's read (e.g. a stalled Flight stream) cannot be
        interrupted — after the deadline the daemon thread is abandoned
        (it dies with the source or the process) rather than hanging the
        caller's CPU fallback."""
        import queue
        import time

        self._closed = True
        self._exhausted = True
        give_up = time.monotonic() + deadline_s
        while self._thread.is_alive() and time.monotonic() < give_up:
            try:
                self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(0.05)


def _shuffle_coalesce_rows(config) -> int:
    """Resolved host-coalescing target for shuffle-fed device stages:
    ``ballista.shuffle.coalesce_rows`` (0 → follow ``ballista.batch.size``,
    negative → disabled)."""
    n = config.shuffle_coalesce_rows
    if n < 0:
        return 0
    return n or config.batch_size


def _reads_shuffle(plan) -> bool:
    """Does this stage source pull from a shuffle reader (whose batches
    arrive as per-map-task fragments worth coalescing)?"""
    from ..shuffle.execution_plans import ShuffleReaderExec

    if isinstance(plan, ShuffleReaderExec):
        return True
    return any(_reads_shuffle(c) for c in plan.children())


@contextlib.contextmanager
def _closing_on_error(ra: Optional[_ReadAhead]):
    """Stop the prefetch pump when the device stage aborts into a CPU
    re-run (_CapacityExceeded / ExecutionError): the re-run opens a
    FRESH source iterator, so the old pump must not keep reading the
    abandoned one."""
    try:
        yield
    except BaseException:
        if ra is not None:
            ra.close()
        raise


class _BufferedExec(ExecutionPlan):
    """In-memory stand-in for a stage source whose batches were already
    pulled by a peek (optionally chaining the still-live remainder)."""

    def __init__(self, template: ExecutionPlan, batches: list, tail=None):
        super().__init__()
        self._template = template
        self._batches = batches
        self._tail = tail

    @property
    def schema(self) -> pa.Schema:
        return self._template.schema

    def output_partitioning(self) -> Partitioning:
        return self._template.output_partitioning()

    def children(self) -> list[ExecutionPlan]:
        return []

    def with_new_children(self, children):
        return self

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        yield from self._batches
        if self._tail is not None:
            yield from self._tail


# Compiled-kernel cache: plans are rebuilt per query, but the fused kernel
# is a pure function of the stage's structural signature — reuse the jitted
# callable (and with it XLA's compilation cache) across plan instances.
# One jit a key, whichever of a stage's partitions asks first: a jit
# compiles once for each device its arguments are committed to.
_KERNEL_CACHE: dict[tuple, object] = {}
_KERNEL_CACHE_LOCK = threading.Lock()

# One lock a jitted program, taken by ``TpuStageExec._timed_jit`` around a
# call: whether the call compiled is read under it, so two calls that meet
# in a program's first compile (partitions on several chips side by side)
# count one compile each where each compiled, and none where one did not.
# The program is kept with its lock, so that its ``id`` is never reused.
_JIT_LOCKS: dict[int, tuple] = {}
_JIT_LOCKS_GUARD = threading.Lock()


def _jit_lock(fn) -> threading.Lock:
    with _JIT_LOCKS_GUARD:
        held = _JIT_LOCKS.get(id(fn))
        if held is None:
            held = _JIT_LOCKS[id(fn)] = (fn, threading.Lock())
        return held[1]


def partition_device(partition: int, ctx: TaskContext) -> tuple:
    """``(chip, device)`` that partition ``partition`` of a device stage runs
    on: chip ``partition % n`` of the mesh the exchanges use (the same
    ``_mesh_width`` and ``make_mesh``), where the exchange delivers that
    partition.  ``device`` is None, the default device, where that mesh is
    one chip or the mesh is off: one chip places nothing."""
    from ..parallel import mesh as M
    from ..parallel.mesh_stage import _mesh_width

    if not ctx.config.mesh_enable:
        return 0, None
    n_dev = _mesh_width(0, ctx)
    if n_dev <= 1:
        return 0, None
    chip = partition % n_dev
    return chip, M.make_mesh(n_dev).devices.flat[chip]


class _StageTimer:
    """One interval added to ``tpu_stage_time_ns`` and to its chip's share,
    ``tpu_stage_time_ns_dev<chip>``, so that the chips' counters sum to the
    stage's."""

    def __init__(self, metrics, chip: int):
        self.metrics, self.chip = metrics, chip

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter_ns() - self.t0
        self.metrics.add("tpu_stage_time_ns", dt)
        self.metrics.add(f"tpu_stage_time_ns_dev{self.chip}", dt)


# ----------------------------------------------------------- substitution
def _subst(e: pe.PhysicalExpr, mapping: list[pe.PhysicalExpr]) -> pe.PhysicalExpr:
    """Rewrite ``e`` (defined over an intermediate projection schema) onto
    the stage source schema by inlining the producing expressions."""
    if isinstance(e, pe.Col):
        return mapping[e.index]
    if isinstance(e, pe.Binary):
        return pe.Binary(_subst(e.left, mapping), e.op, _subst(e.right, mapping))
    if isinstance(e, pe.Not):
        return pe.Not(_subst(e.expr, mapping))
    if isinstance(e, pe.Negative):
        return pe.Negative(_subst(e.expr, mapping))
    if isinstance(e, pe.IsNull):
        return pe.IsNull(_subst(e.expr, mapping), e.negated)
    if isinstance(e, pe.InList):
        return pe.InList(_subst(e.expr, mapping), e.items, e.negated)
    if isinstance(e, pe.Like):
        return pe.Like(_subst(e.expr, mapping), e.pattern, e.negated)
    if isinstance(e, pe.Case):
        return pe.Case(
            tuple((_subst(w, mapping), _subst(t, mapping)) for w, t in e.whens),
            _subst(e.else_expr, mapping) if e.else_expr is not None else None,
            e.out_type,
        )
    if isinstance(e, pe.Cast):
        return pe.Cast(_subst(e.expr, mapping), e.to_type)
    if isinstance(e, pe.ScalarFn):
        return pe.ScalarFn(
            e.fname, tuple(_subst(a, mapping) for a in e.args), e.out_type
        )
    if isinstance(e, (pe.Lit, pe.IntervalLit)):
        return e
    raise ExecutionError(f"cannot substitute through {type(e).__name__}")


@dataclasses.dataclass
class DeviceJoinSpec:
    """A PK-FK join folded INTO the fused device stage (SURVEY §7 hard
    part: hash join on device).

    Scope: inner single-key equi-join with UNIQUE build keys (every TPC-H
    join).  The build side (smaller input) collects once on host, sorts by
    key and ships [m]-sized arrays; each probe batch joins ON DEVICE with
    a searchsorted + gather — static shapes, no dynamic output: the match
    mask simply folds into the stage's row mask, so the joined rows feed
    the fused aggregate without EVER materializing the join.
    """

    build: ExecutionPlan  # collected on host, must have unique keys
    probe_key: pe.PhysicalExpr  # over the probe (source) schema
    build_key_index: int  # plain column of the build schema
    build_cols: list[int]  # build columns the stage reads, virtual order
    # (group-only build columns resolve on HOST at materialize time; only
    # the ones the kernel reads ship to the device — see _join_slots)


@dataclasses.dataclass
class _FusedStage:
    """The flattened eligible subtree, rewritten onto the source schema."""

    source: ExecutionPlan
    filters: list[pe.PhysicalExpr]
    group_exprs: list[tuple[pe.PhysicalExpr, str]]
    aggs: list[AggSpec]
    mode: str
    join: Optional[DeviceJoinSpec] = None


def _flatten(
    agg: HashAggregateExec, fold_join: bool = True
) -> Optional[_FusedStage]:
    chain: list[ExecutionPlan] = []
    node = agg.input
    while isinstance(node, (FilterExec, ProjectionExec, RenameSchemaExec)):
        chain.append(node)
        node = node.children()[0]
    source = node
    mapping: list[pe.PhysicalExpr] = [
        pe.Col(i, f.name) for i, f in enumerate(source.schema)
    ]
    filters: list[pe.PhysicalExpr] = []
    try:
        for op in reversed(chain):
            if isinstance(op, RenameSchemaExec):
                continue
            if isinstance(op, FilterExec):
                filters.append(_subst(op.predicate, mapping))
            else:
                mapping = [_subst(e, mapping) for e, _ in op.exprs]
        group_exprs = [(_subst(g, mapping), name) for g, name in agg.group_exprs]
        aggs = [
            dataclasses.replace(
                a,
                arg=_subst(a.arg, mapping) if a.arg is not None else None,
                arg2=_subst(a.arg2, mapping) if a.arg2 is not None else None,
            )
            for a in agg.aggs
        ]
    except ExecutionError:
        return None
    fused = _FusedStage(source, filters, group_exprs, aggs, agg.mode)
    if fold_join:
        return _maybe_fold_join(fused) or fused
    return fused


def _cols_used(e: pe.PhysicalExpr, out: set) -> None:
    if isinstance(e, pe.Col):
        out.add(e.index)
    for name in ("left", "right", "expr", "else_expr"):
        sub = getattr(e, name, None)
        if sub is not None:
            _cols_used(sub, out)
    for name in ("args",):
        for sub in getattr(e, name, ()) or ():
            _cols_used(sub, out)
    if isinstance(e, pe.Case):
        for w, t in e.whens:
            _cols_used(w, out)
            _cols_used(t, out)


def _shift_cols(e: pe.PhysicalExpr, remap: dict) -> pe.PhysicalExpr:
    """Rewrite column indexes through ``remap`` (join schema → probe +
    virtual build columns)."""
    mapping = [None] * (max(remap) + 1 if remap else 0)
    for i, j in remap.items():
        mapping[i] = pe.Col(j, f"c{j}")
    return _subst(e, mapping)


def _maybe_fold_join(fused: _FusedStage) -> Optional[_FusedStage]:
    """Fold an eligible HashJoinExec source into a DeviceJoinSpec."""
    from ..exec.joins import HashJoinExec

    join = fused.source
    if not isinstance(join, HashJoinExec):
        return None
    if (
        join.join_type != "inner"
        or len(join.on) != 1
        or join.filter is not None
    ):
        return None
    lkey, rkey = join.on[0]
    if not isinstance(lkey, pe.Col):
        return None  # build key must be a plain column (sortable table)
    probe = join.right
    left_n = len(join.left.schema)
    probe_n = len(probe.schema)

    def _int_key(t) -> bool:
        return pa.types.is_integer(t) or pa.types.is_date32(t)

    # float keys would truncate through the int64 key path and match rows
    # SQL equality never joins: integer/date keys only
    if not _int_key(join.left.schema.field(lkey.index).type):
        return None
    try:
        if not _int_key(K._infer_pa_type(rkey, probe.schema)):
            return None
    except Exception:
        return None

    # which join-schema columns does the stage actually read?
    used: set = set()
    for f in fused.filters:
        _cols_used(f, used)
    for g, _ in fused.group_exprs:
        _cols_used(g, used)
    for a in fused.aggs:
        if a.arg is not None:
            _cols_used(a.arg, used)
        if a.arg2 is not None:
            _cols_used(a.arg2, used)

    build_cols: list[int] = []
    remap: dict = {}
    for i in sorted(used):
        if i >= left_n:
            remap[i] = i - left_n  # probe side, shifted onto probe schema
        else:
            if i not in build_cols:
                build_cols.append(i)
            remap[i] = probe_n + build_cols.index(i)

    # group keys on the build side must be PLAIN build columns AND the
    # probe join key must itself be a group key, so materialize can
    # resolve them (unique build keys => functional dependency)
    probe_key = rkey
    group_has_build = False
    key_in_groups = False
    for g, _name in fused.group_exprs:
        gused: set = set()
        _cols_used(g, gused)
        if any(i < left_n for i in gused):
            if not (isinstance(g, pe.Col) and g.index < left_n):
                return None
            group_has_build = True
        elif (
            isinstance(g, pe.Col)
            and g.index >= left_n
            and isinstance(probe_key, pe.Col)
            and g.index - left_n == probe_key.index
        ):
            key_in_groups = True
    if group_has_build and not key_in_groups:
        return None

    try:
        filters = [_shift_cols(f, remap) for f in fused.filters]
        group_exprs = [
            (_shift_cols(g, remap), name) for g, name in fused.group_exprs
        ]
        aggs = [
            dataclasses.replace(
                a,
                arg=_shift_cols(a.arg, remap) if a.arg is not None else None,
                arg2=(
                    _shift_cols(a.arg2, remap)
                    if a.arg2 is not None
                    else None
                ),
            )
            for a in fused.aggs
        ]
    except ExecutionError:
        return None

    return _FusedStage(
        probe,
        filters,
        group_exprs,
        aggs,
        fused.mode,
        join=DeviceJoinSpec(
            join.left, probe_key, lkey.index, build_cols
        ),
    )


class TpuStageExec(ExecutionPlan):
    """Fused scan→filter→project→aggregate stage on device.

    Replaces the interpreted per-batch operator chain (the reference's hot
    loop, ``shuffle_writer.rs:214-256``) with one jit-compiled XLA kernel
    invoked once per batch; partial states accumulate on device and only
    [num_groups]-sized results return to host.  Runtime group-capacity
    overflow falls back to re-executing the original CPU subtree.

    Partition ``p`` runs on chip ``p % n`` of the mesh the exchanges use
    (:func:`partition_device`): its probe uploads, row mask, program and
    states are committed to that chip, and a folded join's build side,
    sorted once on the host, is placed once on each chip the first time a
    partition there asks.  On one chip nothing is placed: the default
    device, as without a mesh.  Partitions on the build-row route share
    nothing else, and that route is known before any batch, so an exchange
    above may run them side by side (:meth:`partitions_apart`); the other
    routes are chosen from each partition's first batch, and an exchange
    runs their partitions one at a time.
    Always-on ``tpu_stage_time_ns`` is counted per chip as well
    (``tpu_stage_time_ns_dev<chip>``; the chips' counters sum to it).
    """

    def __init__(
        self, original: HashAggregateExec, fused: _FusedStage, config: BallistaConfig
    ):
        super().__init__()
        self.original = original
        self.fused = fused
        self.config = config
        self._schema = original.schema

        # device-join stages compile over a VIRTUAL schema: the probe
        # schema plus one appended field per referenced build column
        probe_schema = fused.source.schema
        if fused.join is not None:
            virtual = list(probe_schema) + [
                fused.join.build.schema.field(i) for i in fused.join.build_cols
            ]
            compile_schema = pa.schema(virtual)
        else:
            compile_schema = probe_schema
        self._probe_ncols = len(probe_schema)

        compiler = K.JaxExprCompiler(compile_schema)
        filter_closure = None
        if fused.filters:
            pred = fused.filters[0]
            for f in fused.filters[1:]:
                pred = pe.Binary(pred, "AND", f)
            filter_closure = compiler._lower_or_leaf(pred)
        x32 = K.precision_mode() == "x32"
        # two passes: count(col) resolves AFTER the other aggregates so it
        # can reuse a column leaf's validity that is shipping anyway,
        # instead of adding a duplicate mask leaf
        pending: list = [None] * len(fused.aggs)
        count_cols: list[tuple[int, pe.Col]] = []
        for idx, a in enumerate(fused.aggs):
            if a.arg is None:
                if a.func not in ("count", "count_star"):
                    raise K.NotLowerable(a.func)
                pending[idx] = (K.KernelAggSpec("count_star", False), None)
                continue
            if a.func == "median":
                # exact device median: the keyed path sorts each group's
                # values (order-pair encoded) and gathers the two middle
                # rows — no host percentile pass.  Needs the keyed
                # buffering, so the stage is FORCED onto that route.
                if fused.mode == PARTIAL:
                    raise K.NotLowerable("median is single-stage")
                if not fused.group_exprs:
                    raise K.NotLowerable("global median stays on CPU")
                if not isinstance(a.arg, pe.Col):
                    raise K.NotLowerable("median over expression")
                at = compile_schema.field(a.arg.index).type
                if not (
                    pa.types.is_floating(at) or pa.types.is_integer(at)
                ):
                    raise K.NotLowerable(f"median over {at}")
                compiler.ord_pair_column(a.arg)  # ships the encoded pair
                pending[idx] = ("median", a.arg.index)
                continue
            if a.func == "count_distinct":
                # per-group distinct count rides the same sorted-argument
                # pass as median: run-starts among each group's sorted
                # valid values, one cumsum (q16's count(distinct
                # ps_suppkey) shape)
                if fused.mode == PARTIAL:
                    raise K.NotLowerable("count_distinct is single-stage")
                if not fused.group_exprs:
                    raise K.NotLowerable("global count_distinct on CPU")
                if not isinstance(a.arg, pe.Col):
                    raise K.NotLowerable("count_distinct over expression")
                at = compile_schema.field(a.arg.index).type
                if not (
                    pa.types.is_floating(at)
                    or pa.types.is_integer(at)
                    or pa.types.is_date(at)
                ):
                    raise K.NotLowerable(f"count_distinct over {at}")
                compiler.ord_pair_column(a.arg)
                pending[idx] = ("cdist", a.arg.index)
                continue
            if a.func == "corr":
                # Pearson r on the keyed path, PER-GROUP centered (the
                # CPU operator centers by the global mean; per-group is
                # strictly better conditioned).  Null/NaN in either
                # argument drops the row pairwise (pandas semantics).
                if fused.mode == PARTIAL:
                    raise K.NotLowerable("corr is single-stage")
                if not fused.group_exprs:
                    raise K.NotLowerable("global corr stays on CPU")
                for e in (a.arg, a.arg2):
                    if not isinstance(e, pe.Col):
                        raise K.NotLowerable("corr over expression")
                    at = compile_schema.field(e.index).type
                    if not (
                        pa.types.is_floating(at) or pa.types.is_integer(at)
                    ):
                        raise K.NotLowerable(f"corr over {at}")
                if x32:
                    compiler.pair_column(a.arg)
                    compiler.pair_column(a.arg2)
                else:
                    compiler._leaf_column(a.arg)
                    compiler._leaf_column(a.arg2)
                pending[idx] = ("corr", a.arg.index, a.arg2.index)
                continue
            if a.func in ("stddev", "stddev_pop", "var", "var_pop"):
                # variance family lowers as compensated Σx + Σx² (+ the
                # sum's own count): x32 ships x as an exact double-float
                # pair and squares it error-free via Dekker two-product,
                # so the host-side cancellation (Σx² − (Σx)²/n) starts
                # from ~48-bit-exact moments; a conditioning guard at
                # materialize falls back to CPU when even that is not
                # enough (κ = Σx²/(n·var) past 1e8)
                if fused.mode == PARTIAL:
                    raise K.NotLowerable("variance family is single-stage")
                if a.arg is None:
                    raise K.NotLowerable(a.func)
                ddof = 0 if a.func.endswith("_pop") else 1
                use_sqrt = a.func.startswith("stddev")
                if x32:
                    if not isinstance(a.arg, pe.Col):
                        raise K.NotLowerable("x32 variance over expression")
                    at = compile_schema.field(a.arg.index).type
                    if not (
                        pa.types.is_floating(at) or pa.types.is_integer(at)
                    ):
                        raise K.NotLowerable(f"variance over {at}")
                    pairc = compiler.pair_column(a.arg)
                    parts = [
                        (K.KernelAggSpec("sum", True, pair=True), pairc),
                        (
                            K.KernelAggSpec("sum", True, pair=True),
                            K.square_pair_closure(pairc),
                        ),
                    ]
                else:
                    c = compiler._lower(a.arg)
                    parts = [
                        (K.KernelAggSpec("sum", True), c),
                        (K.KernelAggSpec("sum", True), K.square_closure(c)),
                    ]
                pending[idx] = ("var", ddof, use_sqrt, parts)
                continue
            if a.func not in ("count", "sum", "avg", "min", "max"):
                # count_distinct, udaf:*, anything unknown: reject at PLAN
                # time so no partition pays a failed device trace
                raise K.NotLowerable(a.func)
            if a.func == "count" and isinstance(a.arg, pe.Col):
                count_cols.append((idx, a.arg))
                continue
            t = (
                compile_schema.field(a.arg.index).type
                if isinstance(a.arg, pe.Col)
                else None
            )
            if a.func in ("min", "max"):
                if t is None:
                    try:
                        t = K._infer_pa_type(a.arg, compile_schema)
                    except Exception:
                        t = None
                int_mm = t is not None and (
                    pa.types.is_integer(t) or pa.types.is_date32(t)
                )
                if x32 and not int_mm and not (
                    t is not None and pa.types.is_float32(t)
                ):
                    # f64 min/max must not come back f32-rounded: a
                    # sub-ulp wrong extremum breaks decorrelated equality
                    # (q2's ps_supplycost = (select min(...))).  Plain f64
                    # COLUMNS ride an order-preserving (hi, lo) i32 pair —
                    # lexicographic integer extremum IS the f64 extremum,
                    # bit-exact; computed f64 expressions (already
                    # f32-rounded on device) stay on CPU
                    if isinstance(a.arg, pe.Col) and t is not None and (
                        pa.types.is_float64(t)
                    ):
                        pending[idx] = (
                            K.KernelAggSpec(a.func, True, ord_pair=True),
                            compiler.ord_pair_column(a.arg),
                        )
                        continue
                    raise K.NotLowerable("x32 min/max over f64 expression")
                pending[idx] = (
                    K.KernelAggSpec(a.func, True, int_minmax=int_mm),
                    compiler._lower(a.arg),
                )
                continue
            if (
                x32
                and a.func == "avg"
                and t is not None
                and (pa.types.is_int64(t) or pa.types.is_uint64(t))
            ):
                # avg(i64) rides as an f32 (hi, lo) pair: each VALUE is
                # 48-bit exact, the float average is good to ~1e-7 — no
                # i32 narrowing cliff.  sum(i64) keeps the CPU fallback
                # past i32 range: its INT output must be bit-exact, and
                # block-level f32 partials round at 2^24-scale totals.
                pending[idx] = (
                    K.KernelAggSpec(a.func, True, pair=True),
                    compiler.pair_column(a.arg),
                )
                continue
            pending[idx] = (
                K.KernelAggSpec(a.func, True), compiler._lower(a.arg)
            )
        for idx, colarg in count_cols:
            # count(col) needs only the validity mask — wide i64 / string
            # columns never ship values (round-2 x32 cliff); reuse an
            # existing leaf's validity when the column ships anyway
            existing = None
            for cand in (f"col_{colarg.index}", f"col_{colarg.index}__pair"):
                if cand in compiler.leaves:
                    existing = f"{cand}__valid"
                    break
            if existing is not None:
                closure = (lambda vn: lambda env: (None, env[vn]))(existing)
            else:
                closure = compiler.validity_only(colarg)
            pending[idx] = (K.KernelAggSpec("count", True), closure)
        # flatten per-OUTPUT entries into kernel specs + an emission plan
        # (the variance family expands one output into two kernel sums)
        specs: list[K.KernelAggSpec] = []
        arg_closures: list[Optional[K.JaxClosure]] = []
        emit: list[tuple] = []
        self._median_cols: list[int] = []
        self._corr_cols: list[int] = []
        self._corr_pairs: list[tuple] = []
        for entry in pending:
            if isinstance(entry, tuple) and entry[0] == "var":
                _, ddof, use_sqrt, parts = entry
                emit.append(
                    ("var", len(specs), len(specs) + 1, ddof, use_sqrt)
                )
                for s, c in parts:
                    specs.append(s)
                    arg_closures.append(c)
            elif isinstance(entry, tuple) and entry[0] in ("median", "cdist"):
                ci = entry[1]
                if ci in self._median_cols:
                    slot = self._median_cols.index(ci)
                else:
                    slot = len(self._median_cols)
                    self._median_cols.append(ci)
                emit.append((entry[0], slot))
            elif isinstance(entry, tuple) and entry[0] == "corr":
                slots = []
                for ci in (entry[1], entry[2]):
                    if ci in self._corr_cols:
                        slots.append(self._corr_cols.index(ci))
                    else:
                        slots.append(len(self._corr_cols))
                        self._corr_cols.append(ci)
                # r is symmetric: canonicalize so corr(x,y) and
                # corr(y,x) share one device pass
                pair = tuple(sorted(slots))
                if pair in self._corr_pairs:
                    pslot = self._corr_pairs.index(pair)
                else:
                    pslot = len(self._corr_pairs)
                    self._corr_pairs.append(pair)
                emit.append(("corr", pslot))
            else:
                s, c = entry
                emit.append(("plain", len(specs)))
                specs.append(s)
                arg_closures.append(c)
        self._emit = emit
        # median/count_distinct/corr require the keyed path's buffers
        self._needs_keyed = bool(self._median_cols) or bool(
            self._corr_pairs
        )
        self.leaves = compiler.leaves
        self.specs = specs
        self.capacity = config.tpu_segment_capacity if fused.group_exprs else 1
        self.max_capacity = (
            config.tpu_max_capacity if fused.group_exprs else 1
        )
        self.keyed_buffer_bytes = config.tpu_keyed_buffer_mb << 20
        self._filter_closure = filter_closure
        self._arg_closures = arg_closures

        # device-join plumbing: leaves over virtual (build-side) columns
        # are gathered ON DEVICE by the join wrapper, never read from the
        # probe batch; pair/validity-only kinds and host-evaluated exprs
        # cannot reference the build side
        self._join_slots: dict[str, int] = {}
        if fused.join is not None:
            for name, spec in self.leaves.items():
                if spec.kind == "cpu_expr":
                    used: set = set()
                    _cols_used(spec.cpu_expr, used)
                    if any(i >= self._probe_ncols for i in used):
                        raise K.NotLowerable("host expr over build side")
                    continue
                if spec.col_index >= self._probe_ncols:
                    if spec.kind != "column":
                        raise K.NotLowerable(f"join leaf kind {spec.kind}")
                    spec.kind = "join_col"
                    j = spec.col_index - self._probe_ncols
                    self._join_slots[name] = j
                    self._join_slots[f"{name}__valid"] = j
        # only the build columns the KERNEL reads ship to the device
        # (group-only build columns resolve on host at materialize)
        self._device_build_cols: list[int] = []
        if fused.join is not None and self._join_slots:
            device_js = sorted(set(self._join_slots.values()))
            dense = {j: k for k, j in enumerate(device_js)}
            self._join_slots = {
                n: dense[j] for n, j in self._join_slots.items()
            }
            self._device_build_cols = [
                fused.join.build_cols[j] for j in device_js
            ]

        self._leaf_names = list(self.leaves.keys())
        self._flat_names = K.flat_arg_names(self.leaves)
        self._mode = K.precision_mode()
        join_sig = ()
        if fused.join is not None:
            join_sig = (
                str(fused.join.probe_key),
                fused.join.build_key_index,
                tuple(fused.join.build_cols),
                str(fused.join.build.schema),
            )
        sig = (
            tuple(str(f) for f in fused.filters),
            (
                tuple(
                    (s.func, s.pair, s.int_minmax, s.ord_pair)
                    for s in specs
                ),
                tuple(str(a.arg) for a in fused.aggs),
                tuple(e[0] for e in emit),
            ),
            self.capacity,
            tuple(self._flat_names),
            str(fused.source.schema),
            self._mode,
            join_sig,
        )
        self._sig = sig

        # group plan: which GROUP BY positions encode on host vs resolve
        # from the build table at materialize (functionally dependent on
        # the probe join key — unique build keys)
        self._group_plan: list[tuple[str, int]] = []
        slot = 0
        for g, _n in fused.group_exprs:
            if (
                fused.join is not None
                and isinstance(g, pe.Col)
                and g.index >= self._probe_ncols
            ):
                self._group_plan.append(("build", g.index - self._probe_ncols))
            else:
                self._group_plan.append(("enc", slot))
                slot += 1
        self._n_encoded_groups = slot
        # group exprs at host-ENCODED positions, in slot order (the
        # device key-encode path evaluates these raw and derives codes
        # on device)
        self._enc_group_exprs = [
            g
            for (g, _n), (kind, _s) in zip(
                fused.group_exprs, self._group_plan
            )
            if kind == "enc"
        ]
        self._jk_slot = self._jk_pos = None
        if fused.join is not None:
            pk = fused.join.probe_key
            for pos, (g, _n) in enumerate(fused.group_exprs):
                if (
                    self._group_plan[pos][0] == "enc"
                    and isinstance(g, pe.Col)
                    and isinstance(pk, pe.Col)
                    and g.index == pk.index
                ):
                    self._jk_slot = self._group_plan[pos][1]
                    self._jk_pos = pos
                    break
            if any(k == "build" for k, _ in self._group_plan) and (
                self._jk_slot is None
            ):
                raise K.NotLowerable("build group keys without probe key")
        # the folded join's groups are its build rows: the one host-encoded
        # group key is the probe join key and the build keys are unique
        # (``_build_join_state`` refuses duplicates), so a group is exactly
        # one matched build row; the other keys resolve from the build side
        self._groups_are_build_rows = (
            self._n_encoded_groups == 1
            and self._jk_slot is not None
            and not self._needs_keyed
        )
        self._build_rows_counted = False
        # the folded join's build side, lazily prepared per instance: sorted
        # and padded on the host once, then its arrays on each chip asked for
        self._build_host = None
        self._build_states: dict = {}  # device (None: the default) -> state
        self._build_lock = threading.Lock()
        # (exprs, n_out) installed by a downstream ShuffleWriterExec so
        # the hash-partition ids ride the device instead of the host
        self._shuffle_hint = None
        # whole-stage fusion (ballista.tpu.whole_stage_fusion): set per
        # execute from the ops/fusion.py plan — _fuse_pid asks the fused
        # runner to derive the shuffle pid column inside its trace, and
        # _fused_pids carries the result to _materialize
        self._fuse_pid = False
        self._fused_pids = None

        # raw kernel kept for mesh gang execution: shard_map needs the
        # untraced function to wrap with the cross-chip reduction
        self._raw_kernel, self._jit_kernel = self._kernel_for(self.capacity)

    def _timed_jit(self, fn):
        """Wrap a shared jitted kernel with THIS stage's compile/execute
        attribution: a call that grows the jit's compiled-signature cache
        paid trace + XLA compilation (jit compiles synchronously inside
        the call; only execution is async), everything else is dispatch.
        The call runs under the program's lock (``_jit_lock``), so a call
        that waited for another's compile sees the cache grown already and
        counts as the dispatch it is.  Backs the /api/jobs/{id}/profile
        compile-vs-execute split."""
        metrics = self.metrics
        cache_size = getattr(fn, "_cache_size", None)
        lock = _jit_lock(fn)

        def call(*args):
            with lock:
                before = cache_size() if cache_size is not None else -1
                t0 = time.perf_counter_ns()
                out = fn(*args)
                dt = time.perf_counter_ns() - t0
                compiled = before >= 0 and cache_size() > before
            if compiled:
                metrics.add("tpu_compile_ns", dt)
                metrics.add("kernel_compiles", 1)
            else:
                metrics.add("tpu_execute_ns", dt)
            return out

        return call

    def _note_kernel_cache(self, hit: bool) -> None:
        """Process-wide compiled-kernel cache accounting (plans rebuild
        per query; a miss here means a fresh trace + XLA compile)."""
        self.metrics.add(
            "compile_cache_hits" if hit else "compile_cache_misses", 1
        )

    def _kernel_for(
        self, capacity: int, dense: bool = False, build_rows: bool = False
    ):
        """(raw, jitted) fused kernel at the given segment capacity.

        Group cardinality is data-dependent; capacities grow in 4x buckets
        (execute-time) so the number of distinct XLA compilations stays
        logarithmic while the segment table tracks the data.  ``dense``
        selects the direct-probe join wrapper (decided per execution from
        the prepared build side's key span); ``build_rows`` the program of
        the build-row route (``join_aggregate``), whose capacity is the
        build side's bucket.
        """
        key = (
            self._sig[:2] + (capacity,) + self._sig[3:]
            + (("dense",) if dense else ())
            + (("build_rows",) if build_rows else ())
            + K.algo_cache_token()
        )
        with _KERNEL_CACHE_LOCK:
            cached = _KERNEL_CACHE.get(key)
            self._note_kernel_cache(cached is not None)
            if cached is None:
                with self.metrics.timer("tpu_compile_ns"):
                    inner = K.make_partial_agg_kernel(
                        self._filter_closure,
                        self._arg_closures,
                        self.specs,
                        capacity,
                        self._flat_names,
                        # variance moments need the per-element-compensated scan
                        force_sort=any(e[0] == "var" for e in self._emit),
                    )
                    if self.fused.join is not None:
                        kernel = K.make_join_kernel(
                            inner,
                            self._flat_names,
                            self._join_slots,
                            len(self._device_build_cols),
                            dense=dense,
                            build_rows=capacity if build_rows else None,
                        )
                    else:
                        kernel = inner
                    cached = (kernel, jax.jit(kernel))
                _KERNEL_CACHE[key] = cached
        return cached[0], self._timed_jit(cached[1])

    @property
    def schema(self) -> pa.Schema:
        return self._schema

    def output_partitioning(self) -> Partitioning:
        return self.fused.source.output_partitioning()

    def children(self) -> list[ExecutionPlan]:
        return [self.fused.source]

    def with_new_children(self, children):
        new_original = self.original.with_new_children(
            [_replace_leaf(self.original.input, self.fused.source, children[0])]
        )
        # same fold-then-retry ladder as maybe_accelerate: a shape that
        # lowers only with the join on CPU must not lose acceleration here
        for fold in (True, False):
            fused = _flatten(new_original, fold_join=fold)
            if fused is None:
                return new_original
            try:
                return TpuStageExec(new_original, fused, self.config)
            except K.NotLowerable:
                if fused.join is None:
                    return new_original
        return new_original

    def __str__(self) -> str:
        return (
            f"TpuStageExec: mode={self.fused.mode}, "
            f"gby={[n for _, n in self.fused.group_exprs]}, "
            f"aggr={[a.name for a in self.fused.aggs]}, "
            f"filters={len(self.fused.filters)}, capacity={self.capacity}"
        )

    def install_shuffle_hint(self, exprs, n_out: int) -> None:
        """Downstream ShuffleWriterExec announces its hash partitioning
        (exprs over THIS stage's output schema, n_out partitions):
        ``_materialize`` then computes the partition-id column through
        the jitted device hash kernel (``K.device_partition_ids``) and
        appends it as ``SHUFFLE_PID_COLUMN``, so the writer's split skips
        the host hash.  Assignments match the host partitioner
        bit-for-bit by construction; keys the kernel can't hash (strings,
        computed expressions) simply leave the hint unused."""
        self._shuffle_hint = (list(exprs), int(n_out))

    def _fused_pid_spec(self):
        """``(slots, n_out)`` when the shuffle pid column can be derived
        INSIDE the fused dispatch, else None.

        Eligible exactly when every hint key is a host-encoded group
        column with a device-hashable type: the group table then holds
        every kept group's key codes at dispatch time, so decoding them
        feeds the same ``partition_id_hash`` the post-materialize kernel
        would run — over identical values, hence bit-identical pids —
        without a second dispatch.  ``slots`` is ``[(enc_slot, out_pos),
        ...]`` in hint-key order (the hash combine is order-sensitive).
        """
        hint = self._shuffle_hint
        if hint is None or not self.fused.group_exprs:
            return None
        exprs, n_out = hint
        if not exprs or n_out <= 0 or n_out > K.PID_MAX_PARTITIONS:
            return None
        slots = []
        for e in exprs:
            if not isinstance(e, pe.Col) or not (
                0 <= e.index < len(self._group_plan)
            ):
                return None
            kind, slot = self._group_plan[e.index]
            if kind != "enc":
                return None
            t = self._schema.field(e.index).type
            if not (
                pa.types.is_integer(t)
                or pa.types.is_floating(t)
                or pa.types.is_boolean(t)
                or pa.types.is_date(t)
                or pa.types.is_timestamp(t)
            ):
                return None
            slots.append((slot, e.index))
        return slots, n_out

    # ------------------------------------------------------------ execute
    def execute(self, partition: int, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        # _execute_device yields the stage's output, or returns the plan
        # that answers in its place where the route or a failure left the
        # device.  That plan runs OUTSIDE this try, so that a real CPU
        # error propagates and is not mistaken for a device failure.
        try:
            instead = yield from self._execute_device(partition, ctx)
        except _JoinIneligible:
            # non-unique or unrepresentable build keys: run the join on
            # CPU and keep ONLY the aggregate on device (round-2 shape)
            self.metrics.add("join_fallback", 1)
            instead = self._nojoin_stage()
        except _CapacityExceeded:
            instead = self._after_overflow()
        except ExecutionError:
            # a column type slipped past plan-time lowering checks
            # (Cancelled is a BallistaError sibling and still propagates)
            self.metrics.add("tpu_fallback", 1)
            instead = self.original
        except _JaxRuntimeError as e:
            # the device/compiler failed mid-stage (chip, round 5, h2o:
            # a SIGKILLed tpu_compile_helper surfaced as JaxRuntimeError
            # and killed the query instead of degrading) — re-run this
            # partition on the CPU operator path.  Only jax's runtime
            # error is caught: a blanket RuntimeError would silently
            # convert genuine bugs into fallbacks.
            note_device_error(self.metrics, str(self), e)
            instead = self.original
        if instead is not None:
            yield from instead.execute(partition, ctx)

    def _cpu_replay(self, batches: list, tail=None) -> ExecutionPlan:
        """The CPU operator plan over the batches a device run already
        pulled from the source, then what is still in the source
        (``tail``): whatever left the device, nothing is scanned twice."""
        return self.original.with_new_children(
            [
                _replace_leaf(
                    self.original.input,
                    self.fused.source,
                    _BufferedExec(self.fused.source, batches, tail),
                )
            ]
        )

    def _after_overflow(self) -> ExecutionPlan:
        """The plan that answers when the gid table cannot hold the
        stage, as the first batch foretold or as the stream showed."""
        self.metrics.add("tpu_fallback", 1)
        if self.fused.join is None:
            return self.original
        # a join-fused stage's gid table holds every distinct PROBE key,
        # pre-filter (a stage whose groups are its build rows never gets
        # here, unless its build side outgrows the ceiling).  The round-2
        # shape (join on CPU, aggregate on device over POST-join rows)
        # keys the gid table on surviving groups instead; its own
        # execute() still falls to full CPU if even that overflows.
        self.metrics.add("join_fallback", 1)
        return self._nojoin_stage()

    def _cache_key(self, ctx: TaskContext):
        """(provider, signature) when the stage source is a cacheable scan."""
        if not ctx.config.tpu_cache_columns:
            return None
        from ..exec.operators import ScanExec

        node = self.fused.source
        while isinstance(node, RenameSchemaExec):
            node = node.children()[0]
        if not isinstance(node, ScanExec):
            return None
        # leaf col_index values are scan-relative, so the signature must pin
        # the scan's actual column identity (projection / schema names) or two
        # queries over different columns of the same provider would collide
        source_cols = ",".join(self.fused.source.schema.names)
        sig = "|".join(
            [
                f"{s.kind}:{s.col_index}:{s.cpu_expr}" for s in self.leaves.values()
            ]
            + [str(g) for g, _ in self.fused.group_exprs]
            + [f"proj={node.projection}", f"cols={source_cols}"]
            + [str(ctx.batch_size), f"cap={self.capacity}", self._mode]
        )
        return node.provider, sig

    def _route_facts(self, build) -> dict:
        """What :func:`choose_route` is told of this stage before any batch,
        with the folded join's prepared ``build`` (None: no join)."""
        return dict(
            highcard_mode=self.config.tpu_highcard_mode,
            device_encode=self.config.tpu_device_encode,
            grouped=bool(self.fused.group_exprs),
            needs_keyed=self._needs_keyed,
            folded_join=self.fused.join is not None,
            max_capacity=self.max_capacity,
            build_rows=(
                build is not None
                and self._groups_are_build_rows
                and K.bucket_rows(len(build[4])) <= self.max_capacity
            ),
        )

    def partitions_apart(self, ctx: TaskContext) -> bool:
        """May this stage's partitions run side by side, each on its own
        chip?  Where :func:`choose_route` says ``Route.BUILD_ROWS`` before
        any batch: that route's states, program and uploads are the
        partition's own, and the build side is prepared under the lock.
        Asking prepares the build side (partition 0's chip first, as its
        run would).  A build the device cannot take says no: each
        partition's own run then takes the join's CPU route, in turn."""
        if self.fused.join is None or not self._groups_are_build_rows:
            return False
        chip, device = partition_device(0, ctx)
        try:
            build = self._prepare_build(ctx, device, chip)
        except (_JoinIneligible, _JaxRuntimeError):
            return False
        if build[0] == "empty":
            return False
        return choose_route(**self._route_facts(build)) is Route.BUILD_ROWS

    def _execute_device(
        self, partition: int, ctx: TaskContext
    ) -> Iterator[pa.RecordBatch]:
        """Yields the stage's output, or returns the plan that answers in
        its place (None where the output was yielded): prepare the build
        side, peek, take the first batch, ask :func:`choose_route`, and
        call the runner it names."""
        from . import device_cache

        fused = self.fused
        chip, device = partition_device(partition, ctx)
        build = None
        if fused.join is not None:
            build = self._prepare_build(ctx, device, chip)
            if build[0] == "empty":
                # inner join against an empty build side: no rows at all
                yield from self._materialize(
                    None, [], None, 0, ctx, partition
                )
                return
        # the device column cache keys on scan inputs; join stages add
        # build-side state and median stages must route keyed, so both
        # skip it (probe sources are usually joins/filters anyway)
        ck = (
            self._cache_key(ctx)
            if fused.join is None and not self._needs_keyed
            else None
        )
        # whole-stage fusion plan (ballista.tpu.whole_stage_fusion;
        # default off keeps today's dispatch sequence byte-identical):
        # when every compute op lands in segment 0, batches are retained
        # and the stage executes as ONE fused dispatch even without a
        # cache key — with the shuffle pid column derived inside the
        # same trace when the pid op fused too
        self._fuse_pid = False
        fusion_retain = False
        if (
            fused.join is None
            and not self._needs_keyed
            and self.config.tpu_whole_stage_fusion
        ):
            from .fusion import plan_segments, stage_ops

            fplan = plan_segments(stage_ops(self), _FUSION_MAX_OPS)
            self.metrics.add("fused_segments", len(fplan.segments))
            self.metrics.add(
                "fused_ops_per_dispatch", fplan.max_segment_ops
            )
            fusion_retain = fplan.compute_fused()
            self._fuse_pid = fplan.pid_fused()
        if ck is not None:
            cached = device_cache.get(ck[0], partition, ck[1])
            if cached is not None:
                entries, key_encoders, group_table, n_rows_in, cap = cached
                with _StageTimer(self.metrics, chip):
                    with self.metrics.timer("device_time_ns"):
                        host_states = self._run_fused(
                            entries, cap,
                            group_table if fused.group_exprs else None,
                            key_encoders,
                        )
                self.metrics.add("cache_hits", 1)
                yield from self._materialize(
                    host_states, key_encoders, group_table, n_rows_in, ctx,
                    partition,
                )
                return

        src = fused.source.execute(partition, ctx)
        coalesce = _shuffle_coalesce_rows(self.config)
        if coalesce > 0 and _reads_shuffle(fused.source):
            # shuffle readers yield one fragment per map task; combine
            # them to the target batch size on host so each device
            # dispatch moves a full batch (fetch + coalesce then overlap
            # device compute through the _ReadAhead pump below)
            from .bridge import coalesce_batches

            src = coalesce_batches(src, coalesce, self.metrics)
        facts = self._route_facts(build)
        min_rows = self.config.tpu_min_rows
        buffered: list[pa.RecordBatch] = []
        small = False
        if min_rows > 0:
            # peek: kernel-launch/compile latency dominates tiny inputs, so
            # a source that ends under the threshold runs the CPU operator
            # path over the batches the peek pulled
            total = 0
            for b in src:
                buffered.append(b)
                total += b.num_rows
                if total >= min_rows:
                    break
            else:
                small = True
            src = itertools.chain(buffered, src)
        route = choose_route(**facts, small_input=small)
        if route is Route.CPU_SMALL:
            self.metrics.add("cpu_fallback", 1)
            return self._cpu_replay(buffered)

        depth = self.config.tpu_readahead
        ra: Optional[_ReadAhead] = None
        if depth > 0:
            src = ra = _ReadAhead(src, depth)

        from .bridge import make_key_encoder
        from .groups import GroupTable

        # encoders exist only for host-ENCODED group positions (build-side
        # group keys resolve from the build table at materialize)
        key_encoders = [
            make_key_encoder(self._schema.field(pos).type)
            for pos, (kind, _s) in enumerate(self._group_plan)
            if kind == "enc"
        ]
        group_table = GroupTable(max(self._n_encoded_groups, 1))
        dense_join = build is not None and build[0] == "dense"
        # join.probe: the batches of this partition through the folded
        # join and the aggregate (one kernel), to the fetch of the states
        probe_span = (
            trace.span("join.probe", partition=partition, device=chip)
            if fused.join is not None else trace.NOOP
        )
        codes = seg = None
        with _closing_on_error(ra), _StageTimer(
            self.metrics, chip
        ), probe_span:
            first = next((b for b in src if b.num_rows), None)
            if route is None:  # grouped: the first batch says
                route = Route.GID
                if first is not None:
                    route, key_encoders, codes, seg = self._route_first_batch(
                        facts, first, key_encoders, group_table
                    )
            if route is Route.BUILD_ROWS:
                group_table = _BuildRows(build[4])
                host_states, n_rows_in = self._run_build_rows(
                    first, src, build, probe_span, device
                )
            elif route is Route.GID:
                probe_span.set_attr("gid", "table")
                _, kernel = self._kernel_for(self.capacity, dense=dense_join)
                host_states, n_rows_in = self._run_gid(
                    first, seg, src, kernel, build, ck, fusion_retain,
                    key_encoders, group_table, probe_span, partition, device,
                )
        if route is Route.KEYED:
            return (
                yield from self._execute_keyed(
                    first, codes, src, ra, key_encoders, ctx, partition,
                    build, chip,
                )
            )
        if route is Route.CPU_HASH:
            # groups ~ rows: hand the stage to the C++ hash aggregate,
            # replaying the consumed batch + chaining the live source
            self.metrics.add("highcard_fallback", 1)
            return self._cpu_replay([first], src)
        if route is Route.NOJOIN:
            if ra is not None:
                ra.close()  # the join-on-CPU shape scans for itself
            return self._after_overflow()
        yield from self._materialize(
            host_states, key_encoders, group_table, n_rows_in, ctx, partition
        )

    def _route_first_batch(
        self, facts: dict, batch, key_encoders: list, group_table
    ) -> tuple:
        """``(route, key encoders, key codes, segment ids)`` of a grouped
        stage's first batch.  The last three are what answering
        :func:`choose_route`'s questions worked out on the way (None where
        it did not ask): the runner is handed them, it does not compute
        them again."""
        encoders, codes, seg = key_encoders, None, None

        def fast_encoders() -> bool:
            nonlocal encoders
            fast = self._keyed_fast_encoders(batch)
            if fast is not None:
                encoders = fast
            return fast is not None

        def encoded() -> list:
            nonlocal codes
            if codes is None:
                with self.metrics.timer("key_encode_time_ns"):
                    codes = self._encode_codes(batch, key_encoders)
            return codes

        def keys_fit() -> bool:
            # keys the device can't take raw (i32 overflow in x32) rule
            # the keyed path out; host-assigned gids are always dense
            # i32, so the gid table stays available
            return self._mode != "x32" or all(
                len(c) == 0
                or (c.min() >= -(1 << 31) and c.max() < (1 << 31))
                for c in encoded()
            )

        def groups() -> Optional[int]:
            nonlocal seg
            try:
                with self.metrics.timer("key_encode_time_ns"):
                    seg = self._assign_gids(encoded(), group_table)
            except _CapacityExceeded:
                # ONE batch outran the gid table / key radix
                return None
            return group_table.n_groups

        route = choose_route(
            **facts,
            first=FirstBatch(batch.num_rows, fast_encoders, keys_fit, groups),
        )
        return route, encoders, codes, seg

    def _run_gid(
        self, first, seg, src, kernel, build, ck, fusion_retain: bool,
        key_encoders: list, group_table, probe_span, partition: int,
        device=None,
    ) -> tuple:
        """The gid-table route: ``first`` (None: the source held no row)
        with its segment ids, then every batch left in ``src``, through
        the stage kernel at the initial capacity ``kernel`` was built
        for, on ``device`` (None: the default).  Returns ``(host states,
        rows in)``."""
        import jax
        import jax.numpy as jnp

        from . import device_cache

        fused = self.fused
        grouped = bool(fused.group_exprs)
        dense_join = build is not None and build[0] == "dense"
        entries = []
        acc = None
        cap = self.capacity
        if grouped and first is not None:
            # first batch: shrink the segment table to the OBSERVED
            # cardinality (2x headroom) — matmul-path FLOPs scale with
            # capacity, so a 6-group q1 must not pay for the 1024-slot
            # default table
            tight = 64
            while tight < 2 * max(1, group_table.n_groups):
                tight *= 4
            if tight < cap:
                cap = min(tight, self.max_capacity)
                _, kernel = self._kernel_for(cap, dense=dense_join)
        n_rows_in = pad_rows = batches = uploads = 0
        stream = itertools.chain(
            [] if first is None else [first], (b for b in src if b.num_rows)
        )
        for batch in stream:
            n = batch.num_rows
            n_rows_in += n
            n_pad = K.bucket_rows(n)
            pad_rows += n_pad - n

            if grouped:
                if batch is not first:
                    with self.metrics.timer("key_encode_time_ns"):
                        seg = self._encode_groups(
                            batch, key_encoders, group_table
                        )
                # adaptive capacity: grow the segment table in 4x
                # buckets when the data's cardinality outruns it,
                # padding accumulated states (VERDICT round-1: fixed
                # 4096 caps fell back to CPU on q3/h2o shapes)
                if group_table.n_groups > cap:
                    while cap < group_table.n_groups:
                        cap *= 4
                    cap = min(cap, self.max_capacity)
                    acc = K.pad_states(self.specs, acc, cap, self._mode)
                    _, kernel = self._kernel_for(cap, dense=dense_join)
                    self.metrics.add("capacity_growths", 1)
                seg = K._pad(seg, n_pad)
            else:
                seg = None  # all rows → group 0, synthesized on device

            with self.metrics.timer("bridge_time_ns"):
                args, trivial_idx = self._kernel_args(
                    batch, n, n_pad, build
                )
            # host arrays this batch hands to the device, each its own
            # transfer (the row mask and the build side are there)
            batches += 1
            uploads += (seg is not None) + sum(
                isinstance(a, np.ndarray) and i not in trivial_idx
                for i, a in enumerate(args)
            )
            with self.metrics.timer("device_time_ns"):
                if ck is None and fusion_retain:
                    # fusion-only retention (whole-stage fusion on a
                    # non-cache-eligible stage): the entries are
                    # consumed ONCE by the fused dispatch right
                    # after this loop, so everything stays on host —
                    # no per-batch eager device op at all; the one
                    # jitted call transfers its operands in bulk
                    tail = np.arange(n_pad, dtype=np.int32) < n
                    args = [
                        tail if i in trivial_idx else a
                        for i, a in enumerate(args)
                    ]
                    seg_h = (
                        np.zeros(n_pad, dtype=np.int32)
                        if seg is None
                        else seg
                    )
                    entries.append((seg_h, tail, args))
                    continue
                # device-built row tail mask, shared by the global
                # valid slot and every all-true leaf companion: two
                # eager ops replace n_pad*(1+n_trivial) host→HBM
                # bytes
                tail = jnp.arange(n_pad, dtype=jnp.int32, device=device) < n
                args = [
                    tail if i in trivial_idx else a
                    for i, a in enumerate(args)
                ]
                seg_d = (
                    jnp.zeros(n_pad, dtype=jnp.int32, device=device)
                    if seg is None
                    else jax.device_put(seg, device)
                )
                if ck is not None:
                    # retained for the device cache (and the fused
                    # single-dispatch run after the loop): each arg
                    # pins on device because the entries outlive
                    # this query
                    args = [
                        a if a is tail else jax.device_put(a, device)
                        for a in args
                    ]
                    entries.append((seg_d, tail, args))
                else:
                    args = [
                        jax.device_put(a, device)
                        if isinstance(a, np.ndarray) else a
                        for a in args
                    ]
                    out = kernel(seg_d, tail, *args)
                    acc = K.combine_states(
                        self.specs, acc, out, self._mode
                    )

        # Cache-eligible stages dispatch ONCE per query: a single
        # jitted call runs every entry's kernel, combines, and packs.
        # The packed fetch is the device sync, so it lives INSIDE
        # the device timer: device_time_ns covers queue + compute +
        # result fetch
        with self.metrics.timer("device_time_ns"):
            if (ck is not None or fusion_retain) and entries:
                host_states = self._run_fused(
                    entries, cap,
                    group_table if grouped else None,
                    key_encoders,
                    # below the measured amortization floor a fused
                    # dispatch costs more than it saves: stream the
                    # retained entries per-batch instead (the cache
                    # path keeps its unconditional fused call)
                    stream=ck is None and n_rows_in < _FUSION_MIN_ROWS,
                )
            else:
                host_states = self._fetch_states(
                    acc, group_table.n_groups if grouped else None
                )
        probe_span.set_attr("rows", n_rows_in)
        probe_span.set_attr("padded_rows", pad_rows)
        self.metrics.add("stage_pad_rows", pad_rows)
        self.metrics.add("stage_batches", batches)
        self.metrics.add("stage_uploads", uploads)
        if fused.join is not None:
            self.metrics.add("join_probe_rows", n_rows_in)
        if ck is not None and entries:
            device_cache.put(
                ck[0], partition, ck[1],
                (entries, key_encoders, group_table, n_rows_in, cap),
            )
        return host_states, n_rows_in

    def _run_build_rows(
        self, first, src, build, probe_span, device=None
    ) -> tuple:
        """The build-row route: ``first`` (None: the source held no row)
        and every batch left in ``src`` through ``join_aggregate``, whose
        segment id is the build row a probe row matched, worked out on the
        device: no gid table, no host key encode, no segment ids to upload.
        Its states are the build side's bucket of slots.  Everything runs on
        ``device`` (None: the default), where ``build`` was placed; nothing
        but the counters is shared with another partition's run.  Returns
        ``(host states, rows in)``; group ``i`` of the states is build row
        ``i``."""
        import jax.numpy as jnp

        m = len(build[4])
        m_pad = K.bucket_rows(m)
        _, kernel = self._kernel_for(
            m_pad, dense=build[0] == "dense", build_rows=True
        )
        with self._build_lock:
            if not self._build_rows_counted:
                self._build_rows_counted = True
                self.metrics.add("join_build_groups", 1)
        acc = matched = None
        n_rows_in = pad_rows = batches = uploads = probe_bytes = 0
        stream = itertools.chain(
            [] if first is None else [first], (b for b in src if b.num_rows)
        )
        for batch in stream:
            n = batch.num_rows
            n_pad = K.bucket_rows(n)
            n_rows_in += n
            pad_rows += n_pad - n
            with self.metrics.timer("bridge_time_ns"):
                args, trivial_idx = self._kernel_args(batch, n, n_pad, build)
            host = [
                (i, a) for i, a in enumerate(args) if isinstance(a, np.ndarray)
            ]
            batches += 1
            uploads += sum(i not in trivial_idx for i, _ in host)
            # what the program reads a row: the row mask and each probe
            # array as handed (an all-true validity reads the mask's byte)
            probe_bytes += n_pad * (1 + sum(a.itemsize for _, a in host))
            with self.metrics.timer("device_time_ns"):
                tail = jnp.arange(n_pad, dtype=jnp.int32, device=device) < n
                args = [
                    tail if i in trivial_idx
                    else jax.device_put(a, device)
                    if isinstance(a, np.ndarray) else a
                    for i, a in enumerate(args)
                ]
                *out, hit = kernel(tail, *args)
                acc = K.combine_states(self.specs, acc, tuple(out), self._mode)
                matched = hit if matched is None else matched + hit
        with self.metrics.timer("device_time_ns"):
            host_states = self._fetch_states(acc, m)
            matched = 0 if matched is None else int(matched)
        groups = 0
        if host_states is not None:
            groups = int(np.count_nonzero(host_states[-1][:m]))
            slots = len(host_states[-1])
            self.metrics.add("join_state_slots", slots)
            self.metrics.add(
                "join_state_bytes", slots * sum(a.dtype.itemsize for a in acc)
            )
        for k, v in (
            ("rows", n_rows_in), ("padded_rows", pad_rows),
            ("gid", "build_row"), ("matched", matched), ("groups", groups),
        ):
            probe_span.set_attr(k, v)
        for k, v in (
            ("stage_pad_rows", pad_rows), ("stage_batches", batches),
            ("stage_uploads", uploads), ("join_probe_rows", n_rows_in),
            ("join_probe_bytes", probe_bytes),
            ("join_matched_rows", matched), ("join_groups", groups),
        ):
            self.metrics.add(k, v)
        return host_states, n_rows_in

    def _execute_keyed(
        self, first, codes, src, ra, key_encoders: list,
        ctx: TaskContext, partition: int, build, chip: int,
    ) -> Iterator[pa.RecordBatch]:
        """The keyed route, with :meth:`_execute_device`'s contract:
        device-keyed aggregation (group ids assigned by the device sort,
        no host hash encode) of ``first`` (``codes``: its key codes where
        the route encoded them) and what is left in ``src``, against the
        folded join's ``build`` (None: no join) on its chip.  Late key
        overflow, cardinality past the segment ceiling, or device OOM (the
        keyed path buffers the stage input in HBM) leave to the CPU
        operators."""
        self.metrics.add("keyed_path", 1)
        tail = _TrackingIter(src)
        try:
            host_states, groups, n_rows_in, aux = self._run_keyed(
                [(first, codes)], tail, key_encoders, build, chip
            )
            out_batches = list(
                self._materialize(
                    host_states, key_encoders, groups, n_rows_in,
                    ctx, partition, aux=aux,
                )
            )
        except (_CapacityExceeded, ExecutionError, _JaxRuntimeError) as e:
            if isinstance(e, _JaxRuntimeError):
                note_device_error(self.metrics, f"{self} (keyed)", e)
            else:
                self.metrics.add("tpu_fallback", 1)
            if not tail.consumed:
                # failed before touching the live source: replay the
                # first batch + chain the tail (no re-scan)
                return self._cpu_replay([first], tail)
            if ra is not None:
                ra.close()
            return self.original
        yield from out_batches

    def _kernel_args(
        self, batch, n: int, n_pad: int, build
    ) -> tuple[list, set]:
        """(args, trivial_idx) — host-side leaf env + join operands for
        one batch (the bridge work shared by the gid-table and keyed
        execution paths).  ``trivial_idx`` holds positions in ``args``
        whose array is exactly the row tail mask (all-true validity):
        the device sections substitute one shared device-built iota mask
        for those instead of shipping the bytes."""
        trivial: set = set()
        env = K.build_env(batch, self.leaves, n_pad, trivial_valid=trivial)
        names = [
            nm for nm in self._flat_names if nm not in self._join_slots
        ]
        args = [env[nm] for nm in names]
        trivial_idx = {i for i, nm in enumerate(names) if nm in trivial}
        if self.fused.join is not None:
            pk = _eval_arr(self.fused.join.probe_key, batch)
            from .bridge import arrow_to_numpy

            pkv, pk_valid = arrow_to_numpy(pk)
            pkv = pkv.astype(np.int64)
            if pk_valid is None:
                pk_valid = np.ones(n, dtype=bool)
            if self._mode == "x32":
                # probe keys outside i32 cannot match the
                # (range-checked) build keys: mask, don't fail
                in_range = (pkv >= -(1 << 31)) & (pkv < 1 << 31)
                if not in_range.all():
                    pk_valid = pk_valid & in_range
                    pkv = np.where(in_range, pkv, 0)
                pkv = pkv.astype(np.int32)
            args += [
                K._pad(pkv, n_pad),
                K._pad(pk_valid, n_pad),
                build[1],  # bkeys (device) / dense slot table
            ]
            if build[0] == "dense":
                args.append(build[6])  # kmin (probe offset scalar)
            args += build[2] + build[3]  # bvals, bvalids
        return args, trivial_idx

    # ---------------------------------------------------- keyed aggregate
    def _keyed_prep(self, dense: bool = False, key_kinds=None):
        """(holder, raw kernel, jitted prep kernel) for the keyed path,
        cached with the other compiled kernels on the stage signature.
        The raw (untraced) kernel backs the fused single-dispatch runner;
        ``key_kinds`` enables the in-kernel device key encode."""
        key = (
            self._sig + ("keyed_prep", key_kinds)
            + (("dense",) if dense else ())
            + K.algo_cache_token()
        )
        cached = _KERNEL_CACHE.get(key)
        self._note_kernel_cache(cached is not None)
        if cached is None:
            import jax

            holder: dict = {}
            inner = K.make_keyed_prep_kernel(
                self._filter_closure,
                self._arg_closures,
                self.specs,
                self._flat_names,
                holder,
                extra_names=self._median_extra_names(),
                key_kinds=key_kinds,
            )
            if self.fused.join is not None:
                kernel = K.make_join_kernel(
                    inner,
                    self._flat_names,
                    self._join_slots,
                    len(self._device_build_cols),
                    dense=dense,
                )
            else:
                kernel = inner
            cached = (holder, kernel, jax.jit(kernel))
            _KERNEL_CACHE[key] = cached
        return cached[0], cached[1], self._timed_jit(cached[2])

    def _key_kinds_for(self, key_encoders) -> tuple:
        """Per-encoded-key device-encode kind ("code" = host encode /
        dictionary handoff), derived from the encoder instances actually
        in play so code spaces can never mix across batches."""
        from .bridge import (
            BoolKeyEncoder,
            FloatKeyEncoder,
            IdentityKeyEncoder,
        )

        if not self.config.tpu_device_encode:
            return tuple("code" for _ in key_encoders)
        kinds = []
        for enc in key_encoders:
            if isinstance(enc, IdentityKeyEncoder):
                kinds.append("ident")
            elif isinstance(enc, BoolKeyEncoder):
                kinds.append("bool")
            elif isinstance(enc, FloatKeyEncoder):
                kinds.append(enc.kind)
            else:
                kinds.append("code")
        return tuple(kinds)

    def _keyed_fast_encoders(self, batch) -> Optional[list]:
        """Encoder set for the PRE-ENCODE keyed fast path, or None when
        this stage/batch must take the host-encode routing.

        :func:`choose_route` asks for a stage pinned keyed (median/corr
        stages, or ``highcard_mode=device``) with device encode enabled;
        where at least one key has a device encoding the batch then goes
        to :meth:`_run_keyed` with NO host group encode at all
        (``key_encode_time_ns`` stays ~0; only dictionary keys still pay
        the host handoff per batch).  A first-batch range precheck sends
        identity keys the device cannot represent (negative values, or
        past-i32 in x32 mode) back to the host-encode routing, which
        lands on the measured host fallbacks."""
        from .bridge import arrow_to_numpy, device_key_encoder

        encs: list = []
        kinds: list = []
        for pos, (kind, _s) in enumerate(self._group_plan):
            if kind != "enc":
                continue
            enc, k = device_key_encoder(
                self._schema.field(pos).type, self._mode
            )
            encs.append(enc)
            kinds.append(k)
        if not encs or all(k is None for k in kinds):
            return None
        for k, g in zip(kinds, self._enc_group_exprs):
            if k != "ident":
                continue
            try:
                vals, _valid = arrow_to_numpy(_eval_arr(g, batch))
            except ExecutionError:
                return None
            v = vals.astype(np.int64, copy=False)
            if len(v) and (
                v.min() < 0
                or (self._mode == "x32" and v.max() > (1 << 31) - 2)
            ):
                return None
        return encs

    def _keyed_key_ops(
        self, batch, kinds, key_state: dict, key_encoders, codes,
        n: int, n_pad: int,
    ) -> tuple:
        """Per-key prep-kernel operand tuples for one batch.

        "code" kinds host-encode (dictionary handoff; ``codes`` reuses
        the detection path's already-encoded first batch).  Device kinds
        ship the RAW evaluated key column as (values, validity);
        identity keys choose a target integer dtype on the first batch —
        i32 when the range allows, unlocking the packed-u64 single-
        operand sort even in x64 mode (measured 6.8x on the sort) — and
        a later batch that overflows the choice raises ExecutionError:
        the late-key-overflow host-route fallback the legacy path has."""
        from .bridge import arrow_to_numpy

        def note_range(slot: int, min_code, max_code) -> None:
            """Track the running per-slot CODE range (None = the slot
            has no non-negative bounded code space): the fused runner
            folds min-rebased codes into one sort word using the exact
            stream-wide spans."""
            if max_code is None or key_state.get(("max", slot), 0) is None:
                key_state[("max", slot)] = None
                return
            key_state[("max", slot)] = max(
                key_state.get(("max", slot), 0), int(max_code)
            )
            cur_min = key_state.get(("min", slot))
            key_state[("min", slot)] = (
                int(min_code)
                if cur_min is None
                else min(cur_min, int(min_code))
            )

        ops: list = []
        for slot, (kind, enc) in enumerate(zip(kinds, key_encoders)):
            g = self._enc_group_exprs[slot]
            if kind == "code":
                if codes is not None and codes[slot] is not None:
                    c = codes[slot]
                else:
                    with self.metrics.timer("key_encode_time_ns"):
                        c = enc.encode(_eval_arr(g, batch))
                note_range(slot, 0, c.max(initial=0))
                ops.append((K._pad(K.coerce_host_values(c), n_pad),))
                continue
            vals, valid = arrow_to_numpy(_eval_arr(g, batch))
            if valid is None:
                valid = np.ones(n, dtype=bool)
            if kind == "ident":
                v = vals.astype(np.int64, copy=False)
                if len(v) and v.min() < 0:
                    raise ExecutionError(
                        "negative group key in identity key encoder"
                    )
                # code = value + 1; null rows carry code 0, so any null
                # in the batch pins the range floor there
                note_range(
                    slot,
                    0 if (not len(v) or not valid.all())
                    else int(v.min()) + 1,
                    v.max(initial=0) + 1,
                )
                dt = key_state.get(("dtype", slot))
                if dt is None:
                    if int(v.max(initial=0)) <= (1 << 31) - 2:
                        dt = np.int32
                    elif self._mode == "x32":
                        raise ExecutionError(
                            "int64 group key exceeds i32 range in x32 mode"
                        )
                    else:
                        dt = np.int64
                    key_state[("dtype", slot)] = dt
                elif dt is np.int32 and len(v) and (
                    int(v.max(initial=0)) > (1 << 31) - 2
                ):
                    raise ExecutionError(
                        "group key outgrew the i32 device encoding"
                    )
                vals = v.astype(dt, copy=False)
            elif kind == "bool":
                vals = np.asarray(vals, dtype=bool)
                note_range(slot, 0, 2)
            else:  # f32 / f64: raw bit-pattern codes
                note_range(slot, 0, None)  # signed bits: no radix fold
                if kind == "f32":
                    vals = vals.astype(np.float32, copy=False)
                    bits = vals.view(np.int32)
                    null = K.FLOAT32_NULL_BITS
                else:
                    vals = vals.astype(np.float64, copy=False)
                    bits = vals.view(np.int64)
                    null = K.FLOAT64_NULL_BITS
                if bool(np.any((bits == null) & valid)):
                    # the one NaN payload reserved for NULL appears as
                    # DATA: no device encoding — host-route fallback
                    raise ExecutionError(
                        "float group key collides with the reserved "
                        "null pattern"
                    )
            ops.append((K._pad(vals, n_pad), K._pad(valid, n_pad)))
        return tuple(ops)

    def _median_extra_names(self) -> tuple:
        """Env names of the median/corr argument leaves, buffered raw
        through the keyed prep for the post-sort passes."""
        out: list[str] = []
        for ci in self._median_cols:
            base = f"col_{ci}__ordpair"
            out.extend([f"{base}__ohi", f"{base}__olo", f"{base}__valid"])
        for ci in self._corr_cols:
            if self._mode == "x32":
                base = f"col_{ci}__pair"
                out.extend(
                    [f"{base}__hi", f"{base}__lo", f"{base}__valid"]
                )
            else:
                out.extend([f"col_{ci}", f"col_{ci}__valid"])
        return tuple(out)

    def _run_keyed(self, first: list, src, key_encoders, build, chip: int):
        """Device-keyed aggregation (VERDICT r3 item 2): per batch the
        fused filter/join/project runs and masked scan-form columns
        buffer in HBM alongside the RAW key codes; at stream end ONE
        multi-key sort assigns group ids from key-change boundaries, one
        segmented scan reduces every aggregate, and one packed fetch
        returns states + unique key codes.  Host work per batch is one
        astype per key — no hash probe, no factorize.

        The route places nothing itself: it computes where the folded
        join's ``build`` is (None: no join, the default device).

        Returns ``(host_states, _KeyedGroups, n_rows_in, aux)`` where
        ``aux = {"median": [...], "corr": [...]}`` holds the post-sort
        pass results; raises ``ExecutionError`` (keys can't ship) or
        ``_CapacityExceeded`` (cardinality past tpu.max_capacity) for
        the caller's CPU fallback.
        """
        dense_join = build is not None and build[0] == "dense"
        kinds = self._key_kinds_for(key_encoders)
        use_kinds = (
            kinds if any(k != "code" for k in kinds) else None
        )
        holder, _prep_raw, prep = self._keyed_prep(
            dense=dense_join, key_kinds=use_kinds
        )
        n_keys = self._n_encoded_groups
        buf: list = []
        chunks: list = []  # flushed (states, key_codes, n_groups) blocks
        buffered = 0
        n_rows_in = 0
        key_state: dict = {}
        # single-dispatch fusion: batches accumulate HOST-side and the
        # whole encode→sort pipeline runs as ONE jitted call at stream
        # end; past the unroll cap or the HBM budget the accumulated
        # entries drain through the per-batch streaming prep instead
        pending: list = []  # (keys_ops, n_live, trivial_idx, args)
        pending_bytes = 0
        fuse = True

        def flush():
            # HBM budget reached: reduce the buffered block to its
            # [distinct]-sized keyed states NOW and merge blocks on host
            # at stream end (merge_keyed_host, the mesh cross-shard
            # combine) instead of letting the buffer grow to the final
            # sort — at SF100 a partition's buffered columns can exceed
            # v5e HBM (16 GiB)
            nonlocal buf, buffered
            if not buf:
                return
            if self._median_cols or self._corr_pairs:
                # medians/corr need every row in ONE sort; refuse the
                # unbounded buffer and fall back before the device OOMs
                raise ExecutionError(
                    "keyed buffer budget exceeded with median/corr "
                    "(order statistics cannot chunk-merge)"
                )
            states, key_codes, n_groups, _post = self._keyed_reduce(
                buf, holder, n_keys
            )
            chunks.append((states, key_codes, n_groups))
            self.metrics.add("keyed_chunks", 1)
            buf = []
            buffered = 0

        import jax.numpy as jnp

        def dispatch_prep(keys_ops, n_live, trivial_idx, args):
            nonlocal buffered
            n_pad = len(args[0]) if args else len(keys_ops[0][0])
            with self.metrics.timer("device_time_ns"):
                # device-built tail mask replaces the host validity ship,
                # shared with every all-true leaf companion (see the
                # gid-path device section)
                tail = jnp.arange(n_pad, dtype=jnp.int32) < n_live
                args = [
                    tail if i in trivial_idx else a
                    for i, a in enumerate(args)
                ]
                keys_in = (
                    keys_ops
                    if use_kinds is not None
                    else tuple(k[0] for k in keys_ops)
                )
                out = prep(keys_in, tail, *args)
            buf.append(out)
            buffered += sum(int(a.nbytes) for a in out)
            if self.keyed_buffer_bytes and buffered >= self.keyed_buffer_bytes:
                flush()

        def feed(batch, codes):
            nonlocal pending_bytes, fuse
            n = batch.num_rows
            n_pad = K.bucket_rows(n)
            keys_ops = self._keyed_key_ops(
                batch, kinds, key_state, key_encoders, codes, n, n_pad
            )
            with self.metrics.timer("bridge_time_ns"):
                args, trivial_idx = self._kernel_args(
                    batch, n, n_pad, build
                )
            if use_kinds is not None:
                self.metrics.add("device_encode_batches", 1)
            if fuse:
                # budget-account only the HOST arrays buffered per batch:
                # device-resident join-build tensors ride every entry's
                # args but are one shared allocation, not per-batch HBM
                ebytes = sum(
                    int(a.nbytes)
                    for a in args
                    if isinstance(a, np.ndarray)
                ) + sum(int(o.nbytes) for op in keys_ops for o in op)
                if len(pending) < _FUSED_MAX_ENTRIES and (
                    not self.keyed_buffer_bytes
                    or pending_bytes + ebytes < self.keyed_buffer_bytes
                ):
                    pending.append((keys_ops, n, trivial_idx, args))
                    pending_bytes += ebytes
                    return
                # over the unroll cap / budget: drain into streaming mode
                fuse = False
                for entry in pending:
                    dispatch_prep(*entry)
                pending.clear()
            dispatch_prep(keys_ops, n, trivial_idx, args)

        with _StageTimer(self.metrics, chip):
            for batch, codes in first:
                n_rows_in += batch.num_rows
                feed(batch, codes)
            for batch in src:
                if batch.num_rows == 0:
                    continue
                n_rows_in += batch.num_rows
                feed(batch, None)

            if chunks:
                flush()
                with self.metrics.timer("keyed_merge_time_ns"):
                    merged, merged_keys, n_groups = K.merge_keyed_host(
                        self.specs, self._mode, chunks
                    )
                if n_groups > self.max_capacity:
                    raise _CapacityExceeded()
                return (
                    merged,
                    _KeyedGroups(merged_keys, n_groups),
                    n_rows_in,
                    {"median": [], "corr": []},
                )

            if pending:
                states, key_codes, n_groups, post = (
                    self._keyed_reduce_fused(
                        pending, holder, n_keys, use_kinds, dense_join,
                        # the radix fold is part of the device-encode
                        # feature; the knob-off leg stays the plain
                        # host-encode baseline
                        combine_bits=(
                            _radix_combine_bits(key_state, n_keys)
                            if use_kinds is not None
                            else None
                        ),
                    )
                )
            else:
                states, key_codes, n_groups, post = self._keyed_reduce(
                    buf, holder, n_keys
                )
            mask, keys, extras, s2, perm, cap = post
            per_corr = 3 if self._mode == "x32" else 2
            with self.metrics.timer("device_time_ns"):
                med_results: list[np.ndarray] = []
                for j in range(len(self._median_cols)):
                    med_fn = K.keyed_median_kernel(n_keys, cap)
                    med_packed = med_fn(
                        mask, tuple(keys),
                        extras[3 * j], extras[3 * j + 1],
                        extras[3 * j + 2],
                    )
                    med_results.append(np.asarray(med_packed))
                corr_results: list[np.ndarray] = []
                corr_base = 3 * len(self._median_cols)

                def corr_col(slot: int):
                    o = corr_base + per_corr * slot
                    return extras[o:o + per_corr]

                for sx, sy in self._corr_pairs:
                    cf = K.keyed_corr_kernel(cap, self._mode)
                    packed_c = cf(
                        s2, perm, *corr_col(sx), *corr_col(sy)
                    )
                    corr_results.append(np.asarray(packed_c))
        aux = {"median": med_results, "corr": corr_results}
        return states, _KeyedGroups(key_codes, n_groups), n_rows_in, aux

    def _keyed_reduce(self, buf: list, holder: dict, n_keys: int):
        """ONE multi-key sort + segmented scan over the buffered blocks.

        Returns ``(host_states, key_codes, n_groups, post)`` where
        ``post = (mask, keys, extras, s2, perm, cap)`` keeps the sorted
        arrays alive for the single-block median/corr passes.  Raises
        ``_CapacityExceeded`` past tpu.max_capacity.
        """
        import jax.numpy as jnp

        with self.metrics.timer("device_time_ns"):
            parts = list(zip(*buf))
            if len(buf) == 1:
                fields = [p[0] for p in parts]
            else:
                fields = [jnp.concatenate(p) for p in parts]
            total = int(fields[0].shape[0])
            n2 = K.bucket_rows(total)
            if n2 != total:
                # pad rows carry mask=False and sink past every
                # boundary in the sort — values never read
                fields = [jnp.pad(f, (0, n2 - total)) for f in fields]
            mask = fields[0]
            per_corr = 3 if self._mode == "x32" else 2
            n_extras = 3 * len(self._median_cols) + per_corr * len(
                self._corr_cols
            )
            keys = fields[1:1 + n_keys]
            flat_end = len(fields) - n_extras
            flat_cols = fields[1 + n_keys:flat_end]
            extras = fields[flat_end:]
            out = K.keyed_sort_kernel(n_keys)(mask, *keys)
            s2, perm = out[0], out[1]
            sk = out[2:-1]
            # the scalar fetch is the one host sync before capacity
            # is known
            n_groups = int(np.asarray(out[-1]))
        if n_groups > self.max_capacity:
            raise _CapacityExceeded()
        cap = K.bucket_rows(n_groups, floor=64)
        finish = K.keyed_finish_kernel(
            holder["kinds"], holder["plan"], self.specs, n_keys, cap,
            self._mode,
        )
        with self.metrics.timer("device_time_ns"):
            packed = finish(s2, perm, tuple(sk), tuple(flat_cols))
            host = np.asarray(packed)
        states, key_codes = K.unpack_keyed_host(
            self.specs, host, self._mode, n_keys
        )
        return states, key_codes, n_groups, (mask, keys, extras, s2, perm, cap)

    def _keyed_reduce_fused(
        self, pending: list, holder: dict, n_keys: int, use_kinds,
        dense: bool, combine_bits=None,
    ):
        """Single-dispatch keyed reduction: every buffered batch's
        (device key encode →) filter/join prep, the cross-batch
        concatenate, and the packed-u64 sort run as ONE jitted call —
        a keyed batch crosses the bridge exactly once, and the whole
        stream costs two device dispatches (this one, then the
        capacity-sized finish once ``n_groups`` is known — the one
        scalar the host must sync on before it can fix the finish
        kernel's static shapes).  Same return contract as
        :meth:`_keyed_reduce`.
        """
        shapes = tuple(
            len(e[3][0]) if e[3] else len(e[0][0][0]) for e in pending
        )
        key_ops_sig = tuple(len(op) for op in pending[0][0])
        n_args = len(pending[0][3])
        trivials = tuple(tuple(sorted(e[2])) for e in pending)
        fn = self._keyed_fused_sort_for(
            shapes, key_ops_sig, n_args, trivials, use_kinds, dense,
            combine_bits,
        )
        flat: list = []
        for keys_ops, n_live, _tidx, args in pending:
            flat.append(np.int32(n_live))
            for op in keys_ops:
                flat.extend(op)
            flat.extend(args)
        with self.metrics.timer("device_time_ns"):
            outs = fn(*flat)
            self.metrics.add("fused_keyed_dispatches", 1)
            n_sort = 2 + n_keys + 1  # s2, perm, sorted keys, n_groups
            fields = outs[:-n_sort]
            s2, perm = outs[-n_sort], outs[-n_sort + 1]
            sk = outs[-n_sort + 2:-1]
            # the scalar fetch is the one host sync before capacity is
            # known
            n_groups = int(np.asarray(outs[-1]))
        if n_groups > self.max_capacity:
            raise _CapacityExceeded()
        per_corr = 3 if self._mode == "x32" else 2
        n_extras = 3 * len(self._median_cols) + per_corr * len(
            self._corr_cols
        )
        mask = fields[0]
        keys = fields[1:1 + n_keys]
        flat_end = len(fields) - n_extras
        flat_cols = fields[1 + n_keys:flat_end]
        extras = fields[flat_end:]
        cap = K.bucket_rows(n_groups, floor=64)
        finish = K.keyed_finish_kernel(
            holder["kinds"], holder["plan"], self.specs, n_keys, cap,
            self._mode,
        )
        with self.metrics.timer("device_time_ns"):
            packed = finish(s2, perm, tuple(sk), tuple(flat_cols))
            host = np.asarray(packed)
        states, key_codes = K.unpack_keyed_host(
            self.specs, host, self._mode, n_keys
        )
        return states, key_codes, n_groups, (mask, keys, extras, s2, perm, cap)

    def _keyed_fused_sort_for(
        self, shapes: tuple, key_ops_sig: tuple, n_args: int,
        trivials: tuple, use_kinds, dense: bool, combine_bits=None,
    ):
        """Jitted (prep×entries → concat → sort) runner, cached on the
        stage signature + per-entry row buckets and trivial-validity
        layouts (both pow2/stable per stage in practice, so distinct
        traces stay bounded like the join-free fused runner's).

        ``combine_bits`` (per-key radix widths, exact because the fused
        runner sees the WHOLE stream's code maxima before tracing)
        folds every key's code into ONE non-negative i32 sort word —
        multi-key plans then ride the u64x1 packed sort instead of
        pairwise words, and the sorted per-key codes unpack back out by
        shifts, so the finish kernel and decode see exactly the codes
        they always did."""
        key = (
            self._sig
            + ("keyedfused", shapes, key_ops_sig, n_args, trivials,
               use_kinds, combine_bits)
            + (("dense",) if dense else ())
            + K.algo_cache_token()
        )
        cached = _KERNEL_CACHE.get(key)
        self._note_kernel_cache(cached is not None)
        if cached is None:
            import jax
            import jax.numpy as jnp

            _holder, prep_raw, _ = self._keyed_prep(
                dense=dense, key_kinds=use_kinds
            )
            n_keys = self._n_encoded_groups
            sort_body = K.keyed_sort_body(
                1 if combine_bits is not None else n_keys
            )
            n_key_flat = sum(key_ops_sig)
            stride = 1 + n_key_flat + n_args
            n_entries = len(shapes)
            total = sum(shapes)
            n2 = K.bucket_rows(total)

            def fn(*flat):
                prep_outs = []
                for e in range(n_entries):
                    base = e * stride
                    n_live = flat[base]
                    keys_ops = []
                    o = base + 1
                    for cnt in key_ops_sig:
                        keys_ops.append(tuple(flat[o:o + cnt]))
                        o += cnt
                    args = list(flat[o:base + stride])
                    tail = (
                        jnp.arange(shapes[e], dtype=jnp.int32) < n_live
                    )
                    args = [
                        tail if i in trivials[e] else a
                        for i, a in enumerate(args)
                    ]
                    keys_in = (
                        tuple(keys_ops)
                        if use_kinds is not None
                        else tuple(k[0] for k in keys_ops)
                    )
                    prep_outs.append(prep_raw(keys_in, tail, *args))
                parts = list(zip(*prep_outs))
                fields = [
                    p[0] if len(p) == 1 else jnp.concatenate(p)
                    for p in parts
                ]
                if n2 != total:
                    # pad rows carry mask=False and sink past every
                    # boundary in the sort — values never read
                    fields = [
                        jnp.pad(f, (0, n2 - total)) for f in fields
                    ]
                mask = fields[0]
                keys_c = fields[1:1 + n_keys]
                if combine_bits is None:
                    sout = sort_body(mask, *keys_c)
                    return tuple(fields) + tuple(sout)
                # radix-combine: one i32 word carries every key's
                # MIN-REBASED code (spans are exact stream-wide ranges,
                # so the fold is injective and stays non-negative)
                m0, _w0 = combine_bits[0]
                comb = keys_c[0].astype(jnp.int32) - jnp.int32(m0)
                for (mk, bk), kk in zip(combine_bits[1:], keys_c[1:]):
                    comb = (comb << bk) | (
                        kk.astype(jnp.int32) - jnp.int32(mk)
                    )
                sout = sort_body(mask, comb)
                s2, perm, skc, n_groups = sout
                sks = []
                rem = skc
                for mk, bk in reversed(combine_bits[1:]):
                    sks.append(
                        (rem & jnp.int32((1 << bk) - 1)) + jnp.int32(mk)
                    )
                    rem = rem >> bk
                sks.append(rem + jnp.int32(combine_bits[0][0]))
                sks.reverse()
                return (
                    tuple(fields) + (s2, perm) + tuple(sks) + (n_groups,)
                )

            cached = jax.jit(fn)
            _KERNEL_CACHE[key] = cached
        return self._timed_jit(cached)

    # ------------------------------------------------------- device join
    def _nojoin_stage(self) -> "TpuStageExec":
        """Sibling stage with the join UNFOLDED (join on CPU, aggregate on
        device) for data the device join cannot handle."""
        with self._build_lock:
            cached = getattr(self, "_nojoin", None)
            if cached is None:
                fused = _flatten(self.original, fold_join=False)
                cached = TpuStageExec(self.original, fused, self.config)
                cached.metrics = self.metrics  # one bag for observability
                self._nojoin = cached
            return cached

    def _prepare_build(self, ctx: TaskContext, device=None, chip: int = 0):
        """The folded join's build side as the kernel takes it, on
        ``device`` (chip ``chip`` of the mesh; None: the default device).
        Collected, sorted and padded on the host once an instance (device
        arrays for the kernel's searchsorted/gather, host copies for group
        resolution); its arrays and the dense table go up to a device the
        first time a partition there asks.  Raises _JoinIneligible (→ join
        on CPU) on non-unique keys or un-shippable key/column ranges."""
        with self._build_lock:
            state = self._build_states.get(device)
            if state is not None:
                return state
            spec = self.fused.join
            batches = []
            if self._build_host is None:
                for p in range(spec.build.output_partitioning().n):
                    for b in spec.build.execute(p, ctx):
                        ctx.check_cancelled()
                        if b.num_rows:
                            batches.append(b)
            # join_build_ns / join.build: the build itself (sort, pad,
            # upload, table), not the child's execute that feeds it; on a
            # further chip, its upload and table alone
            with self.metrics.timer("join_build_ns"), trace.span(
                "join.build", device=chip
            ) as span:
                if self._build_host is None:
                    self._build_host = self._sort_build(spec, batches)
                host = self._build_host
                state = self._place_build(host, device)
                m_pad = len(host[1]) if host[0] != "empty" else 0
                span.set_attr("rows", len(host[4]) if m_pad else 0)
                span.set_attr("capacity", m_pad)
                span.set_attr("dense", host[0] == "dense")
            self._build_states[device] = state
            return state

    def _sort_build(self, spec: DeviceJoinSpec, batches: list) -> tuple:
        """The build side on the host: ``("empty",)``, or ``(kind, keys,
        padded values, padded validities, sorted keys, sorted table, kmin,
        table span)`` with kind ``"dense"`` (``keys`` = each row's slot in
        the dense table) or ``"ok"`` (``keys`` = the sorted keys, padded)."""
        from .bridge import arrow_to_numpy

        if batches:
            table = pa.Table.from_batches(batches, schema=spec.build.schema)
        else:
            table = spec.build.schema.empty_table()
        key_col = table.column(spec.build_key_index)
        kv, kvalid = arrow_to_numpy(
            key_col.combine_chunks()
            if isinstance(key_col, pa.ChunkedArray)
            else key_col
        )
        kv = kv.astype(np.int64)
        if kvalid is not None:
            table = table.filter(pa.array(kvalid))
            kv = kv[kvalid]  # null build keys never match an inner join
        order = np.argsort(kv, kind="stable")
        kv_sorted = kv[order]
        if len(kv_sorted) > 1 and bool(
            np.any(kv_sorted[1:] == kv_sorted[:-1])
        ):
            raise _JoinIneligible("device join requires unique build keys")
        table = table.take(pa.array(order))

        if len(kv_sorted) == 0:
            return ("empty",)

        # the build side goes up at its bucket, not at its own length, so
        # that the probe kernel's shapes do not follow the rows the build
        # side's filters let through.  A pad row can match no probe key:
        # the sorted probe sees the LAST key repeated (searchsorted-left
        # finds the real row first), the dense table gives it no slot; its
        # columns read invalid.
        m = len(kv_sorted)
        m_pad = K.bucket_rows(m)
        try:
            keys_host = K.coerce_host_values(kv_sorted)  # range-checked
            bvals, bvalids = [], []
            for ci in self._device_build_cols:
                col = table.column(ci).combine_chunks()
                vals, validity = arrow_to_numpy(col)
                bvals.append(K._pad(K.coerce_host_values(vals), m_pad))
                if validity is None:
                    validity = np.ones(len(vals), dtype=bool)
                bvalids.append(K._pad(validity, m_pad))
        except ExecutionError as e:
            # un-shippable key/column ranges or types: join on CPU,
            # aggregate on device (not a full-CPU fallback)
            raise _JoinIneligible(str(e)) from e
        self.metrics.add("join_build_rows", m)
        self.metrics.add("join_build_capacity", m_pad)
        kmin = int(kv_sorted[0])
        span = int(kv_sorted[-1]) - kmin + 1
        if span <= _DENSE_JOIN_SPAN_CAP:
            # Dense-key direct probe (chip, round 5, star join:
            # searchsorted's log2(m) serial gather passes dominated 38s of
            # device time): scatter build rows into a [span]-slot table
            # once, probe with ONE gather.  Built device-side, so only the
            # build rows' slot numbers cross the bridge, never the table.
            # TPC-H integer keys (orderkey/custkey/partkey) always qualify
            # at SF<=10; wider spans keep the sorted probe.
            span_b = K.bucket_rows(span, floor=16)
            slots = K._pad(
                (kv_sorted - kmin).astype(np.int32), m_pad, fill=span_b
            )
            self.metrics.add("dense_join", 1)
            return (
                "dense", slots, bvals, bvalids, kv_sorted, table,
                np.int64(kmin), span_b,
            )
        bkeys = K._pad(keys_host, m_pad, fill=keys_host[-1])
        return ("ok", bkeys, bvals, bvalids, kv_sorted, table, None, None)

    @staticmethod
    def _place_build(host: tuple, device) -> tuple:
        """The kernel's build state on ``device`` (None: the default) from
        :meth:`_sort_build`'s host arrays: ``("empty",)``, ``("dense",
        table, values, validities, sorted keys, sorted table, kmin)`` or
        ``("ok", sorted keys, values, validities, sorted keys, sorted
        table)``."""
        if host[0] == "empty":
            return host
        kind, keys, bvals, bvalids, kv_sorted, table, kmin, span_b = host
        bvals = [jax.device_put(v, device) for v in bvals]
        bvalids = [jax.device_put(v, device) for v in bvalids]
        keys = jax.device_put(keys, device)
        if kind == "dense":
            return (
                "dense", _dense_join_table(keys, span_b), bvals, bvalids,
                kv_sorted, table, kmin,
            )
        return ("ok", keys, bvals, bvalids, kv_sorted, table)

    def _fetch_states(self, acc, n_groups: Optional[int] = None) -> Optional[list]:
        """One packed device→host fetch of the whole state tuple.

        ``n_groups`` (when the stage aggregates by key) bounds the fetch:
        only the pow2 bucket covering the assigned group ids moves to the
        host instead of the full grown capacity (up to 4x fewer bytes at
        high cardinality)."""
        if acc is None:
            return None
        keep = None if n_groups is None else _keep_bucket(n_groups)
        packed = K.pack_for_fetch(self.specs, acc, self._mode, keep=keep)
        return K.unpack_host(self.specs, np.asarray(packed), self._mode)

    def _run_fused(
        self, entries, cap: int, group_table, key_encoders=None,
        stream: bool = False,
    ) -> Optional[list]:
        """ONE jitted dispatch for the whole query over retained entries:
        per-entry kernel → cross-entry combine → packed fetch layout.

        The previous per-batch loop (kernel dispatch per entry, eager
        combine ops, separate pack dispatch) put 3+ dispatches on q6's
        critical path even with every column device-resident.  All
        entries run at the FINAL capacity, so mid-stream state padding
        disappears with the per-batch dispatches.

        The runner UNROLLS one kernel body per entry, so entry count is
        capped: past _FUSED_MAX_ENTRIES (default batch sizes can give
        hundreds of batches per partition) the XLA program would hit a
        compile cliff, and the per-batch dispatch loop degrades linearly
        instead."""
        # cache-eligible stages are join-free (_cache_key); the dense
        # join-kernel variant must never replay through this runner,
        # which builds the sorted-probe form
        assert self.fused.join is None, "fused runner is join-free"
        self._fused_pids = None
        n_groups = group_table.n_groups if group_table is not None else None
        if stream or len(entries) > _FUSED_MAX_ENTRIES:
            acc = None
            _, kernel = self._kernel_for(cap)
            for seg, valid, args in entries:
                out = kernel(seg, valid, *args)
                acc = K.combine_states(self.specs, acc, out, self._mode)
            return self._fetch_states(acc, n_groups)
        keep = None if n_groups is None else _keep_bucket(n_groups)
        # shuffle-pid-in-kernel (whole-stage fusion): the group table is
        # complete at dispatch time, so every group's hint-key values
        # decode NOW and their hash rides the same trace — the stage's
        # compute + partition-id derivation become ONE dispatch
        pid_args = None
        pid_static = None
        if (
            self._fuse_pid
            and group_table is not None
            and key_encoders is not None
        ):
            spec = self._fused_pid_spec()
            if spec is not None:
                slots, n_out = spec
                arrs = [
                    key_encoders[slot].decode(
                        group_table.codes_for(np.arange(n_groups), slot),
                        self._schema.field(pos).type,
                    )
                    for slot, pos in slots
                ]
                pid_args = K.pid_limb_args(arrs, min(keep, cap))
                if pid_args is not None:
                    pid_static = (len(slots), n_out)
        shapes = tuple(int(e[1].shape[0]) for e in entries)
        n_args = len(entries[0][2])
        fn = self._fused_for(cap, shapes, n_args, keep, pid_static)
        flat = []
        for seg, valid, args in entries:
            flat.append(seg)
            flat.append(valid)
            flat.extend(args)
        if pid_static is not None:
            flat.extend(pid_args)
        try:
            packed = fn(*flat)
        except Exception:
            # trace/compile failure of the unrolled program: degrade to
            # the per-batch dispatch loop instead of failing the stage
            # (knob-off keeps the pre-fusion failure path: the execute()
            # ladder falls back to the CPU operators)
            if not self.config.tpu_whole_stage_fusion:
                raise
            self.metrics.add("fused_degraded", 1)
            acc = None
            _, kernel = self._kernel_for(cap)
            for seg, valid, args in entries:
                out = kernel(seg, valid, *args)
                acc = K.combine_states(self.specs, acc, out, self._mode)
            return self._fetch_states(acc, n_groups)
        self.metrics.add("fused_dispatches", 1)
        packed_np = np.asarray(packed)
        if pid_static is not None:
            # last packed row is the int pid lane; peel it for
            # _materialize and hand the rest to the normal unpack
            self._fused_pids = packed_np[-1].astype(np.int64)
            packed_np = packed_np[:-1]
            self.metrics.add("fused_pid_in_kernel", 1)
        return K.unpack_host(self.specs, packed_np, self._mode)

    def _fused_for(
        self, cap: int, shapes: tuple, n_args: int, keep, pid=None
    ):
        """Jitted (kernel×entries → combine → pack) runner, cached on the
        stage signature + per-entry row buckets (pow2, so distinct traces
        stay logarithmic in partition size).  ``pid`` (static
        ``(n_key_cols, n_out)`` or None) extends the trace with the
        shuffle partition-id hash over trailing limb args, appended to
        the packed fetch as one extra integer row."""
        key = (
            self._sig[:2] + (cap,) + self._sig[3:]
            + ("fusedall", shapes, n_args, keep, pid)
            + K.algo_cache_token()
        )
        cached = _KERNEL_CACHE.get(key)
        self._note_kernel_cache(cached is not None)
        if cached is None:
            import jax
            import jax.numpy as jnp

            raw, _ = self._kernel_for(cap)
            specs, mode = self.specs, self._mode
            stride = 2 + n_args
            n_entries = len(shapes)

            def fn(*flat):
                acc = None
                for i in range(n_entries):
                    seg = flat[i * stride]
                    valid = flat[i * stride + 1]
                    args = flat[i * stride + 2:(i + 1) * stride]
                    out = raw(seg, valid, *args)
                    acc = K.combine_states(specs, acc, out, mode)
                packed = K.pack_states(specs, acc, mode, keep)
                if pid is not None:
                    pids = K.partition_id_hash(
                        flat[n_entries * stride:], pid[1]
                    )
                    packed = jnp.concatenate(
                        [packed, pids[None, :].astype(packed.dtype)],
                        axis=0,
                    )
                return packed

            cached = jax.jit(fn)
            _KERNEL_CACHE[key] = cached
        return self._timed_jit(cached)

    def _encode_groups(self, batch, key_encoders, group_table):
        """Vectorized multi-key → dense group id encoding, any key count.

        Per-key global dictionary codes fold into one int64 via growing
        per-key radix bits; known combinations resolve through a pandas
        hash-index probe and only MISSES pay one pandas.factorize
        (ops/groups.py — the round-2 design looped Python over every new
        combination: 6 of q3 SF10's 7.8 stage-seconds).  The keyed path
        (:meth:`_run_keyed`) skips the gid table entirely and ships the
        per-key codes raw.
        """
        return self._assign_gids(
            self._encode_codes(batch, key_encoders), group_table
        )

    def _encode_codes(self, batch, key_encoders) -> list[np.ndarray]:
        """Per-key dictionary/identity code arrays for one batch."""
        encoded_exprs = [
            g
            for (g, _), (kind, _s) in zip(
                self.fused.group_exprs, self._group_plan
            )
            if kind == "enc"
        ]
        return [
            enc.encode(_eval_arr(g, batch))
            for g, enc in zip(encoded_exprs, key_encoders)
        ]

    def _assign_gids(self, code_arrays: list, group_table) -> np.ndarray:
        from .groups import RadixOverflow

        try:
            gids = group_table.encode(code_arrays)
        except RadixOverflow:
            raise _CapacityExceeded()
        if group_table.n_groups > self.max_capacity:
            raise _CapacityExceeded()
        return gids

    # ------------------------------------------------------- materialize
    def _materialize(
        self, host_states, key_encoders, group_table, n_rows_in,
        ctx: TaskContext, partition: int, aux=None,
    ) -> Iterator[pa.RecordBatch]:
        """Build the output batch from already-fetched numpy state arrays
        (``host_states`` comes from :meth:`_fetch_states`; device work and
        the fetch are accounted to device_time_ns by then).  Everything is
        vectorized — per-group Python loops cost seconds at q3/h2o
        cardinalities."""
        fused = self.fused
        schema = self._schema

        if host_states is None:
            if not fused.group_exprs:
                # empty input, global aggregate: the CPU operator supplies
                # the exact SQL empty-input row for THIS (empty) partition
                yield from self.original.execute(partition, ctx)
            return

        n_groups = group_table.n_groups if fused.group_exprs else 1
        host = [a[:n_groups] for a in host_states]
        presence = host[-1]
        keep = np.nonzero(presence > 0)[0] if fused.group_exprs else np.arange(1)

        cols: list[pa.Array] = []
        jk_positions = None
        build_rows = isinstance(group_table, _BuildRows)
        for pos, (kind, slot) in enumerate(self._group_plan):
            field_t = schema.field(len(cols)).type
            if kind == "enc" and build_rows:
                # the probe join key: the matched build row's own key
                cols.append(group_table.key_column(keep, field_t))
                continue
            if kind == "enc":
                codes = group_table.codes_for(keep, slot)
                cols.append(key_encoders[slot].decode(codes, field_t))
                continue
            # build-resolved group key: look the kept groups' probe join
            # keys up in the sorted build table (unique keys => exact); on
            # the build-row route a kept group IS its build row
            if jk_positions is None and build_rows:
                jk_positions = keep
            if jk_positions is None:
                jk_codes = group_table.codes_for(keep, self._jk_slot)
                jk_vals = (
                    key_encoders[self._jk_slot]
                    .decode(jk_codes, schema.field(self._jk_pos).type)
                    .cast(pa.int64())
                    .to_numpy(zero_copy_only=False)
                    .astype(np.int64)
                )
                bkeys_host = self._build_host[4]
                jk_positions = np.searchsorted(bkeys_host, jk_vals)
                jk_positions = np.minimum(
                    jk_positions, max(len(bkeys_host) - 1, 0)
                )
            build_table = self._build_host[5]
            ci = fused.join.build_cols[slot]
            vals = build_table.column(ci).take(pa.array(jk_positions))
            if not vals.type.equals(field_t):
                import pyarrow.compute as pc

                vals = pc.cast(vals, field_t)
            cols.append(
                vals.combine_chunks()
                if isinstance(vals, pa.ChunkedArray)
                else vals
            )

        partial = fused.mode == PARTIAL
        # state-field offset of each kernel spec in the host arrays
        offs: list[int] = []
        off = 0
        for spec in self.specs:
            offs.append(off)
            off += len(K.state_fields(spec, self._mode))

        def sum_and_n(o: int):
            """(Σ as f64, count) of a sum-spec's states at offset o."""
            if self._mode == "x32":
                v = (
                    host[o][keep].astype(np.float64)
                    + host[o + 1][keep].astype(np.float64)
                )
                return v, host[o + 2][keep]
            return host[o][keep].astype(np.float64), host[o + 1][keep]

        for entry in self._emit:
            if entry[0] == "corr":
                if aux is None:
                    raise ExecutionError("corr requires the keyed path")
                pkd = aux["corr"][entry[1]]
                if self._mode == "x32":
                    f32 = np.float32
                    sxy = (
                        pkd[0][keep].view(f32).astype(np.float64)
                        + pkd[1][keep].view(f32)
                    )
                    sxx = (
                        pkd[2][keep].view(f32).astype(np.float64)
                        + pkd[3][keep].view(f32)
                    )
                    syy = (
                        pkd[4][keep].view(f32).astype(np.float64)
                        + pkd[5][keep].view(f32)
                    )
                    n_arr = pkd[6][keep]
                else:
                    sxy = pkd[0][keep].view(np.float64)
                    sxx = pkd[1][keep].view(np.float64)
                    syy = pkd[2][keep].view(np.float64)
                    n_arr = pkd[3][keep]
                empty = (n_arr < 2) | (sxx <= 0) | (syy <= 0)
                with np.errstate(all="ignore"):
                    r = sxy / np.sqrt(sxx * syy)
                r = np.where(empty, 0.0, r)
                field_t = schema.field(len(cols)).type
                arr = pa.array(r, pa.float64(), mask=empty)
                if not arr.type.equals(field_t):
                    import pyarrow.compute as pc

                    arr = pc.cast(arr, field_t, safe=False)
                cols.append(arr)
                continue
            if entry[0] == "cdist":
                if aux is None:
                    raise ExecutionError(
                        "count_distinct requires the keyed path"
                    )
                cd = aux["median"][entry[1]][5][keep].astype(np.int64)
                field_t = schema.field(len(cols)).type
                arr = pa.array(cd, pa.int64())
                if not arr.type.equals(field_t):
                    import pyarrow.compute as pc

                    arr = pc.cast(arr, field_t, safe=False)
                cols.append(arr)
                continue
            if entry[0] == "median":
                if aux is None:
                    # only the keyed path buffers the value columns
                    raise ExecutionError("median requires the keyed path")
                from .bridge import order_decode_f64

                med = aux["median"][entry[1]]
                cv = med[4][keep]
                empty = cv == 0
                va = order_decode_f64(
                    np.where(empty, 0, med[0][keep]).astype(np.int32),
                    np.where(empty, 0, med[1][keep]).astype(np.int32),
                )
                vb = order_decode_f64(
                    np.where(empty, 0, med[2][keep]).astype(np.int32),
                    np.where(empty, 0, med[3][keep]).astype(np.int32),
                )
                v = (va + vb) / 2.0
                field_t = schema.field(len(cols)).type
                arr = pa.array(v, pa.float64(), mask=empty)
                if not arr.type.equals(field_t):
                    import pyarrow.compute as pc

                    arr = pc.cast(arr, field_t, safe=False)
                cols.append(arr)
                continue
            if entry[0] == "var":
                _, si, qi, ddof, use_sqrt = entry
                s_v, n_arr = sum_and_n(offs[si])
                q_v, _n2 = sum_and_n(offs[qi])
                n_f = n_arr.astype(np.float64)
                empty = n_arr < (ddof + 1)
                with np.errstate(all="ignore"):
                    var = (
                        q_v - s_v * s_v / np.maximum(n_f, 1.0)
                    ) / np.maximum(n_f - ddof, 1.0)
                # conditioning guard: when the subtraction consumed more
                # reliable digits than the compensated moments carry
                # (~2^-45 in x32 via the forced scan path, ~2^-52 in
                # x64), only the exact CPU path can answer — incl. var
                # cancelled all the way to <= 0.  Constant columns trip
                # too (their true variance IS the rounding floor); the
                # CPU re-run returns the exact 0.
                with np.errstate(all="ignore"):
                    m2 = q_v / np.maximum(n_f, 1.0)
                live = (~empty) & (m2 > 0)
                kmax = 1e-6 if self._mode == "x32" else 1e-8
                if bool(np.any(live & (var < m2 * kmax))):
                    raise ExecutionError(
                        "variance cancellation past device moment precision"
                    )
                var = np.where(var < 0, 0.0, var)  # rounding guard
                out_v = np.sqrt(var) if use_sqrt else var
                field_t = schema.field(len(cols)).type
                arr = pa.array(out_v, pa.float64(), mask=empty)
                if not arr.type.equals(field_t):
                    import pyarrow.compute as pc

                    arr = pc.cast(arr, field_t, safe=False)
                cols.append(arr)
                continue
            spec = self.specs[entry[1]]
            i = offs[entry[1]]
            if spec.func in ("count", "count_star"):
                cols.append(pa.array(host[i][keep], pa.int64()))
                i += 1
                continue
            if spec.ord_pair:
                # order-pair f64 extremum: lexicographic (hi, lo) i32
                # decodes to the BIT-exact f64 min/max
                from .bridge import order_decode_f64

                ohi = host[i][keep]
                olo = host[i + 1][keep]
                n_arr = host[i + 2][keep]
                i += 3
                empty = n_arr == 0
                v = order_decode_f64(
                    np.where(empty, 0, ohi).astype(np.int32),
                    np.where(empty, 0, olo).astype(np.int32),
                )
                field_t = schema.field(len(cols)).type
                cols.append(pa.array(v, field_t, mask=empty))
                continue
            if spec.int_minmax:
                # integer extrema stay in INT dtype end-to-end (an f64
                # round-trip would round int64 values above 2^53 — the
                # exactness this path exists to guarantee)
                v_exact = host[i][keep]
                n_arr = host[i + 1][keep]
                i += 2
                empty = n_arr == 0
                field_t = schema.field(len(cols)).type
                vals = np.where(empty, 0, v_exact).astype(np.int64)
                if pa.types.is_date32(field_t):
                    cols.append(
                        pa.array(
                            vals.astype("datetime64[D]"), field_t, mask=empty
                        )
                    )
                else:
                    cols.append(pa.array(vals, field_t, mask=empty))
                continue
            if spec.func in ("sum", "avg") and self._mode == "x32":
                # double-float state: hi + lo recombine in f64 on host,
                # recovering ~48-bit precision from f32 device math
                v = (
                    host[i][keep].astype(np.float64)
                    + host[i + 1][keep].astype(np.float64)
                )
                n_arr = host[i + 2][keep]
                i += 3
            else:
                v = host[i][keep].astype(np.float64)
                n_arr = host[i + 1][keep]
                i += 2
            empty = n_arr == 0
            if spec.func == "avg":
                if partial:
                    cols.append(pa.array(v, pa.float64()))
                    cols.append(pa.array(n_arr, pa.int64()))
                else:
                    denom = np.where(empty, 1, n_arr)
                    cols.append(
                        pa.array(v / denom, pa.float64(), mask=empty)
                    )
                continue
            field_t = schema.field(len(cols)).type
            if pa.types.is_integer(field_t) or pa.types.is_date32(field_t):
                # device accumulates in f64; exact for |sum| < 2^53
                # (±inf extrema identities of empty groups are masked out,
                # zeroed first so the int cast can't warn)
                v_int = np.round(np.where(np.isfinite(v), v, 0.0)).astype(
                    np.int64
                )
                if pa.types.is_date32(field_t):
                    cols.append(
                        pa.array(
                            v_int.astype("datetime64[D]"), field_t, mask=empty
                        )
                    )
                else:
                    cols.append(pa.array(v_int, field_t, mask=empty))
            else:
                cols.append(pa.array(v, field_t, mask=empty))

        out = pa.RecordBatch.from_arrays(cols, schema=schema)
        self.metrics.add("output_rows", out.num_rows)
        self.metrics.add("input_rows", n_rows_in)
        hint = self._shuffle_hint
        if hint is not None and out.num_rows:
            fp = self._fused_pids
            if fp is not None:
                # already derived INSIDE the fused dispatch over the full
                # group table — select the kept groups' ids; bit-identical
                # to the separate kernel by construction (same limb prep,
                # same hash, identical decoded key values)
                self._fused_pids = None
                pids = fp[:n_groups][keep]
            else:
                pids = K.device_partition_ids(out, hint[0], hint[1])
            if pids is not None:
                from ..exec.operators import SHUFFLE_PID_COLUMN

                # device_pid_batches is counted ONCE, by the consuming
                # writer — a second add here would double it in the
                # per-stage profile rollup
                out = pa.RecordBatch.from_arrays(
                    out.columns + [pa.array(pids.astype(np.int32), pa.int32())],
                    schema=schema.append(
                        pa.field(SHUFFLE_PID_COLUMN, pa.int32())
                    ),
                )
        yield out


def _radix_combine_bits(key_state: dict, n_keys: int) -> Optional[tuple]:
    """Per-key ``(min_code, width)`` plan when every key's MIN-REBASED
    codes fold into one non-negative i32 sort word (None otherwise).
    Ranges are the EXACT stream-wide code spans ``_keyed_key_ops``
    tracked — the fused runner traces after the whole stream buffered,
    so unlike the host ``GroupTable``'s growing radixes there is no
    mid-stream regrow or overflow: the plan is right by construction.
    Rebasing matters: q3's orderdate key spans ~121 distinct days but
    its identity codes sit near 9000 — 7 bits after rebase vs 14 raw."""
    if n_keys < 2:
        return None
    plan = []
    total = 0
    for slot in range(n_keys):
        m = key_state.get(("max", slot), None)
        if m is None:
            return None  # float bit-pattern codes are signed: no fold
        if int(m) > (1 << 31) - 2:
            # the fold runs in i32: a key whose CODES exceed i32 (wide
            # int64 values with a narrow span still ship as i64 arrays)
            # must not reach the jnp.int32 casts — rebasing would wrap
            return None
        lo = key_state.get(("min", slot), 0) or 0
        width = max(1, int(m - lo).bit_length())
        plan.append((int(lo), width))
        total += width
    if total > 31:
        return None
    return tuple(plan)


def _eval_arr(e: pe.PhysicalExpr, batch: pa.RecordBatch) -> pa.Array:
    v = e.evaluate(batch)
    if isinstance(v, pa.ChunkedArray):
        v = v.combine_chunks()
    if isinstance(v, pa.Scalar):
        v = pa.array([v.as_py()] * batch.num_rows, v.type)
    return v


def _replace_leaf(
    plan: ExecutionPlan, old: ExecutionPlan, new: ExecutionPlan
) -> ExecutionPlan:
    if plan is old:
        return new
    kids = plan.children()
    if not kids:
        return plan
    return plan.with_new_children([_replace_leaf(c, old, new) for c in kids])


# ------------------------------------------------------------------ rule
def maybe_accelerate(plan: ExecutionPlan, config: BallistaConfig) -> ExecutionPlan:
    """PhysicalOptimizerRule: replace eligible aggregates with TpuStageExec
    (counterpart of the north star's operator-level TPU plugin)."""
    if not config.tpu_enable:
        return plan
    kids = plan.children()
    if kids:
        plan = plan.with_new_children([maybe_accelerate(c, config) for c in kids])
    from ..exec.window import WindowExec

    if isinstance(plan, WindowExec):
        from .window_compiler import TpuWindowExec

        try:
            return TpuWindowExec(plan, config)
        except K.NotLowerable:
            return plan
    if isinstance(plan, HashAggregateExec) and plan.mode in (PARTIAL, SINGLE):
        fused = _flatten(plan)
        if fused is None:
            return plan
        try:
            return TpuStageExec(plan, fused, config)
        except K.NotLowerable:
            if fused.join is not None:
                # the folded-join shape didn't lower (e.g. a pair/cpu
                # leaf over the build side): retry with the join on CPU
                # so the aggregate still accelerates (round-2 shape)
                fused = _flatten(plan, fold_join=False)
                if fused is not None:
                    try:
                        return TpuStageExec(plan, fused, config)
                    except K.NotLowerable:
                        return plan
            return plan
    return plan
