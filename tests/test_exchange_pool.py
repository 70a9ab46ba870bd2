"""The exchange prepares its input partitions side by side (PR 32): a
partition is pulled, coalesced, hashed to its destinations and flattened to
the exchange's columns once, strings against dictionaries of its own, by
the pool the gang stage has; the task thread takes the partitions in order.
Held here: the exchanged batches are, row for row, what the per-batch loop
it replaces gave, at every width; an error surfaces at its partition's
turn, a cancel ends the stage, and either way every input iterator is
closed and every worker joined; the row ceiling still raises and the writer
still falls back; a device stage below is prepared inline."""

import datetime
import threading

import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu import BallistaConfig, SessionContext
from arrow_ballista_tpu.catalog import MemoryTable
from arrow_ballista_tpu.errors import Cancelled
from arrow_ballista_tpu.exec import expressions as pe
from arrow_ballista_tpu.exec.operators import (
    FilterExec, Partitioning, ScanExec, TaskContext,
)
from arrow_ballista_tpu.obs import trace
from arrow_ballista_tpu.obs.recorder import get_recorder
from arrow_ballista_tpu.ops import kernels as K
from arrow_ballista_tpu.parallel import mesh as M
from arrow_ballista_tpu.parallel import mesh_stage
from arrow_ballista_tpu.parallel.mesh_stage import (
    MeshExchangeError, MeshRepartitionExec,
)

SCHEMA = pa.schema([
    ("k", pa.int64()), ("v", pa.float64()), ("d", pa.date32()),
    ("n", pa.int64()), ("s", pa.string()),
])
N_OUT = 3


def _force_width(monkeypatch, width):
    """The width comes from the cores the process can see: narrow those."""
    monkeypatch.setattr(mesh_stage, "_usable_cores", lambda: width)


def _gang_threads():
    return [t for t in threading.enumerate() if t.name.startswith("gang")]


def _batch(rng, n, shift=0):
    day0 = datetime.date(1995, 1, 1)
    words = np.array(["delta", "alpha", None, "charlie", "bravo", "echo", "fox"], dtype=object)
    nullable = rng.integers(-5, 5, n).astype(object)
    nullable[rng.random(n) < 0.2] = None
    return pa.RecordBatch.from_arrays(
        [
            pa.array(rng.integers(-(1 << 40), 1 << 40, n), pa.int64()),
            pa.array(rng.normal(size=n) * 1e9, pa.float64()),
            pa.array([day0 + datetime.timedelta(days=int(x)) for x in rng.integers(0, 900, n)], pa.date32()),
            pa.array(nullable.tolist(), pa.int64()),
            pa.array(words[(rng.integers(0, 4, n) + shift) % len(words)].tolist(), pa.string()),
        ],
        schema=SCHEMA,
    )


def _ragged_partitions(seed=32):
    """Seven partitions: several batches, one batch, none, only empty ones."""
    rng = np.random.default_rng(seed)
    return [
        [_batch(rng, 700), _batch(rng, 1, 3), _batch(rng, 1300, 1)],
        [],
        [_batch(rng, 0), _batch(rng, 0)],
        [_batch(rng, 513, 4)],
        [_batch(rng, 0), _batch(rng, 2048, 2), _batch(rng, 90, 5)],
        [_batch(rng, 257, 6)],
        [_batch(rng, 64)],
    ]


def _node(table, cfg=None):
    """FilterExec over ScanExec under the exchange, hashed on ``k``."""
    scan = ScanExec("t", table, None)
    kept = FilterExec(pe.Binary(pe.Col(1, "v"), ">", pe.Lit(-1e9)), scan)
    node = MeshRepartitionExec(kept, Partitioning("hash", N_OUT, (pe.Col(0, "k"),)))
    return node, TaskContext(config=cfg or BallistaConfig({}))


def _kept(batch) -> int:
    """Rows of a batch that pass ``_node``'s filter."""
    return int((np.asarray(batch.column(1)) > -1e9).sum())


def _per_batch_reference(node, tctx):
    """The loop this PR replaced, kept as the reference: one thread, the
    partitions one after the other, destinations and columns a BATCH, one
    concatenate a column over the lot; then the same device call and decode."""
    from arrow_ballista_tpu.shuffle.execution_plans import partition_indices

    n_out, exprs = node.partitioning.n, list(node.partitioning.exprs)
    n_dev = mesh_stage._mesh_width(node.n_devices, tctx)
    batches, dest_parts = [], []
    for p in range(node.input.output_partitioning().n):
        for b in node.input.execute(p, tctx):
            if b.num_rows:
                batches.append(b)
                dest_parts.append(partition_indices(b, exprs, n_out).astype(np.int32))
    if not batches:
        return []
    mesh = M.make_mesh(n_dev)
    ext_schema = pa.schema(list(node.input.schema) + [pa.field("__part", pa.int32())])
    dest_dev = (np.concatenate(dest_parts) % n_dev).astype(np.int32)
    total = len(dest_dev)
    rows = M.exchange_rows(total, n_dev)
    shard_id = np.arange(total, dtype=np.int64) // (rows // n_dev)
    need = int(np.bincount(shard_id * n_dev + dest_dev, minlength=n_dev * n_dev).max())
    ex = M.BatchExchanger(mesh, ext_schema, K.bucket_rows(need, floor=1))
    per_batch = [
        ex.to_columns(pa.RecordBatch.from_arrays(list(b.columns) + [pa.array(d)], schema=ext_schema))
        for b, d in zip(batches, dest_parts)
    ]
    cols = [np.concatenate(parts) for parts in zip(*per_batch)]
    recv_cols, recv_valid, dropped = ex.exchange(dest_dev, np.ones(total, bool), cols)
    assert dropped == 0
    out, part_col = [], len(ext_schema) - 1
    for recv in ex.to_batches(recv_cols, recv_valid):
        if recv.num_rows == 0:
            continue
        parts = np.asarray(recv.column(part_col))
        order = np.argsort(parts, kind="stable")
        shuffled = recv.select(range(part_col)).take(pa.array(order))
        bounds = np.searchsorted(parts[order], np.arange(n_out + 1)).tolist()
        out += [(q, shuffled.slice(bounds[q], bounds[q + 1] - bounds[q]))
                for q in range(n_out) if bounds[q + 1] > bounds[q]]
    return out


def _same_sequence(got, want):
    assert [p for p, _ in got] == [p for p, _ in want]
    for (_, g), (_, w) in zip(got, want):
        assert g.schema.equals(w.schema) and g.equals(w)


# ------------------------------------- (a) the same rows in the same order
@pytest.fixture(params=["x64", "x32"])
def precision(request):
    K.set_precision(request.param)
    yield request.param
    K.set_precision(None)


@pytest.mark.parametrize("width", [1, 2, 6])
def test_same_batches_at_every_width_as_the_per_batch_loop(monkeypatch, precision, width):
    table = MemoryTable(_ragged_partitions(), SCHEMA)
    want = _per_batch_reference(*_node(table))
    assert sum(b.num_rows for _, b in want) > 4000 and {p for p, _ in want} == {0, 1, 2}
    _force_width(monkeypatch, width)
    node, tctx = _node(table)
    got = list(node.execute_exchanged(tctx))
    assert _gang_threads() == []
    _same_sequence(got, want)
    m = node.metrics.to_dict()
    assert m["exchange_workers"] == width
    assert m["mesh_exchange_rows"] == sum(b.num_rows for _, b in want)
    for k in ("exchange_wait_ns", "exchange_pull_ns", "repart_time_ns",
              "exchange_convert_ns", "exchange_encode_ns", "device_time_ns", "exchange_decode_ns"):
        assert m[k] > 0, k


def test_strings_flattened_apart_and_adopted_in_order_are_one_encoders_codes():
    """A partition's own dictionaries, mapped at the hand-over: the codes,
    and the exchanger's dictionary, are those of one encoder fed the
    partitions one after the other (nulls included)."""
    schema = pa.schema([("s", pa.string()), ("k", pa.int64())])
    parts = [
        pa.record_batch({"s": pa.array(["b", None, "a", "b"]), "k": pa.array([1, 2, 3, 4])}, schema=schema),
        pa.record_batch({"s": pa.array(["c", "a", None]), "k": pa.array([5, 6, 7])}, schema=schema),
        pa.record_batch({"s": pa.array(["d", "b"]), "k": pa.array([8, 9])}, schema=schema),
    ]
    one = M.ExchangeLayout(schema)
    want = [one.flatten(b, one.encoders) for b in parts]
    pooled = M.ExchangeLayout(schema)
    apart = []
    for b in reversed(parts):  # flattened in any order ...
        enc = pooled.new_encoders()
        apart.append((pooled.flatten(b, enc), enc))
    for (cols, enc), w in zip(reversed(apart), want):  # ... adopted in partition order
        pooled.adopt_codes(cols, enc)
        assert all(np.array_equal(c, x) and c.dtype == x.dtype for c, x in zip(cols, w))
    assert pooled.encoders[0].to_arrow(pa.string()).equals(one.encoders[0].to_arrow(pa.string()))
    # an exchanger over the input's fields and the destination column takes
    # the layout's dictionaries, and a capacity retry keeps them
    ext = pa.schema(list(schema) + [pa.field("__part", pa.int32())])
    ex = M.BatchExchanger(M.make_mesh(2), ext, 8, share_from=pooled)
    assert ex.encoders is pooled.encoders and ex.n_cols == pooled.n_cols + 2
    assert M.BatchExchanger(M.make_mesh(2), ext, 16, share_from=ex).encoders is pooled.encoders
    with pytest.raises(ValueError, match="columns"):
        ex.to_columns(parts[0])  # the input's fields alone are not the exchanger's schema
    with pytest.raises(ValueError, match="string fields"):
        M.BatchExchanger(M.make_mesh(2), pa.schema([("k", pa.int64())]), 8, share_from=pooled)


# ------------------------------- (b) empty partitions, and nothing at all
@pytest.mark.parametrize("width", [1, 4])
def test_an_input_of_empty_partitions_exchanges_nothing(monkeypatch, width):
    rng = np.random.default_rng(1)
    table = MemoryTable([[], [_batch(rng, 0)], [], [_batch(rng, 0), _batch(rng, 0)]], SCHEMA)
    _force_width(monkeypatch, width)
    node, tctx = _node(table)
    assert list(node.execute_exchanged(tctx)) == []
    m = node.metrics.to_dict()
    assert m["exchange_workers"] == width and "mesh_exchange_rows" not in m
    assert _gang_threads() == []


# ------------------------- (c) an error, a cancel: closed, joined, in turn
class _Scripted(MemoryTable):
    """A MemoryTable whose scan runs ``on_batch(partition, i)`` before it
    yields a partition's i-th batch, and records which partitions' scans
    were opened and which were closed."""

    def __init__(self, partitions, schema, on_batch):
        super().__init__(partitions, schema)
        self.on_batch = on_batch
        self.yielded, self.opened, self.closed = [], [], []

    def scan_partition(self, partition, projection, batch_size=8192):
        self.opened.append(partition)
        try:
            for i, b in enumerate(super().scan_partition(partition, projection, batch_size)):
                self.on_batch(partition, i)
                self.yielded.append((partition, i))
                yield b
        finally:
            self.closed.append(partition)


def _scripted(on_batch, n_parts=6, batches=4, cfg=None):
    rng = np.random.default_rng(7)
    table = _Scripted([[_batch(rng, 128) for _ in range(batches)] for _ in range(n_parts)], SCHEMA, on_batch)
    return (table, *_node(table, cfg))


@pytest.mark.parametrize("width", [1, 4])
def test_an_error_in_a_partition_surfaces_at_its_turn_with_everything_closed(monkeypatch, width):
    _force_width(monkeypatch, width)

    def on_batch(p, i):
        if (p, i) in ((3, 2), (4, 0)):
            raise RuntimeError(f"partition {p} broke")

    table, node, tctx = _scripted(on_batch)
    # partition 4 fails first on the clock at width 4; partition 3's error
    # is the one whose turn comes first.  Not a MeshExchangeError: an
    # input's failure is the stage's, not a reason to hash-split instead
    with pytest.raises(RuntimeError, match="partition 3 broke"):
        list(node.execute_exchanged(tctx))
    assert _gang_threads() == []
    assert sorted(table.closed) == sorted(table.opened) and 3 in table.opened
    assert all(p <= 3 + width for p in table.opened)
    m = node.metrics.to_dict()
    assert m["exchange_workers"] == width and "mesh_exchange_rows" not in m


@pytest.mark.parametrize("width", [1, 4])
def test_a_cancel_ends_the_workers(monkeypatch, width):
    _force_width(monkeypatch, width)
    cancel = threading.Event()

    def on_batch(p, i):
        if (p, i) == (2, 1):
            cancel.set()

    table, node, _ = _scripted(on_batch)
    with pytest.raises(Cancelled):
        list(node.execute_exchanged(TaskContext(config=BallistaConfig({}), cancel_event=cancel)))
    assert _gang_threads() == []
    assert sorted(table.closed) == sorted(table.opened)
    # every partition checks at every batch: none was read to its end after
    assert len(table.yielded) < 6 * 4 and "mesh_exchange_rows" not in node.metrics.to_dict()


def test_an_error_stops_the_partitions_still_in_the_making(monkeypatch):
    """The pool's ``stop``: once partition 0 has failed, the partitions in
    the making end at their next batch instead of being read to the end."""
    _force_width(monkeypatch, 4)
    seen = {}
    real = mesh_stage._in_partition_order

    def spy(prepare, n_parts, width, stop):
        seen["stop"] = stop
        return real(prepare, n_parts, width, stop)

    def on_batch(p, i):
        if (p, i) == (0, 1):
            raise RuntimeError("partition 0 broke")
        if p > 0 and i == 1:
            assert seen["stop"].wait(30)  # held until the stage is closing

    monkeypatch.setattr(mesh_stage, "_in_partition_order", spy)
    table, node, tctx = _scripted(on_batch)
    with pytest.raises(RuntimeError, match="partition 0 broke"):
        list(node.execute_exchanged(tctx))
    assert _gang_threads() == [] and sorted(table.closed) == sorted(table.opened)
    # the first four were submitted; one not yet started when 0 failed never opens
    assert 0 in table.opened and set(table.opened) <= {0, 1, 2, 3}
    assert all(i <= 1 for _, i in table.yielded)


# ------------------------------------------- (d) the ceiling, the fallback
@pytest.mark.parametrize("width", [1, 4])
def test_past_the_row_ceiling_raises_and_the_writer_falls_back(monkeypatch, tmp_path, width):
    from arrow_ballista_tpu.shuffle import memory_store
    from arrow_ballista_tpu.shuffle.execution_plans import ShuffleWriterExec

    _force_width(monkeypatch, width)
    cfg = BallistaConfig({"ballista.mesh.exchange_max_rows": "1000"})
    table = MemoryTable(_ragged_partitions(), SCHEMA)
    node, tctx = _node(table, cfg)
    before = MeshRepartitionExec.exchanges_completed
    # partition 0 holds 2,001 rows: over the ceiling before any other
    with pytest.raises(MeshExchangeError, match="exchange_max_rows"):
        list(node.execute_exchanged(tctx))
    assert _gang_threads() == []
    # the running count, not one partition's: about 431 rows pass the filter
    # in the first non-empty partition, about 216 more in the next
    small = MemoryTable([p for p in _ragged_partitions() if sum(b.num_rows for b in p) < 1000], SCHEMA)
    node, tctx = _node(small, BallistaConfig({"ballista.mesh.exchange_max_rows": "500"}))
    with pytest.raises(MeshExchangeError, match=r"\(6\d\d > 500\)"):
        list(node.execute_exchanged(tctx))
    assert "mesh_exchange_rows" not in node.metrics.to_dict() and _gang_threads() == []

    node, _ = _node(table, cfg)
    writer = ShuffleWriterExec("job32", 4, node, str(tmp_path), node.partitioning)
    try:
        stats = writer.execute_shuffle_write(0, TaskContext(config=cfg, work_dir=str(tmp_path)))
    finally:
        memory_store.clear()
    assert writer.metrics.to_dict()["mesh_exchange_fallback"] == 1
    assert MeshRepartitionExec.exchanges_completed == before
    total = sum(_kept(b) for p in _ragged_partitions() for b in p)
    assert sum(s.num_rows for s in stats) == total and len(stats) == N_OUT


@pytest.mark.parametrize("width", [1, 6])
def test_partitions_each_under_the_ceiling_and_together_over_it_stop_every_worker(monkeypatch, width):
    """The ceiling bounds what the stage BUFFERS: the workers sum the rows
    pulled at every batch, and when the sum passes the ceiling they all stop
    pulling; the error is the ceiling's at whichever partition's turn
    comes first, so the writer falls back."""
    _force_width(monkeypatch, width)
    max_rows, n_parts, batches, rows = 1000, 8, 8, 128
    cfg = BallistaConfig({"ballista.mesh.exchange_max_rows": str(max_rows)})
    table, node, tctx = _scripted(lambda p, i: None, n_parts=n_parts, batches=batches, cfg=cfg)
    kept = [[_kept(b) for b in part] for part in table.partitions]
    assert all(sum(part) < max_rows for part in kept) and sum(map(sum, kept)) > 5 * max_rows
    with pytest.raises(MeshExchangeError, match="exchange_max_rows"):
        list(node.execute_exchanged(tctx))
    assert _gang_threads() == [] and sorted(table.closed) == sorted(table.opened)
    assert "mesh_exchange_rows" not in node.metrics.to_dict()
    # a worker may count the batch it holds when the sum passes and pull one
    # more before it sees the stop: the ceiling and two batches a worker
    pulled = sum(kept[p][i] for p, i in table.yielded)
    assert max_rows < pulled <= max_rows + 2 * width * rows
    assert len(table.opened) <= width + 1


@pytest.mark.parametrize("width", [1, 4])
def test_a_partition_coalesced_in_several_runs_gives_the_same_batches(monkeypatch, width):
    """A partition past ``_COALESCE_BYTES`` (a string column's int32
    offsets end at 2 GiB) is coalesced, hashed and flattened a run at a
    time against the partition's one set of dictionaries."""
    table = MemoryTable(_ragged_partitions(), SCHEMA)
    want = _per_batch_reference(*_node(table))
    _force_width(monkeypatch, width)
    monkeypatch.setattr(mesh_stage, "_COALESCE_BYTES", 20_000)
    runs = [len(list(mesh_stage._byte_groups(p, 20_000))) for p in _ragged_partitions()]
    assert max(runs) >= 3 and min(runs) == 0
    node, tctx = _node(table)
    _same_sequence(list(node.execute_exchanged(tctx)), want)


def test_byte_groups_keep_the_order_and_the_limit():
    rng = np.random.default_rng(3)
    parts = [_batch(rng, n) for n in (10, 400, 10, 10, 900, 5)]
    limit = sum(b.nbytes for b in parts[:3])
    groups = list(mesh_stage._byte_groups(parts, limit))
    assert [b for g in groups for b in g] == parts and len(groups) == 4
    # a batch larger than the limit alone is a run of its own
    assert [parts[4]] in groups
    assert all(sum(b.nbytes for b in g) <= limit or len(g) == 1 for g in groups)
    assert list(mesh_stage._byte_groups([], limit)) == [] == list(mesh_stage._byte_groups([], None))
    assert list(mesh_stage._byte_groups(parts, 1 << 40)) == [parts] == list(mesh_stage._byte_groups(parts, None))


# --------------------------------------- (e) what engages the pool, and not
def test_a_device_stage_below_is_prepared_inline(monkeypatch):
    from arrow_ballista_tpu.ops.stage_compiler import TpuStageExec

    _force_width(monkeypatch, 8)
    cfg = BallistaConfig({"ballista.tpu.min_rows": "0", "ballista.mesh.enable": "false",
                          "ballista.shuffle.partitions": "2"})
    ctx = SessionContext(cfg)
    ctx.register_table("t", MemoryTable(_ragged_partitions(), SCHEMA))
    plan = ctx.sql("select s, sum(v) as sv, count(*) as c from t group by s").physical_plan()
    stack, stages = [plan], []
    while stack:
        n = stack.pop()
        if isinstance(n, TpuStageExec) and n.output_partitioning().n > 1:
            stages.append(n)
        stack.extend(n.children())
    (stage,) = stages
    assert mesh_stage._holds_device_stage(plan) and not mesh_stage._holds_device_stage(_node(MemoryTable([], SCHEMA))[0])
    node = MeshRepartitionExec(stage, Partitioning("hash", 2, (pe.Col(0, "s"),)))
    got = list(node.execute_exchanged(TaskContext(config=cfg)))
    assert node.metrics.to_dict()["exchange_workers"] == 1 and _gang_threads() == []
    # the partial states of every input partition, each on the side its key hashes to
    distinct = sum(len({s for b in p for s in b.column(4).to_pylist()}) for p in _ragged_partitions())
    assert sum(b.num_rows for _, b in got) == distinct
    sides = {}
    for p, b in got:
        for s in b.column(0).to_pylist():
            assert sides.setdefault(s, p) == p


def test_width_follows_what_the_gang_stage_observes(monkeypatch):
    """cores, task slots and partitions: the gang stage's rule, unchanged."""
    table = MemoryTable(_ragged_partitions(), SCHEMA)
    monkeypatch.setattr(mesh_stage, "_usable_cores", lambda: 13)
    node, _ = _node(table)
    list(node.execute_exchanged(TaskContext(config=BallistaConfig({}), task_slots=4)))
    assert node.metrics.to_dict()["exchange_workers"] == 6
    two = MemoryTable(_ragged_partitions()[:2], SCHEMA)
    node, _ = _node(two)
    list(node.execute_exchanged(TaskContext(config=BallistaConfig({}), task_slots=4)))
    assert node.metrics.to_dict()["exchange_workers"] == 2


# ------------------------------------------------------------ (f) the spans
@pytest.mark.parametrize("width", [1, 4])
def test_partition_and_handover_spans_hang_under_the_tasks_span(monkeypatch, width):
    _force_width(monkeypatch, width)
    parts = _ragged_partitions()
    node, tctx = _node(MemoryTable(parts, SCHEMA))
    trace.configure(enabled=True, process="local")
    try:
        get_recorder().drain()
        trace_id = trace.new_id()
        with trace.root_span("job", trace_id), trace.span("task.execute") as task:
            list(node.execute_exchanged(tctx))
        task_id = task.span_id
    finally:
        trace.configure(enabled=False)
    spans = [s for s in get_recorder().drain() if s["trace"] == trace_id]
    made = sorted((s for s in spans if s["name"] == "exchange.partition"), key=lambda s: s["attrs"]["partition"])
    handed = [s for s in spans if s["name"] == "exchange.handover"]
    assert len(made) == len(handed) == len(parts)
    assert all(s["parent"] == task_id for s in made + handed)
    for s, p in zip(made, parts):
        a = s["attrs"]
        assert a["rows"] == sum(_kept(b) for b in p)
        assert a["batches"] == sum(1 for b in p if _kept(b))
        assert a["pull_ns"] > 0 and (a["convert_ns"] > 0) == bool(a["rows"]) == (a["hash_ns"] > 0)
        assert a["worker"].startswith("gang") == (width > 1)
    assert all(s["attrs"]["wait_ns"] >= 0 for s in handed)
    # the older three stay where they were
    for name in ("exchange.encode", "exchange.device", "exchange.decode"):
        (s,) = [x for x in spans if x["name"] == name]
        assert s["parent"] == task_id
