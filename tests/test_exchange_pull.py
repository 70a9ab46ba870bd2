"""An exchange's workers pull an input of host operators a parquet row group
at a time (``mesh_stage._PULL_ROWS`` rows a batch), not at the session's
batch size: the partition is coalesced whole anyway, so the batch size only
set how often the scan and the filter went back through Python and the GIL.
Held here, over lineitem-shaped parquet files cut in small row groups and a
session batch of 64 rows: the exchanged batches are, row for row, those of
the same exchange pulled at the session's batch size, at every width;
``exchange_pull_batches`` counts the large pulls; over a device stage the
pull keeps the session's batch size (part of the stage's program); the row
ceiling still raises and the writer's fallback, at the session's batch
size, gives the same rows; a cancel between two batches still ends it."""

import datetime
import math
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from arrow_ballista_tpu import BallistaConfig, SessionContext
from arrow_ballista_tpu.catalog import ParquetTable
from arrow_ballista_tpu.errors import Cancelled
from arrow_ballista_tpu.exec import expressions as pe
from arrow_ballista_tpu.exec.operators import (
    FilterExec, Partitioning, ScanExec, TaskContext,
)
from arrow_ballista_tpu.obs import trace
from arrow_ballista_tpu.obs.recorder import get_recorder
from arrow_ballista_tpu.parallel import mesh_stage
from arrow_ballista_tpu.parallel.mesh_stage import (
    MeshExchangeError, MeshRepartitionExec,
)
from benchmarks.tpch import datagen

BATCH = 64  # the session's batch size
ROW_GROUP = 2_500  # rows a row group of the files
N_FILES = 6
N_OUT = 3
COLUMNS = ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate", "l_shipmode"]
CUTOFF = datetime.date(1995, 3, 15)  # q3's DATE: about half the rows pass


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    """SF0.01's lineitem in ``N_FILES`` files of ``ROW_GROUP``-row groups;
    the path, and the rows of each file and those that pass the filter."""
    tbl = datagen.gen_table("lineitem", 0.01).select(COLUMNS)
    root = tmp_path_factory.mktemp("lineitem")
    per = math.ceil(tbl.num_rows / N_FILES)
    cutoff = (CUTOFF - datetime.date(1970, 1, 1)).days
    rows, kept = [], []
    for i in range(N_FILES):
        part = tbl.slice(i * per, per)
        pq.write_table(part, str(root / f"part-{i}.parquet"), row_group_size=ROW_GROUP)
        rows.append(part.num_rows)
        kept.append(int(np.sum(np.asarray(part.column("l_shipdate").cast(pa.int32())) > cutoff)))
    assert pq.ParquetFile(str(root / "part-0.parquet")).metadata.num_row_groups >= 3
    return str(root), rows, kept


class _Recorded(ParquetTable):
    """A parquet table that records the batch size each scan was asked for
    and the batches it yielded, and runs ``on_batch(partition, i)`` before
    it yields a partition's i-th batch."""

    def __init__(self, path, on_batch=None):
        super().__init__(path)
        self.on_batch = on_batch or (lambda p, i: None)
        self.sizes, self.yielded = [], []

    def scan_partition(self, partition, projection, batch_size=8192):
        self.sizes.append(batch_size)
        for i, b in enumerate(super().scan_partition(partition, projection, batch_size)):
            self.on_batch(partition, i)
            self.yielded.append((partition, i))
            yield b


def _node(path, cfg=None, on_batch=None):
    """FilterExec (q3's ``l_shipdate > DATE``) over ScanExec under the
    exchange, hashed on ``l_orderkey``; the table; a task context at
    ``BATCH`` rows a batch."""
    table = _Recorded(path, on_batch)
    scan = ScanExec("lineitem", table, None)
    kept = FilterExec(pe.Binary(pe.Col(3, "l_shipdate"), ">", pe.Lit(CUTOFF, pa.date32())), scan)
    node = MeshRepartitionExec(kept, Partitioning("hash", N_OUT, (pe.Col(0, "l_orderkey"),)))
    settings = {"ballista.batch.size": str(BATCH), **(cfg or {})}
    return node, table, TaskContext(config=BallistaConfig(settings))


def _force_width(monkeypatch, width):
    monkeypatch.setattr(mesh_stage, "_usable_cores", lambda: width)


def _same_sequence(got, want):
    assert [p for p, _ in got] == [p for p, _ in want]
    for (_, g), (_, w) in zip(got, want):
        assert g.schema.equals(w.schema) and g.equals(w)


def _gang_threads():
    return [t for t in threading.enumerate() if t.name.startswith(("gang", "exchange"))]


# ------------------------------------- the same rows, in the same order
@pytest.mark.parametrize("width", [1, 6])
def test_same_batches_as_the_pull_at_the_sessions_batch_size(monkeypatch, lineitem, width):
    path, _, kept = lineitem
    _force_width(monkeypatch, width)
    node, table, tctx = _node(path)
    got = list(node.execute_exchanged(tctx))
    assert set(table.sizes) == {mesh_stage._PULL_ROWS}
    assert tctx.batch_size == BATCH  # the task's own context is untouched
    # a file is smaller than a pull: one batch a partition
    assert node.metrics.to_dict()["exchange_pull_batches"] == N_FILES

    with monkeypatch.context() as m:
        # the pull as it was: at the session's batch size
        m.setattr(mesh_stage, "_pull_context", lambda ctx: ctx)
        ref, ref_table, rctx = _node(path)
        want = list(ref.execute_exchanged(rctx))
    assert set(ref_table.sizes) == {BATCH}
    assert ref.metrics.to_dict()["exchange_pull_batches"] == len(ref_table.yielded) > 400
    assert sum(b.num_rows for _, b in want) == sum(kept) > 25_000
    assert {p for p, _ in want} == set(range(N_OUT))
    assert pa.types.is_string(want[0][1].schema.field(4).type)
    _same_sequence(got, want)
    assert node.metrics.to_dict()["exchange_workers"] == ref.metrics.to_dict()["exchange_workers"] == width
    assert _gang_threads() == []


def test_a_session_batch_past_a_row_group_is_kept():
    big = TaskContext(config=BallistaConfig({"ballista.batch.size": str(4 << 20)}))
    assert mesh_stage._pull_context(big) is big
    small = TaskContext(config=BallistaConfig({"ballista.batch.size": "64", "ballista.shuffle.partitions": "5"}),
                        job_id="j", stage_id=4, task_slots=3, cancel_event=threading.Event())
    pulled = mesh_stage._pull_context(small)
    assert pulled.batch_size == mesh_stage._PULL_ROWS and small.batch_size == 64
    assert pulled.config.shuffle_partitions == 5
    assert (pulled.job_id, pulled.stage_id, pulled.task_slots) == ("j", 4, 3)
    assert pulled.cancel_event is small.cancel_event


# ----------------------------------------- the counter and the span agree
@pytest.mark.parametrize("pull_rows", [ROW_GROUP, 3 * ROW_GROUP])
def test_pull_batches_count_the_large_pulls_not_the_sessions_batches(monkeypatch, lineitem, pull_rows):
    """With pulls of ``pull_rows`` rows a file of ``n`` rows takes
    ceil(n / pull_rows) batches, each with rows past the filter, and the
    ``exchange.partition`` spans' ``batches`` sum to the counter."""
    path, rows, _ = lineitem
    _force_width(monkeypatch, 4)
    monkeypatch.setattr(mesh_stage, "_PULL_ROWS", pull_rows)
    node, table, tctx = _node(path)
    trace.configure(enabled=True, process="local")
    try:
        get_recorder().drain()
        trace_id = trace.new_id()
        with trace.root_span("job", trace_id), trace.span("task.execute"):
            list(node.execute_exchanged(tctx))
    finally:
        trace.configure(enabled=False)
    spans = [s for s in get_recorder().drain() if s["trace"] == trace_id and s["name"] == "exchange.partition"]
    want = sum(math.ceil(n / pull_rows) for n in rows)
    assert node.metrics.to_dict()["exchange_pull_batches"] == want == len(table.yielded)
    assert len(spans) == N_FILES and sum(s["attrs"]["batches"] for s in spans) == want
    # against the session's batches: rows / 64
    assert want * 30 < sum(rows) / BATCH
    assert set(table.sizes) == {pull_rows}


# ---------------------------- over a device stage: the session's batch size
def test_a_device_stage_below_is_pulled_at_the_sessions_batch_size(monkeypatch, lineitem):
    """The stage's program signature holds ``ctx.batch_size``: the pull
    leaves it as the session set it, so no new program is compiled."""
    from arrow_ballista_tpu.ops.stage_compiler import TpuStageExec

    path, _, _ = lineitem
    cfg = BallistaConfig({"ballista.batch.size": str(BATCH), "ballista.tpu.min_rows": "0"})
    ctx = SessionContext(cfg)
    table = _Recorded(path)
    ctx.register_table("lineitem", table)
    plan = ctx.sql(
        "select l_shipmode, sum(l_discount) from lineitem where l_orderkey < 600 group by l_shipmode"
    ).physical_plan()
    stack, stages = [plan], []
    while stack:
        n = stack.pop()
        if isinstance(n, TpuStageExec):
            stages.append(n)
        stack.extend(n.children())
    (stage,) = stages
    seen = []
    real = TpuStageExec.execute

    def spy(self, partition, tctx):
        seen.append(tctx.batch_size)
        return real(self, partition, tctx)

    monkeypatch.setattr(TpuStageExec, "execute", spy)
    node = MeshRepartitionExec(stage, Partitioning("hash", N_OUT, (pe.Col(0, "l_shipmode"),)))
    got = list(node.execute_exchanged(TaskContext(config=cfg)))
    assert sum(b.num_rows for _, b in got) > 0
    assert seen and set(seen) == {BATCH} and set(table.sizes) == {BATCH}
    m = node.metrics.to_dict()
    assert m["exchange_workers"] == 1 and m["exchange_pull_batches"] >= 1


# ----------------------------------------- the ceiling, the writer's fallback
@pytest.mark.parametrize("width", [1, 6])
def test_past_the_row_ceiling_raises_and_the_fallback_gives_the_same_rows(monkeypatch, tmp_path, lineitem, width):
    from arrow_ballista_tpu.shuffle import memory_store
    from arrow_ballista_tpu.shuffle.execution_plans import ShuffleWriterExec

    path, _, kept = lineitem
    _force_width(monkeypatch, width)
    cfg = {"ballista.mesh.exchange_max_rows": "10000"}
    node, table, tctx = _node(path, cfg)
    before = MeshRepartitionExec.exchanges_completed
    with pytest.raises(MeshExchangeError, match="exchange_max_rows"):
        list(node.execute_exchanged(tctx))
    assert _gang_threads() == [] and "mesh_exchange_rows" not in node.metrics.to_dict()

    node, table, tctx = _node(path, cfg)
    writer = ShuffleWriterExec("job-pull", 4, node, str(tmp_path), node.partitioning)
    try:
        stats = writer.execute_shuffle_write(
            0, TaskContext(config=tctx.config, work_dir=str(tmp_path))
        )
    finally:
        memory_store.clear()
    assert writer.metrics.to_dict()["mesh_exchange_fallback"] == 1
    assert MeshRepartitionExec.exchanges_completed == before
    assert sum(s.num_rows for s in stats) == sum(kept) and len(stats) == N_OUT
    # the exchange's pull at a row group, then the fallback's at the session's
    assert mesh_stage._PULL_ROWS in table.sizes and BATCH in table.sizes


# ------------------------------------------ a cancel between two batches
@pytest.mark.parametrize("width", [1, 4])
def test_a_cancel_between_two_pulled_batches_ends_the_exchange(monkeypatch, lineitem, width):
    path, rows, _ = lineitem
    _force_width(monkeypatch, width)
    monkeypatch.setattr(mesh_stage, "_PULL_ROWS", ROW_GROUP)
    cancel = threading.Event()

    def on_batch(p, i):
        if (p, i) == (2, 1):
            cancel.set()

    node, table, tctx = _node(path, on_batch=on_batch)
    tctx.cancel_event = cancel
    with pytest.raises(Cancelled):
        list(node.execute_exchanged(tctx))
    assert _gang_threads() == []
    # every partition checks at every batch: none was read to its end after
    assert len(table.yielded) < sum(math.ceil(n / ROW_GROUP) for n in rows)
    assert "mesh_exchange_rows" not in node.metrics.to_dict()
