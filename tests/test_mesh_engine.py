"""Mesh execution wired into the ENGINE (VERDICT.md round-1 item 3).

The round-1 gap: parallel/mesh.py was only reachable from tests and the
graft entry.  These tests prove the collectives now run inside the real
query path — SessionContext locally, BallistaContext through the
scheduler/executor — replacing the ShuffleWriter→Flight→ShuffleReader hop
for eligible stages, with zero shuffle files when the memory data plane
is on.
"""

import glob
import os

import pyarrow as pa
import pytest

from arrow_ballista_tpu import BallistaConfig, SessionContext
from arrow_ballista_tpu.parallel.mesh_stage import MeshGangExec


def _cfg(**extra):
    settings = {
        "ballista.tpu.min_rows": "0",
        "ballista.shuffle.partitions": "2",
    }
    settings.update({k: str(v) for k, v in extra.items()})
    return BallistaConfig(settings)


def _register(ctx):
    from benchmarks.tpch.datagen import register_all

    register_all(ctx, sf=0.01, partitions=4)


# ------------------------------------------------------------ local engine
def test_local_plan_contains_mesh_gang():
    from benchmarks.tpch.queries import QUERIES

    ctx = SessionContext(_cfg())
    _register(ctx)
    assert "MeshGangExec" in ctx.sql(QUERIES[1]).explain()


def test_local_q1_mesh_uses_collectives_and_matches():
    from benchmarks.tpch.queries import QUERIES

    ctx_mesh = SessionContext(_cfg())
    ctx_off = SessionContext(
        _cfg(**{"ballista.mesh.enable": "false", "ballista.tpu.enable": "false"})
    )
    _register(ctx_mesh)
    _register(ctx_off)

    df = ctx_mesh.sql(QUERIES[1])
    plan = df.physical_plan()
    got = ctx_mesh.execute(plan)
    want = ctx_off.sql(QUERIES[1]).collect()

    # the mesh program actually ran (not the sequential fallback)
    gangs = _find(plan, MeshGangExec)
    assert gangs, "no MeshGangExec in executed plan"
    m = gangs[0].metrics.to_dict()
    assert m.get("mesh_devices") == 8, m
    assert m.get("mesh_rows_in", 0) > 0, m
    assert "mesh_fallback" not in m, m

    _assert_tables_close(got, want)


def _assert_tables_close(got, want, rel=1e-9):
    """One tolerance-compare for every mesh test (tables pre-aligned)."""
    assert got.num_rows == want.num_rows
    for name in want.schema.names:
        for x, y in zip(
            got.column(name).to_pylist(), want.column(name).to_pylist()
        ):
            if isinstance(x, float) and x is not None and y is not None:
                assert y == pytest.approx(x, rel=rel), name
            else:
                assert x == y, name


def _find(plan, cls):
    out = []
    stack = [plan]
    while stack:
        n = stack.pop()
        if isinstance(n, cls):
            out.append(n)
        stack.extend(n.children())
    return out


# ------------------------------------------------------- distributed plan
def test_distributed_planner_gangs_partial_agg_stage():
    from arrow_ballista_tpu.scheduler.planner import DistributedPlanner

    ctx = SessionContext(_cfg(**{"ballista.tpu.enable": "true"}))
    _register(ctx)
    from benchmarks.tpch.queries import QUERIES

    # unaccelerated physical plan, as the scheduler sees it
    from arrow_ballista_tpu.exec.planner import PhysicalPlanner

    phys = PhysicalPlanner(ctx.config).create_physical_plan(
        ctx.sql(QUERIES[1]).optimized_plan()
    )
    stages = DistributedPlanner("/tmp/unused", ctx.config).plan_query_stages(
        "jobx", phys
    )
    gang_stages = [
        s for s in stages if isinstance(s.input, MeshGangExec)
    ]
    assert gang_stages, "partial-agg stage was not gang-wrapped"
    for s in gang_stages:
        assert s.output_partitioning().n == 1  # one task for the scheduler


def test_mesh_gang_serde_roundtrip():
    from arrow_ballista_tpu.serde import BallistaCodec

    ctx = SessionContext(_cfg())
    _register(ctx)
    from arrow_ballista_tpu.exec.planner import PhysicalPlanner
    from arrow_ballista_tpu.scheduler.planner import DistributedPlanner
    from benchmarks.tpch.queries import QUERIES

    phys = PhysicalPlanner(ctx.config).create_physical_plan(
        ctx.sql(QUERIES[6]).optimized_plan()
    )
    stages = DistributedPlanner("/tmp/unused", ctx.config).plan_query_stages(
        "joby", phys
    )
    gang = next(s for s in stages if isinstance(s.input, MeshGangExec))
    blob = BallistaCodec.encode_physical(gang)
    back = BallistaCodec.decode_physical(blob, "/tmp/unused")
    assert isinstance(back.input, MeshGangExec)
    assert back.input.n_devices == gang.input.n_devices
    assert str(back.input.input.schema) == str(gang.input.input.schema)


# ------------------------------------------------- distributed end-to-end
def test_distributed_q1_zero_shuffle_files_matches_flight_path(tmp_path):
    """THE round-2 acceptance test: q1 through BallistaContext with mesh
    gang + memory data plane writes NO shuffle files and matches the
    disk+Flight answer."""
    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.shuffle import memory_store
    from benchmarks.tpch.datagen import gen_lineitem
    from benchmarks.tpch.queries import QUERIES

    import pyarrow.parquet as pq

    li = gen_lineitem(0.01)
    pq.write_table(li, str(tmp_path / "lineitem.parquet"))

    def run(mesh: bool, work_dir: str):
        cfg = _cfg(
            **{
                "ballista.mesh.enable": str(mesh).lower(),
                "ballista.shuffle.to_memory": str(mesh).lower(),
                "ballista.tpu.enable": str(mesh).lower(),
            }
        )
        bctx = BallistaContext.standalone(config=cfg, work_dir=work_dir)
        try:
            bctx.register_parquet("lineitem", str(tmp_path / "lineitem.parquet"))
            out = bctx.sql(QUERIES[1]).collect()
            return out, memory_store.job_ids()
        finally:
            bctx.close()

    flight_dir = str(tmp_path / "wd_flight")
    mesh_dir = str(tmp_path / "wd_mesh")
    want, _ = run(False, flight_dir)
    memory_store.clear()
    got, mem_jobs = run(True, mesh_dir)

    # the flight path wrote shuffle files; the mesh path wrote NONE
    assert glob.glob(os.path.join(flight_dir, "**", "*.arrow"), recursive=True)
    assert not glob.glob(os.path.join(mesh_dir, "**", "*.arrow"), recursive=True)
    # its exchanges went through the memory plane, and close() released them
    assert mem_jobs
    assert not memory_store.job_ids()

    got = got.sort_by(
        [(got.column_names[0], "ascending"), (got.column_names[1], "ascending")]
    )
    want = want.sort_by(
        [(want.column_names[0], "ascending"), (want.column_names[1], "ascending")]
    )
    _assert_tables_close(got, want)


def test_gang_streaming_shards_unequal_partitions():
    """Round-3: gang stages stream per-partition shards to devices (no
    host concat).  Unequal partition sizes and n_parts != n_devices force
    the per-device pad/assemble path; answers must still match."""
    import numpy as np

    from arrow_ballista_tpu.catalog import MemoryTable

    rng = np.random.default_rng(3)
    n = 10_000
    t = pa.table(
        {
            "g": pa.array(rng.integers(0, 7, n), pa.int64()),
            "v": pa.array(rng.uniform(0, 100, n)),
        }
    )
    sql = "select g, sum(v), count(*), min(v), max(v) from t group by g order by g"

    # 5 partitions on an 8-device mesh; MemoryTable splits unevenly enough
    ctx_mesh = SessionContext(_cfg())
    ctx_mesh.register_table("t", MemoryTable.from_table(t, 5))
    ctx_off = SessionContext(
        _cfg(**{"ballista.mesh.enable": "false", "ballista.tpu.enable": "false"})
    )
    ctx_off.register_table("t", MemoryTable.from_table(t, 5))

    df = ctx_mesh.sql(sql)
    plan = df.physical_plan()
    got = ctx_mesh.execute(plan)
    want = ctx_off.sql(sql).collect()

    gangs = _find(plan, MeshGangExec)
    assert gangs and "mesh_fallback" not in gangs[0].metrics.to_dict()
    _assert_tables_close(got, want)


def test_memory_partitions_served_over_flight(tmp_path):
    """Cross-executor reads of memory partitions go through DoGet."""
    from arrow_ballista_tpu.flight.client import BallistaClient
    from arrow_ballista_tpu.flight.server import FlightServerHandle
    from arrow_ballista_tpu.shuffle import memory_store

    batch = pa.record_batch({"x": pa.array([1, 2, 3], pa.int64())})
    path = memory_store.put("jobf", 1, 0, 0, batch.schema, [batch])

    handle = FlightServerHandle(str(tmp_path), "127.0.0.1", 0).start()
    try:
        client = BallistaClient.get("127.0.0.1", handle.port)
        got = list(client.fetch_partition("jobf", 1, 0, path))
        assert sum(b.num_rows for b in got) == 3
    finally:
        handle.shutdown()
        memory_store.delete_job("jobf")


def test_mesh_gang_with_sort_algorithm():
    """The gang kernel shares make_partial_agg_kernel, so on real TPU
    hardware high cardinality routes to the SORT strategy INSIDE the
    shard_map program — lax.sort_key_val + segmented associative_scan
    must trace and run under the mesh (forced here on the CPU mesh)."""
    from arrow_ballista_tpu.ops import kernels as K
    from benchmarks.tpch.queries import QUERIES

    K.set_agg_algorithm("sort")
    try:
        ctx_mesh = SessionContext(_cfg())
        _register(ctx_mesh)
        plan = ctx_mesh.sql(QUERIES[1]).physical_plan()
        got = ctx_mesh.execute(plan)
        gangs = _find(plan, MeshGangExec)
        assert gangs
        m = gangs[0].metrics.to_dict()
        assert "mesh_fallback" not in m, m
    finally:
        K.set_agg_algorithm(None)

    ctx_off = SessionContext(
        _cfg(**{"ballista.mesh.enable": "false", "ballista.tpu.enable": "false"})
    )
    _register(ctx_off)
    want = ctx_off.sql(QUERIES[1]).collect()
    key = [("l_returnflag", "ascending"), ("l_linestatus", "ascending")]
    _assert_tables_close(got.sort_by(key), want.sort_by(key), rel=1e-6)


def test_mesh_gang_highcard_gid_mode():
    """highcard_mode=gid pins a groups~rows aggregate on the gang's
    GID-TABLE path (no mesh_fallback, no keyed route) with the sort
    strategy, matching the CPU oracle — the capacity ceiling is raised
    to fit every group."""
    import numpy as np

    from arrow_ballista_tpu.ops import kernels as K

    rng = np.random.default_rng(13)
    n = 1 << 17
    tbl = pa.table(
        {
            "g": pa.array(rng.permutation(n).astype(np.int64)),
            "v": pa.array(rng.uniform(0, 100, n)),
        }
    )
    sql = "select g, sum(v) as s, count(*) as c from t group by g"

    off = SessionContext(
        _cfg(**{"ballista.mesh.enable": "false", "ballista.tpu.enable": "false"})
    )
    off.register_arrow_table("t", tbl, partitions=4)
    want = off.sql(sql).collect().sort_by([("g", "ascending")])

    K.set_agg_algorithm("sort")
    try:
        ctx = SessionContext(
            _cfg(
                **{
                    "ballista.tpu.highcard_mode": "gid",
                    "ballista.tpu.max_capacity": str(1 << 19),
                }
            )
        )
        ctx.register_arrow_table("t", tbl, partitions=4)
        plan = ctx.sql(sql).physical_plan()
        got = ctx.execute(plan)
        gangs = _find(plan, MeshGangExec)
        assert gangs
        m = gangs[0].metrics.to_dict()
        assert "mesh_fallback" not in m, m
        assert "mesh_keyed" not in m, m  # gid path, not the keyed gang
    finally:
        K.set_agg_algorithm(None)

    _assert_tables_close(got.sort_by([("g", "ascending")]), want, rel=1e-6)


def test_mesh_gang_highcard_keyed_across_shards(monkeypatch):
    """Keyed gang routing (highcard_mode=device — 'auto' resolves to
    the C++ hash handoff on the CPU platform these tests run on): a
    groups~rows gang runs the KEYED reduction per shard — every device
    concurrently — with a [distinct]-sized host merge (mesh_keyed
    metric), matching the CPU oracle.  Groups straddle shard
    boundaries, so the merge must combine cross-shard states by key."""
    import numpy as np

    from arrow_ballista_tpu.ops import stage_compiler as SC

    # per-partition batches cap first-batch group counts well below the
    # production threshold: shrink the detector for the fixture
    monkeypatch.setattr(SC, "_HIGHCARD_MIN_GROUPS", 1024)

    rng = np.random.default_rng(31)
    n = 1 << 17
    # every group appears in EVERY partition (round-robin keys)
    g = np.arange(n) % (n // 8)
    tbl = pa.table(
        {
            "g": pa.array(g.astype(np.int64)),
            "v": pa.array(rng.uniform(0, 100, n)),
            "w": pa.array(rng.integers(0, 1000, n).astype(np.int64)),
        }
    )
    sql = (
        "select g, sum(v) as s, count(*) as c, min(w) as mn, max(w) as mx "
        "from t group by g"
    )

    off = SessionContext(
        _cfg(**{"ballista.mesh.enable": "false", "ballista.tpu.enable": "false"})
    )
    off.register_arrow_table("t", tbl, partitions=4)
    want = off.sql(sql).collect().sort_by([("g", "ascending")])

    ctx = SessionContext(_cfg(**{
        "ballista.tpu.max_capacity": str(1 << 19),
        "ballista.tpu.highcard_mode": "device",
    }))
    ctx.register_arrow_table("t", tbl, partitions=4)
    plan = ctx.sql(sql).physical_plan()
    got = ctx.execute(plan)
    gangs = _find(plan, MeshGangExec)
    assert gangs
    m = gangs[0].metrics.to_dict()
    assert m.get("mesh_keyed", 0) >= 1, m
    assert "mesh_fallback" not in m, m
    assert m.get("mesh_devices") == 8, m
    # the first batch chose the route before its partition uploaded
    assert m["gang_batches"] == 0 and m["gang_uploads"] == 0, m
    _assert_tables_close(got.sort_by([("g", "ascending")]), want, rel=1e-6)


def test_mesh_gang_highcard_auto_cpu_sequential_fallback(monkeypatch):
    """Platform default on the CPU backend: 'auto' routes a groups~rows
    gang to the sequential fallback (each partition on the C++ hash
    aggregate — the measured winner off-accelerator), NOT the keyed
    gang, and results still match the oracle."""
    import numpy as np

    from arrow_ballista_tpu.ops import stage_compiler as SC

    monkeypatch.setattr(SC, "_HIGHCARD_MIN_GROUPS", 1024)
    rng = np.random.default_rng(37)
    n = 1 << 15
    g = np.arange(n) % (n // 8)
    tbl = pa.table(
        {
            "g": pa.array(g.astype(np.int64)),
            "v": pa.array(rng.uniform(0, 100, n)),
        }
    )
    sql = "select g, sum(v) as s, count(*) as c from t group by g"

    off = SessionContext(
        _cfg(**{"ballista.mesh.enable": "false", "ballista.tpu.enable": "false"})
    )
    off.register_arrow_table("t", tbl, partitions=4)
    want = off.sql(sql).collect().sort_by([("g", "ascending")])

    ctx = SessionContext(_cfg())  # highcard_mode defaults to auto
    ctx.register_arrow_table("t", tbl, partitions=4)
    plan = ctx.sql(sql).physical_plan()
    got = ctx.execute(plan)
    gangs = _find(plan, MeshGangExec)
    assert gangs
    m = gangs[0].metrics.to_dict()
    assert m.get("mesh_fallback", 0) >= 1, m
    assert "mesh_keyed" not in m, m
    # the first batch left the gang path before its partition uploaded
    assert m["gang_batches"] == 0 and m["gang_uploads"] == 0, m
    assert m["gang_upload_bytes"] == 0, m
    _assert_tables_close(got.sort_by([("g", "ascending")]), want, rel=1e-6)


# ------------------------------------------------- phase self-times (PR 26)
GANG_PHASES = (
    "gang_scan_ns", "key_encode_time_ns", "gang_convert_ns", "gang_upload_ns",
    "gang_assemble_ns", "gang_step_ns", "gang_materialize_ns",
)
# since PR 29: what the task thread's wall splits into, and what is summed
# over the workers that prepare partitions side by side
GANG_TASK_PHASES = (
    "gang_wait_ns", "gang_merge_ns", "gang_upload_ns", "gang_assemble_ns",
    "gang_step_ns", "gang_materialize_ns",
)
GANG_WORKER_PHASES = ("gang_scan_ns", "key_encode_time_ns", "gang_convert_ns")


class _UploadSpy:
    """Wraps ``jax.device_put`` and keeps the host arrays of every call that
    hands over a list (the gang stage's one call a partition); calls with a
    single array (``assemble_shards``' placements) pass through unseen."""

    def __init__(self, monkeypatch):
        import jax

        self.calls: list[tuple[list, object]] = []
        real = jax.device_put

        def device_put(x, device=None, **kw):
            if isinstance(x, list):
                self.calls.append((list(x), device))
            return real(x, device, **kw)

        monkeypatch.setattr(jax, "device_put", device_put)


def test_local_gang_q1_counts_every_phase_once(monkeypatch):
    """A local gang q1: MeshGangExec carries every phase counter, the counts
    follow from the input (one device_put a non-empty partition, carrying
    every column), the task thread's six self times (wait, merge, upload,
    assemble, step, materialize) sum to at most the one wall, and the
    workers' three (scan, encode, convert) to at most that wall a worker."""
    from benchmarks.tpch.queries import QUERIES
    from arrow_ballista_tpu.exec.operators import TaskContext
    from arrow_ballista_tpu.ops.stage_compiler import TpuStageExec

    from arrow_ballista_tpu.catalog import MemoryTable
    from benchmarks.tpch.datagen import gen_table

    cfg = _cfg()
    ctx = SessionContext(cfg)
    lineitem = gen_table("lineitem", 0.01)
    per = -(-lineitem.num_rows // 4)
    parts = [
        lineitem.slice(i * per, per).combine_chunks().to_batches(max_chunksize=4096)
        for i in range(4)
    ]
    parts.insert(2, [])  # an empty partition uploads nothing
    ctx.register_table("lineitem", MemoryTable(parts))
    plan = ctx.sql(QUERIES[1]).physical_plan()
    spy = _UploadSpy(monkeypatch)
    ctx.execute(plan)

    (gang,) = _find(plan, MeshGangExec)
    m = gang.metrics.to_dict()
    (tpu,) = _find(gang, TpuStageExec)
    source = tpu.fused.source
    n_parts = source.output_partitioning().n
    batches = [
        b for p in range(n_parts)
        for b in source.execute(p, TaskContext(config=cfg)) if b.num_rows
    ]
    assert n_parts == 5 and len(batches) > n_parts
    columns = 2 + len(tpu._flat_names)  # [seg, valid, *flat_names]
    assert m["gang_partitions"] == n_parts
    assert m["gang_batches"] == len(batches)
    assert len(spy.calls) == 4  # the non-empty partitions
    assert all(len(cols) == columns for cols, _ in spy.calls)
    assert m["gang_uploads"] == 4 * columns
    assert m["gang_upload_bytes"] == sum(
        a.nbytes for cols, _ in spy.calls for a in cols
    )
    assert m["mesh_rows_in"] == sum(b.num_rows for b in batches)
    for k in GANG_PHASES + GANG_TASK_PHASES + ("mesh_stage_time_ns", "gang_cpu_ns"):
        assert m[k] >= 0, k
    wall, workers = m["mesh_stage_time_ns"], m["gang_workers"]
    assert 1 <= workers <= n_parts
    assert 0 < sum(m[k] for k in GANG_TASK_PHASES) <= wall
    assert m["gang_wait_ns"] > 0 and m["gang_merge_ns"] > 0
    assert 0 < sum(m[k] for k in GANG_WORKER_PHASES) <= workers * wall
    # the two lumps the older readers know are now sums of phases
    assert m["bridge_time_ns"] == m["gang_convert_ns"] + m["gang_upload_ns"]
    assert m["device_time_ns"] == m["gang_assemble_ns"] + m["gang_step_ns"]


# ------------------------------------------ upload per partition (PR 27)
RAGGED_SQL = (
    "select g, sum(v) as s, count(*) as c, min(v) as mn, max(v) as mx "
    "from t group by g order by g"
)


def _ragged_table():
    """Five partitions: three unequal batches, none at all, only empty
    batches, one batch, two batches.  Every v is a multiple of 1/4 and every
    sum stays under 2**24 quarters, so float32 sums are exact in any order
    and two device paths can be held to the same bits."""
    import numpy as np

    from arrow_ballista_tpu.catalog import MemoryTable

    rng = np.random.default_rng(27)
    schema = pa.schema([("g", pa.int64()), ("v", pa.float64())])

    def batch(n):
        return pa.RecordBatch.from_arrays(
            [
                pa.array(rng.integers(0, 7, n), pa.int64()),
                pa.array(rng.integers(0, 400, n) / 4.0, pa.float64()),
            ],
            schema=schema,
        )

    return MemoryTable(
        [
            [batch(700), batch(1), batch(1300)],
            [],
            [batch(0), batch(0)],
            [batch(513)],
            [batch(0), batch(2048), batch(90)],
        ],
        schema,
    )


def _gang_devices(n_dev):
    from arrow_ballista_tpu.parallel import mesh as M

    return list(M.make_mesh(n_dev).devices.flatten())


@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_gang_upload_per_partition_equals_sequential_on_ragged_input(
    n_dev, monkeypatch
):
    """The gang path (one upload a partition) against the sequential device
    path (one kernel call a batch), bit for bit; and what a partition hands
    to device_put is its batches in batch order, on device p % n_dev."""
    import numpy as np

    from arrow_ballista_tpu.ops.stage_compiler import TpuStageExec

    table = _ragged_table()
    seq = SessionContext(_cfg(**{"ballista.mesh.enable": "false"}))
    seq.register_table("t", table)
    seq_plan = seq.sql(RAGGED_SQL).physical_plan()
    want = seq.execute(seq_plan)
    assert _find(seq_plan, TpuStageExec) and not _find(seq_plan, MeshGangExec)

    ctx = SessionContext(_cfg(**{"ballista.mesh.devices": n_dev}))
    ctx.register_table("t", table)
    plan = ctx.sql(RAGGED_SQL).physical_plan()
    spy = _UploadSpy(monkeypatch)
    got = ctx.execute(plan)

    (gang,) = _find(plan, MeshGangExec)
    m = gang.metrics.to_dict()
    assert "mesh_fallback" not in m and m["mesh_devices"] == n_dev, m
    assert got.schema == want.schema
    assert got.to_pydict() == want.to_pydict()

    (tpu,) = _find(gang, TpuStageExec)
    columns = 2 + len(tpu._flat_names)
    assert m["gang_partitions"] == 5 and m["gang_batches"] == 6
    assert m["gang_uploads"] == 3 * columns and m["mesh_rows_in"] == 4652
    (leaf,) = tpu.leaves  # v, as one column (x64) or a hi/lo pair (x32)
    flat = list(tpu._flat_names)
    vi = 2 + flat.index(leaf if leaf in flat else f"{leaf}__hi")
    non_empty = [p for p, bs in enumerate(table.partitions) if sum(map(len, bs))]
    assert non_empty == [0, 3, 4] and len(spy.calls) == 3
    for p, (cols, device) in zip(non_empty, spy.calls):
        v = np.concatenate([b.column(1).to_numpy() for b in table.partitions[p]])
        assert device == _gang_devices(n_dev)[p % n_dev]
        assert [len(c) for c in cols] == [len(v)] * columns
        assert cols[1].all() and np.array_equal(cols[vi], v.astype(cols[vi].dtype))


def test_gang_cancel_between_batches_raises_before_the_partition_uploads(
    monkeypatch,
):
    """Cancellation is still checked at every batch: an event set while the
    stage's first batch is probed for the route stops every partition at its
    next batch, before any partition (whose upload waits for its last batch)
    has handed anything over."""
    import threading

    from arrow_ballista_tpu.errors import Cancelled
    from arrow_ballista_tpu.exec.operators import TaskContext

    cfg = _cfg()
    ctx = SessionContext(cfg)
    ctx.register_table("t", _ragged_table())
    plan = ctx.sql(RAGGED_SQL).physical_plan()
    (gang,) = _find(plan, MeshGangExec)

    from arrow_ballista_tpu.ops import stage_compiler as SC

    cancel = threading.Event()
    real_choose = SC.choose_route

    def choose_route(**kw):
        cancel.set()
        return real_choose(**kw)

    monkeypatch.setattr(SC, "choose_route", choose_route)
    spy = _UploadSpy(monkeypatch)
    with pytest.raises(Cancelled):
        list(gang.execute(0, TaskContext(config=cfg, cancel_event=cancel)))
    m = gang.metrics.to_dict()
    assert spy.calls == []
    # the probed batch is all that any partition's worker kept
    assert m["gang_batches"] == 1 and m["gang_partitions"] == 1
    assert m["gang_uploads"] == 0 and m["gang_upload_bytes"] == 0
    assert not [t for t in threading.enumerate() if t.name.startswith("gang")]


def test_scan_timer_does_not_count_its_consumer():
    """scan_time_ns stops at each yield: a consumer that sleeps 20 ms a
    batch is not counted as scan."""
    import time

    import numpy as np

    from arrow_ballista_tpu.catalog import MemoryTable
    from arrow_ballista_tpu.exec.operators import ScanExec, TaskContext

    t = pa.table({"v": pa.array(np.arange(8 * 1024, dtype=np.int64))})
    scan = ScanExec("t", MemoryTable([t.to_batches(max_chunksize=1024)]))
    cfg = _cfg()
    slept = rows = 0
    for batch in scan.execute(0, TaskContext(config=cfg)):
        t0 = time.perf_counter_ns()
        time.sleep(0.02)
        slept += time.perf_counter_ns() - t0
        rows += batch.num_rows
    m = scan.metrics.to_dict()
    assert rows == m["output_rows"] == t.num_rows
    assert slept >= 4 * 20_000_000  # several batches were consumed
    assert 0 <= m["scan_time_ns"] < slept / 2
