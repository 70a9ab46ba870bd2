"""Shapes that do not follow the data (PR 28): every row count the data
decides on the join / exchange / per-partition device path is rounded up
to a bucket (``kernels.bucket_rows``) and padded (``kernels._pad``), so
that two data sets of like size share every compiled program, and a pad
row never reaches an answer.

(a) q3 through a local scheduler + executor equals the plain reference,
    on the device route; (b) a second data set compiles nothing; (c) the
    bucket's edges on the join's build side and the exchange's input;
    (d) the exchange.* and join.* spans.
"""

import json
import os
import urllib.request

import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu import BallistaConfig, SessionContext
from arrow_ballista_tpu.catalog import MemoryTable
from arrow_ballista_tpu.exec.operators import Partitioning, TaskContext
from arrow_ballista_tpu.exec import expressions as pe
from arrow_ballista_tpu.obs import trace
from arrow_ballista_tpu.obs.recorder import get_recorder
from arrow_ballista_tpu.ops import kernels as K
from arrow_ballista_tpu.ops.stage_compiler import TpuStageExec
from arrow_ballista_tpu.parallel import mesh as M
from arrow_ballista_tpu.parallel.mesh_stage import MeshRepartitionExec

from benchmark import compare, datagen, queries, reference

TABLES = ("lineitem", "orders", "customer")
# one q3 text for every data set: the seed decides the rows, not the literals
Q3_PARAMS = {"segment": "BUILDING", "date": "1995-03-15"}
OFF_ROUTE = ("join_fallback", "tpu_fallback", "device_error")


def _tpch(out_dir: str, seed: int, sf: float = 0.01, files: int = 4) -> str:
    """The benchmark's tables from ``seed``, written in this process (no
    worker pool inside a test)."""
    for name in TABLES:
        os.makedirs(os.path.join(out_dir, name), exist_ok=True)
        for i in range(files):
            datagen._write_chunk((name, i, files, sf, seed, out_dir))
    return out_dir


@pytest.fixture(scope="module")
def served():
    """A local scheduler + one executor (four task slots, in-thread tasks)
    with the device route on for partitions of any size, and its REST API."""
    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.scheduler.api import ApiServerHandle

    ctx = BallistaContext.standalone(
        config=BallistaConfig({
            "ballista.tpu.min_rows": "0",
            "ballista.client.poll_interval_seconds": "0.05",
            "ballista.client.poll_max_interval_seconds": "0.05",
        }),
        num_executors=1, concurrent_tasks=4,
    )
    scheduler, _ = ctx._standalone_handles
    api = ApiServerHandle(scheduler.server, "127.0.0.1", 0).start()
    try:
        yield ctx, f"http://127.0.0.1:{api.port}"
    finally:
        api.stop()
        ctx.close()


def _q3(served, data_dir: str):
    """(answer, {operator: summed counters} of the job, its profile's
    ``tpu`` rows merged) of q3 over the tables under ``data_dir``."""
    ctx, base = served
    for t in TABLES:
        ctx.register_parquet(t, os.path.join(data_dir, t))
    seen = set(ctx._job_ids)
    answer = ctx.sql(queries.render(3, Q3_PARAMS)).collect()
    (job_id,) = set(ctx._job_ids) - seen
    detail = json.load(urllib.request.urlopen(f"{base}/api/job/{job_id}"))
    ops: dict = {}
    for st in detail["stages"]:
        for op, vals in st["metrics"].items():
            if not op.startswith("__"):
                bag = ops.setdefault(op, {})
                for k, v in vals.items():
                    bag[k] = bag.get(k, 0) + v
    profile = json.load(urllib.request.urlopen(f"{base}/api/jobs/{job_id}/profile"))
    tpu_rows: dict = {}
    for st in profile["stages"]:
        for k, v in (st.get("tpu") or {}).items():
            tpu_rows[k] = tpu_rows.get(k, 0) + v
    return answer, ops, tpu_rows


@pytest.mark.parametrize("seed", [5, 2**31 + 9, 123456789])
def test_q3_served_on_the_device_route_equals_the_plain_reference(served, tmp_path, seed):
    data_dir = _tpch(str(tmp_path), seed)
    answer, ops, profile = _q3(served, data_dir)
    ref = reference.answer(reference.Data(data_dir), 3, Q3_PARAMS)
    verdict = compare.judge([(answer, ref)])
    assert verdict["correct"], verdict["numbers"]
    assert answer.num_rows == 10
    # the ten rows come in the query's order: revenue descending
    revenue = answer.column("revenue").to_pylist()
    assert revenue == sorted(revenue, reverse=True)
    tpu = ops["TpuStageExec"]
    assert all(not bag.get(k) for bag in ops.values() for k in OFF_ROUTE)
    assert not tpu.get("cpu_fallback") and tpu["dense_join"] >= 1
    # the folded join and the exchanges ran padded to their buckets
    assert tpu["join_build_capacity"] == K.bucket_rows(tpu["join_build_rows"])
    assert tpu["join_probe_rows"] > 0 and tpu["stage_pad_rows"] > 0
    assert 1 <= tpu["stage_batches"] < tpu["stage_uploads"]
    ex = ops["MeshRepartitionExec"]
    assert ex["mesh_exchange_rows"] > 0 and ex["mesh_exchange_padded_rows"] > 0
    assert ex["mesh_exchange_bytes"] > 4 * (ex["mesh_exchange_rows"] + ex["mesh_exchange_padded_rows"])
    # the job profile's tpu rows carry the same counters
    assert profile["exchange_rows"] == ex["mesh_exchange_rows"]
    assert profile["exchange_padded_rows"] == ex["mesh_exchange_padded_rows"]
    assert profile["exchange_bytes"] == ex["mesh_exchange_bytes"]
    assert profile["join_build_rows"] == tpu["join_build_rows"]
    assert profile["join_build_capacity"] == tpu["join_build_capacity"]
    assert profile["join_probe_rows"] == tpu["join_probe_rows"] and profile["stage_pad_rows"] == tpu["stage_pad_rows"]
    assert profile["join_build_ms"] > 0 and profile["exchange_device_ms"] > 0
    assert profile["exchange_encode_ms"] > 0 and profile["exchange_decode_ms"] > 0
    # five exchanges' pools summed in the row (PR 32); the workers' sums beside the task thread's wait
    assert profile["exchange_workers"] == ex["exchange_workers"] >= 5 and profile["exchange_wait_ms"] > 0
    assert profile["exchange_pull_ms"] > 0 and profile["exchange_hash_ms"] > 0 and profile["exchange_convert_ms"] > 0


def test_a_second_data_set_compiles_nothing(served, tmp_path):
    """The same q3 text over two data sets of different seeds: the rows
    that pass the filters differ, their buckets do not, so the second run
    obtains no executable at all."""
    rows, compiles = [], []
    for seed in (41, 42):
        _, ops, _ = _q3(served, _tpch(str(tmp_path / str(seed)), seed))
        rows.append((ops["MeshRepartitionExec"]["mesh_exchange_rows"],
                     ops["TpuStageExec"]["join_build_rows"], ops["TpuStageExec"]["join_probe_rows"]))
        compiles.append(sum(bag.get("xla_compiles", 0) for bag in ops.values()))
        padded = ops["MeshRepartitionExec"]["mesh_exchange_padded_rows"]
        sent = rows[-1][0] + padded
        assert sent % 1024 == 0 and padded > 0
    assert all(a != b for a, b in zip(*rows)), rows  # the data did differ
    assert compiles[1] == 0, compiles


# ---------------------------------------------------------- (c) the edges
EDGES = [0, 1, 1023, 1024, 1025]


def _stages(plan, cls):
    out, stack = [], [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, cls):
            out.append(node)
        stack.extend(node.children())
    return out


def _join_case(m: int, wide: bool, seed: int = 0):
    """A build side of ``m`` unique keys and a probe side that asks for
    every build key, for keys between them, for the largest build key and
    for keys above it (where the sorted probe's pad keys sit).  ``wide``
    spreads the keys over more than the dense table's span, which forces
    the sorted probe."""
    rng = np.random.default_rng(seed)
    step = (1 << 30) // max(m, 1) if wide else 3
    bkeys = 7 + step * np.arange(m, dtype=np.int64)
    top = int(bkeys[-1]) if m else 7
    pkeys = np.concatenate([
        bkeys, bkeys + 1, np.full(5, top), top + 1 + np.arange(5), rng.integers(0, top + 10, 300),
    ]).astype(np.int64)
    build = pa.table({"pk": pa.array(bkeys), "dv": pa.array(rng.uniform(0.5, 1.5, m))})
    probe = pa.table({"fk": pa.array(pkeys), "v": pa.array(rng.uniform(0, 100, len(pkeys)))})
    # the unpadded numpy join
    pos = np.searchsorted(bkeys, pkeys)
    pos[pos == m] = 0
    hit = (bkeys[pos] == pkeys) if m else np.zeros(len(pkeys), bool)
    want = (int(hit.sum()), float((probe.column("v").to_numpy()[hit] * build.column("dv").to_numpy()[pos[hit]]).sum()))
    return build, probe, want


@pytest.mark.parametrize("wide", [False, True], ids=["dense", "sorted"])
@pytest.mark.parametrize("m", EDGES)
def test_build_side_at_a_bucket_edge_joins_as_the_unpadded_numpy_join(m, wide):
    build, probe, (want_n, want_sum) = _join_case(m, wide)
    ctx = SessionContext(BallistaConfig({
        "ballista.tpu.min_rows": "0", "ballista.shuffle.partitions": "1",
    }))
    ctx.register_table("dim", MemoryTable.from_table(build, 1))
    ctx.register_table("fact", MemoryTable.from_table(probe, 1))
    plan = ctx.sql("select sum(v * dv) as s, count(*) as c from dim, fact where pk = fk").physical_plan()
    out = ctx.execute(plan)
    assert out.column("c").to_pylist() == [want_n]
    if want_n:
        assert out.column("s").to_pylist()[0] == pytest.approx(want_sum, rel=1e-5)
    (stage,) = [s for s in _stages(plan, TpuStageExec) if s.fused.join is not None]
    got = stage.metrics.to_dict()
    assert not any(got.get(k) for k in OFF_ROUTE + ("cpu_fallback",))
    if m == 0:
        assert stage._build_state == ("empty",)
        return
    # a pad row matched no probe key: c above is the unpadded join's count
    # although the probe asks for the key the pad rows repeat and for keys
    # past it; and the build side went up at its bucket
    assert got["join_build_rows"] == m and got["join_build_capacity"] == K.bucket_rows(m)
    wide = wide and m > 1  # one key spans one slot: always the dense table
    assert got.get("dense_join", 0) == (0 if wide else 1)
    state = stage._build_state
    assert state[0] == ("ok" if wide else "dense")
    assert all(int(a.shape[0]) == K.bucket_rows(m) for a in state[2] + state[3])
    assert all(not bool(np.asarray(v)[m:].any()) for v in state[3])  # pad columns read invalid
    if wide:
        keys = np.asarray(state[1])
        assert len(keys) == K.bucket_rows(m) and (keys[m:] == keys[m - 1]).all()
    else:
        table = np.asarray(state[1])
        assert int((table > 0).sum()) == m and int(table.max()) == m  # pad rows have no slot


def _exchange_case(n: int, seed: int = 3):
    rng = np.random.default_rng(seed)
    t = pa.table({
        "k": pa.array(rng.integers(0, 1 << 40, n), pa.int64()),
        "v": pa.array(rng.normal(size=n)),
        "s": pa.array([f"s{i % 7}" for i in range(n)], pa.string()),
    })
    ctx = SessionContext(BallistaConfig({"ballista.shuffle.partitions": "3"}))
    ctx.register_table("t", MemoryTable.from_table(t, 2))
    scan = ctx.sql("select k, v, s from t").physical_plan()
    node = MeshRepartitionExec(scan, Partitioning("hash", 3, (pe.Col(0, "k"),)))
    return t, node, TaskContext(ctx.config)


def _rows(batches) -> list:
    out = []
    for b in batches:
        out += list(zip(*(b.column(i).to_pylist() for i in range(b.num_columns))))
    return sorted(out)


@pytest.mark.parametrize("n", EDGES)
def test_exchange_input_at_a_bucket_edge_repartitions_as_numpy_does(n):
    from arrow_ballista_tpu.shuffle.execution_plans import partition_indices

    t, node, tctx = _exchange_case(n)
    got: dict = {}
    for p, batch in node.execute_exchanged(tctx):
        got.setdefault(p, []).append(batch)
    whole = t.combine_chunks().to_batches()[0] if n else None
    idx = partition_indices(whole, [pe.Col(0, "k")], 3) if n else np.zeros(0, int)
    for p in range(3):
        want = _rows([whole.take(pa.array(np.flatnonzero(idx == p)))]) if n else []
        assert _rows(got.get(p, [])) == want, p
    counters = node.metrics.to_dict()
    if n == 0:
        assert "mesh_exchange_rows" not in counters
        return
    sent = M.exchange_rows(n, counters["mesh_devices"])
    # every pad row went up and none arrived: the rows above are exactly n
    assert counters["mesh_exchange_rows"] == n
    assert counters["mesh_exchange_padded_rows"] == sent - n
    assert sent == K.bucket_rows(n) and counters["mesh_exchange_bytes"] % sent == 0
    assert sum(b.num_rows for bs in got.values() for b in bs) == n


@pytest.mark.parametrize("n", [1, 1023, 1025])
def test_a_padded_exchange_row_arrives_nowhere(n):
    """At the program itself: n real rows go up as ``exchange_rows(n)``; the
    receive side holds exactly n valid slots, whatever the pad rows' zeros
    would have hashed to."""
    mesh = M.make_mesh(8)
    ks = np.arange(n, dtype=np.int64)
    batch = pa.record_batch({"k": pa.array(ks)})
    ex = M.BatchExchanger(mesh, batch.schema, capacity=K.bucket_rows(n, floor=1))
    recv_cols, recv_valid, dropped = ex.exchange(
        np.zeros(n, np.int32), np.ones(n, bool), ex.to_columns(batch)
    )
    assert dropped == 0 and int(recv_valid.sum()) == n
    out = pa.Table.from_batches(ex.to_batches(recv_cols, recv_valid))
    assert sorted(out.column("k").to_pylist()) == ks.tolist()


def test_capacity_growth_retry_still_delivers_every_row(monkeypatch):
    """A first capacity that is too small (here: the need reckoned as if
    every bucket held one row) reports drops; the stage doubles it until
    none is left, and every row arrives once."""
    t, node, tctx = _exchange_case(1025)
    real = K.bucket_rows
    monkeypatch.setattr(K, "bucket_rows", lambda n, floor=1024: real(1 if floor == 1 else n, floor))
    got = [b for _, b in node.execute_exchanged(tctx)]
    monkeypatch.undo()
    counters = node.metrics.to_dict()
    assert counters["capacity_growths"] >= 1
    assert _rows(got) == _rows(t.to_batches())


# ----------------------------------------------------------- (d) the spans
def _span_run():
    """One folded join and one exchange, run on this thread."""
    build, probe, _ = _join_case(1025, wide=False)
    ctx = SessionContext(BallistaConfig({
        "ballista.tpu.min_rows": "0", "ballista.shuffle.partitions": "1",
    }))
    ctx.register_table("dim", MemoryTable.from_table(build, 1))
    ctx.register_table("fact", MemoryTable.from_table(probe, 1))
    ctx.execute(ctx.sql("select sum(v * dv) as s from dim, fact where pk = fk").physical_plan())
    _, node, tctx = _exchange_case(1025)
    return sum(b.num_rows for _, b in node.execute_exchanged(tctx))


def test_exchange_and_join_spans_nest_under_the_task_when_obs_is_on():
    trace.configure(enabled=True, process="local")
    try:
        trace_id = trace.new_id()
        with trace.root_span("job", trace_id), trace.span("task.execute") as task:
            assert _span_run() == 1025
        task_id = task.span_id
    finally:
        trace.configure(enabled=False)
    spans = get_recorder().drain()
    by_name = {s["name"]: s for s in spans}
    for name in ("exchange.encode", "exchange.device", "exchange.decode", "join.build", "join.probe"):
        assert by_name[name]["trace"] == trace_id and by_name[name]["parent"] == task_id, name
    dev = by_name["exchange.device"]["attrs"]
    assert dev["rows"] == 1025 and dev["padded_rows"] == 2048 - 1025
    assert dev["growths"] == 0 and dev["capacity"] >= 1025 // 64
    build = by_name["join.build"]["attrs"]
    assert (build["rows"], build["capacity"], build["dense"]) == (1025, 2048, True)
    assert by_name["join.probe"]["attrs"]["rows"] > 1025


def test_exchange_and_join_make_no_span_object_when_obs_is_off(monkeypatch):
    made = []

    class CountingSpan(trace.Span):
        def __init__(self, *a, **kw):
            made.append(a[0])
            super().__init__(*a, **kw)

    monkeypatch.setattr(trace, "Span", CountingSpan)
    assert not trace.is_enabled()
    get_recorder().drain()
    assert _span_run() == 1025
    assert made == [] and get_recorder().drain() == []
