"""The exchange's device call in four phases: ``BatchExchanger.exchange``
pads every input array, uploads them and waits until all are on the mesh,
runs the program and waits for its outputs, then fetches them, ending each
phase with a read of one clock.  ``MeshRepartitionExec`` counts the phases
as ``exchange_pad_ns``, ``exchange_upload_ns``, ``exchange_program_ns`` and
``exchange_fetch_ns``, summed over capacity growths.  Held here, on
lineitem-shaped rows of SF0.01 at mesh widths 1 and 4: the four sum to
``device_time_ns`` exactly, a forced capacity growth too; the batches are
those of the one-call exchange (pad and put a column at a time, dispatch,
fetch) it replaces; with obs on the four are child spans of
``exchange.device``; the profile's ``tpu`` row names them."""

import jax
import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu import BallistaConfig
from arrow_ballista_tpu.catalog import MemoryTable
from arrow_ballista_tpu.exec import expressions as pe
from arrow_ballista_tpu.exec.operators import Partitioning, ScanExec, TaskContext
from arrow_ballista_tpu.obs import trace
from arrow_ballista_tpu.obs.export import job_profile
from arrow_ballista_tpu.obs.recorder import get_recorder
from arrow_ballista_tpu.ops import kernels as K
from arrow_ballista_tpu.parallel import mesh as M
from arrow_ballista_tpu.parallel.mesh_stage import MeshRepartitionExec
from benchmarks.tpch import datagen

PHASES = ("exchange_pad_ns", "exchange_upload_ns", "exchange_program_ns", "exchange_fetch_ns")
COLUMNS = ["l_orderkey", "l_extendedprice", "l_discount", "l_shipdate", "l_shipmode"]
N_OUT = 3


@pytest.fixture(scope="module")
def partitions():
    """SF0.01's lineitem columns in four input partitions of several batches."""
    tbl = datagen.gen_table("lineitem", 0.01).select(COLUMNS)
    per = -(-tbl.num_rows // 4)
    return [tbl.slice(i * per, per).to_batches(max_chunksize=5000) for i in range(4)]


def _node(partitions, n_dev):
    table = MemoryTable(partitions, partitions[0][0].schema)
    node = MeshRepartitionExec(
        ScanExec("lineitem", table, None),
        Partitioning("hash", N_OUT, (pe.Col(0, "l_orderkey"),)),
        n_dev,
    )
    return node, TaskContext(config=BallistaConfig({}))


def _exchange(partitions, n_dev):
    node, tctx = _node(partitions, n_dev)
    out = list(node.execute_exchanged(tctx))
    return out, node.metrics.to_dict()


def _one_call(self, part, valid, cols, laps=None):
    """The exchange call as it was before the split: pad and put each
    column in turn, dispatch, and fetch (the first fetch waits)."""
    rows = M.exchange_rows(len(part), self.mesh.devices.size)
    sharding = M.NamedSharding(self.mesh, M.P(M.DATA_AXIS))
    sharded = [jax.device_put(K._pad(a, rows), sharding) for a in [part, valid] + list(cols)]
    out = self._fn(*sharded)
    host = [np.asarray(o) for o in out[:-1]]
    return host[:-1], host[-1], int(np.asarray(out[-1]))


def _force_growth(monkeypatch):
    """The first capacity reckoned for one row: the call drops rows and
    the stage doubles the capacity until none is dropped."""
    real = K.bucket_rows
    monkeypatch.setattr(K, "bucket_rows", lambda n, floor=1024: real(1 if floor == 1 else n, floor))


def _same_batches(got, want):
    assert [p for p, _ in got] == [p for p, _ in want]
    for (_, g), (_, w) in zip(got, want):
        assert g.schema.equals(w.schema) and g.equals(w)


@pytest.mark.parametrize("n_dev", [1, 4])
def test_the_four_phases_split_the_device_call_exactly(partitions, n_dev):
    _, m = _exchange(partitions, n_dev)
    assert m["mesh_exchange_rows"] == sum(b.num_rows for p in partitions for b in p)
    assert all(m[k] > 0 for k in PHASES), {k: m[k] for k in PHASES}
    assert sum(m[k] for k in PHASES) == m["device_time_ns"]
    assert "capacity_growths" not in m


@pytest.mark.parametrize("n_dev", [1, 4])
def test_the_phases_still_sum_across_a_capacity_growth(monkeypatch, partitions, n_dev):
    _force_growth(monkeypatch)
    _, m = _exchange(partitions, n_dev)
    assert m["capacity_growths"] >= 1
    assert sum(m[k] for k in PHASES) == m["device_time_ns"]


@pytest.mark.parametrize("growth", [False, True])
@pytest.mark.parametrize("n_dev", [1, 4])
def test_same_batches_as_the_one_call_exchange(monkeypatch, partitions, n_dev, growth):
    if growth:
        _force_growth(monkeypatch)
    got, _ = _exchange(partitions, n_dev)
    monkeypatch.setattr(M.BatchExchanger, "exchange", _one_call)
    want, m = _exchange(partitions, n_dev)
    # the reference ran, not the split: the whole call is the last lap's
    assert m["exchange_pad_ns"] == m["exchange_upload_ns"] == m["exchange_program_ns"] == 0
    assert sum(b.num_rows for _, b in got) == m["mesh_exchange_rows"]
    _same_batches(got, want)


def test_a_call_alone_laps_its_own_clock():
    """Called with no clock, as the tests of the program do: the same
    three results, and a clock of one's own reads the four phases."""
    mesh = M.make_mesh(1)
    ex = M.BatchExchanger(mesh, pa.schema([("x", pa.int64())]), 1024, n_out=2)
    x = np.arange(100, dtype=np.int64)
    part = (x % 2).astype(np.int32)
    cols = ex.to_columns(pa.record_batch([pa.array(x)], names=["x"]))
    recv_cols, recv_valid, dropped = ex.exchange(part, np.ones(100, bool), cols)
    assert dropped == 0 and int(recv_valid.sum()) == 100
    laps = M.PhaseClock()
    start = laps.last
    again = ex.exchange(part, np.ones(100, bool), cols, laps)
    assert all(np.array_equal(a, b) for a, b in zip(again[0], recv_cols))
    assert list(laps.ns) == list(M.EXCHANGE_PHASES)
    assert sum(laps.ns.values()) == laps.last - start


@pytest.mark.parametrize("growth", [False, True])
def test_the_phases_are_child_spans_of_the_device_call(monkeypatch, partitions, growth):
    if growth:
        _force_growth(monkeypatch)
    node, tctx = _node(partitions, 4)
    trace.configure(enabled=True, process="local")
    try:
        get_recorder().drain()
        trace_id = trace.new_id()
        with trace.root_span("job", trace_id), trace.span("task.execute"):
            list(node.execute_exchanged(tctx))
    finally:
        trace.configure(enabled=False)
    spans = [s for s in get_recorder().drain() if s["trace"] == trace_id]
    m = node.metrics.to_dict()
    (device,) = [s for s in spans if s["name"] == "exchange.device"]
    calls = 1 + m.get("capacity_growths", 0)
    assert (calls > 1) == growth
    for name in ("exchange.pad", "exchange.upload", "exchange.program", "exchange.fetch"):
        mine = [s for s in spans if s["name"] == name]
        assert len(mine) == calls, name
        assert all(s["parent"] == device["span"] for s in mine), name
        assert all(device["ts"] <= s["ts"] <= device["ts"] + device["dur"] for s in mine), name
    (up,) = [s for s in spans if s["name"] == "exchange.upload"][-1:]
    (fetch,) = [s for s in spans if s["name"] == "exchange.fetch"][-1:]
    # what the program was handed, and handed back (and the one scalar
    # count of dropped rows)
    assert up["attrs"]["bytes"] == m["mesh_exchange_bytes"]
    assert 0 < fetch["attrs"]["bytes"] - m["mesh_exchange_recv_bytes"] <= 8


def test_the_profile_names_the_four_phases(partitions):
    _, m = _exchange(partitions, 1)
    detail = {"stages": [{"stage_id": 1, "state": "completed", "partitions": 1,
                          "metrics": {"MeshRepartitionExec": m}}]}
    (row,) = job_profile(detail, [])["stages"]
    tpu = row["tpu"]
    for k in PHASES:
        assert tpu[k[:-3] + "_ms"] == round(m[k] / 1e6, 3), k
    assert tpu["exchange_device_ms"] == round(m["device_time_ns"] / 1e6, 3)
