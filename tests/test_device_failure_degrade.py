"""Device/compiler failures must degrade, never kill a query.

Round 5, h2o on the chip: the mesh gang's shard_map compile got its
tpu_compile_helper SIGKILLed and the uncaught JaxRuntimeError destroyed
the whole run.  These tests inject JaxRuntimeError into the device
stage and the mesh gang and assert the query still returns the CPU
oracle's answer — loudly: the traceback is logged at WARNING and the
degradation counts as ``device_error``, apart from the counters of
routes the DATA chose (``tpu_fallback``, ``mesh_fallback``) — while
non-jax RuntimeErrors (genuine bugs) still propagate.
"""

import logging

import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu import BallistaConfig, SessionContext
from arrow_ballista_tpu.catalog import MemoryTable
from arrow_ballista_tpu.ops import stage_compiler as SC


def _table(n=4000, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array(rng.integers(0, 6, n), pa.int64()),
        "v": pa.array(rng.uniform(-10, 10, n)),
    })


def _ctx(tpu=True, **extra):
    s = {
        "ballista.tpu.enable": str(tpu).lower(),
        "ballista.tpu.min_rows": "0",
        "ballista.shuffle.partitions": "1",
    }
    s.update({k: str(v) for k, v in extra.items()})
    return SessionContext(BallistaConfig(s))


SQL = "select k, sum(v), count(*) from t group by k"


def _metrics(plan):
    agg = {}
    stack = [plan]
    while stack:
        n = stack.pop()
        if isinstance(n, SC.TpuStageExec):
            for k, v in n.metrics.values.items():
                agg[k] = agg.get(k, 0) + v
        stack.extend(n.children())
    return agg


def _oracle(t):
    c = _ctx(False)
    c.register_table("t", MemoryTable.from_table(t, 1))
    return c.sql(SQL).collect().sort_by([("k", "ascending")])


def _assert_logged_device_error(caplog, needle):
    recs = [
        r for r in caplog.records
        if r.levelno == logging.WARNING and "device error" in r.getMessage()
    ]
    assert recs, "device-error degradation was not logged at WARNING"
    assert recs[0].exc_info and needle in str(recs[0].exc_info[1])


def test_stage_jax_runtime_error_degrades_to_cpu(monkeypatch, caplog):
    caplog.set_level(logging.WARNING)
    t = _table()
    want = _oracle(t)

    def boom(self, entries, cap, group_table, *args, **kwargs):
        raise SC._JaxRuntimeError("INTERNAL: tpu_compile_helper SIGKILL")

    monkeypatch.setattr(SC.TpuStageExec, "_run_fused", boom)
    ctx = _ctx(True)
    ctx.register_table("t", MemoryTable.from_table(t, 1))
    plan = ctx.sql(SQL).physical_plan()
    got = ctx.execute(plan).sort_by([("k", "ascending")])
    assert got.equals(want)
    m = _metrics(plan)
    assert m.get("device_error", 0) >= 1, m
    assert "tpu_fallback" not in m and "cpu_fallback" not in m, m
    _assert_logged_device_error(caplog, "tpu_compile_helper SIGKILL")


def test_keyed_route_jax_runtime_error_counts_as_device_error(
    monkeypatch, caplog
):
    # the keyed handler used to catch blanket RuntimeError under
    # tpu_fallback: a device failure there is a device_error too, and a
    # plain RuntimeError (a bug) propagates
    caplog.set_level(logging.WARNING)
    t = _table()
    want = _oracle(t)

    def boom(self, *args, **kwargs):
        raise SC._JaxRuntimeError("RESOURCE_EXHAUSTED: keyed buffer")

    monkeypatch.setattr(SC.TpuStageExec, "_run_keyed", boom)
    ctx = _ctx(True, **{"ballista.tpu.highcard_mode": "device"})
    monkeypatch.setattr(SC, "_HIGHCARD_MIN_GROUPS", 1)
    monkeypatch.setattr(SC, "_HIGHCARD_RATIO", 0.0)
    ctx.register_table("t", MemoryTable.from_table(t, 1))
    plan = ctx.sql(SQL).physical_plan()
    got = ctx.execute(plan).sort_by([("k", "ascending")])
    assert got.equals(want)
    m = _metrics(plan)
    assert m.get("keyed_path", 0) >= 1 and m.get("device_error", 0) >= 1, m
    assert "tpu_fallback" not in m, m
    _assert_logged_device_error(caplog, "RESOURCE_EXHAUSTED")

    def bug(self, *args, **kwargs):
        raise RuntimeError("logic bug in the keyed path")

    monkeypatch.setattr(SC.TpuStageExec, "_run_keyed", bug)
    with pytest.raises(RuntimeError, match="logic bug"):
        ctx.sql(SQL).collect()


def test_stage_plain_runtime_error_propagates(monkeypatch):
    # a non-jax RuntimeError is a genuine bug: it must NOT silently
    # become a fallback
    t = _table()

    def boom(self, entries, cap, group_table, *args, **kwargs):
        raise RuntimeError("logic bug, not a device failure")

    monkeypatch.setattr(SC.TpuStageExec, "_run_fused", boom)
    ctx = _ctx(True)
    ctx.register_table("t", MemoryTable.from_table(t, 1))
    with pytest.raises(RuntimeError, match="logic bug"):
        ctx.sql(SQL).collect()


def test_mesh_gang_jax_runtime_error_degrades(monkeypatch, caplog):
    from arrow_ballista_tpu.parallel import mesh_stage as MS

    caplog.set_level(logging.WARNING)

    t = _table(n=60000, seed=1)
    want = _oracle(t)

    def boom(self, inner, ctx):
        raise SC._JaxRuntimeError("INTERNAL: remote_compile HTTP 500")
        yield  # pragma: no cover - generator shape

    monkeypatch.setattr(MS.MeshGangExec, "_execute_mesh", boom)
    ctx = _ctx(True, **{"ballista.mesh.enable": "true",
                        "ballista.shuffle.partitions": "2"})
    ctx.register_table("t", MemoryTable.from_table(t, 2))
    plan = ctx.sql(SQL).physical_plan()
    gangs = []
    stack = [plan]
    while stack:
        n = stack.pop()
        if isinstance(n, MS.MeshGangExec):
            gangs.append(n)
        stack.extend(n.children())
    assert gangs, "plan did not gang-wrap the partial aggregate"
    got = ctx.execute(plan).sort_by([("k", "ascending")])
    # sequential fallback sums in a different order: approx floats
    assert got.column("k").to_pylist() == want.column("k").to_pylist()
    assert got.column("count(*)").to_pylist() == (
        want.column("count(*)").to_pylist()
    )
    for x, y in zip(got.column("sum(v)").to_pylist(),
                    want.column("sum(v)").to_pylist()):
        assert y == pytest.approx(x, rel=1e-9)
    assert sum(
        g.metrics.values.get("device_error", 0) for g in gangs
    ) >= 1
    assert not any("mesh_fallback" in g.metrics.values for g in gangs)
    _assert_logged_device_error(caplog, "remote_compile HTTP 500")
