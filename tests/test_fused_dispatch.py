"""Single-dispatch fused runner: cache-eligible TpuStageExec stages run
(per-batch kernel → combine → pack) as ONE jitted call, so a query costs
one execute dispatch + one fetch instead of
one dispatch per batch plus a separate pack dispatch.

Results must be identical to the CPU operator path; the route is
observable through the ``fused_dispatches`` stage metric.
"""

import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu import BallistaConfig, SessionContext
from arrow_ballista_tpu.catalog import MemoryTable


def _reg(ctx, name, table, partitions=1):
    ctx.register_table(name, MemoryTable.from_table(table, partitions))


def _ctx(tpu: bool, **extra) -> SessionContext:
    settings = {
        "ballista.tpu.enable": "true" if tpu else "false",
        "ballista.tpu.min_rows": "0",
        "ballista.shuffle.partitions": "1",
    }
    settings.update({k: str(v) for k, v in extra.items()})
    return SessionContext(BallistaConfig(settings))


def _assert_tables_equal(a: pa.Table, b: pa.Table, rel=1e-9):
    assert a.schema.names == b.schema.names
    assert a.num_rows == b.num_rows
    a = a.sort_by([(c, "ascending") for c in a.column_names
                   if not pa.types.is_floating(a.schema.field(c).type)])
    b = b.sort_by([(c, "ascending") for c in b.column_names
                   if not pa.types.is_floating(b.schema.field(c).type)])
    for name in a.schema.names:
        for x, y in zip(a.column(name).to_pylist(), b.column(name).to_pylist()):
            if isinstance(x, float) and x is not None and y is not None:
                assert y == pytest.approx(x, rel=rel), name
            else:
                assert x == y, name


def _stage_metrics(plan) -> dict:
    from arrow_ballista_tpu.ops.stage_compiler import TpuStageExec

    agg: dict = {}
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, TpuStageExec):
            for k, v in node.metrics.values.items():
                agg[k] = agg.get(k, 0) + v
        stack.extend(node.children())
    return agg


def _run(ctx, sql):
    df = ctx.sql(sql)
    plan = df.physical_plan()
    table = ctx.execute(plan)
    return table, _stage_metrics(plan)


def _mktable(n=5000, groups=7, nulls=False, seed=0):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, groups, n)
    v = rng.uniform(-100, 100, n)
    q = rng.integers(1, 50, n).astype(np.float64)
    varr = pa.array(v, pa.float64())
    if nulls:
        mask = rng.uniform(size=n) < 0.1
        varr = pa.array([None if m else x for m, x in zip(mask, v)],
                        pa.float64())
    return pa.table({"k": pa.array(k, pa.int64()), "v": varr,
                     "q": pa.array(q, pa.float64())})


GROUPED = "select k, sum(v), count(v), min(q), max(v) from t group by k"
SCALAR = "select sum(v), count(*), min(v) from t where q < 25"


@pytest.mark.parametrize("sql", [GROUPED, SCALAR])
@pytest.mark.parametrize("nulls", [False, True])
def test_fused_matches_cpu(sql, nulls):
    t = _mktable(nulls=nulls)
    c_cpu, c_tpu = _ctx(False), _ctx(True)
    _reg(c_cpu, "t", t)
    _reg(c_tpu, "t", t)
    cpu, _ = _run(c_cpu, sql)
    tpu, m = _run(c_tpu, sql)
    _assert_tables_equal(cpu, tpu)
    assert m.get("fused_dispatches", 0) >= 1, m


def test_fused_multi_batch_matches_cpu():
    # several batches per partition → the fused call inlines every
    # entry's kernel and combines inside ONE trace
    t = _mktable(n=20000)
    c_cpu = _ctx(False, **{"ballista.batch.size": 4096})
    c_tpu = _ctx(True, **{"ballista.batch.size": 4096})
    _reg(c_cpu, "t", t)
    _reg(c_tpu, "t", t)
    cpu, _ = _run(c_cpu, GROUPED)
    tpu, m = _run(c_tpu, GROUPED)
    _assert_tables_equal(cpu, tpu)
    assert m.get("fused_dispatches", 0) >= 1, m


def test_fused_cache_hit_matches():
    # second execution serves device-resident entries through the same
    # fused call; results must be identical both times
    t = _mktable(n=8000)
    ctx = _ctx(True)
    _reg(ctx, "t", t)
    first, m1 = _run(ctx, GROUPED)
    second, m2 = _run(ctx, GROUPED)
    _assert_tables_equal(first, second)
    assert m2.get("cache_hits", 0) >= 1, m2
    assert m2.get("fused_dispatches", 0) >= 1, m2


def test_fused_capacity_growth():
    # cardinality outruns the initial segment capacity: the fused call
    # runs every entry at the FINAL grown capacity (no mid-stream state
    # padding), and the result still matches the CPU oracle
    n = 30000
    rng = np.random.default_rng(1)
    t = pa.table({
        "k": pa.array(rng.integers(0, 3000, n), pa.int64()),
        "v": pa.array(rng.uniform(-10, 10, n), pa.float64()),
        "q": pa.array(rng.integers(1, 50, n).astype(np.float64)),
    })
    c_cpu = _ctx(False, **{"ballista.batch.size": 4096})
    c_tpu = _ctx(True, **{"ballista.batch.size": 4096})
    _reg(c_cpu, "t", t)
    _reg(c_tpu, "t", t)
    cpu, _ = _run(c_cpu, GROUPED)
    tpu, m = _run(c_tpu, GROUPED)
    _assert_tables_equal(cpu, tpu)
    assert m.get("fused_dispatches", 0) >= 1, m


def test_entry_cap_streams_instead_of_unrolling():
    # more retained batches than _FUSED_MAX_ENTRIES: the runner must NOT
    # unroll an XLA program linear in batch count — it streams per-batch
    # dispatches (fused_dispatches stays 0) and still matches the oracle
    from arrow_ballista_tpu.ops import stage_compiler as SC

    t = _mktable(n=40 * 256)
    # one partition of 40 explicit 256-row batches (MemoryTable combines
    # chunks when built via from_table, so hand it the batch list)
    batches = pa.Table.from_batches(t.to_batches()).to_batches(
        max_chunksize=256
    )
    c_cpu, c_tpu = _ctx(False), _ctx(True)
    c_cpu.register_table("t", MemoryTable([batches], t.schema))
    c_tpu.register_table("t", MemoryTable([batches], t.schema))
    cpu, _ = _run(c_cpu, GROUPED)
    tpu, m = _run(c_tpu, GROUPED)
    _assert_tables_equal(cpu, tpu)
    assert 40 > SC._FUSED_MAX_ENTRIES or m.get("fused_dispatches", 0) >= 1
    if 40 > SC._FUSED_MAX_ENTRIES:
        assert m.get("fused_dispatches", 0) == 0, m


def test_streamed_join_still_correct():
    # join stages (ck is None) keep the streamed per-batch path; the
    # fused-tail combine+pack must not change their results
    n = 6000
    rng = np.random.default_rng(2)
    fact = pa.table({
        "fk": pa.array(rng.integers(0, 100, n), pa.int64()),
        "grp": pa.array(rng.integers(0, 5, n), pa.int64()),
        "x": pa.array(rng.uniform(0, 1, n), pa.float64()),
    })
    dim = pa.table({
        "pk": pa.array(np.arange(100), pa.int64()),
        "dv": pa.array(np.linspace(0.5, 1.5, 100)),
    })
    sql = ("select grp, sum(x * dv), count(*) from dim, fact "
           "where pk = fk group by grp")
    c_cpu, c_tpu = _ctx(False), _ctx(True)
    for c in (c_cpu, c_tpu):
        _reg(c, "fact", fact)
        _reg(c, "dim", dim)
    cpu, _ = _run(c_cpu, sql)
    tpu, _ = _run(c_tpu, sql)
    _assert_tables_equal(cpu, tpu)
