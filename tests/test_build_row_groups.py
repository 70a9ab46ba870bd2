"""The build-row route (PR 34): a folded PK-FK join whose one host-encoded
group key is the probe join key aggregates with the MATCHED BUILD ROW as its
group, worked out inside the device program (``join_aggregate``): no gid
table, no host key encode, no segment ids uploaded.  The groups are bounded
by the build rows, however many distinct keys the probe side holds (q3 at
SF10: 15M order keys, 1.45M build rows).

TPC-H q3 on seeded generated tables is compared with a plain numpy q3 and
with the CPU operators (``ballista.tpu.enable=false``), on the dense and the
sorted probe, in x32 and x64, with null probe keys and with an empty build
side; a group key on a probe column other than the join key keeps the gid
table.
"""

import datetime

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from arrow_ballista_tpu import BallistaConfig, SessionContext
from arrow_ballista_tpu.catalog import MemoryTable
from arrow_ballista_tpu.ops import kernels as K
from arrow_ballista_tpu.ops import stage_compiler as SC
from benchmarks.tpch import datagen
from benchmarks.tpch.queries import QUERIES

# q3 without its order and limit: every group is compared, not ten
Q3_GROUPS = QUERIES[3].split("order by")[0]
DATE = datetime.date(1995, 3, 15)
SEGMENT = "BUILDING"


@pytest.fixture(scope="module")
def tables():
    return {t: datagen.gen_table(t, 0.005) for t in ("customer", "orders", "lineitem")}


def _ctx(tpu: bool) -> SessionContext:
    return SessionContext(BallistaConfig({
        "ballista.tpu.enable": str(tpu).lower(),
        "ballista.tpu.min_rows": "0",
        "ballista.mesh.enable": "false",
    }))


def _run(tables: dict, sql: str, tpu: bool):
    ctx = _ctx(tpu)
    for name, t in tables.items():
        ctx.register_table(name, MemoryTable.from_table(t, 2))
    plan = ctx.sql(sql).physical_plan()
    return ctx.execute(plan), plan


def _join_stage_metrics(plan) -> dict:
    stages, stack = [], [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, SC.TpuStageExec) and node.fused.join is not None:
            stages.append(node)
        stack.extend(node.children())
    assert len(stages) == 1, stages
    return dict(stages[0].metrics.values)


def numpy_q3(tables: dict, segment: str = SEGMENT) -> tuple:
    """({l_orderkey: (revenue, o_orderdate, o_shippriority)} of q3's groups,
    the lineitem rows that joined), in plain numpy over the same tables."""
    c, o, li = tables["customer"], tables["orders"], tables["lineitem"]
    custs = c["c_custkey"].to_numpy()[
        np.asarray(pc.equal(c["c_mktsegment"], segment).to_numpy(zero_copy_only=False))
    ]
    date = np.datetime64(DATE, "D")
    odate = o["o_orderdate"].to_numpy().astype("datetime64[D]")
    keep = np.isin(o["o_custkey"].to_numpy(), custs) & (odate < date)
    okeys = o["o_orderkey"].to_numpy()[keep]
    by_key = dict(zip(okeys.tolist(), zip(odate[keep].tolist(), o["o_shippriority"].to_numpy()[keep].tolist())))
    lkey = li["l_orderkey"]
    valid = np.asarray(lkey.is_valid().to_numpy(zero_copy_only=False))
    lk = np.asarray(lkey.fill_null(-1).to_numpy())
    ship = li["l_shipdate"].to_numpy().astype("datetime64[D]")
    rows = valid & (ship > date) & np.isin(lk, okeys)
    rev = li["l_extendedprice"].to_numpy()[rows] * (1 - li["l_discount"].to_numpy()[rows])
    sums: dict = {}
    for k, r in zip(lk[rows].tolist(), rev.tolist()):
        sums[k] = sums.get(k, 0.0) + r
    return {k: (s, *by_key[k]) for k, s in sums.items()}, int(rows.sum())


def _as_groups(t: pa.Table) -> dict:
    return {
        k: (r, d, p) for k, r, d, p in zip(
            t["l_orderkey"].to_pylist(), t["revenue"].to_pylist(),
            t["o_orderdate"].to_pylist(), t["o_shippriority"].to_pylist(),
        )
    }


def _assert_groups(got: dict, want: dict) -> None:
    assert got.keys() == want.keys()
    for k, (r, d, p) in want.items():
        gr, gd, gp = got[k]
        assert (gd, gp) == (d, p), k
        assert gr == pytest.approx(r, rel=1e-6), k


@pytest.fixture(params=[("x64", None), ("x32", None), ("x32", "matmul")],
                ids=["x64", "x32", "x32-matmul"])
def precision(request):
    mode, algo = request.param
    K.set_precision(mode)
    K.set_agg_algorithm(algo)
    yield mode
    K.set_agg_algorithm(None)
    K.set_precision(None)


@pytest.mark.parametrize("probe", ["dense", "sorted"])
@pytest.mark.parametrize("case", ["plain", "null-probe-keys", "empty-build"])
def test_q3_on_the_build_row_route(tables, precision, probe, case, monkeypatch):
    if probe == "sorted":
        # every build key span is over the dense table's cap
        monkeypatch.setattr(SC, "_DENSE_JOIN_SPAN_CAP", 0)
    segment = SEGMENT
    if case == "null-probe-keys":
        lk = tables["lineitem"]["l_orderkey"].to_pylist()
        lk[::7] = [None] * len(lk[::7])
        tables = dict(tables, lineitem=tables["lineitem"].set_column(
            0, "l_orderkey", pa.array(lk, pa.int64())))
    elif case == "empty-build":
        segment = "NO SUCH SEGMENT"
    sql = Q3_GROUPS.replace(f"'{SEGMENT}'", f"'{segment}'")
    got, plan = _run(tables, sql, True)
    want_cpu, _ = _run(tables, sql, False)
    want, joined = numpy_q3(tables, segment)
    _assert_groups(_as_groups(got), want)
    _assert_groups(_as_groups(want_cpu), want)
    m = _join_stage_metrics(plan)
    for off in ("join_fallback", "tpu_fallback", "cpu_fallback", "highcard_fallback", "device_error"):
        assert m.get(off, 0) == 0, (off, m)
    if case == "empty-build":
        assert not want and got.num_rows == 0
        return
    assert want
    assert m["join_build_groups"] == 1, m
    assert ("dense_join" in m) == (probe == "dense"), m
    # no gid table: nothing was key-encoded on the host
    assert "key_encode_time_ns" not in m, m
    assert m["join_groups"] == len(want) == got.num_rows
    # every lineitem row that found its order (the ship date filter runs
    # below the join); the build side's bucket of slots a partition
    assert m["join_matched_rows"] == joined
    assert m["join_state_slots"] == 2 * K.bucket_rows(m["join_build_rows"], floor=64)
    assert m["join_probe_bytes"] > m["join_probe_rows"]


def test_q3_answers_equal_with_order_and_limit(tables):
    """The query as the cell sends it: ten rows, in the query's order."""
    got, plan = _run(tables, QUERIES[3], True)
    want, _ = _run(tables, QUERIES[3], False)
    assert got.num_rows == want.num_rows == 10
    assert got["l_orderkey"].to_pylist() == want["l_orderkey"].to_pylist()
    assert got["revenue"].to_pylist() == pytest.approx(want["revenue"].to_pylist(), rel=1e-6)
    assert _join_stage_metrics(plan)["join_build_groups"] == 1


def test_a_group_key_on_another_probe_column_keeps_the_gid_table(tables):
    sql = (
        "select l_orderkey, l_linenumber, sum(l_extendedprice) as revenue "
        "from orders, lineitem where l_orderkey = o_orderkey "
        "and o_orderdate < date '1995-03-15' group by l_orderkey, l_linenumber"
    )
    small = {k: tables[k] for k in ("orders", "lineitem")}
    got, plan = _run(small, sql, True)
    want, _ = _run(small, sql, False)
    keys = [("l_orderkey", "ascending"), ("l_linenumber", "ascending")]
    got, want = got.sort_by(keys), want.sort_by(keys)
    assert got.num_rows == want.num_rows > 0
    assert got["l_orderkey"].to_pylist() == want["l_orderkey"].to_pylist()
    assert got["revenue"].to_pylist() == pytest.approx(want["revenue"].to_pylist(), rel=1e-9)
    m = _join_stage_metrics(plan)
    assert "join_build_groups" not in m and m.get("join_fallback", 0) == 0, m
    assert m["key_encode_time_ns"] > 0, m
