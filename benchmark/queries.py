"""TPC-H q1, q6, q3 with the spec's substitution parameters (the ``tpch``
suite's kinds).

The texts are the program's validation texts (``benchmarks/tpch/queries.py``)
with their literals replaced by the substitution parameters of TPC-H spec
v3 sections 2.4.1.3, 2.4.6.3 and 2.4.3.3.  Every kind has a finite list of
parameter sets, and a seed orders that list.  The program bakes a query's
literals into its jitted stage program: a parameter set it has not seen
costs one compile of 1.5-3.5 s (my chip run, PR 25).  Nothing may compile
inside a measured window, so a window cycles through the first
``parameter_sets`` entries of a kind's list (a traffic parameter) and
warm-up runs each of them once; the CPU-operator read uses an entry of its
own.  Every seed gives other literals; a run repeats its own.
"""

from __future__ import annotations

import datetime as dt

SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY")

# base tables each kind scans
TABLES_OF = {1: ("lineitem",), 6: ("lineitem",), 3: ("customer", "orders", "lineitem")}
# columns each kind reads of each table (for the bytes a scan has to move)
COLUMNS_OF = {
    1: {"lineitem": ("l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
                     "l_discount", "l_tax", "l_shipdate")},
    6: {"lineitem": ("l_extendedprice", "l_discount", "l_quantity", "l_shipdate")},
    3: {"customer": ("c_mktsegment", "c_custkey"),
        "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"),
        "lineitem": ("l_orderkey", "l_extendedprice", "l_discount", "l_shipdate")},
}

# the order each kind's text asks its rows in: (column, descending)
ORDER_BY = {
    1: (("l_returnflag", False), ("l_linestatus", False)),
    6: (),
    3: (("revenue", True), ("o_orderdate", False)),
}

# bytes a row holds of each column, as stored (float64/int64 8, date32/int32
# 4, one-letter flags 1, c_mktsegment its mean length)
COLUMN_BYTES = {
    "l_returnflag": 1, "l_linestatus": 1, "l_quantity": 8, "l_extendedprice": 8,
    "l_discount": 8, "l_tax": 8, "l_shipdate": 4, "l_orderkey": 8,
    "c_mktsegment": 9, "c_custkey": 8,
    "o_orderkey": 8, "o_custkey": 8, "o_orderdate": 4, "o_shippriority": 4,
}

_Q1 = """
select
    l_returnflag,
    l_linestatus,
    sum(l_quantity) as sum_qty,
    sum(l_extendedprice) as sum_base_price,
    sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
    sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
    avg(l_quantity) as avg_qty,
    avg(l_extendedprice) as avg_price,
    avg(l_discount) as avg_disc,
    count(*) as count_order
from
    lineitem
where
    l_shipdate <= date '1998-12-01' - interval '{delta}' day
group by
    l_returnflag,
    l_linestatus
order by
    l_returnflag,
    l_linestatus
"""

_Q6 = """
select
    sum(l_extendedprice * l_discount) as revenue
from
    lineitem
where
    l_shipdate >= date '{year}-01-01'
    and l_shipdate < date '{year}-01-01' + interval '1' year
    and l_discount between {lo:.2f} and {hi:.2f}
    and l_quantity < {quantity}
"""

_Q3 = """
select
    l_orderkey,
    sum(l_extendedprice * (1 - l_discount)) as revenue,
    o_orderdate,
    o_shippriority
from
    customer,
    orders,
    lineitem
where
    c_mktsegment = '{segment}'
    and c_custkey = o_custkey
    and l_orderkey = o_orderkey
    and o_orderdate < date '{date}'
    and l_shipdate > date '{date}'
group by
    l_orderkey,
    o_orderdate,
    o_shippriority
order by
    revenue desc,
    o_orderdate
limit 10
"""


def parameter_sets(kind: int) -> list:
    """Every parameter set the spec allows for ``kind``, in a fixed order."""
    if kind == 1:  # DELTA in [60, 120]
        return [{"delta": d} for d in range(60, 121)]
    if kind == 6:  # DATE Jan 1 of [1993, 1997]; DISCOUNT [0.02, 0.09]; QUANTITY 24|25
        return [
            {"year": y, "discount": d / 100.0, "quantity": q}
            for y in range(1993, 1998)
            for d in range(2, 10)
            for q in (24, 25)
        ]
    if kind == 3:  # SEGMENT; DATE a day of [1995-03-01, 1995-03-31]
        return [
            {"segment": s, "date": dt.date(1995, 3, day).isoformat()}
            for s in SEGMENTS
            for day in range(1, 32)
        ]
    raise ValueError(f"no query text for kind {kind}")


def render(kind: int, p: dict) -> str:
    if kind == 1:
        return _Q1.format(delta=p["delta"])
    if kind == 6:
        return _Q6.format(
            year=p["year"], lo=p["discount"] - 0.01, hi=p["discount"] + 0.01,
            quantity=p["quantity"],
        )
    if kind == 3:
        return _Q3.format(segment=p["segment"], date=p["date"])
    raise ValueError(f"no query text for kind {kind}")

