"""The plain reference: q1, q6 and q3 over the generated parquet, in numpy.

It imports nothing of the program and takes nothing the program made: it
reads the files the generator wrote and answers a query from its
parameters.  ``precision="float64"`` is the reference.  ``"bfloat16"`` is the
CONTROL of the comparison that decides ``correct``.  The program's device
path states float32 (its x32 mode: native f32 with compensated sums), so
the control is the same arithmetic in the nearest precision below: columns
and every product rounded to bfloat16, sums accumulated in float32, as a
single-pass MXU matmul would do it.  The control has to come out as not
correct.  (``"float32"``, plain pairwise float32 sums, is kept as a reading:
it lands BELOW the program's own gap, see PERF.md.)
"""

from __future__ import annotations

import datetime as dt
import glob
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

_EPOCH = dt.date(1970, 1, 1)


def _days(d: dt.date) -> int:
    return (d - _EPOCH).days


class Data:
    """Columns of the generated tables, loaded once as numpy arrays.
    ``files`` narrows a table to some of its parquet files (used only to
    plant faults in tests)."""

    def __init__(self, data_dir: str, files=None):
        self.data_dir = data_dir
        self.files = files
        self._cols: dict = {}

    def col(self, table: str, name: str) -> np.ndarray:
        key = (table, name)
        if key not in self._cols:
            paths = sorted(glob.glob(os.path.join(self.data_dir, table, "*.parquet")))
            if self.files is not None:
                paths = [p for i, p in enumerate(paths) if i in self.files]
            arr = pa.concat_arrays(
                [c for p in paths for c in pq.read_table(p, columns=[name]).column(0).chunks]
            )
            if pa.types.is_string(arr.type):
                enc = pc.dictionary_encode(arr)
                self._cols[key] = (
                    np.asarray(enc.indices), [str(x) for x in enc.dictionary.to_pylist()]
                )
            elif pa.types.is_date32(arr.type):
                self._cols[key] = np.asarray(arr.cast(pa.int32()))
            else:
                self._cols[key] = np.asarray(arr)
        return self._cols[key]


def _bf16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest even), held as float32."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return b.view(np.float32)


def _sum(x: np.ndarray, precision: str) -> float:
    # numpy's pairwise sum, in float64 for the reference and float32 below it
    return float(np.sum(x, dtype=np.float64 if precision == "float64" else np.float32))


def _f(x: np.ndarray, precision: str) -> np.ndarray:
    """A column, or a product, in the stated precision."""
    if precision == "float64":
        return x
    x = x.astype(np.float32)
    return _bf16(x) if precision == "bfloat16" else x


def q1(data: Data, p: dict, precision: str = "float64") -> pa.Table:
    cutoff = _days(dt.date(1998, 12, 1) - dt.timedelta(days=p["delta"]))
    keep = data.col("lineitem", "l_shipdate") <= cutoff
    rf_codes, rf_dict = data.col("lineitem", "l_returnflag")
    ls_codes, ls_dict = data.col("lineitem", "l_linestatus")
    qty = _f(data.col("lineitem", "l_quantity")[keep], precision)
    price = _f(data.col("lineitem", "l_extendedprice")[keep], precision)
    disc = _f(data.col("lineitem", "l_discount")[keep], precision)
    tax = _f(data.col("lineitem", "l_tax")[keep], precision)
    one = qty.dtype.type(1)
    disc_price = _f(price * _f(one - disc, precision), precision)
    charge = _f(disc_price * _f(one + tax, precision), precision)
    group = (rf_codes[keep].astype(np.int64) * len(ls_dict) + ls_codes[keep])
    rows = []
    for g in np.unique(group):
        m = group == g
        n = int(m.sum())
        s_qty, s_price, s_disc = (_sum(a[m], precision) for a in (qty, price, disc))
        rows.append(
            (rf_dict[g // len(ls_dict)], ls_dict[g % len(ls_dict)], s_qty, s_price,
             _sum(disc_price[m], precision), _sum(charge[m], precision),
             s_qty / n, s_price / n, s_disc / n, n)
        )
    rows.sort(key=lambda r: (r[0], r[1]))
    names = ("l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
             "sum_disc_price", "sum_charge", "avg_qty", "avg_price", "avg_disc",
             "count_order")
    cols = list(zip(*rows)) if rows else [[] for _ in names]
    types = [pa.string(), pa.string()] + [pa.float64()] * 7 + [pa.int64()]
    return pa.table({n: pa.array(list(c), t) for n, c, t in zip(names, cols, types)})


def q6(data: Data, p: dict, precision: str = "float64") -> pa.Table:
    ship = data.col("lineitem", "l_shipdate")
    disc = data.col("lineitem", "l_discount")
    qty = data.col("lineitem", "l_quantity")
    lo, hi = _days(dt.date(p["year"], 1, 1)), _days(dt.date(p["year"] + 1, 1, 1))
    # the SQL compares the float64 column with the 2-decimal literals
    d_lo, d_hi = float(f"{p['discount'] - 0.01:.2f}"), float(f"{p['discount'] + 0.01:.2f}")
    keep = (ship >= lo) & (ship < hi) & (disc >= d_lo) & (disc <= d_hi) & (qty < p["quantity"])
    price = _f(data.col("lineitem", "l_extendedprice")[keep], precision)
    revenue = _sum(_f(price * _f(disc[keep], precision), precision), precision) if keep.any() else None
    return pa.table({"revenue": pa.array([revenue], pa.float64())})


def q3(data: Data, p: dict, precision: str = "float64") -> pa.Table:
    date = _days(dt.date.fromisoformat(p["date"]))
    seg_codes, seg_dict = data.col("customer", "c_mktsegment")
    custkeys = data.col("customer", "c_custkey")[seg_codes == seg_dict.index(p["segment"])]
    o_date = data.col("orders", "o_orderdate")
    o_keep = (o_date < date) & np.isin(data.col("orders", "o_custkey"), custkeys)
    o_key = data.col("orders", "o_orderkey")[o_keep]
    o_date, o_prio = o_date[o_keep], data.col("orders", "o_shippriority")[o_keep]
    order = np.argsort(o_key)
    o_key, o_date, o_prio = o_key[order], o_date[order], o_prio[order]
    l_keep = data.col("lineitem", "l_shipdate") > date
    l_key = data.col("lineitem", "l_orderkey")[l_keep]
    pos = np.searchsorted(o_key, l_key)
    pos[pos == len(o_key)] = 0
    hit = (o_key[pos] == l_key) if len(o_key) else np.zeros(len(l_key), bool)
    pos = pos[hit]
    price = _f(data.col("lineitem", "l_extendedprice")[l_keep][hit], precision)
    disc = _f(data.col("lineitem", "l_discount")[l_keep][hit], precision)
    value = _f(price * _f(price.dtype.type(1) - disc, precision), precision)
    # an order has at most 7 lines: a sequential sum per order IS the plain sum
    revenue = np.zeros(len(o_key), value.dtype)
    np.add.at(revenue, pos, value)
    has = np.zeros(len(o_key), bool)
    has[pos] = True
    idx = np.flatnonzero(has)
    top = idx[np.lexsort((o_date[idx], -revenue[idx].astype(np.float64)))][:10]
    return pa.table(
        {
            "l_orderkey": pa.array(o_key[top], pa.int64()),
            "revenue": pa.array(revenue[top].astype(np.float64), pa.float64()),
            "o_orderdate": pa.array(o_date[top].astype(np.int32), pa.int32()).cast(pa.date32()),
            "o_shippriority": pa.array(o_prio[top], pa.int32()),
        }
    )


ANSWER = {1: q1, 6: q6, 3: q3}


def answer(data: Data, kind: int, params: dict, precision: str = "float64") -> pa.Table:
    return ANSWER[kind](data, params, precision)
