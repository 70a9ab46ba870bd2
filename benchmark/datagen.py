"""TPC-H lineitem/orders/customer from a seed, written as parquet.

A copy of the program's ``benchmarks/tpch/datagen.py`` generators (numpy,
dbgen-like distributions, NOT dbgen: correct schemas, key relationships and
value ranges) and of ``chip_smoke.py``'s parallel writer, kept here so that
no later PR can change the data a cell is measured on.  One change from the
original: every slice of lineitem holds exactly four rows an order
(``_exact_total``), so that row counts, and with them every array shape the
program sees, are the same for every seed.  Row and record
widths are the source's: every column of the three tables is written.
"""

from __future__ import annotations

import datetime as dt
import multiprocessing
import os
import time

import numpy as np
import pyarrow as pa

_EPOCH = dt.date(1970, 1, 1)
_START = (dt.date(1992, 1, 1) - _EPOCH).days
_END = (dt.date(1998, 8, 2) - _EPOCH).days
_CUT = (dt.date(1995, 6, 17) - _EPOCH).days

SHIP_MODES = np.array(["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"])
SHIP_INSTRUCT = np.array(
    ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]
)
ORDER_STATUS = np.array(["F", "O", "P"])
PRIORITIES = np.array(["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"])
SEGMENTS = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"])
_WORDS = np.array(
    ["furiously", "quickly", "special", "pending", "final", "express",
     "regular", "ironic", "even", "bold", "silent", "deposits", "accounts",
     "requests", "packages", "theodolites", "instructions", "foxes"]
)

# the tables a configuration may name; a run writes those its traffic's kinds scan
TABLES = ("lineitem", "orders", "customer")


def _slice(total: int, seed: int, i: int, k: int):
    """(rng, lo, hi): the i-th of k slices of the key range [0, total) on
    an independent stream, so k processes generate one table at once."""
    return np.random.default_rng([seed, i]), total * i // k, total * (i + 1) // k


def _orderkeys(lo: int, hi: int) -> np.ndarray:
    idx = np.arange(lo, hi)  # dbgen sparsifies: 8 keys per 32-key block
    return ((idx // 8) * 32 + idx % 8 + 1).astype(np.int64)


def _comments(rng, n: int) -> np.ndarray:
    return np.char.add(np.char.add(rng.choice(_WORDS, n), " "), rng.choice(_WORDS, n))


def _exact_total(rng, lines: np.ndarray, total: int) -> np.ndarray:
    """Move single lines between orders (staying in 1..7) until the slice
    holds exactly ``total`` rows.  The draw's sum strays from its mean of 4
    an order by ~0.1%; left alone, every seed would give every file another
    row count, so another tail-batch shape, and the program would compile
    ~50 executables anew in every run (my chip run, PR 25).  With it every
    seed gives the same sizes: 6,000,000 lineitem rows at SF1."""
    diff = total - int(lines.sum())
    while diff:
        step = 1 if diff > 0 else -1
        free = np.flatnonzero(lines < 7) if step > 0 else np.flatnonzero(lines > 1)
        take = rng.choice(free, min(abs(diff), len(free)), replace=False)
        lines[take] += step
        diff -= step * len(take)
    return lines


def gen_lineitem(sf: float, seed: int, i: int, k: int) -> pa.Table:
    rng, lo, hi = _slice(int(1_500_000 * sf), seed, i, k)
    lines = _exact_total(rng, rng.integers(1, 8, hi - lo), 4 * (hi - lo))
    n = int(lines.sum())
    orderkey = np.repeat(_orderkeys(lo, hi), lines)
    starts = np.cumsum(lines) - lines
    linenumber = (
        np.arange(n, dtype=np.int64) - np.repeat(starts, lines) + 1
    ).astype(np.int32)
    quantity = rng.integers(1, 51, n).astype(np.float64)
    extendedprice = np.round(rng.uniform(900.0, 105000.0, n), 2)
    discount = np.round(rng.integers(0, 11, n) / 100.0, 2)
    tax = np.round(rng.integers(0, 9, n) / 100.0, 2)
    shipdate = rng.integers(_START, _END, n, dtype=np.int32)
    commitdate = shipdate + rng.integers(-30, 60, n)
    receiptdate = shipdate + rng.integers(1, 31, n)
    rf = np.where(receiptdate <= _CUT, rng.choice(np.array(["A", "R"]), n), "N")
    ls = np.where(shipdate > _CUT, "O", "F")
    return pa.table(
        {
            "l_orderkey": pa.array(orderkey, pa.int64()),
            "l_partkey": pa.array(rng.integers(1, max(int(200_000 * sf), 2), n), pa.int64()),
            "l_suppkey": pa.array(rng.integers(1, max(int(10_000 * sf), 2), n), pa.int64()),
            "l_linenumber": pa.array(linenumber, pa.int32()),
            "l_quantity": pa.array(quantity, pa.float64()),
            "l_extendedprice": pa.array(extendedprice, pa.float64()),
            "l_discount": pa.array(discount, pa.float64()),
            "l_tax": pa.array(tax, pa.float64()),
            "l_returnflag": pa.array(rf, pa.string()),
            "l_linestatus": pa.array(ls, pa.string()),
            "l_shipdate": pa.array(shipdate, pa.date32()),
            "l_commitdate": pa.array(commitdate.astype(np.int32), pa.date32()),
            "l_receiptdate": pa.array(receiptdate.astype(np.int32), pa.date32()),
            "l_shipinstruct": pa.array(rng.choice(SHIP_INSTRUCT, n), pa.string()),
            "l_shipmode": pa.array(rng.choice(SHIP_MODES, n), pa.string()),
            "l_comment": pa.array(_comments(rng, n), pa.string()),
        }
    )


def gen_orders(sf: float, seed: int, i: int, k: int) -> pa.Table:
    rng, lo, hi = _slice(int(1_500_000 * sf), seed, i, k)
    n = hi - lo
    return pa.table(
        {
            "o_orderkey": pa.array(_orderkeys(lo, hi), pa.int64()),
            "o_custkey": pa.array(rng.integers(1, max(int(150_000 * sf), 2), n), pa.int64()),
            "o_orderstatus": pa.array(rng.choice(ORDER_STATUS, n), pa.string()),
            "o_totalprice": pa.array(np.round(rng.uniform(850.0, 600000.0, n), 2), pa.float64()),
            "o_orderdate": pa.array(rng.integers(_START, _END, n, dtype=np.int32), pa.date32()),
            "o_orderpriority": pa.array(rng.choice(PRIORITIES, n), pa.string()),
            "o_clerk": pa.array(
                np.char.add("Clerk#", rng.integers(1, 1001, n).astype(str)), pa.string()
            ),
            "o_shippriority": pa.array(np.zeros(n, np.int32), pa.int32()),
            "o_comment": pa.array(_comments(rng, n), pa.string()),
        }
    )


def gen_customer(sf: float, seed: int, i: int, k: int) -> pa.Table:
    rng, lo, hi = _slice(int(150_000 * sf), seed, i, k)
    n = hi - lo
    key = np.arange(lo + 1, hi + 1, dtype=np.int64)
    return pa.table(
        {
            "c_custkey": pa.array(key, pa.int64()),
            "c_name": pa.array(np.char.add("Customer#", key.astype(str)), pa.string()),
            "c_address": pa.array(_comments(rng, n), pa.string()),
            "c_nationkey": pa.array(rng.integers(0, 25, n), pa.int64()),
            "c_phone": pa.array(
                np.char.add(rng.integers(10, 35, n).astype(str),
                            np.char.add("-", rng.integers(100, 1000, n).astype(str))),
                pa.string(),
            ),
            "c_acctbal": pa.array(np.round(rng.uniform(-999.99, 9999.99, n), 2), pa.float64()),
            "c_mktsegment": pa.array(rng.choice(SEGMENTS, n), pa.string()),
            "c_comment": pa.array(_comments(rng, n), pa.string()),
        }
    )


_GEN = {"lineitem": gen_lineitem, "orders": gen_orders, "customer": gen_customer}


def _write_chunk(job) -> tuple:
    """Pool worker: one slice of one table, generated and written."""
    import pyarrow.parquet as pq

    name, i, k, sf, seed, out_dir = job
    tbl = _GEN[name](sf, seed + TABLES.index(name), i, k)
    path = os.path.join(out_dir, name, f"part-{i:03d}.parquet")
    pq.write_table(tbl, path)
    return name, tbl.num_rows, os.path.getsize(path)


def generate(out_dir: str, tables, sf: float, seed: int, files: int, procs: int = 0) -> dict:
    """Write ``tables`` under ``out_dir/<table>/part-NNN.parquet``; returns
    the row counts, the bytes written and the seconds it took."""
    unknown = [t for t in tables if t not in _GEN]
    if unknown:
        raise ValueError(f"no generator for table(s) {unknown}")
    for name in tables:
        os.makedirs(os.path.join(out_dir, name), exist_ok=True)
    jobs = [(name, i, files, sf, seed, out_dir) for name in tables for i in range(files)]
    procs = procs or max(1, min(files, (os.cpu_count() or 2) - 1))
    t0 = time.monotonic()
    rows = dict.fromkeys(tables, 0)
    size = 0
    # spawn: workers import this module alone and never see jax
    with multiprocessing.get_context("spawn").Pool(procs) as pool:
        for name, n, nbytes in pool.imap_unordered(_write_chunk, jobs):
            rows[name] += n
            size += nbytes
    return {
        "rows": rows,
        "parquet_bytes": size,
        "files_per_table": files,
        "seconds": time.monotonic() - t0,
        "processes": procs,
    }
