"""Synthetic TPC-H data generator (numpy, deterministic).

Counterpart of the reference's tbl-file converter workflow
(``benchmarks/src/bin/tpch.rs`` `convert` subcommand): since dbgen isn't
available in this image, tables are generated directly with dbgen-like
distributions — correct schemas, key relationships (orderkey/custkey/
partkey/suppkey joins work), realistic value ranges.  Queries are verified
by cross-checking execution paths (CPU vs TPU vs distributed), not against
official dbgen answers.
"""

from __future__ import annotations

import datetime as dt

import numpy as np
import pyarrow as pa

_EPOCH = dt.date(1970, 1, 1)
_START = (dt.date(1992, 1, 1) - _EPOCH).days
_END = (dt.date(1998, 8, 2) - _EPOCH).days

RETURN_FLAGS = np.array(["A", "N", "R"])
LINE_STATUS = np.array(["F", "O"])
SHIP_MODES = np.array(["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"])
SHIP_INSTRUCT = np.array(
    ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]
)
ORDER_STATUS = np.array(["F", "O", "P"])
PRIORITIES = np.array(["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"])
SEGMENTS = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"])
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1), ("EGYPT", 4),
    ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3), ("INDIA", 2), ("INDONESIA", 2),
    ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0),
    ("MOROCCO", 0), ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1),
]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
# TPC-H spec P_NAME words (dbgen's colors list, subset) — q9 filters
# '%green%' and q20 'forest%', so part names must draw from these
P_NAME_WORDS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished", "chartreuse",
    "chiffon", "chocolate", "coral", "cornflower", "cornsilk", "cream", "cyan",
    "dark", "deep", "dim", "dodger", "drab", "firebrick", "floral", "forest",
    "frosted", "gainsboro", "ghost", "goldenrod", "green", "grey", "honeydew",
    "hot", "indian", "ivory", "khaki", "lace", "lavender", "lawn", "lemon",
    "light", "lime", "linen", "magenta", "maroon", "medium", "metallic",
    "midnight", "mint", "misty", "moccasin", "navajo", "navy", "olive", "orange",
    "orchid", "pale", "papaya", "peach", "peru", "pink", "plum", "powder",
    "puff", "purple", "red", "rose", "rosy", "royal", "saddle", "salmon",
    "sandy", "seashell", "sienna", "sky", "slate", "smoke", "snow", "spring",
    "steel", "tan", "thistle", "tomato", "turquoise", "violet", "wheat", "white",
    "yellow",
]

PART_TYPES_1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
PART_TYPES_2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
PART_TYPES_3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINERS_1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINERS_2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]


def _dates(rng: np.random.Generator, n: int) -> np.ndarray:
    return rng.integers(_START, _END, n, dtype=np.int32)


def _chunk(total: int, seed: int, chunk):
    """``(rng, lo, hi)`` for one slice of a keyed table.

    ``chunk=None`` is the whole table on the seed's own stream (what every
    fixture has always used).  ``chunk=(i, k)`` is the i-th of k slices of
    the key range ``[0, total)`` on an independent stream, so a large
    table can be generated and written by k processes at once: the slices
    together have the whole table's keys, schema and distributions — not
    the rows ``chunk=None`` would draw.
    """
    if chunk is None:
        return np.random.default_rng(seed), 0, total
    i, k = chunk
    return np.random.default_rng([seed, i]), total * i // k, total * (i + 1) // k


def gen_lineitem(sf: float, seed: int = 42, chunk=None) -> pa.Table:
    rng, lo, hi = _chunk(int(1_500_000 * sf), seed, chunk)
    lines_per_order = rng.integers(1, 8, hi - lo)
    n = int(lines_per_order.sum())
    orderkey = np.repeat(_orderkeys(lo, hi), lines_per_order)
    # vectorized within-order line numbers (a 15M-iteration Python loop at
    # SF10 otherwise dominates datagen)
    starts = np.cumsum(lines_per_order) - lines_per_order
    linenumber = (
        np.arange(n, dtype=np.int64) - np.repeat(starts, lines_per_order) + 1
    ).astype(np.int32)
    quantity = rng.integers(1, 51, n).astype(np.float64)
    extendedprice = np.round(rng.uniform(900.0, 105000.0, n), 2)
    discount = np.round(rng.integers(0, 11, n) / 100.0, 2)
    tax = np.round(rng.integers(0, 9, n) / 100.0, 2)
    shipdate = _dates(rng, n)
    commitdate = shipdate + rng.integers(-30, 60, n)
    receiptdate = shipdate + rng.integers(1, 31, n)
    rf = np.where(
        receiptdate <= (dt.date(1995, 6, 17) - _EPOCH).days,
        rng.choice(np.array(["A", "R"]), n),
        "N",
    )
    ls = np.where(shipdate > (dt.date(1995, 6, 17) - _EPOCH).days, "O", "F")
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


def _orderkeys(lo: int, hi: int) -> np.ndarray:
    # dbgen sparsifies order keys: 8 per 32-key block
    idx = np.arange(lo, hi)
    return ((idx // 8) * 32 + idx % 8 + 1).astype(np.int64)


def _comments(rng: np.random.Generator, n: int) -> np.ndarray:
    words = np.array(
        ["furiously", "quickly", "special", "pending", "final", "express",
         "regular", "ironic", "even", "bold", "silent", "deposits", "accounts",
         "requests", "packages", "theodolites", "instructions", "foxes"]
    )
    return np.char.add(
        np.char.add(rng.choice(words, n), " "), rng.choice(words, n)
    )


def _part_names(rng: np.random.Generator, n: int) -> np.ndarray:
    # dbgen: P_NAME is 5 distinct color words; 2 suffice for the LIKE
    # predicates ('forest%' prefix, '%green%' containment) to hit
    w = np.array(P_NAME_WORDS)
    return np.char.add(
        np.char.add(rng.choice(w, n), " "), rng.choice(w, n)
    )


def gen_orders(sf: float, seed: int = 43, chunk=None) -> pa.Table:
    rng, lo, hi = _chunk(int(1_500_000 * sf), seed, chunk)
    n = hi - lo
    orderkey = _orderkeys(lo, hi)
    orderdate = _dates(rng, n)
    return pa.table(
        {
            "o_orderkey": pa.array(orderkey, pa.int64()),
            "o_custkey": pa.array(rng.integers(1, max(int(150_000 * sf), 2), n), pa.int64()),
            "o_orderstatus": pa.array(rng.choice(ORDER_STATUS, n), pa.string()),
            "o_totalprice": pa.array(np.round(rng.uniform(850.0, 600000.0, n), 2), pa.float64()),
            "o_orderdate": pa.array(orderdate, pa.date32()),
            "o_orderpriority": pa.array(rng.choice(PRIORITIES, n), pa.string()),
            "o_clerk": pa.array(
                np.char.add("Clerk#", rng.integers(1, 1001, n).astype(str)), pa.string()
            ),
            "o_shippriority": pa.array(np.zeros(n, np.int32), pa.int32()),
            "o_comment": pa.array(_comments(rng, n), pa.string()),
        }
    )


def gen_customer(sf: float, seed: int = 44, chunk=None) -> pa.Table:
    rng, lo, hi = _chunk(int(150_000 * sf), seed, chunk)
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


def gen_part(sf: float, seed: int = 45) -> pa.Table:
    rng = np.random.default_rng(seed)
    n = int(200_000 * sf)
    key = np.arange(1, n + 1, dtype=np.int64)
    ptype = np.char.add(
        np.char.add(rng.choice(np.array(PART_TYPES_1), n), " "),
        np.char.add(
            np.char.add(rng.choice(np.array(PART_TYPES_2), n), " "),
            rng.choice(np.array(PART_TYPES_3), n),
        ),
    )
    container = np.char.add(
        np.char.add(rng.choice(np.array(CONTAINERS_1), n), " "),
        rng.choice(np.array(CONTAINERS_2), n),
    )
    return pa.table(
        {
            "p_partkey": pa.array(key, pa.int64()),
            "p_name": pa.array(_part_names(rng, n), pa.string()),
            "p_mfgr": pa.array(
                np.char.add("Manufacturer#", rng.integers(1, 6, n).astype(str)),
                pa.string(),
            ),
            "p_brand": pa.array(
                np.char.add("Brand#", rng.integers(11, 56, n).astype(str)), pa.string()
            ),
            "p_type": pa.array(ptype, pa.string()),
            "p_size": pa.array(rng.integers(1, 51, n).astype(np.int32), pa.int32()),
            "p_container": pa.array(container, pa.string()),
            "p_retailprice": pa.array(np.round(900 + key % 1000 + 0.01 * (key % 100), 2), pa.float64()),
            "p_comment": pa.array(_comments(rng, n), pa.string()),
        }
    )


def gen_supplier(sf: float, seed: int = 46) -> pa.Table:
    rng = np.random.default_rng(seed)
    n = int(10_000 * sf)
    key = np.arange(1, n + 1, dtype=np.int64)
    return pa.table(
        {
            "s_suppkey": pa.array(key, pa.int64()),
            "s_name": pa.array(np.char.add("Supplier#", key.astype(str)), pa.string()),
            "s_address": pa.array(_comments(rng, n), pa.string()),
            "s_nationkey": pa.array(rng.integers(0, 25, n), pa.int64()),
            "s_phone": pa.array(
                np.char.add(rng.integers(10, 35, n).astype(str),
                            np.char.add("-", rng.integers(100, 1000, n).astype(str))),
                pa.string(),
            ),
            "s_acctbal": pa.array(np.round(rng.uniform(-999.99, 9999.99, n), 2), pa.float64()),
            "s_comment": pa.array(_comments(rng, n), pa.string()),
        }
    )


def gen_partsupp(sf: float, seed: int = 47) -> pa.Table:
    rng = np.random.default_rng(seed)
    n_part = int(200_000 * sf)
    partkey = np.repeat(np.arange(1, n_part + 1, dtype=np.int64), 4)
    n = len(partkey)
    suppkey = rng.integers(1, max(int(10_000 * sf), 2), n)
    return pa.table(
        {
            "ps_partkey": pa.array(partkey, pa.int64()),
            "ps_suppkey": pa.array(suppkey, pa.int64()),
            "ps_availqty": pa.array(rng.integers(1, 10000, n).astype(np.int32), pa.int32()),
            "ps_supplycost": pa.array(np.round(rng.uniform(1.0, 1000.0, n), 2), pa.float64()),
            "ps_comment": pa.array(_comments(rng, n), pa.string()),
        }
    )


def gen_nation() -> pa.Table:
    return pa.table(
        {
            "n_nationkey": pa.array(np.arange(25, dtype=np.int64), pa.int64()),
            "n_name": pa.array([n for n, _ in NATIONS], pa.string()),
            "n_regionkey": pa.array([r for _, r in NATIONS], pa.int64()),
            "n_comment": pa.array(["" for _ in NATIONS], pa.string()),
        }
    )


def gen_region() -> pa.Table:
    return pa.table(
        {
            "r_regionkey": pa.array(np.arange(5, dtype=np.int64), pa.int64()),
            "r_name": pa.array(REGIONS, pa.string()),
            "r_comment": pa.array(["" for _ in REGIONS], pa.string()),
        }
    )


GENERATORS = {
    "lineitem": gen_lineitem,
    "orders": gen_orders,
    "customer": gen_customer,
    "part": gen_part,
    "supplier": gen_supplier,
    "partsupp": gen_partsupp,
}


def gen_table(name: str, sf: float) -> pa.Table:
    if name == "nation":
        return gen_nation()
    if name == "region":
        return gen_region()
    return GENERATORS[name](sf)


ALL_TABLES = ["lineitem", "orders", "customer", "part", "supplier", "partsupp", "nation", "region"]


def register_all(ctx, sf: float = 0.01, partitions: int = 1) -> None:
    """Register all 8 TPC-H tables as in-memory tables on a context."""
    from arrow_ballista_tpu.catalog import MemoryTable

    for name in ALL_TABLES:
        tbl = gen_table(name, sf)
        ctx.register_table(name, MemoryTable.from_table(tbl, partitions))


def write_parquet(dir_path: str, sf: float = 0.1, partitions: int = 2) -> None:
    """Materialize the dataset as partitioned parquet files."""
    import os

    import pyarrow.parquet as pq

    for name in ALL_TABLES:
        tbl = gen_table(name, sf)
        tdir = os.path.join(dir_path, name)
        os.makedirs(tdir, exist_ok=True)
        n = partitions if name not in ("nation", "region") else 1
        rows = tbl.num_rows
        per = (rows + n - 1) // n
        for i in range(n):
            pq.write_table(tbl.slice(i * per, per), os.path.join(tdir, f"part-{i}.parquet"))
