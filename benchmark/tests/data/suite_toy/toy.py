"""A toy suite for the tests: one table of keyed rows, a group-by kind with an
order (an int id) and a filtered sum (a string id).  It shows that a suite is
added as files alone: tests copy it to ``suites/toy.py`` of a checkout."""

import numpy as np
import pyarrow as pa

GROUPS = 20


def gen_x(rows: int, seed: int, i: int, k: int) -> pa.Table:
    rng = np.random.default_rng([seed, i])
    n = rows * (i + 1) // k - rows * i // k
    id4 = rng.integers(1, GROUPS + 1, n)
    return pa.table({
        "id1": pa.array(np.char.add("id", np.char.zfill(id4.astype(str), 3)), pa.string()),
        "id4": pa.array(id4, pa.int64()),
        "v1": pa.array(rng.integers(1, 6, n), pa.int64()),
        "v3": pa.array(np.round(rng.uniform(0.0, 100.0, n), 6), pa.float64()),
    })


TABLES = {"x": gen_x}
TABLES_OF = {1: ("x",), "sum_v3": ("x",)}
COLUMNS_OF = {1: {"x": ("id1", "id4", "v3")}, "sum_v3": {"x": ("v1", "v3")}}
COLUMN_BYTES = {"id1": 5, "id4": 8, "v1": 8, "v3": 8}
ORDER_BY = {1: (("id1", False),), "sum_v3": ()}


def scale(config: dict, sf=None) -> int:
    """Rows of ``x``: the configuration's, or a million a unit of ``sf``."""
    return int(config["rows"]) if sf is None else int(sf * 1_000_000)


def parameter_sets(kind) -> list:
    if kind == 1:
        return [{"cut": c} for c in range(GROUPS // 2, GROUPS + 1)]
    if kind == "sum_v3":
        return [{"lo": lo} for lo in (1, 2, 3)]
    raise ValueError(f"no query text for kind {kind!r}")


def render(kind, p: dict) -> str:
    if kind == 1:
        return (f"select id1, sum(v3) as v3, count(*) as count_rows from x where id4 <= {p['cut']} "
                "group by id1 order by id1")
    if kind == "sum_v3":
        return f"select sum(v3) as v3 from x where v1 >= {p['lo']}"
    raise ValueError(f"no query text for kind {kind!r}")


def answer(data, kind, p: dict, precision: str = "float64") -> pa.Table:
    codes, names = data.col("x", "id1")
    v3 = data.col("x", "v3")
    if kind == "sum_v3":
        return pa.table({"v3": pa.array([float(np.sum(v3[data.col("x", "v1") >= p["lo"]]))], pa.float64())})
    keep = data.col("x", "id4") <= p["cut"]
    sums = np.bincount(codes[keep], v3[keep], len(names))
    counts = np.bincount(codes[keep], minlength=len(names))
    rows = sorted((names[c], float(sums[c]), int(counts[c])) for c in np.flatnonzero(counts))
    cols = list(zip(*rows))
    return pa.table({"id1": pa.array(cols[0], pa.string()), "v3": pa.array(cols[1], pa.float64()),
                     "count_rows": pa.array(cols[2], pa.int64())})


def label(kind) -> str:
    return f"toy_{kind}"
