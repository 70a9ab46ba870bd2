"""The comparison that decides ``correct``.

Every query the window completed is compared, once the window has closed,
with the plain reference's answer for the same parameters.  Two numbers,
each with a limit of its own (``LIMITS``; PERF.md gives the readings they
were set from):

- ``cells_wrong``: rows missing or extra, plus cells that are not floats
  (group keys, order keys, dates, counts) and differ.  Exact: limit 0.
- ``rel_gap_max``: the widest gap of a float cell from the reference's,
  as a share of the reference's magnitude.
- ``rows_out_of_order``: neighbouring rows of an answer that stand the wrong
  way round in the order its text asks (``queries.ORDER_BY``; q3: revenue
  descending, then order date).  Two neighbours whose float keys lie within
  ``rel_gap_max``'s limit of each other may stand either way round: the
  program sums in float32 and may rank them so.  Exact otherwise: limit 0.
"""

from __future__ import annotations

import math

import pyarrow as pa

# A number has to be <= its limit.  rel_gap_max lies between the program's
# largest reading over its seeds (7.7e-7, steady) and the bfloat16 control's
# smallest (~1e-3); PERF.md section 2 gives the readings.
LIMITS = {"cells_wrong": 0.0, "rel_gap_max": 3e-5, "rows_out_of_order": 0.0}


def _rows(t: pa.Table, keys) -> dict:
    cols = {n: t.column(n).to_pylist() for n in t.column_names}
    out = {}
    for i in range(t.num_rows):
        out[tuple(str(cols[k][i]) for k in keys)] = {n: cols[n][i] for n in cols}
    return out


def table_gap(served: pa.Table, ref: pa.Table) -> tuple:
    """(cells_wrong, rel_gap_max, column of that gap) of one answer against
    the reference's."""
    if served.column_names != ref.column_names:
        return ref.num_rows * max(1, ref.num_columns), 0.0, ""
    keys = [
        n for n in ref.column_names
        if not pa.types.is_floating(ref.schema.field(n).type) and not n.startswith("count")
    ]
    a, b = _rows(served, keys), _rows(ref, keys)
    wrong = len(set(a) ^ set(b)) + abs(served.num_rows - len(a)) + abs(ref.num_rows - len(b))
    gap, where = 0.0, ""
    for k in set(a) & set(b):
        for name, want in b[k].items():
            got = a[k][name]
            if isinstance(want, float) and isinstance(got, (float, int)) and not isinstance(got, bool):
                if math.isnan(got) or math.isinf(got):
                    wrong += 1
                else:
                    g = abs(got - want) / max(abs(want), 1e-300)
                    if g > gap:
                        gap, where = g, name
            elif got != want:
                wrong += 1
    return wrong, gap, where


def out_of_order(served: pa.Table, order) -> int:
    """Neighbouring rows of ``served`` that stand the wrong way round under
    ``order``, a list of (column, descending).  Keys are compared one after
    the other: equal keys pass to the next; float keys that differ by no
    more than the limit allow either way."""
    if not order or any(name not in served.column_names for name, _ in order):
        return 0
    cols = [(served.column(name).to_pylist(), desc) for name, desc in order]
    wrong = 0
    for i in range(served.num_rows - 1):
        for values, desc in cols:
            a, b = values[i], values[i + 1]
            if a == b:
                continue  # the next key decides
            if isinstance(a, float) and abs(a - b) <= LIMITS["rel_gap_max"] * max(abs(a), abs(b)):
                break  # a tie to the comparison's eye: either way round
            wrong += (a < b) if desc else (a > b)
            break
    return wrong


def judge(pairs) -> dict:
    """``pairs``: (served table, reference table[, order]) of every answer
    compared; ``order`` as ``out_of_order`` takes it.  Returns {"correct",
    "numbers": {name: {"value", "limit"}}, "compared", "widest": the column
    of the widest gap}."""
    wrong, gap, widest, disorder = 0, 0.0, "", 0
    n = 0
    for served, ref, *order in pairs:
        w, g, where = table_gap(served, ref)
        wrong += w
        if g > gap:
            gap, widest = g, where
        disorder += out_of_order(served, order[0]) if order else 0
        n += 1
    numbers = {
        "cells_wrong": {"value": float(wrong), "limit": LIMITS["cells_wrong"]},
        "rel_gap_max": {"value": gap, "limit": LIMITS["rel_gap_max"]},
        "rows_out_of_order": {"value": float(disorder), "limit": LIMITS["rows_out_of_order"]},
    }
    ok = n > 0 and all(v["value"] <= v["limit"] for v in numbers.values())
    return {"correct": bool(ok), "numbers": numbers, "compared": n, "widest": widest}
