"""Query suites, one file each, found by the name a configuration gives.

A configuration names its suite under ``"suite"``; one that names none runs
``tpch``.  ``suites/<name>.py`` provides:

- ``TABLES``: table name -> generator ``(scale, seed, i, k) -> pa.Table``
  of the i-th of k slices, in a fixed order: a table's seed is the run's
  seed plus the table's index here.
- ``scale(config, sf)``: the value the generators take, read from the
  configuration, or ``sf`` where a ``--platform cpu`` rehearsal gives one.
- ``TABLES_OF``, ``COLUMNS_OF``, ``ORDER_BY`` by kind: the base tables a
  query scans, the columns it reads of each, and the order its text asks
  its rows in, as (column, descending) pairs; ``COLUMN_BYTES`` by column:
  the bytes a row holds of it as stored.
- ``parameter_sets(kind)``: every parameter set of the kind, in a fixed
  order; ``render(kind, params)``: the SQL text.
- ``answer(data, kind, params, precision)``: the plain reference over a
  ``reference.Data`` of the generated files; it imports nothing of the
  program.
- ``label(kind)``: the name the result line and the logs give the kind.

Kind ids are ints or strings, as the traffic file gives them.  A suite file
may import another's generators, so that tables two suites share come out
the same.  What no suite owns lives here: ``load``, ``generate`` (a run's
tables from the seed, written as parquet by a pool of processes) and
``Draws`` (a kind's parameter sets in an order fixed by the seed).
"""

from __future__ import annotations

import importlib.util
import multiprocessing
import os
import random
import time

HERE = os.path.dirname(os.path.abspath(__file__))

REQUIRED = ("TABLES", "scale", "TABLES_OF", "COLUMNS_OF", "COLUMN_BYTES", "ORDER_BY",
            "parameter_sets", "render", "answer", "label")


def known(suites_dir: str = HERE) -> list:
    return sorted(f[:-3] for f in os.listdir(suites_dir) if f.endswith(".py") and not f.startswith("_"))


def _exec(path: str, name: str):
    spec = importlib.util.spec_from_file_location(f"benchmark_suite_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(name: str, suites_dir: str = HERE):
    """The module of ``suites/<name>.py``, checked for every part a suite
    provides."""
    path = os.path.join(suites_dir, f"{name}.py")
    if name.startswith("_") or not os.path.isfile(path):
        raise KeyError(f"no suite {name!r} in {suites_dir}; known: {known(suites_dir)}")
    mod = _exec(path, name)
    missing = [a for a in REQUIRED if not hasattr(mod, a)]
    if missing:
        raise AttributeError(f"suite {name!r} ({path}) lacks {', '.join(missing)}")
    return mod


def write_chunk(job) -> tuple:
    """Pool worker: one slice of one table, generated and written."""
    import pyarrow.parquet as pq

    suite_file, name, i, k, scale, seed, out_dir = job
    tables = _exec(suite_file, os.path.basename(suite_file)[:-3]).TABLES
    tbl = tables[name](scale, seed + list(tables).index(name), i, k)
    path = os.path.join(out_dir, name, f"part-{i:03d}.parquet")
    pq.write_table(tbl, path)
    return name, tbl.num_rows, os.path.getsize(path)


def generate(out_dir: str, suite, tables, scale, seed: int, files: int, procs: int = 0) -> dict:
    """Write the suite's ``tables`` under ``out_dir/<table>/part-NNN.parquet``;
    returns the row counts, the bytes written and the seconds it took."""
    unknown = [t for t in tables if t not in suite.TABLES]
    if unknown:
        raise ValueError(f"no generator for table(s) {unknown}; the suite has {list(suite.TABLES)}")
    for name in tables:
        os.makedirs(os.path.join(out_dir, name), exist_ok=True)
    jobs = [(suite.__file__, name, i, files, scale, seed, out_dir) for name in tables for i in range(files)]
    procs = procs or max(1, min(files, (os.cpu_count() or 2) - 1))
    t0 = time.monotonic()
    rows = dict.fromkeys(tables, 0)
    size = 0
    # spawn: workers import the suite alone and never see jax
    with multiprocessing.get_context("spawn").Pool(procs) as pool:
        for name, n, nbytes in pool.imap_unordered(write_chunk, jobs):
            rows[name] += n
            size += nbytes
    return {
        "rows": rows,
        "parquet_bytes": size,
        "files_per_table": files,
        "seconds": time.monotonic() - t0,
        "processes": procs,
    }


class Draws:
    """Per-kind parameter sets in an order fixed by the seed.  The window's
    i-th query of a kind uses entry ``i % sets``; ``window_sets(k)`` are the
    entries warm-up has to run; ``aside(k)`` is one the window never uses."""

    def __init__(self, suite, seed: int, kinds, sets: int = 1):
        self.sets = max(1, int(sets))
        self._sets = {}
        for kind in dict.fromkeys(kinds):
            sets = suite.parameter_sets(kind)
            random.Random(f"{seed}/params/{kind}").shuffle(sets)
            self._sets[kind] = sets

    def window_sets(self, kind) -> list:
        return self._sets[kind][: self.sets]

    def window(self, kind, i: int) -> dict:
        return self._sets[kind][i % min(self.sets, len(self._sets[kind]))]

    def aside(self, kind) -> dict:
        return self._sets[kind][-1]
