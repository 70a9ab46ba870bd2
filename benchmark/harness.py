"""Resolution by name: a cell, its configuration, its traffic mix, the
configuration's query suite and the per-layer metric readers are files found
by the names in ``BENCHMARK.json`` and the configuration.  A later PR adds a
file and an entry, and edits nothing."""

from __future__ import annotations

import importlib.util
import json
import os

from benchmark import suites

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_json(root: str = REPO) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def resolve(workload: str, bench: dict, bench_dir: str = HERE) -> dict:
    """The cell ``workload`` with its configuration, traffic and suite
    (``suites/<config's "suite">.py``, ``tpch`` where it names none) loaded.
    Cells of BENCHMARK.json first, then those kept for later."""
    cells = list(bench["workloads"])
    later = os.path.join(bench_dir, "cells_later.json")
    if os.path.exists(later):
        cells += load_json(later)["workloads"]
    for cell in cells:
        if cell["name"] == workload:
            break
    else:
        raise KeyError(f"no workload {workload!r}; known: {[c['name'] for c in cells]}")
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = load_json(os.path.join(os.path.dirname(bench_dir), files[cell["config"]]))
    traffic = load_json(os.path.join(bench_dir, "traffic", cell["traffic"] + ".json"))
    if int(config["chips"]) != int(cell["chips"]):
        raise ValueError(f"cell {workload} asks {cell['chips']} chips, its configuration {config['chips']}")
    suite = suites.load(config.get("suite", "tpch"), os.path.join(bench_dir, "suites"))
    return {"cell": cell, "config": config, "traffic": traffic, "suite": suite}


def load_readers(bench_dir: str = HERE) -> dict:
    """name -> module of every ``metrics/<name>.py`` (one reader a file)."""
    out = {}
    mdir = os.path.join(bench_dir, "metrics")
    for fn in sorted(os.listdir(mdir)):
        if not fn.endswith(".py") or fn.startswith("_"):
            continue
        name = fn[:-3]
        spec = importlib.util.spec_from_file_location(f"benchmark_metric_{name}", os.path.join(mdir, fn))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        for attr in ("UNIT", "BETTER", "SOURCE", "LAYER", "MOVES", "read"):
            if not hasattr(mod, attr):
                raise AttributeError(f"metric reader {fn} lacks {attr}")
        out[name] = mod
    return out


def metrics_of_cell(bench: dict, cell_name: str, group: str) -> list:
    """Entries of ``group`` ('end_to_end' | 'per_layer') that this cell
    reports: those that list it or list no cells.  A cell kept for later
    (not in BENCHMARK.json) is listed nowhere and takes every entry."""
    listed = cell_name in [c["name"] for c in bench["workloads"]]
    return [
        m for m in bench[group]
        if not listed or "workloads" not in m or cell_name in m["workloads"]
    ]


def read_per_layer(bench: dict, cell_name: str, run: dict, readers: dict) -> dict:
    """The cell's per-layer metrics.  A cell of BENCHMARK.json reports the
    entries that list it; a cell kept for later also tries every reader
    that has no entry yet.  A reader that finds nothing is left out."""
    names = [m["name"] for m in metrics_of_cell(bench, cell_name, "per_layer")]
    if cell_name not in [c["name"] for c in bench["workloads"]]:
        names += sorted(set(readers) - set(names))
    out = {}
    for name in names:
        if name not in readers:
            raise KeyError(f"BENCHMARK.json names per-layer metric {name!r} but metrics/{name}.py is missing")
        value = readers[name].read(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": readers[name].UNIT}
    return out
