"""The exchange decode's reader (PR 35): its arithmetic on a job detail built
by hand (a q3: five exchanges a query, one of which moved no rows), None
(never 0) where the program has no such counter, its ``per_layer`` entry,
found by name, and its place in the q3 cells' lines and in no other's."""

import pytest

from benchmark import harness
from benchmark.tests.test_gang_phase_metrics import MS, _stage

NAME = "exchange_decode_ms"
Q3_CELLS = ["tpch-q3-sf1-1chip.join-agg", "tpch-sf1-1chip.loadtest4", "tpch-q3-sf10-1chip.join-agg",
            "tpch-q3-sf10-4chip.join-agg"]
# stage -> (mesh_exchange_rows, exchange_decode_ns): stage 3's exchange moved no rows
EXCHANGES = {1: (100, 30 * MS), 2: (700, 80 * MS), 3: (0, 9 * MS), 4: (3000, 500 * MS), 5: (50, 10 * MS)}


def _q3(strip=(), scale=1):
    stages = []
    for sid, (rows, decode_ns) in EXCHANGES.items():
        ops = {"mesh_exchange_rows": rows, "exchange_decode_ns": decode_ns * scale, "exchange_grouped": 1,
               "device_time_ns": 5 * MS}
        ops = {k: v for k, v in ops.items() if k not in strip}
        stages.append(_stage(sid, 0, 100 * sid, 90 * sid, {"MeshRepartitionExec": ops}))
    stages.append(_stage(6, 600, 700, 30, partitions=2))
    return {"stages": stages}


def _run(strip=()):
    """A window of two q3s (the second decoded twice as long), a query
    whose job detail was lost and a q6 with no exchange."""
    window = [{"job": _q3(strip)}, {"job": _q3(strip, scale=2)}, {"job": None},
              {"job": {"stages": [_stage(1, 0, 50, 40)]}}]
    return {"window": window, "window_all": window, "warmup": [], "cpu_ops": [],
            "trace": None, "memory": {}, "chips": 1}


@pytest.fixture(scope="module")
def reader():
    return harness.load_readers()[NAME]


@pytest.mark.parametrize("scale,want", [(1, 620.0), (2, 1240.0)])
def test_sums_the_exchanges_that_moved_rows_over_a_query(reader, scale, want):
    run = _run()
    one = {**run, "window": [run["window"][scale - 1]]}
    assert reader.read(one) == want
    # mean over the q3s: the lost job and the q6 are left out, not read as 0
    assert reader.read(run) == pytest.approx(930.0)


@pytest.mark.parametrize("strip", [("exchange_decode_ns",), ("mesh_exchange_rows",)])
def test_finds_nothing_where_the_program_counts_nothing(reader, strip):
    assert reader.read(_run(strip=strip)) is None
    assert reader.read({"window": [{"job": None}], "window_all": []}) is None


def test_entry_is_appended_for_the_q3_cells_alone(reader):
    bench = harness.benchmark_json()
    m = next(e for e in bench["per_layer"] if e["name"] == NAME)
    assert m["workloads"] == Q3_CELLS
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES)
    assert m["layer"] == next(e for e in bench["per_layer"] if e["name"] == "exchange_ms")["layer"]
    readers = {NAME: reader}
    only = {"workloads": bench["workloads"], "per_layer": [m]}
    one = {**_run(), "window": _run()["window"][:1]}
    for cell in Q3_CELLS:
        assert harness.read_per_layer(only, cell, one, readers) == {NAME: {"value": 620.0, "unit": "ms"}}
    for cell in ("tpch-sf1-1chip.scan-agg", "tpch-sf1-4chip-gang.scan-agg"):
        assert harness.read_per_layer(only, cell, one, readers) == {}
