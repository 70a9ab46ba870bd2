"""The exchange pull's reader: its arithmetic on a job detail built by hand
(a q3: five exchanges a query, summed), None (never 0) where the program
has no such counter, and its entry in ``per_layer`` for the cells that run
q3, found by name."""

import pytest

from benchmark import harness
from benchmark.tests.test_gang_phase_metrics import _stage

NAME = "exchange_pull_batches"
Q3_CELLS = ["tpch-q3-sf1-1chip.join-agg", "tpch-sf1-1chip.loadtest4",
            "tpch-q3-sf10-1chip.join-agg", "tpch-q3-sf10-4chip.join-agg"]
# stage -> the batches its exchange's workers pulled (q3 at SF1, a row
# group a batch: customer, orders, the CPU join's output, lineitem, and the
# device stage's two partitions at the session's batch size)
PULLED = {1: 12, 2: 12, 3: 2, 4: 12, 5: 40}


def _q3(strip=(), scale=1):
    stages = []
    for sid, batches in PULLED.items():
        ops = {"exchange_pull_batches": batches * scale, "mesh_exchange_rows": 1000, "exchange_workers": 6}
        ops = {k: v for k, v in ops.items() if k not in strip}
        stages.append(_stage(sid, 0, 100 * sid, 90 * sid, {"MeshRepartitionExec": ops}))
    stages.append(_stage(6, 600, 700, 30, partitions=2))
    return {"stages": stages}


def _run(strip=()):
    """Two q3s (the second pulled twice the batches), a query whose job
    detail was lost and a q6 with no exchange."""
    window = [{"job": _q3(strip)}, {"job": _q3(strip, scale=2)}, {"job": None},
              {"job": {"stages": [_stage(1, 0, 50, 40)]}}]
    return {"window": window, "window_all": window, "warmup": [], "cpu_ops": [],
            "trace": None, "memory": {}, "chips": 1}


@pytest.fixture(scope="module")
def reader():
    return harness.load_readers()[NAME]


@pytest.mark.parametrize("scale,want", [(1, 78.0), (2, 156.0)])
def test_sums_a_querys_exchanges_and_means_over_the_queries_that_have_one(reader, scale, want):
    run = _run()
    assert reader.read({**run, "window": [run["window"][scale - 1]]}) == want
    # the lost job and the q6 are left out, not read as 0
    assert reader.read(run) == pytest.approx(117.0)


def test_finds_nothing_where_the_program_counts_nothing(reader):
    assert reader.read(_run(strip=(NAME,))) is None
    assert reader.read({"window": [{"job": None}], "window_all": []}) is None


def test_entry_lists_the_q3_cells_and_reads_there_alone(reader):
    bench = harness.benchmark_json()
    (m,) = [e for e in bench["per_layer"] if e["name"] == NAME]
    assert m["workloads"] == Q3_CELLS
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES)
    assert m["layer"] == next(e for e in bench["per_layer"] if e["name"] == "exchange_ms")["layer"]
    only = {"workloads": bench["workloads"], "per_layer": [m]}
    one = {**_run(), "window": _run()["window"][:1]}
    for cell in Q3_CELLS:
        assert harness.read_per_layer(only, cell, one, {NAME: reader}) == {NAME: {"value": 78.0, "unit": "count"}}
    for cell in ("tpch-sf1-1chip.scan-agg", "tpch-sf1-4chip-gang.scan-agg"):
        assert harness.read_per_layer(only, cell, one, {NAME: reader}) == {}
