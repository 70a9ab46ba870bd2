"""The two readers of the exchange's worker pool (PR 32): their arithmetic
on a job detail built by hand (a q3: five exchanges a query), None (never
0) where the program has no such counter, as a parent commit has not, and
their place in the join cell's line and in no other cell's."""

import pytest

from benchmark import harness
from benchmark.tests.test_gang_phase_metrics import MS, _stage

CELL = "tpch-q3-sf1-1chip.join-agg"
# stage -> (exchange_workers, exchange_wait_ns): the lineitem and orders
# scans get the pool, the two-partition join gets two, the exchange over the
# device stage is prepared inline and its wait (that stage's own run) is not
# the exchange's to report
EXCHANGES = {
    1: (6, 40 * MS), 2: (6, 120 * MS), 3: (2, 30 * MS), 4: (6, 400 * MS), 5: (1, 10 * MS),
}
EXPECTED = {"exchange_workers": 6.0, "exchange_wait_ms": 590.0}
COUNTER = {"exchange_workers": "exchange_workers", "exchange_wait_ms": "exchange_wait_ns"}


def _q3(strip=(), scale=1):
    stages = []
    for sid, (workers, wait_ns) in EXCHANGES.items():
        ops = {"exchange_workers": workers, "exchange_wait_ns": wait_ns * scale,
               "mesh_exchange_rows": 1000 * sid, "device_time_ns": 5 * MS}
        ops = {k: v for k, v in ops.items() if k not in strip}
        stages.append(_stage(sid, 0, 100 * sid, 90 * sid, {"MeshRepartitionExec": ops, "ScanExec": {"output_rows": 7}}))
    stages.append(_stage(6, 600, 700, 30, partitions=2))
    return {"stages": stages}


def _run(strip=()):
    """A window of two q3s (the second waited twice as long) and a query
    whose job detail was lost."""
    window = [{"job": _q3(strip)}, {"job": _q3(strip, scale=2)}, {"job": None}]
    return {"window": window, "window_all": window, "warmup": [], "cpu_ops": [],
            "trace": None, "memory": {}, "chips": 1}


@pytest.fixture(scope="module")
def readers():
    return harness.load_readers()


def test_reader_arithmetic_over_five_exchanges_a_query(readers):
    run = _run()
    # the widest pool of a query, mean over the queries
    assert readers["exchange_workers"].read(run) == 6.0
    # the four pools' waits, (590 + 1180) / 2 queries
    assert readers["exchange_wait_ms"].read(run) == pytest.approx(885.0)
    one = {**run, "window": run["window"][:1]}
    assert {n: readers[n].read(one) for n in EXPECTED} == EXPECTED


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_on_a_program_without_the_pool(readers, name):
    assert readers[name].read(_run(strip=(COUNTER[name],))) is None
    empty = {"window": [{"job": None}], "window_all": []}
    assert readers[name].read(empty) is None


def test_every_exchange_inline_reads_one_and_a_query_without_an_exchange_is_left_out(readers):
    run = _run()
    for q in run["window"][:2]:
        for st in q["job"]["stages"][:5]:
            st["ops"]["MeshRepartitionExec"]["exchange_workers"] = 1
    run["window"].append({"job": {"stages": [_stage(1, 0, 50, 40)]}})  # a q6: no exchange
    assert readers["exchange_workers"].read(run) == 1.0
    # no pool, so no wait on one: a reading of 0, not a missing counter
    assert readers["exchange_wait_ms"].read(run) == 0.0
    # beside q3s that ran pools, a q1 or q6 is left out of the mean, not averaged in as 0
    mixed = _run()
    mixed["window"].append({"job": {"stages": [_stage(1, 0, 50, 40)]}})
    assert readers["exchange_wait_ms"].read(mixed) == pytest.approx(885.0)
    assert readers["exchange_ms"].read(mixed) == 25.0  # five exchanges of 5 ms a q3


def test_the_join_cell_lists_the_two_and_the_scan_agg_cells_neither(readers):
    bench = harness.benchmark_json()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        m, mod = entries[name], readers[name]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES)
        assert m["workloads"][0] == CELL and m["layer"] == entries["exchange_ms"]["layer"]
        assert not {"tpch-sf1-1chip.scan-agg", "tpch-sf1-4chip-gang.scan-agg"} & set(m["workloads"])
    # appended after everything PR 29 left
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("gang_workers") < names.index("exchange_workers") < names.index("exchange_wait_ms")
    only = {"workloads": bench["workloads"],
            "per_layer": [m for m in bench["per_layer"] if m["name"] in (*EXPECTED, "exchange_ms")]}
    one = {**_run(), "window": _run()["window"][:1]}
    out = harness.read_per_layer(only, CELL, one, readers)
    assert {n: out[n]["value"] for n in EXPECTED} == EXPECTED and out["exchange_workers"]["unit"] == "count"
    # on a parent commit the line leaves the two out and keeps the rest
    parent = harness.read_per_layer(only, CELL, _run(strip=tuple(COUNTER.values())), readers)
    assert not set(EXPECTED) & set(parent) and parent["exchange_ms"]["value"] == 25.0
    for cell in ("tpch-sf1-1chip.scan-agg", "tpch-sf1-4chip-gang.scan-agg"):
        assert not set(EXPECTED) & set(harness.read_per_layer(only, cell, one, readers))


def test_benchmark_json_still_keeps_the_contract():
    from benchmark.tests.test_harness_data import (
        test_benchmark_json_keeps_the_contract_and_matches_the_files as contract,
    )

    contract()
