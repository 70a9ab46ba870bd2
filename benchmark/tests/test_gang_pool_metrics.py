"""The three readers of the gang stage's worker pool (PR 29): their
arithmetic on a ``run`` built by hand, None (never 0) where the program has
no such counter, as a parent commit has not, and their place in the two
scan-agg cells' lines."""

import pytest

from benchmark import harness
from benchmark.tests.test_gang_phase_metrics import MS, Q1_GANG, Q6_GANG, _stage

POOL = {
    1: {"gang_workers": 4, "gang_wait_ns": 520 * MS, "gang_merge_ns": 14 * MS},
    6: {"gang_workers": 4, "gang_wait_ns": 180 * MS, "gang_merge_ns": 2 * MS},
}
EXPECTED = {"gang_wait_ms": 350.0, "gang_merge_ms": 8.0, "gang_workers": 4.0}
COUNTER = {"gang_wait_ms": "gang_wait_ns", "gang_merge_ms": "gang_merge_ns",
           "gang_workers": "gang_workers"}


def _run(strip=()):
    """A window of one q1 and one q6 whose gang stages carry the pool's
    counters beside the older ones; ``strip`` drops counters."""
    def job(kind, older):
        ops = {k: v for k, v in {**older, **POOL[kind]}.items() if k not in strip}
        return {"stages": [_stage(1, 0, 900, 850, {"MeshGangExec": ops}), _stage(2, 910, 1020, 8)]}

    window = [{"job": job(1, Q1_GANG)}, {"job": job(6, Q6_GANG)}, {"job": None}]
    return {"window": window, "window_all": window, "warmup": [], "cpu_ops": [],
            "trace": None, "memory": {}, "chips": 1}


@pytest.fixture(scope="module")
def readers():
    return harness.load_readers()


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_arithmetic(readers, name):
    assert readers[name].read(_run()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_on_a_program_without_the_pool(readers, name):
    assert readers[name].read(_run(strip=(COUNTER[name],))) is None
    empty = {"window": [{"job": None}], "window_all": []}
    assert readers[name].read(empty) is None


def test_a_width_of_one_reads_one_and_a_query_without_a_gang_stage_is_left_out(readers):
    run = _run()
    for q in run["window"][:2]:
        q["job"]["stages"][0]["ops"]["MeshGangExec"]["gang_workers"] = 1
    run["window"].append({"job": {"stages": [_stage(1, 0, 50, 40)]}})  # a q3: no gang stage
    assert readers["gang_workers"].read(run) == 1.0
    assert readers["gang_wait_ms"].read(run) == pytest.approx(350.0)


def test_the_scan_agg_cells_list_the_three_and_the_join_cell_none(readers):
    bench = harness.benchmark_json()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        m, mod = entries[name], readers[name]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES)
        assert m["workloads"][:2] == ["tpch-sf1-1chip.scan-agg", "tpch-sf1-4chip-gang.scan-agg"]
        assert "tpch-q3-sf1-1chip.join-agg" not in m["workloads"]
    # appended after PR 26's entries, side by side, in this order: by position,
    # since later PRs append theirs behind
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("gang_wait_ms")
    assert names[at:at + 3] == ["gang_wait_ms", "gang_merge_ms", "gang_workers"] and names.index("task_overhead_ms") < at
    # the hand-built run holds gang stages only: read the gang stage's metrics
    bench = {"workloads": bench["workloads"],
             "per_layer": [m for m in bench["per_layer"] if m["layer"] == "gang stage"]}
    for cell in ("tpch-sf1-1chip.scan-agg", "tpch-sf1-4chip-gang.scan-agg"):
        out = harness.read_per_layer(bench, cell, _run(), readers)
        assert {n: out[n]["value"] for n in EXPECTED} == EXPECTED
        assert out["gang_workers"]["unit"] == "count"
        # on a parent commit the line leaves the three out and keeps the rest
        parent = harness.read_per_layer(bench, cell, _run(strip=tuple(COUNTER.values())), readers)
        assert not set(EXPECTED) & set(parent) and "gang_scan_ms" in parent
    q3 = harness.read_per_layer(bench, "tpch-q3-sf1-1chip.join-agg", _run(), readers)
    assert not set(EXPECTED) & set(q3)


def test_benchmark_json_still_keeps_the_contract():
    from benchmark.tests.test_harness_data import (
        test_benchmark_json_keeps_the_contract_and_matches_the_files as contract,
    )

    contract()
