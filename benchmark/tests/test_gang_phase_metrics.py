"""The gang-stage phase readers and ``task_overhead_ms`` (PR 26): their
arithmetic on a ``run`` built by hand, and None (never 0) where the program
has no such counter, as a parent commit has not."""

import pytest

from benchmark import harness

MS = 1_000_000  # ns

Q1_GANG = {
    "mesh_devices": 1, "mesh_stage_time_ns": 4000 * MS, "gang_cpu_ns": 3600 * MS,
    "gang_wait_ns": 2000 * MS, "gang_merge_ns": 40 * MS,  # the workers' three lie inside the wait
    "gang_scan_ns": 500 * MS, "key_encode_time_ns": 900 * MS, "gang_convert_ns": 700 * MS,
    "gang_upload_ns": 1500 * MS, "gang_uploads": 6600, "gang_assemble_ns": 200 * MS,
    "gang_step_ns": 90 * MS, "gang_materialize_ns": 10 * MS,
    "gang_batches": 733, "gang_partitions": 12,
}
Q6_GANG = {
    "mesh_devices": 1, "mesh_stage_time_ns": 2000 * MS, "gang_cpu_ns": 1400 * MS,
    "gang_wait_ns": 850 * MS, "gang_merge_ns": 10 * MS,
    "gang_scan_ns": 300 * MS, "key_encode_time_ns": 0, "gang_convert_ns": 500 * MS,
    "gang_upload_ns": 1000 * MS, "gang_uploads": 4400, "gang_assemble_ns": 100 * MS,
    "gang_step_ns": 30 * MS, "gang_materialize_ns": 10 * MS,
    "gang_batches": 733, "gang_partitions": 12,
}
EXPECTED = {
    "gang_scan_ms": 400.0, "gang_encode_ms": 450.0, "gang_convert_ms": 600.0,
    "gang_upload_ms": 1250.0, "gang_uploads": 5500.0, "gang_assemble_ms": 150.0,
    "gang_step_ms": 60.0, "gang_materialize_ms": 10.0,
    # walls 6000 ms, the task thread's six phases 3840 + 2000 = 5840 ms
    "gang_unaccounted_share": 100.0 * 160 / 6000,
    # q1: (4110 - 4000) + (110 - 8/2) + (105 - 5) = 316; q6: (2100 - 2000) + (110 - 10) = 200
    "task_overhead_ms": 258.0,
}


def _stage(sid, start_ms, end_ms, run_ms, ops=None, partitions=1):
    ops = dict(ops or {})
    ops["ShuffleWriterExec"] = {"write_time_ns": 5, "task_run_ns": int(run_ms * MS)}
    return {"stage_id": sid, "partitions": partitions, "start_us": start_ms * 1000,
            "end_us": end_ms * 1000, "ops": ops}


def _run(strip=()):
    """A window of one q1 and one q6; ``strip`` drops counters, as a program
    that does not count them would."""
    def gang(vals):
        return {"MeshGangExec": {k: v for k, v in vals.items() if k not in strip}}

    q1 = {"stages": [
        _stage(1, 0, 4110, 4000, gang(Q1_GANG)),
        _stage(2, 4120, 4230, 8, partitions=2),  # two tasks ran 3 + 5 ms
        _stage(3, 4240, 4345, 5),
    ]}
    q6 = {"stages": [_stage(1, 0, 2100, 2000, gang(Q6_GANG)), _stage(2, 2110, 2220, 10)]}
    for job in (q1, q6):
        for st in job["stages"]:
            if "task_run_ns" in strip:
                del st["ops"]["ShuffleWriterExec"]["task_run_ns"]
    window = [{"job": q1}, {"job": q6}, {"job": None}]
    return {"window": window, "window_all": window, "warmup": [], "cpu_ops": [],
            "trace": None, "memory": {}, "chips": 1}


@pytest.fixture(scope="module")
def readers():
    return harness.load_readers()


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_arithmetic(readers, name):
    assert readers[name].read(_run()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name,missing", [
    ("gang_scan_ms", "gang_scan_ns"), ("gang_encode_ms", "key_encode_time_ns"),
    ("gang_convert_ms", "gang_convert_ns"), ("gang_upload_ms", "gang_upload_ns"),
    ("gang_uploads", "gang_uploads"), ("gang_assemble_ms", "gang_assemble_ns"),
    ("gang_step_ms", "gang_step_ns"), ("gang_materialize_ms", "gang_materialize_ns"),
    ("gang_unaccounted_share", "gang_wait_ns"), ("gang_unaccounted_share", "mesh_stage_time_ns"),
    ("gang_unaccounted_share", "gang_merge_ns"), ("gang_unaccounted_share", "gang_step_ns"),
    ("task_overhead_ms", "task_run_ns"),
])
def test_reader_finds_nothing_without_its_counter(readers, name, missing):
    assert readers[name].read(_run(strip=(missing,))) is None


def test_a_parent_commit_reports_none_of_the_new_metrics_but_its_own_encode_timer(readers):
    """The parent's MeshGangExec has the wall, the old lumps and q1's key
    encode timer only; no stage has task_run_ns; a window with no job at all
    reads nothing anywhere."""
    new = set(Q1_GANG) - {"mesh_devices", "mesh_stage_time_ns", "key_encode_time_ns"}
    parent = _run(strip=tuple(new) + ("task_run_ns",))
    del parent["window"][1]["job"]["stages"][0]["ops"]["MeshGangExec"]["key_encode_time_ns"]
    out = {n: readers[n].read(parent) for n in EXPECTED}
    assert out.pop("gang_encode_ms") == pytest.approx(450.0)  # q6 never had the timer: it adds 0
    assert set(out.values()) == {None}
    empty = {"window": [{"job": None}], "window_all": []}
    assert {readers[n].read(empty) for n in EXPECTED} == {None}


def test_the_line_of_a_cell_holds_the_phase_metrics(readers):
    bench = harness.benchmark_json()
    for cell in ("tpch-sf1-1chip.scan-agg", "tpch-sf1-4chip-gang.scan-agg"):
        listed = {m["name"] for m in harness.metrics_of_cell(bench, cell, "per_layer")}
        assert set(EXPECTED) <= listed
    out = harness.read_per_layer(
        {"workloads": bench["workloads"],
         "per_layer": [m for m in bench["per_layer"] if m["name"] in EXPECTED]},
        "tpch-sf1-1chip.scan-agg", _run(), readers)
    assert set(out) == set(EXPECTED)
    assert out["gang_upload_ms"] == {"value": 1250.0, "unit": "ms"}


def test_benchmark_json_still_keeps_the_contract():
    from benchmark.tests.test_harness_data import (
        test_benchmark_json_keeps_the_contract_and_matches_the_files as contract,
    )

    contract()
