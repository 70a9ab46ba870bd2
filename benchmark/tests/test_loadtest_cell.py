"""The first concurrent cell, ``tpch-sf1-1chip.loadtest4`` (PR 33): it
resolves to the one-chip configuration and the four-client traffic, its two
readers read a stand-in run, and a ``--platform cpu`` rehearsal of the whole
command gives every record the job of its own kind, where the time rule
alone, put back, does not."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, jobstats
from benchmark.tests.test_pairing import _job

CELL = "tpch-sf1-1chip.loadtest4"
REPO = harness.REPO


def test_cell_resolves_to_the_one_chip_configuration_and_four_clients():
    bench = harness.benchmark_json()
    got = harness.resolve(CELL, bench)
    assert got["cell"] == {**got["cell"], "config": "tpch-sf1-1chip", "traffic": "loadtest4", "chips": 1}
    traffic = {k: got["traffic"][k] for k in ("kinds", "clients", "order", "parameter_sets", "loop")}
    assert traffic == {"kinds": [1, 6, 3], "clients": 4, "order": "shuffle", "parameter_sets": 1, "loop": "closed"}
    assert set(got["traffic"]["assumed"]) == {"clients", "order", "parameter_sets"}
    cfg = got["config"]
    assert cfg["gang_kinds"] == [1, 6] and cfg["cluster"]["executor_task_slots"] == 4 == got["traffic"]["clients"]
    assert cfg == harness.resolve("tpch-sf1-1chip.scan-agg", bench)["config"]  # the configuration cell 1 has
    listed = {m["name"] for m in harness.metrics_of_cell(bench, CELL, "per_layer")}
    # a window holds all three kinds: the gang stage's, the exchange's and the join's readers all find something
    assert {"slots_busy_share", "client_completion_spread", "gang_wait_ms", "exchange_wait_ms", "join_build_ms",
            "device_stage_ms", "scan_roofline", "exchange_roofline", "device_route_share"} <= listed
    assert "collective_ms" not in listed  # the mesh's collectives are what the cell bypasses
    for name in ("slots_busy_share", "client_completion_spread"):
        assert next(m for m in bench["per_layer"] if m["name"] == name)["workloads"] == [CELL]
    e2e = {m["name"] for m in harness.metrics_of_cell(bench, CELL, "end_to_end")}
    assert e2e == {"setup_s", "query_geomean_s", "scan_rows_rate"}
    assert harness.load_json(os.path.join(harness.HERE, "cells_later.json"))["workloads"] == []


# ------------------------------------------------------------ the readers
def _run(done_by_client=(3, 3, 2, 4)):
    """A window of q1s: client c completed ``done_by_client[c]``; one more,
    of client 0, failed (it is in ``window_all`` only)."""
    def rec(c):
        job = jobstats.summarize({
            "job_id": f"j{c}", "state": "completed", "submitted_us": 1_000_000, "planning_us": 10,
            "stages": [
                {"stage_id": 1, "partitions": 1, "metrics": {"MeshGangExec": {"mesh_devices": 1}},
                 "timing": {"dispatch_us": {"0": 1_000_100}, "finish_us": {"0": 1_600_100}}},
                {"stage_id": 2, "partitions": 2, "metrics": {},  # two tasks side by side: 0.1 s each
                 "timing": {"dispatch_us": {"0": 1_600_200, "1": 1_600_300}, "finish_us": {"0": 1_700_200, "1": 1_700_300}}},
            ]})
        return {"client": c, "kind": 1, "job": job, "error": None}

    good = [rec(c) for c, n in enumerate(done_by_client) for _ in range(n)]
    resolved = harness.resolve(CELL, harness.benchmark_json())
    return {"window": good, "window_all": good + [{**rec(0), "error": "boom"}, {"client": 1, "kind": 6, "job": None}],
            "window_s": 10.0, "config": resolved["config"], "traffic": resolved["traffic"], "chips": 1,
            "warmup": [], "cpu_ops": [], "trace": None, "memory": {}}


@pytest.fixture(scope="module")
def readers():
    return harness.load_readers()


def test_slots_busy_share_is_task_time_over_slot_time(readers):
    # 13 jobs (the failed one's tasks held slots too) x (0.6 + 0.1 + 0.1) s over 4 slots x 10 s
    assert readers["slots_busy_share"].read(_run()) == pytest.approx(100.0 * 13 * 0.8 / 40.0)
    for broken in ({"window_s": 0.0}, {"config": {}}, {"window_all": [{"job": None}]}):
        assert readers["slots_busy_share"].read({**_run(), **broken}) is None


def test_client_completion_spread_is_most_over_fewest(readers):
    read = readers["client_completion_spread"].read
    assert read(_run()) == pytest.approx(4 / 2) and read(_run((5, 5, 5, 5))) == 1.0
    assert read(_run((3, 3, 0, 4))) is None  # a starved client: no ratio; the run's `attempted` shows it
    assert read({**_run(), "traffic": {"clients": 1}}) is None and read({**_run(), "traffic": None}) is None
    bench = harness.benchmark_json()
    new = [m for m in bench["per_layer"] if m["name"] in ("slots_busy_share", "client_completion_spread")]
    out = harness.read_per_layer({"workloads": bench["workloads"], "per_layer": new}, CELL, _run(), readers)
    assert out["client_completion_spread"] == {"value": 2.0, "unit": "ratio"} and out["slots_busy_share"]["unit"] == "%"
    for cell in ("tpch-sf1-1chip.scan-agg", "tpch-q3-sf1-1chip.join-agg"):  # one client: neither is listed
        assert harness.read_per_layer({"workloads": bench["workloads"], "per_layer": new}, cell, _run(), readers) == {}


# ------------------------------------------------------------ the rehearsal
def _shape(job: dict) -> tuple:
    """(stages, gang stages, exchanges) of a summarized job."""
    return (len(job["stages"]), len(jobstats.gang_stages(job)),
            sum("MeshRepartitionExec" in st["ops"] for st in job["stages"]))


SHAPE_OF = {1: (3, 1, 0), 6: (2, 1, 0), 3: (7, 0, 5)}


def test_the_hand_built_jobs_of_the_pairing_tests_have_the_shapes_a_rehearsal_sees():
    assert all(_shape(_job("x", kind, 0.0)) == shape for kind, shape in SHAPE_OF.items())


@pytest.fixture(scope="module")
def crossed():
    """Records that took another kind's job once their ids were taken away,
    summed over the rehearsals of this module."""
    return []


@pytest.mark.parametrize("seed", [7, 2**31 + 33, 3_300_000_019])
def test_rehearsal_gives_every_record_the_job_of_its_own_kind(tmp_path, seed, crossed):
    """SF0.02 and not 0.01: under ``ballista.tpu.min_rows`` (16,384) a
    partition of q3's device stage runs the CPU operators and counts
    ``cpu_fallback``, which since PR 33 is off the cell's path."""
    kept = str(tmp_path / "kept")
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload", CELL, "--seed", str(seed),
         "--seconds", "12", "--trace", "0", "--platform", "cpu", "--sf", "0.02", "--keep", kept],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 4, p.stderr[-3000:]
    assert "off the cell's path" not in p.stderr and "carry no job id" not in p.stderr
    assert out["window"]["paired_by_time"] == 0 and min(out["window"]["completions_by_client"]) >= 1
    assert set(out["window"]["latencies_s"]) == {"q1", "q6", "q3"} and all(out["window"]["latencies_s"].values())
    with open(os.path.join(kept, "queries.json")) as f:
        records = [r for r in json.load(f) if "seq" in r]
    assert len(records) == out["attempted"] and len({r["job_id"] for r in records}) == len(records)
    assert {r["client"] for r in records} == {0, 1, 2, 3}
    for r in records:
        assert r["job"]["job_id"] == r["job_id"] and _shape(r["job"]) == SHAPE_OF[r["kind"]], r
    # the time rule put back: the same records without their ids
    with open(os.path.join(kept, "job_details.json")) as f:
        jobs = [jobstats.summarize(d) for d in json.load(f)]
    bare = [{k: v for k, v in r.items() if k not in ("job", "job_id")} for r in records]
    assert len(jobstats.match(bare, jobs)) == len(bare)
    crossed += [r for r in bare if r["job"] is None or _shape(r["job"]) != SHAPE_OF[r["kind"]]]


def test_the_time_rule_put_back_crosses_the_rehearsals_records(crossed):
    """Runs after the three rehearsals above (file order): by the clocks
    alone at least one of their records held another kind's job."""
    assert crossed, "the time rule paired every record of three rehearsals with a job of its own kind"
