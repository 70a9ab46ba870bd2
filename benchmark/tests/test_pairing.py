"""A record finds its job by the id its own ``collect()`` ran (PR 33), not
by the clocks: with several clients the order of the clients' calls and the
order of the scheduler's ``submitted_us`` cross.  The records and jobs here
are the first four of a window of ``tpch-sf1-1chip.loadtest4`` as a
rehearsal kept them (ISSUE 33): all four clients leave within 30 ms, a q3
plans longer than a q6, and the time rule hands a q1 the job of a q3."""

import pytest

from benchmark import cluster, harness, jobstats, run, suites, window
from benchmark.tests.fake_served import FakeServed
from benchmark.tests.test_gang_phase_metrics import _stage

CELL = "tpch-sf1-1chip.loadtest4"
T = 1_791_000_000.0  # unix seconds


def _job(job_id: str, kind: int, submitted_s: float) -> dict:
    """A summarized job with the stages of its kind: q1 three (a gang stage
    first), q6 two, q3 seven (five exchanges, one over a device stage)."""
    gang = {"MeshGangExec": {"mesh_devices": 1}}
    stages = {
        1: [_stage(1, 0, 500, 450, gang), _stage(2, 510, 620, 8, partitions=2), _stage(3, 630, 740, 5)],
        6: [_stage(1, 0, 250, 220, gang), _stage(2, 260, 370, 5)],
        3: [_stage(s, 0, 100 * s, 90 * s, {"MeshRepartitionExec": {"mesh_exchange_rows": 1000},
                                            **({"TpuStageExec": {"input_rows": 9}} if s == 5 else {})})
            for s in range(1, 6)] + [_stage(6, 600, 700, 30, partitions=2), _stage(7, 710, 800, 20)],
    }[kind]
    return {"job_id": job_id, "state": "completed", "submitted_us": int(submitted_s * 1e6),
            "planning_us": 900, "end_us": int((submitted_s + 1) * 1e6), "stages": stages, "kind": kind}


def _crossing():
    """(records, jobs): the clients' calls in one order, the scheduler's
    stamps in another (the table of ISSUE 33)."""
    calls = [(0, 3, .7692, "871fwyq"), (1, 1, .7804, "o9fycd6"), (2, 3, .7867, "29qvjx8"), (3, 6, .7972, "0h3v3y7")]
    records = [
        {"client": c, "seq": 0, "kind": kind, "unix_submit": T + at, "unix_done": T + at + 4.0,
         "job_id": job_id, "error": None}
        for c, kind, at, job_id in calls
    ]
    jobs = [_job("871fwyq", 3, T + .7913), _job("29qvjx8", 3, T + .8087),
            _job("0h3v3y7", 6, T + .8509), _job("o9fycd6", 1, T + .8658)]
    return records, jobs


def test_by_id_every_record_gets_the_job_of_its_own_kind():
    records, jobs = _crossing()
    assert jobstats.match(records, jobs) == []  # none fell to the time rule
    assert [(r["kind"], r["job"]["kind"], r["job"]["job_id"]) for r in records] == [
        (r["kind"], r["kind"], r["job_id"]) for r in records]
    gang_kinds = harness.resolve(CELL, harness.benchmark_json())["config"]["gang_kinds"]
    assert [jobstats.wrong_route(r["job"], 1, r["kind"] in gang_kinds) for r in records] == [""] * 4


def test_the_time_rule_alone_hands_a_q1_the_job_of_a_q3():
    """The planted fault: the same records without their ids."""
    records, jobs = _crossing()
    for r in records:
        del r["job_id"]
    assert len(jobstats.match(records, jobs)) == 4  # all reported, for the caller to log
    assert [(r["kind"], r["job"]["kind"]) for r in records] == [(3, 3), (1, 3), (3, 6), (6, 1)]
    assert jobstats.wrong_route(records[1]["job"], 1, True) == "no gang stage"
    # and the q3 that holds a q6's job passes unchecked: a device stage is a device stage
    assert jobstats.wrong_route(records[2]["job"], 1, False) == ""


def test_an_id_with_no_job_detail_stays_without_a_job_and_takes_no_other():
    records, jobs = _crossing()
    lost = [j for j in jobs if j["job_id"] != "o9fycd6"]
    assert jobstats.match(records, lost) == []
    assert records[1]["job"] is None and all(r["job"] for i, r in enumerate(records) if i != 1)
    # a record without an id takes only a job that no id claims
    records[3].pop("job_id")
    extra = _job("warmup0", 6, T + .7990)
    assert jobstats.match(records, jobs + [extra]) == [records[3]]
    assert records[3]["job"] is extra and records[1]["job"]["job_id"] == "o9fycd6"


def test_new_job_id_reads_what_the_context_submitted_since_it_was_last_asked():
    class Ctx:
        _job_ids: set = set()

    ctx, seen = Ctx(), set()
    assert cluster.new_job_id(ctx, seen) is None  # the call submitted nothing
    ctx._job_ids = {"a"}
    assert cluster.new_job_id(ctx, seen) == "a" and seen == {"a"}
    ctx._job_ids = {"a", "b", "c"}
    assert cluster.new_job_id(ctx, seen) is None and seen == {"a", "b", "c"}  # two at once: not one query's
    ctx._job_ids = {"a", "b", "c", "d"}
    assert cluster.new_job_id(ctx, seen) == "d"
    assert cluster.new_job_id(object(), set()) is None  # a stand-in keeps no ids


def test_a_window_record_keeps_the_id_its_submit_returned_or_raised():
    class Refused(RuntimeError):
        job_id = "j-failed"

    def submit(c, kind, params):
        if kind == 6:
            raise Refused("refused")
        return window.WithJob((kind, params), f"j{c}") if kind == 1 else (kind, params)

    res = window.run_window(suites.load("tpch"), {"kinds": [1, 6, 3], "clients": 2}, 1, 0.05, submit)
    by_kind = {r["kind"]: r for r in res["queries"] if r["client"] == 1}
    assert by_kind[1]["job_id"] == "j1" and by_kind[1]["answer"][0] == 1
    assert by_kind[6]["job_id"] == "j-failed" and by_kind[6]["error"].startswith("Refused")
    assert by_kind[3]["job_id"] is None and by_kind[3]["answer"][0] == 3


@pytest.mark.parametrize("ids", [True, False])
def test_four_clients_against_the_stand_in(small_data, ids, capsys):
    """The cell's traffic through ``run.measure``: with ids no record falls
    to the time rule; without (a stand-in that keeps none) all do, and the
    run says so."""
    data_dir, info = small_data
    resolved = harness.resolve(CELL, harness.benchmark_json())
    measured = run.measure(FakeServed(data_dir, ids=ids), resolved, info, 2**31 + 33, 2.0, False)
    records = measured["records"]
    assert {r["client"] for r in records} == {0, 1, 2, 3} and {r["kind"] for r in records} == {1, 6, 3}
    assert all(r["job"] for r in records)
    assert measured["paired_by_time"] == (0 if ids else len(records) + 3)  # the three warm-up queries too
    assert ("carry no job id" in capsys.readouterr().err) == (not ids)
    if ids:
        assert all(r["job"]["job_id"] == r["job_id"] for r in records)
        assert run.judge(measured, data_dir)["correct"]
