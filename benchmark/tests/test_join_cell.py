"""The q3 cell (PR 28): it resolves to its configuration and traffic, its
rehearsal is compared and a pad row that leaks into an answer comes out not
correct, and each new reader reads a recorded job detail
(``data/job_detail_q3.json``: q3 at SF0.01 through a local scheduler and
executor with the device route on) and finds nothing on a q1 job."""

import json
import os

import pyarrow as pa
import pytest

from benchmark import harness, jobstats, run
from benchmark.tests.fake_served import FakeServed

CELL = "tpch-q3-sf1-1chip.join-agg"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# readers this PR adds (exchange_ms was shipped by PR 25; its entry is new)
NEW = ("exchange_ms", "exchange_pad_share", "exchange_roofline", "join_build_ms",
       "device_stage_ms", "stage_pad_share", "warmup_xla_compiles")


def test_cell_resolves_to_its_configuration_and_traffic():
    bench = harness.benchmark_json()
    got = harness.resolve(CELL, bench)
    cfg, traffic = got["config"], got["traffic"]
    assert got["cell"]["chips"] == 1 == cfg["chips"] and got["cell"]["traffic"] == "join-agg"
    assert cfg["name"] == "tpch-q3-sf1-1chip" and cfg["gang_kinds"] == []
    assert cfg["scale_factor"] == 1.0 and cfg["published"]["scale_factor"] == 10.0
    assert cfg["reduced"] == ["scale_factor", "tables"] and cfg["files_per_table"] == 12
    assert traffic["kinds"] == [3] and traffic["clients"] == 1 and traffic["parameter_sets"] == 1
    # same cluster, session pins and assumptions as the scan-agg configuration
    base = harness.resolve("tpch-sf1-1chip.scan-agg", bench)["config"]
    for key in ("cluster", "session", "assumed"):
        assert cfg[key] == base[key], key
    listed = {m["name"] for m in harness.metrics_of_cell(bench, CELL, "per_layer")}
    assert set(NEW) <= listed and "gang_step_ms" not in listed and "scan_roofline" not in listed
    e2e = {m["name"] for m in harness.metrics_of_cell(bench, CELL, "end_to_end")}
    assert e2e == {"setup_s", "query_geomean_s", "scan_rows_rate"}


class PadLeak(FakeServed):
    """A pad row that reached the answer: the build side's pad rows repeat
    its last key, so had one matched, an order's lines would count twice
    (``double``); a pad row of an exchange is all zeros, so had one been
    delivered it would be a group of its own (``zero_group``)."""

    def __init__(self, data_dir, leak):
        super().__init__(data_dir)
        self.leak = leak

    def _answer(self, text, ctx=None):
        table = super()._answer(text, ctx)
        cols = {n: table.column(n).to_pylist() for n in table.column_names}
        if self.leak == "double":
            cols["revenue"][-1] *= 2.0
        else:
            for n in cols:
                cols[n][-1] = type(cols[n][0])(0) if n != "o_orderdate" else cols[n][0].min
        return pa.table({n: pa.array(v, table.schema.field(n).type) for n, v in cols.items()})


def _drive(served, info, data_dir):
    resolved = harness.resolve(CELL, harness.benchmark_json())
    measured = run.measure(served, resolved, info, 2**31 + 77, 1.5, False)
    return measured, run.judge(measured, data_dir)


def test_rehearsal_of_the_cell_is_compared_and_correct(small_data):
    data_dir, info = small_data
    measured, verdict = _drive(FakeServed(data_dir), info, data_dir)
    assert verdict["correct"] and verdict["compared"] == len(measured["records"]) >= 2
    assert {r["kind"] for r in measured["records"]} == {3}
    # q3 is no gang kind: a device stage and no device_error is the cell's path
    assert not any(r.get("wrong_route") for r in measured["records"])
    assert measured["rows_of_kind"] == {3: sum(info["rows"].values())}


@pytest.mark.parametrize("leak", ["double", "zero_group"])
def test_a_pad_row_that_leaks_into_the_answer_is_not_correct(small_data, leak):
    data_dir, info = small_data
    _, verdict = _drive(PadLeak(data_dir, leak), info, data_dir)
    assert not verdict["correct"]
    numbers = verdict["numbers"]
    if leak == "double":
        assert numbers["rel_gap_max"]["value"] > numbers["rel_gap_max"]["limit"]
    else:
        assert numbers["cells_wrong"]["value"] > 0


@pytest.mark.parametrize("counter", ["join_fallback", "tpu_fallback", "cpu_fallback", "highcard_fallback"])
def test_a_q3_whose_device_stage_fell_back_is_off_the_cells_path(counter):
    """A kind that is no gang kind is held to its route too (PR 33): a device
    stage with a fallback counter set left the device, whatever it answered."""
    job = _job(3)
    assert jobstats.wrong_route(job, 1, False) == "" and len(jobstats.device_stages(job)) == 1
    stage = jobstats.device_stages(job)[0]
    stage["ops"]["TpuStageExec"][counter] = 2
    assert jobstats.wrong_route(job, 1, False) == "device stage fell back"
    # a counter on a stage that was never the device's (the CPU hash join) says nothing
    job = _job(3)
    next(st for st in job["stages"] if "HashJoinExec" in st["ops"])["ops"]["HashJoinExec"][counter] = 1
    assert jobstats.wrong_route(job, 1, False) == ""
    # and a whole run counts such a query under failed, never averaged in
    job = _job(3)
    job["stages"] = [st for st in job["stages"] if st not in jobstats.device_stages(job)]
    assert jobstats.wrong_route(job, 1, False) == "no device stage"


class FellBack(FakeServed):
    def _answer(self, text, ctx=None):
        table = super()._answer(text, ctx)
        self.jobs[-1]["stages"][0]["metrics"]["MeshGangExec"]["cpu_fallback"] = 1
        return table


def test_a_run_counts_a_q3_that_fell_back_as_failed(small_data):
    data_dir, info = small_data
    measured, verdict = _drive(FellBack(data_dir), info, data_dir)
    assert verdict["correct"]  # the answers are right: the route is what failed
    assert measured["records"] and all(r["wrong_route"] == "device stage fell back" for r in measured["records"])


# ------------------------------------------------------------ the readers
def _job(kind: int) -> dict:
    with open(os.path.join(DATA, f"job_detail_q{kind}.json")) as f:
        return jobstats.summarize(json.load(f))


def _run_of(kind: int) -> dict:
    job = _job(kind)
    window = [{"kind": kind, "job": job}, {"kind": kind, "job": job}, {"kind": kind, "job": None}]
    peaks = harness.load_json(os.path.join(harness.HERE, "peaks.json"))["TPU v5 lite"]
    return {
        "window": window, "window_all": window, "warmup": [{"kind": kind, "job": job}], "cpu_ops": [],
        "memory": {}, "chips": 1, "peaks": peaks,
        # a trace's reduction as trace_reduce.reduce gives it: one traced query
        "trace": {"queries": window[:1], "programs_s": 0.6, "window_s": 5.0, "busy_s": 0.6,
                  "device_ops": [["jit_local_exchange", 0.5], ["jit_fn", 0.1]]},
    }


@pytest.fixture(scope="module")
def readers():
    return harness.load_readers()


def _counters(job: dict, op: str) -> dict:
    out: dict = {}
    for st in job["stages"]:
        for k, v in st["ops"].get(op, {}).items():
            out[k] = out.get(k, 0) + v
    return out


def test_each_new_reader_reads_the_recorded_q3_job(readers):
    job, q3 = _job(3), _run_of(3)
    ex, tpu = _counters(job, "MeshRepartitionExec"), _counters(job, "TpuStageExec")
    sent = ex["mesh_exchange_rows"] + ex["mesh_exchange_padded_rows"]
    assert sent % 1024 == 0 and tpu["stage_pad_rows"] > 0 and tpu["join_build_ns"] > 0
    want = {
        "exchange_ms": ex["device_time_ns"] / 1e6,
        "exchange_pad_share": 100.0 * ex["mesh_exchange_padded_rows"] / sent,
        # every input read once, every output written once, over 0.5 s of the program
        "exchange_roofline": 100.0 * (ex["mesh_exchange_bytes"] + ex["mesh_exchange_recv_bytes"]) / 819e9 / 0.5,
        "join_build_ms": tpu["join_build_ns"] / 1e6,
        "device_stage_ms": tpu["tpu_stage_time_ns"] / 1e6,
        "stage_pad_share": 100.0 * tpu["stage_pad_rows"] / (tpu["stage_pad_rows"] + tpu["input_rows"]),
        "warmup_xla_compiles": 0.0,  # recorded on a repeat: nothing obtained
    }
    for name in NEW:
        assert readers[name].read(q3) == pytest.approx(want[name]), name
    assert 0 < want["exchange_roofline"] < 100 and 0 < want["exchange_pad_share"] < 50
    bench = harness.benchmark_json()
    line = harness.read_per_layer(
        {"workloads": bench["workloads"], "per_layer": [m for m in bench["per_layer"] if m["name"] in NEW]},
        CELL, q3, readers)
    assert set(line) == set(NEW) and line["exchange_pad_share"]["unit"] == "%"


def test_new_readers_find_nothing_on_a_q1_job_or_a_parent(readers):
    q1 = _run_of(1)
    for name in NEW:
        if name != "warmup_xla_compiles":  # any warm-up job has that count
            assert readers[name].read(q1) is None, name
    assert readers["warmup_xla_compiles"].read(q1) == 0.0
    assert readers["warmup_xla_compiles"].read({**q1, "warmup": []}) is None
    # a parent commit runs q3 without this PR's counters: the readers that
    # need them return None and do not raise
    parent = _run_of(3)
    for st in parent["window"][0]["job"]["stages"]:
        for vals in st["ops"].values():
            for k in ("mesh_exchange_padded_rows", "mesh_exchange_bytes", "mesh_exchange_recv_bytes",
                      "join_build_ns", "stage_pad_rows"):
                vals.pop(k, None)
    for name in ("exchange_pad_share", "exchange_roofline", "join_build_ms", "stage_pad_share"):
        assert readers[name].read(parent) is None, name
    assert readers["exchange_ms"].read(parent) > 0 and readers["device_stage_ms"].read(parent) > 0
    # a q1 traced beside the q3 (four clients) moves nothing through the exchange and changes nothing
    mixed = _run_of(3)
    mixed["trace"]["queries"] = mixed["trace"]["queries"] + [{"kind": 1, "job": _job(1)}]
    assert readers["exchange_roofline"].read(mixed) == pytest.approx(readers["exchange_roofline"].read(_run_of(3)))
    # no trace, no exchange program in it, or no peaks: no roofline
    for broken in ({"trace": None}, {"peaks": None},
                   {"trace": {**_run_of(3)["trace"], "device_ops": [["jit_fn", 0.1]]}}):
        assert readers["exchange_roofline"].read({**_run_of(3), **broken}) is None
