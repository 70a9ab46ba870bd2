"""The q3 cell at the published SF10 (PR 34): it resolves to its
configuration and traffic, ``_join.py``'s bytes of the build-row program are
worked by hand for one job detail, and ``join_agg_roofline`` reads a stub
run, and returns None where the trace has no build-row program or the job no
build-row counters (a parent commit)."""

import os

import pytest

from benchmark import harness
from benchmark.metrics import _join

CELL = "tpch-q3-sf10-1chip.join-agg"
SF1_CELL = "tpch-q3-sf1-1chip.join-agg"
PEAKS = harness.load_json(os.path.join(harness.HERE, "peaks.json"))["TPU v5 lite"]


def test_cell_resolves_to_its_configuration_and_traffic():
    bench = harness.benchmark_json()
    got = harness.resolve(CELL, bench)
    cfg, traffic = got["config"], got["traffic"]
    assert got["cell"]["chips"] == 1 == cfg["chips"] and got["cell"]["traffic"] == "join-agg"
    assert cfg["name"] == "tpch-q3-sf10-1chip" and cfg["gang_kinds"] == []
    # the published scale, three of the eight tables
    assert cfg["scale_factor"] == cfg["published"]["scale_factor"] == 10.0
    assert cfg["reduced"] == ["tables"] == next(
        c["reduced"] for c in bench["configs"] if c["name"] == cfg["name"])
    assert traffic["kinds"] == [3] and traffic["clients"] == 1 and traffic["parameter_sets"] == 1
    # the SF1 q3 configuration's cluster, session, defaults and assumptions
    sf1 = harness.resolve(SF1_CELL, bench)["config"]
    for key in ("cluster", "session", "session_defaults_relied_on", "assumed", "tables",
                "files_per_table", "mesh_shape", "published"):
        assert cfg[key] == sf1[key], key
    # every per-layer metric of the SF1 cell, and the build-row program's share
    listed = {m["name"] for m in harness.metrics_of_cell(bench, CELL, "per_layer")}
    sf1_listed = {m["name"] for m in harness.metrics_of_cell(bench, SF1_CELL, "per_layer")}
    assert listed == sf1_listed and "join_agg_roofline" in listed
    assert CELL in next(m for m in bench["per_layer"] if m["name"] == "join_agg_roofline")["workloads"]
    e2e = {m["name"] for m in harness.metrics_of_cell(bench, CELL, "end_to_end")}
    assert e2e == {"setup_s", "query_geomean_s", "scan_rows_rate"}


# one partition's worth of stage 5 as the program counts it: 3,000,000 probe
# rows padded to 4,194,304, an x32 row of 11 bytes (mask, l_extendedprice
# f32, l_discount f32, the probe key i32 and its validity), 300,000 matched
# rows, 262,144 slots of 16 bytes (sum hi, lo, count, presence)
STAGE = {
    "join_build_groups": 1, "dense_join": 1, "join_build_capacity": 262_144,
    "join_probe_rows": 3_000_000, "stage_pad_rows": 1_194_304,
    "join_probe_bytes": 4_194_304 * 11, "join_matched_rows": 300_000,
    "join_state_bytes": 262_144 * 16, "join_state_slots": 262_144,
}


def _job(**counters) -> dict:
    return {"stages": [
        {"ops": {"MeshRepartitionExec": {"mesh_exchange_rows": 5}}},
        {"ops": {"TpuStageExec": dict(STAGE, **counters), "MeshRepartitionExec": {}}},
    ]}


def test_join_bytes_worked_by_hand():
    probe = 4_194_304 * 11          # every padded row's inputs, read once
    table = 4 * 4_194_304           # the dense table's i32 slot, once a padded row
    update = 2 * 300_000 * 16       # a read and a write of a matched row's slot
    fetch = 262_144 * 16            # the states fetched
    assert _join.agg_bytes(_job()) == probe + table + update + fetch == 76_708_864
    # the sorted form searches 262,144 keys: 19 reads of 4 B a padded row
    sorted_job = _job()
    del sorted_job["stages"][1]["ops"]["TpuStageExec"]["dense_join"]
    assert _join.agg_bytes(sorted_job) == probe + 19 * table + update + fetch
    # a parent commit's q3 has no build-row counters
    assert _join.agg_bytes({"stages": [{"ops": {"TpuStageExec": {"join_probe_rows": 5}}}]}) is None


def _run(device_ops, job) -> dict:
    window = [{"kind": 3, "job": job}]
    return {"window": window, "chips": 1, "peaks": PEAKS,
            "trace": {"queries": window, "programs_s": 0.02, "device_ops": device_ops}}


def test_the_reader_on_a_stub_run():
    reader = harness.load_readers()["join_agg_roofline"]
    got = reader.read(_run([["jit_local_exchange", 0.5], ["jit_join_aggregate", 0.002]], _job()))
    assert got == pytest.approx(100.0 * 76_708_864 / 819e9 / 0.002)
    assert 0 < got <= 100
    # a trace without the build-row program (the parent's stage 5 is jit_fn)
    assert reader.read(_run([["jit_local_exchange", 0.5], ["jit_fn", 0.1]], _job())) is None
    # the program in the trace, no build-row counters in the job
    parent_job = {"stages": [{"ops": {"TpuStageExec": {"join_probe_rows": 5}}}]}
    assert reader.read(_run([["jit_join_aggregate", 0.002]], parent_job)) is None
    assert reader.read({**_run([], _job()), "trace": None}) is None
