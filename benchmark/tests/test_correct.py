"""The comparison that decides ``correct`` has been shown to fail: on the
control (the reference one precision lower, in the program's place) and on
each fault the cells can have, planted under the rest of a run."""

import pytest

from benchmark import compare, harness, reference, run
from benchmark.tests.fake_served import FakeServed


def _resolved(cell="tpch-sf1-1chip.scan-agg"):
    return harness.resolve(cell, harness.benchmark_json())


def _drive(served, data_info, data_dir, cell="tpch-sf1-1chip.scan-agg", seconds=1.5, seed=77):
    measured = run.measure(served, _resolved(cell), data_info, seed, seconds, False)
    return measured, run.judge(measured, data_dir)


def test_sound_run_is_correct(small_data):
    data_dir, info = small_data
    measured, verdict = _drive(FakeServed(data_dir), info, data_dir)
    assert verdict["correct"] and verdict["compared"] == len(measured["records"]) >= 2
    assert not any(r.get("wrong_route") for r in measured["records"])
    assert {r["kind"] for r in measured["records"]} == {1, 6}
    assert verdict["numbers"]["rel_gap_max"]["value"] == 0.0


@pytest.mark.parametrize("seed", [3, 2**31 + 9, 123456789])
def test_control_bfloat16_reference_in_the_programs_place_is_not_correct(small_data, seed):
    data_dir, info = small_data
    _, verdict = _drive(FakeServed(data_dir, precision="bfloat16"), info, data_dir, seed=seed)
    assert not verdict["correct"]
    assert verdict["numbers"]["rel_gap_max"]["value"] > compare.LIMITS["rel_gap_max"]
    assert verdict["numbers"]["cells_wrong"]["value"] == 0  # keys and counts are still exact


@pytest.mark.parametrize("fault", ["altered", "half_batch", "no_exchange"])
@pytest.mark.parametrize("cell,chips", [("tpch-sf1-1chip.scan-agg", 1), ("tpch-sf1-4chip-gang.scan-agg", 4)])
def test_planted_fault_comes_out_not_correct(small_data, fault, cell, chips):
    data_dir, info = small_data
    _, verdict = _drive(FakeServed(data_dir, chips=chips, fault=fault), info, data_dir, cell=cell)
    assert not verdict["correct"]


@pytest.mark.parametrize("fault,mesh", [("mesh_fallback", 4), (None, 1)])
def test_query_off_the_cells_path_is_failed_not_averaged(small_data, fault, mesh):
    data_dir, info = small_data
    served = FakeServed(data_dir, chips=4, fault=fault, mesh_devices=mesh)
    measured, _ = _drive(served, info, data_dir, cell="tpch-sf1-4chip-gang.scan-agg")
    assert measured["records"] and all(r.get("wrong_route") for r in measured["records"])


def test_join_traffic_is_compared_too(small_data):
    data_dir, info = small_data
    _, ok = _drive(FakeServed(data_dir), info, data_dir, cell="tpch-sf1-1chip.join-agg")
    assert ok["correct"]
    _, bad = _drive(FakeServed(data_dir, fault="altered"), info, data_dir, cell="tpch-sf1-1chip.join-agg")
    assert not bad["correct"]


def test_table_gap_counts_missing_rows_keys_and_counts(small_data):
    data_dir, _ = small_data
    ref = reference.answer(reference.Data(data_dir), 1, {"delta": 90})
    assert compare.table_gap(ref, ref) == (0, 0.0, "")
    assert compare.table_gap(ref.slice(1), ref)[0] >= 1
    counts = ref.column("count_order").to_pylist()
    counts[0] += 1
    off = ref.set_column(ref.column_names.index("count_order"), "count_order", [counts])
    assert compare.table_gap(off, ref)[0] == 1
    assert not compare.judge([])["correct"]  # nothing compared is not correct
