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


@pytest.mark.parametrize("cell", ["tpch-sf1-1chip.scan-agg", "tpch-sf1-1chip.loadtest4"])
@pytest.mark.parametrize("seed", [3, 2**31 + 9, 123456789])
def test_control_bfloat16_reference_in_the_programs_place_is_not_correct(small_data, seed, cell):
    data_dir, info = small_data
    _, verdict = _drive(FakeServed(data_dir, precision="bfloat16"), info, data_dir, seed=seed, cell=cell)
    assert not verdict["correct"]
    assert verdict["numbers"]["rel_gap_max"]["value"] > compare.LIMITS["rel_gap_max"]
    assert verdict["numbers"]["cells_wrong"]["value"] == 0  # keys and counts are still exact


@pytest.mark.parametrize("fault", ["altered", "half_batch", "no_exchange"])
@pytest.mark.parametrize("cell,chips", [("tpch-sf1-1chip.scan-agg", 1), ("tpch-sf1-4chip-gang.scan-agg", 4),
                                        ("tpch-sf1-1chip.loadtest4", 1)])
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


@pytest.mark.parametrize("cell", ["tpch-q3-sf1-1chip.join-agg", "tpch-sf1-1chip.scan-agg"])
def test_two_rows_the_wrong_way_round_are_not_correct(small_data, cell):
    """The right ten rows (q1: the right groups) in another order than the
    text asks: every cell is equal, only ``rows_out_of_order`` reads it."""
    data_dir, info = small_data
    _, ok = _drive(FakeServed(data_dir), info, data_dir, cell=cell)
    assert ok["correct"] and ok["numbers"]["rows_out_of_order"] == {"value": 0.0, "limit": 0.0}
    _, bad = _drive(FakeServed(data_dir, fault="swapped"), info, data_dir, cell=cell)
    assert not bad["correct"]
    assert bad["numbers"]["cells_wrong"]["value"] == 0 and bad["numbers"]["rel_gap_max"]["value"] == 0.0
    assert bad["numbers"]["rows_out_of_order"]["value"] >= 1


def test_neighbours_within_the_limit_may_stand_either_way_round():
    import datetime as dt

    import pyarrow as pa

    order = (("revenue", True), ("o_orderdate", False))
    day = dt.date(1995, 3, 1)

    def rows(revenues, dates=None):
        return pa.table({"revenue": revenues, "o_orderdate": dates or [day] * len(revenues)})

    assert compare.out_of_order(rows([300.0, 200.0, 100.0]), order) == 0
    assert compare.out_of_order(rows([200.0, 300.0, 100.0]), order) == 1
    # float32 sums may rank two revenues 1e-5 apart the other way: a tie to the comparison
    assert compare.out_of_order(rows([200.0, 200.0 * (1 + 1e-5), 100.0]), order) == 0
    assert compare.out_of_order(rows([200.0, 200.0 * (1 + 1e-4), 100.0]), order) == 1
    # equal revenues: the order date decides, ascending
    later = day + dt.timedelta(days=1)
    assert compare.out_of_order(rows([200.0, 200.0], [day, later]), order) == 0
    assert compare.out_of_order(rows([200.0, 200.0], [later, day]), order) == 1
    assert compare.out_of_order(rows([200.0, 200.0 * (1 + 1e-5)], [later, day]), order) == 0  # within the limit
    assert compare.out_of_order(rows([100.0, 100.0]), (("o_orderdate", False),)) == 0
    assert compare.out_of_order(rows([1.0, 1.0], [later, day]), (("o_orderdate", False),)) == 1
    assert compare.out_of_order(rows([1.0]), ()) == 0 and compare.out_of_order(rows([1.0, 2.0]), (("x", True),)) == 0


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
