"""The q3 cell at SF10 over a four-chip host: it resolves to its
configuration, which is the one-chip SF10 configuration's but for the chips,
the mesh and four shuffle partitions; it reports that cell's per-layer
metrics, ``collective_ms`` and the two per-chip readers; and ``stage_chips``
and ``stage_chip_skew`` read values worked by hand from a stub job, and None
from a parent commit's job, which counts no chip."""

import pytest

from benchmark import harness

CELL = "tpch-q3-sf10-4chip.join-agg"
ONE_CHIP = "tpch-q3-sf10-1chip.join-agg"
MS = 1_000_000  # ns


@pytest.fixture(scope="module")
def bench():
    return harness.benchmark_json()


def test_cell_resolves_to_four_chips_and_the_one_chip_cells_configuration(bench):
    got = harness.resolve(CELL, bench)
    cfg, traffic = got["config"], got["traffic"]
    assert got["cell"]["chips"] == 4 == cfg["chips"] and cfg["mesh_shape"] == [2, 2]
    assert got["cell"]["traffic"] == "join-agg" and traffic["kinds"] == [3] and traffic["clients"] == 1
    one = harness.resolve(ONE_CHIP, bench)["config"]
    for key in ("cluster", "tables", "files_per_table", "scale_factor", "gang_kinds"):
        assert cfg[key] == one[key], key
    # the session differs in the partitions alone: one a chip
    assert {k: v for k, v in cfg["session"].items() if k != "ballista.shuffle.partitions"} == one["session"]
    assert cfg["session"]["ballista.shuffle.partitions"] == 4
    assert "ballista.shuffle.partitions" not in cfg["session_defaults_relied_on"]
    # one's assumptions, and the partitions and the executors added
    assert {k: v for k, v in cfg["assumed"].items() if k in one["assumed"]} == one["assumed"]
    assert set(cfg["assumed"]) - set(one["assumed"]) == {"shuffle_partitions", "executors"}
    # the published SF100, cut to SF10; the one chip's guarantees and two more
    assert cfg["published"]["scale_factor"] == 100.0 and cfg["scale_factor"] == 10.0
    assert cfg["reduced"] == ["scale_factor", "tables"] == next(
        c["reduced"] for c in bench["configs"] if c["name"] == cfg["name"])
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert set(one["guarantees"]) < set(cfg["guarantees"]) and len(cfg["guarantees"]) == len(one["guarantees"]) + 2


def test_cell_reports_the_one_chip_cells_metrics_the_collectives_and_the_chips(bench):
    listed = [m["name"] for m in harness.metrics_of_cell(bench, CELL, "per_layer")]
    one = [m["name"] for m in harness.metrics_of_cell(bench, ONE_CHIP, "per_layer")]
    assert len(listed) == len(set(listed)) == len(one) + 3
    assert set(listed) == set(one) | {"collective_ms", "stage_chips", "stage_chip_skew"}
    # the two entries, found by name, are for this cell alone, on the device stage's layer
    chips = [m for m in bench["per_layer"] if m["name"] in ("stage_chips", "stage_chip_skew")]
    assert [m["name"] for m in chips] == ["stage_chips", "stage_chip_skew"]
    readers = harness.load_readers()
    layer = next(m["layer"] for m in bench["per_layer"] if m["name"] == "device_stage_ms")
    for m in chips:
        r = readers[m["name"]]
        assert m["workloads"] == [CELL]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES) and m["layer"] == layer
    e2e = {m["name"] for m in harness.metrics_of_cell(bench, CELL, "end_to_end")}
    assert e2e == {"setup_s", "query_geomean_s", "scan_rows_rate"}
    four_chip = [c for c in bench["workloads"] if c["chips"] == 4]
    assert 2 * len(four_chip) <= len(bench["workloads"])


def _q3(per_chip_ms: dict, exchange_chips: int = 4) -> dict:
    """A q3's job detail: five exchanges and stage 5's device stage, whose
    partitions counted ``per_chip_ms`` (chip -> ms) on their chips."""
    stages = [{"stage_id": s, "ops": {"MeshRepartitionExec": {"mesh_devices": exchange_chips}}}
              for s in range(1, 5)]
    tpu = {"tpu_stage_time_ns": sum(per_chip_ms.values()) * MS, "join_build_groups": 1}
    tpu.update({f"tpu_stage_time_ns_dev{c}": ms * MS for c, ms in per_chip_ms.items()})
    stages.append({"stage_id": 5, "ops": {"TpuStageExec": tpu, "MeshRepartitionExec": {"mesh_devices": 4}}})
    stages.append({"stage_id": 6, "ops": {"HashAggregateExec": {"output_rows": 10}}})
    return {"stages": stages}


def _run(*jobs) -> dict:
    window = [{"kind": 3, "job": j} for j in jobs]
    return {"window": window, "window_all": window, "chips": 4, "trace": None, "memory": {}}


@pytest.fixture(scope="module")
def readers():
    r = harness.load_readers()
    return r["stage_chips"], r["stage_chip_skew"]


def test_both_readers_worked_by_hand(readers):
    chips, skew = readers
    even = _q3({0: 500, 1: 500, 2: 500, 3: 500})
    # chip 2's partition twice the others'; chip 3 ran nothing
    lopsided = _q3({0: 400, 1: 400, 2: 800, 3: 0})
    assert chips.read(_run(even)) == 4.0 and skew.read(_run(even)) == 1.0
    assert chips.read(_run(lopsided)) == 3.0
    assert skew.read(_run(lopsided)) == pytest.approx(800 / (1600 / 3))  # 1.5
    # mean over the window's queries; a lost job detail is left out, not read as 0
    assert chips.read(_run(even, lopsided, None)) == 3.5
    assert skew.read(_run(even, lopsided, None)) == pytest.approx(1.25)
    # one chip: every partition counted on chip 0
    one = _q3({0: 2000}, exchange_chips=1)
    assert chips.read(_run(one)) == 1.0 and skew.read(_run(one)) == 1.0
    # the most over a query's device stages, the sum over them for the skew
    two = _q3({0: 100, 1: 100})
    two["stages"].append({"stage_id": 7, "ops": {"TpuStageExec": {
        "tpu_stage_time_ns": 600 * MS, "tpu_stage_time_ns_dev2": 300 * MS, "tpu_stage_time_ns_dev0": 300 * MS}}})
    assert chips.read(_run(two)) == 2.0
    assert skew.read(_run(two)) == pytest.approx(400 / (800 / 3))  # chips 0, 1, 2: 400, 100, 300


def test_both_readers_find_nothing_in_a_parent_commits_job(readers):
    parent = _q3({})
    parent["stages"][4]["ops"]["TpuStageExec"]["tpu_stage_time_ns"] = 2000 * MS
    for reader in readers:
        assert reader.read(_run(parent)) is None
        assert reader.read(_run(None)) is None
        assert reader.read({"window": [], "window_all": []}) is None
