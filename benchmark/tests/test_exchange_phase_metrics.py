"""The four readers of the exchange call's phases: each one's arithmetic on a
job detail built by hand (a q3: five exchanges a query, one of which moved
no rows), the four summing to ``exchange_ms``, None (never 0) where the
program has no such counter, and their entries, found by name, in the lines
of the four cells that report ``exchange_ms`` and of no other."""

import pytest

from benchmark import harness
from benchmark.tests.test_gang_phase_metrics import MS, _stage

PHASES = ("pad", "upload", "program", "fetch")
NAMES = [f"exchange_{p}_ms" for p in PHASES]
CELLS = ["tpch-q3-sf1-1chip.join-agg", "tpch-sf1-1chip.loadtest4",
         "tpch-q3-sf10-1chip.join-agg", "tpch-q3-sf10-4chip.join-agg"]
# stage -> (mesh_exchange_rows, ms of pad, upload, program, fetch):
# stage 3's exchange moved no rows
EXCHANGES = {1: (100, 1, 2, 3, 4), 2: (700, 5, 10, 20, 15), 3: (0, 9, 9, 9, 9),
             4: (3000, 100, 300, 400, 250), 5: (50, 2, 3, 4, 5)}
# one query, the stages that moved rows
ONE = {"pad": 108.0, "upload": 315.0, "program": 427.0, "fetch": 274.0}


def _q3(strip=(), scale=1):
    stages = []
    for sid, (rows, *ms) in EXCHANGES.items():
        ops = {"mesh_exchange_rows": rows, "device_time_ns": sum(ms) * MS * scale}
        ops.update({f"exchange_{p}_ns": t * MS * scale for p, t in zip(PHASES, ms)})
        ops = {k: v for k, v in ops.items() if k not in strip}
        stages.append(_stage(sid, 0, 100 * sid, 90 * sid, {"MeshRepartitionExec": ops}))
    stages.append(_stage(6, 600, 700, 30, partitions=2))
    return {"stages": stages}


def _run(strip=()):
    """A window of two q3s (the second's calls twice as long), a query
    whose job detail was lost and a q6 with no exchange."""
    window = [{"job": _q3(strip)}, {"job": _q3(strip, scale=2)}, {"job": None},
              {"job": {"stages": [_stage(1, 0, 50, 40)]}}]
    return {"window": window, "window_all": window, "warmup": [], "cpu_ops": [],
            "trace": None, "memory": {}, "chips": 1}


@pytest.fixture(scope="module")
def readers():
    every = harness.load_readers()
    return {name: every[name] for name in NAMES + ["exchange_ms"]}


@pytest.mark.parametrize("phase", PHASES)
def test_sums_the_exchanges_that_moved_rows_and_means_over_the_queries(readers, phase):
    reader = readers[f"exchange_{phase}_ms"]
    run = _run()
    first = {**run, "window": run["window"][:1]}
    assert reader.read(first) == pytest.approx(ONE[phase])
    # mean over the q3s: the lost job and the q6 are left out, not read as 0
    assert reader.read(run) == pytest.approx(1.5 * ONE[phase])


def test_the_four_sum_to_exchange_ms(readers):
    run = _run()
    whole = readers["exchange_ms"].read(run)
    assert sum(readers[n].read(run) for n in NAMES) == pytest.approx(whole)


@pytest.mark.parametrize("phase", PHASES)
def test_finds_nothing_on_a_program_without_the_counter(readers, phase):
    reader = readers[f"exchange_{phase}_ms"]
    # the parent's exchange: device_time_ns and no phase counter
    assert reader.read(_run(strip=[f"exchange_{p}_ns" for p in PHASES])) is None
    assert reader.read(_run(strip=["mesh_exchange_rows"])) is None
    assert reader.read({"window": [{"job": None}], "window_all": []}) is None


@pytest.mark.parametrize("name", NAMES)
def test_entry_found_by_name_lists_the_cells_that_report_exchange_ms(readers, name):
    bench = harness.benchmark_json()
    (m,) = [e for e in bench["per_layer"] if e["name"] == name]
    (whole,) = [e for e in bench["per_layer"] if e["name"] == "exchange_ms"]
    assert m["workloads"] == whole["workloads"] == CELLS
    reader = readers[name]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER, reader.MOVES)
    assert (m["layer"], m["moves"]) == (whole["layer"], whole["moves"])
    only = {"workloads": bench["workloads"], "per_layer": [m]}
    first = {**_run(), "window": _run()["window"][:1]}
    phase = name[len("exchange_"):-len("_ms")]
    for cell in CELLS:
        assert harness.read_per_layer(only, cell, first, {name: reader}) == {
            name: {"value": pytest.approx(ONE[phase]), "unit": "ms"}}
    for cell in ("tpch-sf1-1chip.scan-agg", "tpch-sf1-4chip-gang.scan-agg"):
        assert harness.read_per_layer(only, cell, first, {name: reader}) == {}


def test_span_gaps_puts_each_idle_instant_on_the_innermost_open_span():
    """``benchmark/chip/span_gaps.py``'s sweep on intervals built by hand
    (ns, one stage's window 0-100 on two chips): idle time averaged over
    the chips goes to the span that started last among those open, a span
    of another stage is named with its stage, and an instant under no span
    to ``(no span)``."""
    from benchmark.chip import span_gaps

    spans = [
        ("task.execute", 10, 100, {"stage": 4}),
        ("exchange.pad", 20, 40, {"stage": 4}),
        ("exchange.fetch", 30, 60, {"stage": 2}),
    ]
    busy = {0: [[50, 70]], 1: []}  # chip 0 busy 50-70, chip 1 idle throughout
    got = span_gaps.attribute((0, 100), spans, busy, 2, stage=4)
    ns = {k: v * 1e9 for k, v in got.items()}
    assert ns == pytest.approx({
        "(no span)": 10.0,  # 0-10, both chips idle
        "task.execute": 10.0 + 5.0 + 30.0,  # 10-20 both; 60-70 one chip of two; 70-100 both
        "exchange.pad": 10.0,  # 20-30 both chips
        "exchange.fetch (stage 2)": 20.0 + 5.0,  # 30-50 both; 50-60 one chip of two
    })
    assert sum(ns.values()) == pytest.approx(100 - 20 / 2)
