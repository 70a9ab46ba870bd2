"""A configuration, a traffic mix and a per-layer metric are each added as
new files plus one entry; no file that is there is edited."""

import json
import os
import re
import shutil

from benchmark import harness

REPO = harness.REPO


def test_new_config_traffic_and_metric_resolve_without_an_edit(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = harness.benchmark_json()
    before = {
        p: open(os.path.join(dp, p), "rb").read()
        for dp, _, fs in os.walk(root / "benchmark") for p in fs
    }
    bdir = str(root / "benchmark")
    # one new file each ...
    cfg = harness.load_json(os.path.join(bdir, "configs", "tpch-sf1-1chip.json"))
    cfg.update(name="tpch-sf1-1chip-defaultclient", session={})
    (root / "benchmark/configs/tpch-sf1-1chip-defaultclient.json").write_text(json.dumps(cfg))
    (root / "benchmark/traffic/loadtest8.json").write_text(json.dumps(
        {"kinds": [1, 6, 3], "clients": 8, "loop": "closed", "order": "shuffle"}))
    (root / "benchmark/metrics/key_encode_share.py").write_text(
        'UNIT, BETTER, SOURCE = "%", "lower", "program_span"\n'
        'LAYER, MOVES = "gang stage", "query_geomean_s"\n\n'
        'def read(run):\n'
        '    from benchmark.metrics import _gang\n'
        '    return _gang.share_of_wall(run, ("key_encode_time_ns",))\n')
    # ... and one entry each
    bench["configs"].append({"name": cfg["name"], "source": "x", "reduced": [], "why": "y",
                             "file": "benchmark/configs/tpch-sf1-1chip-defaultclient.json"})
    bench["workloads"].append({"name": "tpch-sf1-1chip-defaultclient.loadtest8", "chips": 1, "why": "z",
                               "config": cfg["name"], "traffic": "loadtest8"})
    bench["per_layer"].append({"name": "key_encode_share", "unit": "%", "better": "lower",
                               "source": "program_span", "layer": "gang stage", "moves": "query_geomean_s",
                               "workloads": ["tpch-sf1-1chip-defaultclient.loadtest8"]})
    got = harness.resolve("tpch-sf1-1chip-defaultclient.loadtest8", bench, bdir)
    assert got["traffic"]["clients"] == 8 and got["config"]["session"] == {}
    readers = harness.load_readers(bdir)
    assert "key_encode_share" in readers
    job = {"stages": [{"start_us": 0, "end_us": 1000, "ops": {
        "MeshGangExec": {"key_encode_time_ns": 250_000, "mesh_stage_time_ns": 1_000_000, "mesh_devices": 1}}}]}
    run = {"window": [{"job": job}], "window_all": [{"job": job}], "warmup": [], "cpu_ops": [],
           "trace": None, "memory": {}, "chips": 1}
    out = harness.read_per_layer(bench, "tpch-sf1-1chip-defaultclient.loadtest8", run, readers)
    assert out["key_encode_share"] == {"value": 25.0, "unit": "%"}
    # a reader with nothing to read is left out of the line, never 0
    assert "scan_roofline" not in out and "device_idle_share" not in out
    # nothing that was there changed
    for dp, _, fs in os.walk(root / "benchmark"):
        for p in fs:
            if p in before:
                assert open(os.path.join(dp, p), "rb").read() == before[p]


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_the_contract_and_matches_the_files():
    bench = harness.benchmark_json()
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"] and 1 <= bench["run_seconds"] <= 51
    readers = harness.load_readers()
    cells = [c["name"] for c in bench["workloads"]]
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    assert sum(c["chips"] == 4 for c in bench["workloads"]) <= max(1, len(cells) // 2)
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and os.path.exists(os.path.join(REPO, c["file"]))
        cfg = harness.load_json(os.path.join(REPO, c["file"]))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and len(w["why"]) <= 200
        harness.resolve(w["name"], bench)
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        r = readers[m["name"]]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == \
            (r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES)
        assert m["moves"] in e2e and UNIT.match(m["unit"]) and NAME.match(m["name"])
        assert set(m.get("workloads", cells)) <= set(cells)
    assert len(json.dumps(bench)) < 64 * 1024
