"""The whole command, as the driver starts it, in a --platform cpu rehearsal
at SF0.01: a real scheduler, a real executor, a remote client."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CMD = [sys.executable, os.path.join(REPO, "benchmark", "run.py")]


def _run(*extra, cwd=REPO, timeout=600):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    return subprocess.run([*CMD, *extra], cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_has_the_contracts_shape(trace):
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = bench["workloads"][0]["name"]
    p = _run("--workload", cell, "--seed", str(2**31 + 5), "--seconds", "6", "--trace", str(trace),
             "--platform", "cpu", "--sf", "0.01")
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(out)[-1] == "compared"  # the numbers compared come last in the line
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 2
    assert out["device"]["platform"] == "cpu" and "rehearsal" in out
    assert set(out["compared"]) == {"cells_wrong", "rel_gap_max", "rows_out_of_order"}
    tail = p.stderr.strip().splitlines()[-4:]
    assert tail[0].startswith("compared cells_wrong") and tail[1].startswith("compared rel_gap_max")
    assert tail[2].startswith("compared rows_out_of_order") and tail[3].startswith("correct = True")
    names = {m["name"] for m in bench["per_layer" if trace else "end_to_end"]}
    assert set(out["metrics"]) <= names and out["metrics"]
    for v in out["metrics"].values():
        assert isinstance(v["value"], float) and v["unit"]
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"]) and "breakdown" in out
        assert len(out["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(out["metrics"]) == {"setup_s", "query_geomean_s", "scan_rows_rate"}
        assert out["window"]["window_s"] <= 6.0 + 1.0  # whole queries, inside the window


def test_no_chip_means_no_result():
    """Without --platform cpu the executor is told 'tpu'; this sandbox has
    none, so the command exits non-zero with nothing on stdout."""
    p = _run("--workload", "tpch-sf1-1chip.scan-agg", "--seed", "1", "--seconds", "2", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tpch-sf1-1chip.scan-agg", "--seed", "1",
         "--seconds", "2", "--trace", "0", "--platform", "cpu", "--sf", "0.01"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert p.returncode != 0 and p.stdout.strip() == ""
