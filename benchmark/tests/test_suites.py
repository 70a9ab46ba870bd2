"""Query suites by file: the ``tpch`` suite gives the tables, texts and
reference answers the benchmark gave before suites were files (digests taken
from the modules as they were), and a suite, a configuration naming it and a
traffic mix added as files alone run through the window, ``measure``,
``judge`` and ``run.py``, with no edit to a file that was there."""

import glob
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmark import compare, harness, reference, run, suites, window
from benchmark.tests.fake_served import FakeServed

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "suite_toy")
TOY_CELL = "toy-1chip.toy-groupby"

# (rows, content digest) of each table at SF0.01, four files a table, and one
# digest over the reference's answers of every tenth parameter set of q1, q6
# and q3 on those tables, as ``datagen.generate`` and ``reference.answer``
# gave them before the suite interface
TABLES = {
    (1, "lineitem"): (60000, "2c3a0300d68d237812b4fe259491082c58c056d60afbc6d99deaacc0e02de7f7"),
    (1, "orders"): (15000, "9e19284dd6f5ede542582eacfa90818d2f180ee8a72f3a3a27e59493bd44c701"),
    (1, "customer"): (1500, "7f26a8a580f09b9fc1d1fc37f1f3e6a02acb266e30aeffa3d10dc0f49965203f"),
    (2, "lineitem"): (60000, "fec0d2534d5e2c74fec629af49410161796e34af26f585acf8c0a460bc2a1d5c"),
    (2, "orders"): (15000, "ff99b3d7f94beda23f4fa3f66147a6e9b1666591677539901ea4095c53005cbc"),
    (2, "customer"): (1500, "f2e75d256aaa3d1b59571ef50765deb30cc3cf1e067f25542379ab552bf92f89"),
}
ANSWERS = {1: "cb923f1eedd68c8c18dd625c0b7264c8333e632f32d05fb922a4e2d9421e8574",
           2: "cf64dc549503e93f702dba67a68a690747ec6c4a88842eaae33cbbd6f00e515c"}
# every (kind, parameter set) of q1, q6, q3 rendered, in order
TEXTS = "73e359db92eaf2bf907aa3c9adefc7f0f8d636f6310c9ffed527239823a5c934"


def _digest(t: pa.Table) -> str:
    h = hashlib.sha256()
    for name in t.column_names:
        col = t.column(name).combine_chunks()
        h.update(name.encode() + b"\0")
        if pa.types.is_string(col.type):
            h.update("\x1f".join(col.to_pylist()).encode())
        else:
            h.update(np.asarray(col).tobytes())
    return h.hexdigest()


def _answer_digest(t: pa.Table) -> str:
    return hashlib.sha256(repr([t.schema.to_string(), t.to_pylist()]).encode()).hexdigest()


@pytest.fixture(scope="module")
def tpch():
    return suites.load("tpch")


@pytest.fixture(scope="module", params=[1, 2])
def tpch_data(request, tpch, tmp_path_factory):
    seed = request.param
    out = str(tmp_path_factory.mktemp(f"tpch{seed}"))
    info = suites.generate(out, tpch, ("lineitem", "orders", "customer"), 0.01, seed, 4, procs=2)
    return seed, out, info


def test_tpch_tables_are_the_ones_the_benchmark_generated(tpch_data):
    seed, out, info = tpch_data
    for name in ("lineitem", "orders", "customer"):
        t = pa.concat_tables(pq.read_table(p) for p in sorted(glob.glob(os.path.join(out, name, "*.parquet"))))
        assert (t.num_rows, _digest(t)) == TABLES[seed, name] and info["rows"][name] == t.num_rows


def test_tpch_texts_are_the_ones_the_benchmark_sent(tpch):
    h = hashlib.sha256()
    for kind in (1, 6, 3):
        for p in tpch.parameter_sets(kind):
            h.update(tpch.render(kind, p).encode())
    assert h.hexdigest() == TEXTS
    assert [tpch.label(k) for k in (1, 6, 3)] == ["q1", "q6", "q3"]


def test_tpch_reference_answers_are_the_ones_the_benchmark_compared(tpch, tpch_data):
    seed, out, _ = tpch_data
    data = reference.Data(out)
    h = hashlib.sha256()
    for kind in (1, 6, 3):
        for p in tpch.parameter_sets(kind)[::10]:
            h.update(_answer_digest(tpch.answer(data, kind, p)).encode())
    assert h.hexdigest() == ANSWERS[seed]


def _checkout(root) -> tuple:
    """A copy of the benchmark with the toy suite, its configuration and its
    traffic added as files and the configuration and cell as entries;
    returns (bench, the copy's benchmark directory, the files before)."""
    bdir = root / "benchmark"
    shutil.copytree(os.path.join(harness.REPO, "benchmark"), bdir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {str(p): p.read_bytes() for p in bdir.rglob("*") if p.is_file()}
    shutil.copy(os.path.join(TOY, "toy.py"), bdir / "suites")
    shutil.copy(os.path.join(TOY, "toy-1chip.json"), bdir / "configs")
    shutil.copy(os.path.join(TOY, "toy-groupby.json"), bdir / "traffic")
    bench = harness.benchmark_json()
    bench["configs"].append({"name": "toy-1chip", "source": "tests", "reduced": [], "why": "tests",
                             "file": "benchmark/configs/toy-1chip.json"})
    bench["workloads"].append({"name": TOY_CELL, "config": "toy-1chip", "traffic": "toy-groupby",
                               "chips": 1, "why": "tests"})
    return bench, str(bdir), before


def _unchanged(before: dict) -> None:
    for path, content in before.items():
        assert open(path, "rb").read() == content, path


def test_a_suite_added_as_files_drives_the_window_and_the_comparison(tmp_path):
    bench, bdir, before = _checkout(tmp_path)
    got = harness.resolve(TOY_CELL, bench, bdir)
    toy = got["suite"]
    assert got["config"]["suite"] == "toy" and toy.label("sum_v3") == "toy_sum_v3"
    # an int kind with an order, a string kind with none, one table
    assert list(toy.TABLES) == ["x"] and toy.ORDER_BY[1] and toy.ORDER_BY["sum_v3"] == ()
    data_dir = str(tmp_path / "data")
    info = suites.generate(data_dir, toy, ["x"], toy.scale(got["config"], 0.02), 2**31 + 41, 4, procs=2)
    assert info["rows"] == {"x": 20000}
    data = reference.Data(data_dir)

    def submit(client, kind, params):
        return toy.answer(data, kind, params)

    res = window.run_window(toy, got["traffic"], 2**31 + 41, 0.3, submit)
    recs = res["queries"]
    assert {r["kind"] for r in recs} == {1, "sum_v3"} and all(r["error"] is None for r in recs)
    pairs = [(r["answer"], toy.answer(data, r["kind"], r["params"]), toy.ORDER_BY[r["kind"]]) for r in recs]
    assert compare.judge(pairs)["correct"]
    served, ref, order = pairs[0]
    col = served.column("v3").to_pylist()
    col[0] *= 1 + 1e-4  # one cell of one answer changed
    off = served.set_column(served.column_names.index("v3"), "v3", [col])
    assert not compare.judge([(off, ref, order), *pairs[1:]])["correct"]
    _unchanged(before)


def test_a_configuration_without_a_suite_runs_tpch_and_a_bad_suite_fails_at_resolve(tmp_path):
    bench = harness.benchmark_json()
    for cell in bench["workloads"]:
        got = harness.resolve(cell["name"], bench)
        assert "suite" not in got["config"] and got["suite"].__name__ == "benchmark_suite_tpch"
    bench, bdir, _ = _checkout(tmp_path)
    cfg = os.path.join(bdir, "configs", "toy-1chip.json")
    good = open(cfg).read()
    open(cfg, "w").write(good.replace('"suite": "toy"', '"suite": "nosuch"'))
    with pytest.raises(KeyError, match="nosuch"):
        harness.resolve(TOY_CELL, bench, bdir)
    open(cfg, "w").write(good)
    lacking = open(os.path.join(TOY, "toy.py")).read().replace("def render(", "def _render(")
    (tmp_path / "benchmark" / "suites" / "toy.py").write_text(lacking)
    with pytest.raises(AttributeError, match="'toy'.* lacks render"):
        harness.resolve(TOY_CELL, bench, bdir)


def test_measure_and_judge_over_a_suite_added_as_files(tmp_path):
    """A rehearsal of the toy cell at --sf 0.02 through ``measure`` and
    ``judge``, the served system stood in for by the suite's reference."""
    bench, bdir, before = _checkout(tmp_path)
    resolved = harness.resolve(TOY_CELL, bench, bdir)
    toy = resolved["suite"]
    data_dir = str(tmp_path / "data")
    info = suites.generate(data_dir, toy, ["x"], toy.scale(resolved["config"], 0.02), 7, 4, procs=2)
    measured = run.measure(FakeServed(data_dir, suite=toy), resolved, info, 7, 1.0, False)
    verdict = run.judge(measured, data_dir)
    assert verdict["correct"] and verdict["compared"] == len(measured["records"]) >= 2
    assert not any(r.get("wrong_route") for r in measured["records"])
    assert measured["rows_of_kind"] == {1: 20000, "sum_v3": 20000}
    assert [r["kind"] for r in measured["warmup"]] == [1, 1, "sum_v3", "sum_v3"]  # two sets a kind
    swapped = FakeServed(data_dir, suite=toy, fault="swapped")  # two rows the wrong way round
    bad = run.judge(run.measure(swapped, resolved, info, 7, 1.0, False), data_dir)
    assert not bad["correct"] and bad["numbers"]["rows_out_of_order"]["value"] >= 1
    _unchanged(before)


def test_run_py_over_a_suite_added_as_files(tmp_path):
    """The whole command over the toy cell, ``--platform cpu --sf 0.02``: a
    real scheduler, executor and client answer the toy suite's texts."""
    bench, _, before = _checkout(tmp_path)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    os.symlink(os.path.join(harness.REPO, "arrow_ballista_tpu"), tmp_path / "arrow_ballista_tpu")
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "PYTHONPATH")}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", TOY_CELL, "--seed", str(2**31 + 43),
         "--seconds", "4", "--trace", "0", "--platform", "cpu", "--sf", "0.02"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["window"]["latencies_s"]) == {"toy_1", "toy_sum_v3"}
    assert set(out["metrics"]) == {"setup_s", "query_geomean_s", "scan_rows_rate"}
    _unchanged(before)
