"""The exchange program moves a row once: it packs the columns into 32-bit
words (a flags word for the bools), sorts the ``[W, rows]`` word matrix by
(device, slot) with one two-operand sort, slices each device's run and
sends it with one ``all_to_all``, then unpacks.  Held here against the
program it replaced, one scatter and one ``all_to_all`` a column
(``benchmarks/exchange_program.py`` keeps it as the oracle): on meshes of 1, 4
and 8 devices, in x32 and x64, every output equal bit for bit (NaN
payloads, -0.0, infinities, int64 extremes, nulls, string codes, narrow
ints), on the grouped route and the ``__part`` route, at an exact fill, an
overflow and a capacity growth.  The lowered program holds no scatter, one
sort of two operands and at most one ``all-to-all`` whatever the column
count (the TPU compiler's time grows with a sort's operands); the
exchange's byte counters and ``exchange_row_words`` read what they
should."""

import datetime
import re

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu import BallistaConfig
from arrow_ballista_tpu.catalog import MemoryTable
from arrow_ballista_tpu.exec import expressions as pe
from arrow_ballista_tpu.exec.operators import Partitioning, ScanExec, TaskContext
from arrow_ballista_tpu.obs import trace
from arrow_ballista_tpu.obs.recorder import get_recorder
from arrow_ballista_tpu.ops import kernels as K
from arrow_ballista_tpu.parallel import mesh as M
from arrow_ballista_tpu.parallel.mesh_stage import MeshExchangeError, MeshRepartitionExec
from benchmarks.exchange_program import main as exchange_bench
from benchmarks.exchange_program import per_column_exchange


@pytest.fixture
def mode(request):
    K.set_precision(request.param)
    yield request.param
    K.set_precision(None)


def _batch(n, seed):
    """Nullable int, float, date, string and narrow-int fields; f64 and f32
    with NaN, -0.0 and both infinities; int64 at its extremes."""
    rng = np.random.default_rng(seed)
    nulls = lambda: rng.random(n) < 0.15  # noqa: E731
    special = np.array([np.nan, -0.0, np.inf, -np.inf, 0.0, 1.5e308, -2.5e-308])
    f64 = np.where(rng.random(n) < 0.3, special[rng.integers(0, len(special), n)], rng.normal(size=n))
    f32 = np.where(rng.random(n) < 0.3, special[rng.integers(0, 5, n)], rng.normal(size=n)).astype(np.float32)
    extremes = np.array([np.iinfo(np.int64).min, np.iinfo(np.int64).max, -1, 0, 1 << 32, -(1 << 31)])
    big = np.where(rng.random(n) < 0.4, extremes[rng.integers(0, len(extremes), n)],
                   rng.integers(-(2**62), 2**62, n))
    words = np.array(["alpha", "beta", "gamma", "delta", ""], dtype=object)
    return pa.record_batch(
        [
            pa.array(rng.integers(-(2**31), 2**31 - 1, n, dtype=np.int32), mask=nulls()),
            pa.array(f64, mask=nulls()),
            pa.array(f32),
            pa.array([datetime.date(1992, 1, 1) + datetime.timedelta(days=int(d))
                      for d in rng.integers(0, 2500, n)], pa.date32(), mask=nulls()),
            pa.array(list(words[rng.integers(0, len(words), n)]), pa.string(), mask=nulls()),
            pa.array(big, pa.int64()),
            pa.array(rng.integers(-128, 128, n, dtype=np.int8), mask=nulls()),
            pa.array(rng.integers(-(2**15), 2**15, n, dtype=np.int16)),
            pa.array(rng.random(n) < 0.5, mask=nulls()),
        ],
        names=["i", "f", "g", "d", "s", "big", "tiny", "short", "b"],
    )


def _inputs(n_dev, route, seed, n=1500):
    """(part, valid, cols, n_out) for the program, padded to a multiple of
    the mesh with invalid rows as the exchange pads.  ``grouped``: a row's
    part is its output partition, ``n_out`` not the mesh width;
    ``part_route``: the program routes by device and the partition rides
    along with a validity column, as ``__part`` does."""
    rng = np.random.default_rng(seed)
    batch = _batch(n, seed)
    layout = M.ExchangeLayout(batch.schema)
    cols = layout.flatten(batch, layout.encoders)
    valid = rng.random(n) < 0.9
    if route == "grouped":
        n_out = {1: 2, 4: 3, 8: 5}[n_dev]
        part = rng.integers(0, n_out, n).astype(np.int32)
    else:
        n_out = n_dev
        out_part = rng.integers(0, 7, n).astype(np.int32)
        part = (out_part % n_dev).astype(np.int32)
        cols += [out_part, np.ones(n, bool)]
    rows = -(-n // n_dev) * n_dev + n_dev  # at least one pad row a shard
    return M.pad_batch(M.make_mesh(n_dev), [part, valid] + cols, rows), n_out


def _need(part, valid, n_dev, n_out):
    """The fullest (source shard, destination device) run."""
    per = len(part) // n_dev
    dest = part % n_dev
    return max(
        int(np.sum(valid[s * per:(s + 1) * per] & (dest[s * per:(s + 1) * per] == d)))
        for s in range(n_dev) for d in range(n_dev)
    )


def _same_bits(got, want):
    assert len(got) == len(want)
    for k, (g, w) in enumerate(zip(got, want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, k
        assert np.array_equal(g.reshape(-1).view(np.uint8), w.reshape(-1).view(np.uint8)), k


def _both(mesh, arrays, capacity, n_out):
    n_cols = len(arrays) - 2
    placed = M.shard_batch(mesh, arrays)
    got = M.ici_batch_exchange(mesh, n_cols, capacity, n_out)(*placed)
    want = per_column_exchange(mesh, n_cols, capacity, n_out)(*placed)
    return got, want


@pytest.mark.parametrize("fill", ["slack", "exact", "overflow", "growth"])
@pytest.mark.parametrize("route", ["grouped", "part_route"])
@pytest.mark.parametrize("mode", ["x32", "x64"], indirect=True)
@pytest.mark.parametrize("n_dev", [1, 4, 8])
def test_the_packed_program_returns_the_per_column_programs_bits(n_dev, mode, route, fill):
    arrays, n_out = _inputs(n_dev, route, seed=n_dev * 10 + len(fill))
    mesh = M.make_mesh(n_dev)
    part, valid = arrays[0], arrays[1]
    need = _need(part, valid, n_dev, n_out)
    if fill == "growth":
        # the exchange's contract: double until nothing is dropped
        cap, calls = 8, 0
        while True:
            got, want = _both(mesh, arrays, cap, n_out)
            _same_bits(got, want)
            calls += 1
            if int(got[-1]) == 0:
                break
            cap *= 2
        assert calls > 1 and cap >= need
        return
    cap = {"slack": K.bucket_rows(need, floor=1), "exact": need, "overflow": need - 3}[fill]
    got, want = _both(mesh, arrays, cap, n_out)
    _same_bits(got, want)
    dropped = int(got[-1])
    if fill == "overflow":
        # the rows past capacity in every full run, summed over the mesh
        per = len(part) // n_dev
        dest = part % n_dev
        expect = sum(
            max(0, int(np.sum(valid[s * per:(s + 1) * per] & (dest[s * per:(s + 1) * per] == d))) - cap)
            for s in range(n_dev) for d in range(n_dev)
        )
        assert dropped == expect > 0
    else:
        assert dropped == 0
        # every valid row delivered, the fullest run to its last slot
        assert int(np.asarray(got[-2]).sum()) == int(valid.sum())


def _columns(n_cols):
    """``n_cols`` payload columns of mixed widths: a third bools."""
    kinds = [np.int32, np.float32, np.bool_]
    return [np.zeros(64, kinds[k % 3]) for k in range(n_cols)]


@pytest.mark.parametrize("n_cols", [3, 11, 20])
def test_the_program_moves_a_row_with_one_sort_and_one_all_to_all(n_cols):
    mesh = M.make_mesh(8)
    arrays = [np.zeros(64, np.int32), np.ones(64, bool)] + _columns(n_cols)
    placed = M.shard_batch(mesh, arrays)

    def count(fn):
        text = fn.lower(*placed).as_text(dialect="hlo")
        return text, tuple(
            len(re.findall(rf" {op}\(", text)) for op in ("scatter", "sort", "all-to-all")
        )

    # the oracle scatters and sends a column at a time: the count sees it
    _, ops = count(per_column_exchange(mesh, n_cols, 16, 8))
    assert ops == (n_cols + 1, 0, n_cols + 1)
    text, (scatters, sorts, sends) = count(M.ici_batch_exchange(mesh, n_cols, 16, 8))
    assert scatters == 0 and sorts == 1 and sends <= 1
    # the key and the word matrix, the key an i32 whatever the x64 flag
    (sort,) = [line for line in text.splitlines() if " sort(" in line]
    assert re.search(r"= \(s32\[\d+,\d+\]\S*, u32\[\d+,\d+\]\S*\) sort\([^,()]+, [^,()]+\)", sort), sort


@pytest.mark.parametrize(
    "dtypes, words",
    [
        ([np.int32] * 7 + [np.bool_] * 4, 8),  # q3's lineitem exchange in x32
        ([np.int64, np.float64, np.int32, np.bool_], 6),
        ([np.int8, np.int16, np.float32], 4),
        ([np.bool_] * 31, 1),
        ([np.bool_] * 32, 2),
        ([], 1),
    ],
)
def test_row_words_count_a_word_a_column_two_a_wide_one_and_the_flag_words(dtypes, words):
    assert M.exchange_row_words(dtypes) == words


LINEITEM = [("l_orderkey", pa.int64()), ("l_extendedprice", pa.float64()),
            ("l_discount", pa.float64()), ("l_shipdate", pa.date32()), ("l_shipmode", pa.string())]


def _lineitem(n=5000):
    rng = np.random.default_rng(40)
    modes = np.array(["AIR", "MAIL", "SHIP", "RAIL"], dtype=object)
    return pa.record_batch(
        [
            pa.array(rng.integers(1, 6_000_000, n)),
            pa.array(rng.uniform(900, 105_000, n)),
            pa.array(rng.integers(0, 11, n) / 100),
            pa.array(rng.integers(8000, 10_500, n).astype(np.int32)).cast(pa.date32()),
            pa.array(list(modes[rng.integers(0, 4, n)])),
        ],
        schema=pa.schema(LINEITEM),
    )


@pytest.mark.parametrize("mode", ["x32", "x64"], indirect=True)
@pytest.mark.parametrize("n_dev", [1, 4])
def test_the_counters_read_the_bytes_and_the_words_of_a_fixed_input(mode, n_dev):
    batch = _lineitem()
    node = MeshRepartitionExec(
        ScanExec("lineitem", MemoryTable([[batch]], batch.schema), None),
        Partitioning("hash", 3, (pe.Col(0, "l_orderkey"),)),
        n_dev,
    )
    trace.configure(enabled=True, process="local")
    try:
        get_recorder().drain()
        trace_id = trace.new_id()
        with trace.root_span("job", trace_id), trace.span("task.execute"):
            out = list(node.execute_exchanged(TaskContext(config=BallistaConfig({}))))
    finally:
        trace.configure(enabled=False)
    m = node.metrics.to_dict()
    assert sum(b.num_rows for _, b in out) == m["mesh_exchange_rows"] == 5000
    # a row's payload, in x32 and x64 alike: 32 bytes (the key, the two
    # prices and the date in 28, the mode's code in 4) and five validities;
    # in the program eight data words and one flags word (the five
    # validities and the delivered bit)
    assert m["exchange_row_words"] == 9
    # handed over: 8,192 padded rows, each its i32 partition, its valid
    # flag and the payload
    assert M.exchange_rows(5000, n_dev) == 8192
    assert m["mesh_exchange_bytes"] == 8192 * (4 + 1 + 32 + 5)
    # handed back: devices x capacity slots, each the payload and the
    # delivered flag (the capacity the fullest run's bucket)
    assert m["mesh_exchange_recv_bytes"] == {1: 8192, 4: 4 * 4096}[n_dev] * (32 + 5 + 1)
    (program,) = [s for s in get_recorder().drain()
                  if s["trace"] == trace_id and s["name"] == "exchange.program"]
    assert program["attrs"]["row_words"] == m["exchange_row_words"]
    assert m["exchange_grouped"] == 1


def test_an_exchange_past_the_programs_slot_key_falls_back(monkeypatch):
    # the program keys a slot as an i32: an exchange whose devices x
    # capacity reach that raises the error the writer falls back on
    batch = _lineitem()
    node = MeshRepartitionExec(
        ScanExec("lineitem", MemoryTable([[batch]], batch.schema), None),
        Partitioning("hash", 3, (pe.Col(0, "l_orderkey"),)),
        1,
    )
    monkeypatch.setattr(M, "EXCHANGE_MAX_SLOTS", 8192)
    with pytest.raises(MeshExchangeError, match="slots"):
        list(node.execute_exchanged(TaskContext(config=BallistaConfig({}))))


@pytest.mark.parametrize("n_dev", [1, 4])
def test_the_microbenchmark_runs_both_programs_on_one_input(n_dev, capsys):
    res = exchange_bench(["--devices", str(n_dev), "--rows", "4096", "--valid", "3000",
                          "--n-out", str(2 * n_dev), "--reps", "1"])
    assert res["equal"] and res["row_words"] == 8
    assert res["packed"]["n_dropped"] == res["per_column"]["n_dropped"] == 0
    assert '"equal": true' in capsys.readouterr().out
