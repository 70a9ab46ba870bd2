"""Typed multi-column ICI exchange (VERDICT.md round-1 item 4).

Roundtrip: random multi-column RecordBatch → on-mesh all_to_all exchange →
reassembled per-destination RecordBatches must equal a host-computed
repartition of the same rows.
"""

import datetime

import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu.ops import kernels as K
from arrow_ballista_tpu.parallel import mesh as M

N_DEV = 8


def _random_batch(n, seed=0):
    rng = np.random.default_rng(seed)
    names = ["f", "i", "s", "b", "d", "big"]
    f = rng.normal(size=n)
    i = rng.integers(-1000, 1000, n).astype(np.int32)
    s = np.array(["alpha", "beta", "gamma", None, "delta"], dtype=object)[
        rng.integers(0, 5, n)
    ]
    b = rng.integers(0, 2, n).astype(bool)
    d = [datetime.date(2020, 1, 1) + datetime.timedelta(days=int(x))
         for x in rng.integers(0, 1000, n)]
    big = rng.integers(-(2**62), 2**62, n)
    fv = pa.array(np.where(rng.random(n) < 0.1, np.nan, f))
    fv = pa.array(f, mask=rng.random(n) < 0.1)
    return pa.record_batch(
        [
            fv,
            pa.array(i, pa.int32()),
            pa.array(list(s), pa.string()),
            pa.array(b),
            pa.array(d, pa.date32()),
            pa.array(big, pa.int64()),
        ],
        names=names,
    )


def _host_repartition(batch, dest, n_dev):
    tables = []
    for d in range(n_dev):
        idx = np.nonzero(dest == d)[0]
        tables.append(batch.take(pa.array(idx)))
    return tables


@pytest.mark.parametrize("mode", ["x64", "x32"])
def test_batch_exchange_roundtrip(mode):
    K.set_precision(mode)
    try:
        mesh = M.make_mesh(N_DEV)
        n = N_DEV * 300  # not a multiple of capacity, not pow2
        batch = _random_batch(n, seed=3)
        rng = np.random.default_rng(7)
        dest = (rng.integers(0, 1 << 30, n) % N_DEV).astype(np.int32)

        ex = M.BatchExchanger(mesh, batch.schema, capacity=1024)
        cols = ex.to_columns(batch)
        recv_cols, recv_valid, n_dropped = ex.exchange(
            dest, np.ones(n, bool), cols
        )
        assert n_dropped == 0
        got = ex.to_batches(recv_cols, recv_valid)

        want = _host_repartition(batch, dest, N_DEV)
        total = 0
        for d in range(N_DEV):
            g, w = got[d], want[d]
            total += g.num_rows
            assert g.num_rows == w.num_rows, f"device {d}"
            # exchange preserves multisets per destination; sort to compare
            gs = pa.table([*g.columns], names=g.schema.names).sort_by(
                [("i", "ascending"), ("big", "ascending")]
            )
            ws = pa.table([*w.columns], names=w.schema.names).sort_by(
                [("i", "ascending"), ("big", "ascending")]
            )
            for name in g.schema.names:
                gl, wl = gs.column(name).to_pylist(), ws.column(name).to_pylist()
                if name == "f":
                    for x, y in zip(gl, wl):
                        if x is None or y is None:
                            assert x == y
                        else:
                            assert y == pytest.approx(x, rel=1e-6)
                else:
                    assert gl == wl, name
        assert total == n
    finally:
        K.set_precision(None)


def test_batch_exchange_overflow_reported():
    K.set_precision("x64")
    try:
        mesh = M.make_mesh(N_DEV)
        n = N_DEV * 64
        batch = _random_batch(n, seed=5)
        dest = np.zeros(n, dtype=np.int32)  # everything to device 0
        ex = M.BatchExchanger(mesh, batch.schema, capacity=16)
        cols = ex.to_columns(batch)
        _, recv_valid, n_dropped = ex.exchange(dest, np.ones(n, bool), cols)
        # the input goes up padded to its bucket, so the n rows fill the
        # first shards whole: each of those holds more rows for dest 0
        # than the capacity of 16
        per_shard = M.exchange_rows(n, N_DEV) // N_DEV
        sources = -(-n // per_shard)
        assert per_shard > 16 and n_dropped == n - sources * 16
        assert int(recv_valid.sum()) == sources * 16
    finally:
        K.set_precision(None)
