"""The exchange places each row in its output partition's run (PR 35): the
program ranks a row within its output partition and puts it at the
partition's offset in the (source shard, device) run, so the host slices
every partition out of the received buffers at the bucket counts it made
before the call.  Held here: the ``(out_p, batch)`` sequence is, batch for
batch, what the mask + stable argsort + take decode it replaced gives, at
1, 2, 4 and 8 devices, 1 to 8 output partitions and one width past the rule
where the program still routes by device, in both precisions; empty
partitions, a shard with none of a partition's rows, pad rows, an input of
nothing and a capacity growth; ``exchange_grouped`` says which way it ran."""

import datetime

import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu import BallistaConfig
from arrow_ballista_tpu.catalog import MemoryTable
from arrow_ballista_tpu.exec import expressions as pe
from arrow_ballista_tpu.exec.operators import Partitioning, ScanExec, TaskContext
from arrow_ballista_tpu.ops import kernels as K
from arrow_ballista_tpu.parallel import mesh as M
from arrow_ballista_tpu.parallel.mesh_stage import MeshRepartitionExec

SCHEMA = pa.schema([
    ("k", pa.int64()), ("v", pa.float64()), ("d", pa.date32()),
    ("n", pa.int64()), ("s", pa.string()), ("b", pa.bool_()),
])


def _batch(rng, n, shift=0):
    day0 = datetime.date(1995, 1, 1)
    words = np.array(["delta", "alpha", None, "charlie", "bravo", "echo"], dtype=object)
    nullable = rng.integers(-5, 5, n).astype(object)
    nullable[rng.random(n) < 0.2] = None
    return pa.RecordBatch.from_arrays(
        [
            pa.array(rng.integers(-(1 << 40), 1 << 40, n), pa.int64()),
            pa.array(rng.normal(size=n) * 1e9, pa.float64()),
            pa.array([day0 + datetime.timedelta(days=int(x)) for x in rng.integers(0, 900, n)], pa.date32()),
            pa.array(nullable.tolist(), pa.int64()),
            pa.array(words[(rng.integers(0, 4, n) + shift) % len(words)].tolist(), pa.string()),
            pa.array(rng.random(n) < 0.5),
        ],
        schema=SCHEMA,
    )


def _partitions(seed=35):
    """Five input partitions: several batches, none, only an empty one."""
    rng = np.random.default_rng(seed)
    return [
        [_batch(rng, 300), _batch(rng, 1, 3), _batch(rng, 700, 1)],
        [],
        [_batch(rng, 0)],
        [_batch(rng, 513, 4)],
        [_batch(rng, 90, 5)],
    ]


def _row_bytes() -> int:
    """Bytes a row takes in the grouped program's inputs: its partition
    (4), its validity (1) and the layout's columns."""
    layout = M.ExchangeLayout(SCHEMA)
    cols = layout.flatten(_batch(np.random.default_rng(0), 1), layout.new_encoders())
    return 4 + 1 + sum(c.itemsize for c in cols)


def _node(n_dev, n_out, partitions=None):
    table = MemoryTable(_partitions() if partitions is None else partitions, SCHEMA)
    node = MeshRepartitionExec(
        ScanExec("t", table, None), Partitioning("hash", n_out, (pe.Col(0, "k"),)), n_dev
    )
    return node, TaskContext(config=BallistaConfig({}))


def _masked_sorted_decode(node, tctx):
    """The decode this PR replaced, kept as the reference: the partition
    rides as a payload column, the program routes by device, every slot is
    masked with ``recv_valid``, then a stable argsort by partition and a
    take over each device's rows."""
    n_out, n_dev = node.partitioning.n, node.n_devices
    layout = M.ExchangeLayout(node.input.schema)
    prepared, _ = node._prepare_partitions(tctx, layout)
    if not prepared:
        return []
    cols = [np.concatenate(one) for one in zip(*prepared)]
    part = cols[-1]
    cols.append(np.ones(len(part), bool))
    ext = pa.schema(list(node.input.schema) + [pa.field("__part", pa.int32())])
    dest = (part % n_dev).astype(np.int32)
    total = len(dest)
    shard = np.arange(total) // (M.exchange_rows(total, n_dev) // n_dev)
    need = int(np.bincount(shard * n_dev + dest, minlength=n_dev * n_dev).max())
    ex = M.BatchExchanger(M.make_mesh(n_dev), ext, K.bucket_rows(need, floor=1), share_from=layout)
    recv_cols, recv_valid, dropped = ex.exchange(dest, np.ones(total, bool), cols)
    assert dropped == 0
    out, last = [], len(ext) - 1
    for recv in ex.to_batches(recv_cols, recv_valid):
        if recv.num_rows == 0:
            continue
        parts = np.asarray(recv.column(last))
        order = np.argsort(parts, kind="stable")
        shuffled = recv.select(range(last)).take(pa.array(order))
        bounds = np.searchsorted(parts[order], np.arange(n_out + 1)).tolist()
        out += [(q, shuffled.slice(bounds[q], bounds[q + 1] - bounds[q]))
                for q in range(n_out) if bounds[q + 1] > bounds[q]]
    return out


def _same_sequence(got, want):
    assert [p for p, _ in got] == [p for p, _ in want]
    for (_, g), (_, w) in zip(got, want):
        assert g.schema.equals(w.schema) and g.equals(w)


@pytest.fixture(params=["x64", "x32"])
def precision(request):
    K.set_precision(request.param)
    yield request.param
    K.set_precision(None)


# ------------------------------ the same (out_p, batch) sequence, each way
@pytest.mark.parametrize("n_out", [1, 2, 3, 8, "past"])
@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_sequence_equals_the_masked_sorted_decode(precision, n_dev, n_out):
    rule = _row_bytes() // 4  # the widest one-hot that costs no more than a row
    assert rule >= 8
    n_out = rule + 1 if n_out == "past" else n_out
    want = _masked_sorted_decode(*_node(n_dev, n_out))
    assert sum(b.num_rows for _, b in want) == 1604
    node, tctx = _node(n_dev, n_out)
    got = list(node.execute_exchanged(tctx))
    _same_sequence(got, want)
    m = node.metrics.to_dict()
    assert m["exchange_grouped"] == int(n_out <= rule)
    assert m["mesh_exchange_rows"] == 1604 and m["mesh_devices"] == n_dev
    # what the program is handed: the partition, the validity and the
    # columns a row, and the partition's value and validity again where it
    # rides as a payload
    sent = M.exchange_rows(1604, n_dev)
    assert m["mesh_exchange_bytes"] == sent * (_row_bytes() + (0 if n_out <= rule else 5))


def test_the_rule_engages_at_its_edge_and_not_past_it():
    rule = _row_bytes() // 4
    for n_out, grouped in ((rule, 1), (rule + 1, 0)):
        node, tctx = _node(2, n_out)
        list(node.execute_exchanged(tctx))
        assert node.metrics.to_dict()["exchange_grouped"] == grouped


# ------------------------------------------ the layout at the program itself
def _by_partition(batch, part, n_out, n_dev):
    """The definition: each partition's rows in row order, devices ascending
    and then their partitions ascending."""
    order = sorted(range(n_out), key=lambda p: (p % n_dev, p))
    return [(p, batch.take(pa.array(np.flatnonzero(part == p))))
            for p in order if (part == p).any()]


def _counts(part, n_dev, n_out):
    per = M.exchange_rows(len(part), n_dev) // n_dev
    return np.stack([np.bincount(part[s * per:(s + 1) * per], minlength=n_out) for s in range(n_dev)])


CASES = {
    # every row in one partition: the others are empty everywhere
    "one_partition": (4, 5, lambda n, rng: np.full(n, 3, np.int32)),
    # the first shard holds none of partition 1's rows, the last none of 0's
    "shard_without_a_partition": (
        4, 3, lambda n, rng: np.where(np.arange(n) < n // 3, 0, np.where(np.arange(n) > 2 * n // 3, 1, 2)).astype(np.int32),
    ),
    # more partitions than rows land in: most are empty
    "few_rows": (2, 8, lambda n, rng: rng.integers(0, 8, n).astype(np.int32)),
    # one device: every partition a zero-copy slice of one run
    "one_device": (1, 3, lambda n, rng: rng.integers(0, 3, n).astype(np.int32)),
    # fewer partitions than devices: half the devices receive nothing
    "fewer_partitions_than_devices": (8, 3, lambda n, rng: rng.integers(0, 3, n).astype(np.int32)),
}


@pytest.mark.parametrize("rows", [5, 1025])  # 1025 goes up as 2048: pad rows in the last shards
@pytest.mark.parametrize("case", sorted(CASES))
def test_partition_batches_slice_each_partition_in_row_order(case, rows):
    n_dev, n_out, make = CASES[case]
    batch = _batch(np.random.default_rng(rows), rows)
    part = make(rows, np.random.default_rng(7))
    counts = _counts(part, n_dev, n_out)
    cap = K.bucket_rows(int(max(counts[:, d::n_dev].sum(axis=1).max() for d in range(min(n_dev, n_out)))), floor=1)
    ex = M.BatchExchanger(M.make_mesh(n_dev), SCHEMA, cap, n_out=n_out)
    recv_cols, recv_valid, dropped = ex.exchange(part, np.ones(rows, bool), ex.to_columns(batch))
    # the pad rows (zeros: partition 0) arrive nowhere
    assert dropped == 0 and int(recv_valid.sum()) == rows
    _same_sequence(list(ex.partition_batches(recv_cols, counts)), _by_partition(batch, part, n_out, n_dev))


def test_device_ids_as_partitions_keep_the_run_of_row_order():
    """A caller that passes device ids (n_out = n_dev, the default) gets a
    device's rows in row order, as before: ``to_batches`` and
    ``partition_batches`` agree."""
    n = 999
    batch = _batch(np.random.default_rng(3), n)
    dest = np.random.default_rng(4).integers(0, 4, n).astype(np.int32)
    ex = M.BatchExchanger(M.make_mesh(4), SCHEMA, 1024)
    recv_cols, recv_valid, _ = ex.exchange(dest, np.ones(n, bool), ex.to_columns(batch))
    masked = ex.to_batches(recv_cols, recv_valid)
    sliced = dict(ex.partition_batches(recv_cols, _counts(dest, 4, 4)))
    for d in range(4):
        assert masked[d].equals(sliced[d]) and masked[d].equals(batch.take(pa.array(np.flatnonzero(dest == d))))


# --------------------------------------------------------- nothing, growth
@pytest.mark.parametrize("n_dev", [1, 8])
def test_an_input_of_nothing_exchanges_nothing(n_dev):
    node, tctx = _node(n_dev, 2, partitions=[[], [_batch(np.random.default_rng(1), 0)]])
    assert list(node.execute_exchanged(tctx)) == []
    assert "exchange_grouped" not in node.metrics.to_dict()


@pytest.mark.parametrize("n_dev,n_out", [(1, 2), (4, 3)])
def test_a_capacity_growth_still_slices_every_partition(monkeypatch, n_dev, n_out):
    """A first capacity that is too small (the need reckoned as one row)
    drops rows; the stage doubles it until none is left, and the slices
    follow the final capacity."""
    want = _masked_sorted_decode(*_node(n_dev, n_out))
    real = K.bucket_rows
    monkeypatch.setattr(K, "bucket_rows", lambda n, floor=1024: real(1 if floor == 1 else n, floor))
    node, tctx = _node(n_dev, n_out)
    got = list(node.execute_exchanged(tctx))
    monkeypatch.undo()
    m = node.metrics.to_dict()
    assert m["capacity_growths"] >= 1 and m["exchange_grouped"] == 1
    _same_sequence(got, want)
