"""The gang stage prepares its partitions side by side (PR 29): a partition
is scanned, key-encoded and converted once, against encoders and a group
table of its own, by a small pool of workers; the task thread merges the
partitions' dictionaries and groups in partition order.  Held here: the
merge assigns what one encoder fed every row in order assigns; the answers
do not depend on the pool's width; an error or a cancel ends the stage with
every worker joined; the route probe still leaves on the first batch."""

import datetime
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu import BallistaConfig, SessionContext
from arrow_ballista_tpu.catalog import MemoryTable
from arrow_ballista_tpu.errors import Cancelled
from arrow_ballista_tpu.exec.operators import TaskContext
from arrow_ballista_tpu.ops.bridge import (
    DictEncoder, make_key_encoder, merge_key_codes,
)
from arrow_ballista_tpu.ops.groups import GroupTable
from arrow_ballista_tpu.parallel import mesh_stage
from arrow_ballista_tpu.parallel.mesh_stage import MeshGangExec


def _cfg(**extra):
    settings = {"ballista.tpu.min_rows": "0", "ballista.shuffle.partitions": "2"}
    settings.update({k: str(v) for k, v in extra.items()})
    return BallistaConfig(settings)


def _find(plan, cls):
    out, stack = [], [plan]
    while stack:
        n = stack.pop()
        if isinstance(n, cls):
            out.append(n)
        stack.extend(n.children())
    return out


def _force_width(monkeypatch, width):
    """The width comes from the cores the process can see: narrow those."""
    monkeypatch.setattr(mesh_stage, "_usable_cores", lambda: width)


def _gang_threads():
    return [t for t in threading.enumerate() if t.name.startswith("gang")]


# ------------------------------------------------ (a) merge == sequential
def _d(day):
    return datetime.date(1995, 1, 1) + datetime.timedelta(days=day)


# name -> (key types, partitions; a partition is a list of batches; a batch
# is one list of values a key column)
MERGE_CASES = {
    "one_string_key": (
        [pa.string()],
        [[[["b", "a", "b"]], [["c", "a"]]], [[["a", "d", "b"]]]],
    ),
    "two_string_keys_q1_shape": (
        [pa.string(), pa.string()],
        [
            [[["A", "N", "R", "N"], ["F", "O", "F", "F"]]],
            [[["N", "N", "A"], ["O", "F", "F"]], [["R", "A"], ["F", "F"]]],
            [[["R", "N"], ["F", "O"]]],
        ],
    ),
    "string_int_date_keys": (
        [pa.string(), pa.int64(), pa.date32()],
        [
            [[["x", "y", "x"], [7, 7, 9], [_d(1), _d(2), _d(1)]]],
            [[["y", "x"], [7, 9], [_d(2), _d(3)]], [["z"], [0], [_d(0)]]],
        ],
    ),
    "bool_and_string_keys": (
        [pa.bool_(), pa.string()],
        [
            [[[True, False, True], ["p", "p", "q"]]],
            [[[False, False], ["q", "p"]], [[True], ["p"]]],
        ],
    ),
    "null_keys": (
        [pa.string(), pa.int32()],
        [
            [[["a", None, "b"], [1, 2, None]], [[None, "a"], [2, None]]],
            [[["c", None, None], [None, None, 2]]],
            [[[None], [5]]],
        ],
    ),
    "value_first_seen_in_a_late_partition": (
        [pa.string()],
        [[[["a", "a"]]], [[["a"]], [["a", "a"]]], [[["a", "late", "a"]]], [[["late", "a"]]]],
    ),
    "empty_partitions": (
        [pa.string(), pa.int64()],
        [[], [[["u", "v"], [1, 2]]], [], [[["v", "w"], [2, 3]], [["u"], [1]]], []],
    ),
    "later_partition_reverses_the_first_appearance_order": (
        [pa.large_string()],
        [[[["a", "b", "c", "d"]]], [[["d", "c", "b", "a"]]], [[["e", "d", "a"]]]],
    ),
}


def _columns(types, batch):
    return [pa.array(vals, t) for vals, t in zip(batch, types)]


def _coalesced(types, batches):
    return [
        pa.concat_arrays([pa.array(b[k], t) for b in batches])
        for k, t in enumerate(types)
    ]


def _fed_in_order(types, pieces):
    """One set of encoders and one table fed ``pieces`` (lists of key
    columns) one after the other: what the sequential loop assigns."""
    encoders = [make_key_encoder(t) for t in types]
    table = GroupTable(len(types))
    segs = [
        table.encode([e.encode(c) for e, c in zip(encoders, cols)]) for cols in pieces
    ]
    return encoders, table, np.concatenate(segs) if segs else np.empty(0, np.int32)


def _decoded_groups(types, encoders, table):
    gids = np.arange(table.n_groups)
    return [
        enc.decode(table.codes_for(gids, k), t).to_pylist()
        for k, (enc, t) in enumerate(zip(encoders, types))
    ]


@pytest.mark.parametrize("case", sorted(MERGE_CASES))
def test_partitions_merged_in_order_equal_one_encoder_fed_in_order(case):
    types, partitions = MERGE_CASES[case]
    stage_encoders = [make_key_encoder(t) for t in types]
    stage_table = GroupTable(len(types))
    merged = []
    for batches in partitions:
        if not batches:
            continue
        # the worker: encoders and a table of the partition's own
        local_encoders, local_table, local_seg = _fed_in_order(
            types, [_coalesced(types, batches)]
        )
        # the task thread: dictionaries, then groups, then one gather
        maps = [merge_key_codes(g, l) for g, l in zip(stage_encoders, local_encoders)]
        remap = stage_table.encode(local_table.key_columns(maps))
        assert remap.dtype == np.int32 and len(remap) == local_table.n_groups
        merged.append(remap[local_seg])
    merged = np.concatenate(merged)

    # fed partition by partition: the very same codes, key_mat and ids
    encoders, table, seg = _fed_in_order(
        types, [_coalesced(types, b) for b in partitions if b]
    )
    assert np.array_equal(merged, seg) and merged.dtype == seg.dtype
    assert np.array_equal(stage_table.key_mat, table.key_mat)
    for got, want, t in zip(stage_encoders, encoders, types):
        if isinstance(want, DictEncoder):
            assert got.to_arrow(t).equals(want.to_arrow(t))
    # fed batch by batch, as the loop before PR 29 did: the same ids and
    # the same group behind each id (only a NULL key's slot in a dictionary
    # may sit elsewhere: it is appended by the batch that first holds one)
    encoders_b, table_b, seg_b = _fed_in_order(
        types, [_columns(types, b) for batches in partitions for b in batches]
    )
    assert np.array_equal(merged, seg_b)
    assert _decoded_groups(types, stage_encoders, stage_table) == _decoded_groups(
        types, encoders_b, table_b
    )


def test_merging_an_encoder_that_saw_nothing_changes_nothing():
    stage, seen, empty = DictEncoder(), DictEncoder(), DictEncoder()
    seen.encode(pa.array(["a", "b"]))
    assert len(stage.merge(empty)) == 0 and stage.size == 0
    assert stage.merge(seen).tolist() == [0, 1]
    assert len(stage.merge(empty)) == 0 and stage.size == 2
    assert merge_key_codes(make_key_encoder(pa.int64()), make_key_encoder(pa.int64())) is None


# ------------------------------------------------------ the width, the pool
@pytest.mark.parametrize("cores,slots,n_parts,want", [
    (13, 4, 12, 6), (30, 4, 12, 6), (8, 1, 12, 6), (8, 1, 3, 3), (2, 4, 12, 1),
    (1, 1, 12, 1), (64, 1, 0, 1), (4, 1, 12, 4), (5, 4, 12, 2), (8, 4, 12, 5),
])
def test_width_follows_cores_slots_and_partitions(monkeypatch, cores, slots, n_parts, want):
    monkeypatch.setattr(mesh_stage, "_usable_cores", lambda: cores)
    ctx = TaskContext(config=_cfg(), task_slots=slots)
    assert mesh_stage._gang_width(ctx, n_parts) == want


def test_usable_cores_reads_the_affinity_mask():
    import os

    assert mesh_stage._usable_cores() == len(os.sched_getaffinity(0)) >= 1


@pytest.mark.parametrize("width", [1, 2, 4, 16])
def test_pool_hands_back_in_order_and_bounds_what_is_in_the_making(width):
    lock = threading.Lock()
    started, consumed, most_ahead = [], 0, 0

    def prepare(p):
        nonlocal most_ahead
        with lock:
            started.append(p)
            most_ahead = max(most_ahead, len(started) - consumed)
        time.sleep(0.002 * ((p * 7) % 5))  # finish out of order
        return p * p

    stop = threading.Event()
    got = []
    for part in mesh_stage._in_partition_order(prepare, 11, width, stop):
        got.append(part)
        with lock:
            consumed += 1
    assert got == [p * p for p in range(11)]
    assert sorted(started) == list(range(11))
    # width in flight or waiting their turn, and the one being handed over
    assert most_ahead <= min(width, 11) + 1
    assert _gang_threads() == []
    assert stop.is_set() == (width > 1)


@pytest.mark.parametrize("width", [1, 3])
def test_pool_raises_a_workers_error_at_its_turn_and_joins(width):
    stop = threading.Event()
    ran = []

    def prepare(p):
        ran.append(p)
        if p == 2:
            raise ValueError("partition 2")
        for _ in range(200):  # a long partition stops early once told to
            if stop.is_set():
                return None
            time.sleep(0.001)
        return p

    it = mesh_stage._in_partition_order(prepare, 9, width, stop)
    assert next(it) == 0 and next(it) == 1
    with pytest.raises(ValueError, match="partition 2"):
        next(it)
    assert _gang_threads() == []
    assert 8 not in ran  # never submitted, or cancelled before it started


def test_pool_closed_by_its_consumer_stops_and_joins():
    stop = threading.Event()
    it = mesh_stage._in_partition_order(lambda p: p, 6, 3, stop)
    assert next(it) == 0
    assert _gang_threads()
    it.close()
    assert stop.is_set() and _gang_threads() == []


# ----------------------------------------- (b) answers whatever the width
RAGGED_SQL = (
    "select g, k, sum(v) as s, count(*) as c, min(v) as mn, max(v) as mx "
    "from t group by g, k"
)


def _ragged_table():
    """Five partitions: three unequal batches, none at all, only empty
    batches, one batch, two batches; a string key whose values first appear
    in another order in every partition (so no partition's local group ids
    are the stage's), NULLs among them.  Every v is a multiple of 1/4: float32
    sums are exact in any order."""
    rng = np.random.default_rng(29)
    schema = pa.schema([("g", pa.string()), ("k", pa.int64()), ("v", pa.float64())])
    names = np.array(["delta", "alpha", None, "charlie", "bravo", "echo"], dtype=object)

    def batch(n, lo=0):
        g = names[(rng.integers(0, 4, n) + lo) % len(names)]
        return pa.RecordBatch.from_arrays(
            [
                pa.array(g.tolist(), pa.string()),
                pa.array(rng.integers(0, 3, n), pa.int64()),
                pa.array(rng.integers(0, 400, n) / 4.0, pa.float64()),
            ],
            schema=schema,
        )

    return MemoryTable(
        [
            [batch(700), batch(1, 3), batch(1300, 1)],
            [],
            [batch(0), batch(0)],
            [batch(513, 4)],
            [batch(0), batch(2048, 2), batch(90, 5)],
        ],
        schema,
    )


def _gang_output(monkeypatch, width, table, sql):
    """(the gang stage's own output, its counters, the query's answer)."""
    _force_width(monkeypatch, width)
    cfg = _cfg()
    ctx = SessionContext(cfg)
    ctx.register_table("t" if sql is RAGGED_SQL else "lineitem", table)
    plan = ctx.sql(sql).physical_plan()
    answer = ctx.execute(plan)
    (gang,) = _find(plan, MeshGangExec)
    counters = gang.metrics.to_dict()
    # the stage alone, again: group order as the device saw it
    (fresh,) = _find(ctx.sql(sql).physical_plan(), MeshGangExec)
    stage_out = pa.Table.from_batches(list(fresh.execute(0, TaskContext(config=cfg))))
    assert _gang_threads() == []
    return stage_out, counters, answer


def test_ragged_table_same_bits_and_group_order_at_width_1_and_4(monkeypatch):
    table = _ragged_table()
    out1, m1, ans1 = _gang_output(monkeypatch, 1, table, RAGGED_SQL)
    out4, m4, ans4 = _gang_output(monkeypatch, 4, table, RAGGED_SQL)
    assert m1["gang_workers"] == 1 and m4["gang_workers"] == 4
    assert "mesh_fallback" not in m1 and "mesh_fallback" not in m4
    assert out4.equals(out1) and ans4.equals(ans1)
    assert out1.num_rows == 18  # 6 names x 3 ints, in first-appearance order
    for k in ("gang_partitions", "gang_batches", "gang_uploads", "gang_upload_bytes",
              "mesh_rows_in"):
        assert m1[k] == m4[k], k
    assert m1["gang_partitions"] == 5 and m1["gang_batches"] == 6
    # against the CPU operators (sums are exact: every v is a quarter)
    off = SessionContext(_cfg(**{"ballista.tpu.enable": "false", "ballista.mesh.enable": "false"}))
    off.register_table("t", table)
    keys = [("g", "ascending"), ("k", "ascending")]
    assert ans1.sort_by(keys).to_pydict() == off.sql(RAGGED_SQL).collect().sort_by(keys).to_pydict()


def test_local_gang_q1_same_bits_and_group_order_at_width_1_and_4(monkeypatch):
    from benchmarks.tpch.datagen import gen_table
    from benchmarks.tpch.queries import QUERIES

    lineitem = gen_table("lineitem", 0.01)
    per = -(-lineitem.num_rows // 5)
    table = MemoryTable([
        lineitem.slice(i * per, per).combine_chunks().to_batches(max_chunksize=2048)
        for i in range(5)
    ])
    out1, m1, ans1 = _gang_output(monkeypatch, 1, table, QUERIES[1])
    out4, m4, ans4 = _gang_output(monkeypatch, 4, table, QUERIES[1])
    assert m1["gang_workers"] == 1 and m4["gang_workers"] == 4
    assert out4.equals(out1) and ans4.equals(ans1)
    assert out1.num_rows == 4 and m1["gang_batches"] == m4["gang_batches"] > 5


# ------------------------------------------- (c) an error, a cancel: joined
class _Scripted(MemoryTable):
    """A MemoryTable whose scan runs ``on_batch(partition, i)`` before it
    yields a partition's i-th batch, and counts what it yielded."""

    def __init__(self, partitions, schema, on_batch):
        super().__init__(partitions, schema)
        self.on_batch = on_batch
        self.yielded = []

    def scan_partition(self, partition, projection, batch_size=8192):
        for i, b in enumerate(super().scan_partition(partition, projection, batch_size)):
            self.on_batch(partition, i)
            self.yielded.append((partition, i))
            yield b


def _scripted_gang(on_batch, n_parts=6, batches=4):
    rng = np.random.default_rng(7)
    schema = pa.schema([("g", pa.string()), ("v", pa.float64())])

    def batch():
        return pa.RecordBatch.from_arrays(
            [pa.array(rng.choice(["a", "b", "c"], 256).tolist()),
             pa.array(rng.integers(0, 100, 256) / 4.0)], schema=schema)

    table = _Scripted([[batch() for _ in range(batches)] for _ in range(n_parts)], schema, on_batch)
    cfg = _cfg()
    ctx = SessionContext(cfg)
    ctx.register_table("t", table)
    plan = ctx.sql("select g, sum(v) as s, count(*) as c from t group by g").physical_plan()
    (gang,) = _find(plan, MeshGangExec)
    return gang, table, cfg


@pytest.mark.parametrize("width", [1, 4])
def test_a_worker_that_raises_ends_the_stage_with_its_error(monkeypatch, width):
    from arrow_ballista_tpu.ops.stage_compiler import TpuStageExec

    _force_width(monkeypatch, width)

    def on_batch(p, i):
        if (p, i) == (3, 2):
            raise RuntimeError("partition 3 broke")

    gang, table, cfg = _scripted_gang(on_batch)
    with pytest.raises(RuntimeError, match="partition 3 broke"):
        list(gang.execute(0, TaskContext(config=cfg)))
    assert _gang_threads() == []
    m = gang.metrics.to_dict()
    (tpu,) = _find(gang, TpuStageExec)
    columns = 2 + len(tpu._flat_names)
    # partitions 0..2 were handed over, the failing one and the rest never
    assert m["gang_uploads"] == 3 * columns and m["gang_partitions"] == 4
    assert m["gang_workers"] == width and "mesh_rows_in" not in m
    assert all(p <= 3 + width for p, _ in table.yielded)


@pytest.mark.parametrize("width", [1, 4])
def test_a_cancel_in_a_late_partition_ends_the_stage_cancelled(monkeypatch, width):
    from arrow_ballista_tpu.ops.stage_compiler import TpuStageExec

    _force_width(monkeypatch, width)
    cancel = threading.Event()

    def on_batch(p, i):
        if (p, i) == (2, 1):
            cancel.set()

    gang, table, cfg = _scripted_gang(on_batch)
    with pytest.raises(Cancelled):
        list(gang.execute(0, TaskContext(config=cfg, cancel_event=cancel)))
    assert _gang_threads() == []
    m = gang.metrics.to_dict()
    (tpu,) = _find(gang, TpuStageExec)
    columns = 2 + len(tpu._flat_names)
    assert m["gang_uploads"] <= 2 * columns and m["gang_partitions"] <= 3
    assert "mesh_rows_in" not in m and "mesh_fallback" not in m
    # every partition checks at every batch: none was read to its end after
    assert len(table.yielded) < 6 * 4


# ------------------------------------ (d) the route leaves on the first batch
@pytest.mark.parametrize("width", [1, 4])
def test_highcard_exit_reads_one_batch_and_starts_no_worker(monkeypatch, width):
    from arrow_ballista_tpu.ops import stage_compiler as SC

    _force_width(monkeypatch, width)
    monkeypatch.setattr(SC, "_HIGHCARD_MIN_GROUPS", 64)
    schema = pa.schema([("g", pa.int64()), ("v", pa.float64())])
    pulled = []

    def batch(lo, n=512):
        return pa.RecordBatch.from_arrays(
            [pa.array(np.arange(lo, lo + n)), pa.array(np.ones(n))], schema=schema)

    table = _Scripted(
        [[batch(0, 0)], [batch(0), batch(512)], [batch(1024)], [batch(1536)]],
        schema, lambda p, i: pulled.append((p, i)),
    )
    cfg = _cfg(**{"ballista.tpu.highcard_mode": "device"})
    ctx = SessionContext(cfg)
    ctx.register_table("t", table)
    plan = ctx.sql("select g, sum(v) as s from t group by g").physical_plan()
    (gang,) = _find(plan, MeshGangExec)
    route, out = gang._execute_mesh(gang.input, TaskContext(config=cfg))
    assert route is SC.Route.KEYED and out is None
    # the empty partition's batch, then the stage's first non-empty one
    assert pulled == [(0, 0), (1, 0)]
    m = gang.metrics.to_dict()
    assert m["gang_batches"] == 0 and m["gang_uploads"] == 0 and m["gang_upload_bytes"] == 0
    assert "gang_partitions" not in m and _gang_threads() == []
    assert m["gang_wait_ns"] > 0 and m["key_encode_time_ns"] > 0
