"""A device stage's partitions on the chips of the mesh: partition ``p`` of a
``TpuStageExec`` runs on chip ``p % n`` of the mesh the exchanges use, where
the exchange delivers it.  The folded join's build side is sorted once on the
host and placed once on each chip; the probe uploads, the row mask, the
program and the states are that chip's.  Over such a stage on the build-row
route an exchange runs the partitions side by side, a worker a chip.

On the CPU's virtual devices (``conftest.py`` forces eight), a four-device
mesh and four shuffle partitions: the served q3 equals the plain reference
and the one-device run; each partition's arrays are on its chip; width 1
and width 4 give the same batches; one device places nothing and stays
inline; the per-chip counters sum to the stage's; the spans say the chip.
And ``_timed_jit``: two calls that meet in a program's first compile count
one compile each where each compiled, and none where one did not.
"""

import json
import os
import threading
import time
import types
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arrow_ballista_tpu import BallistaConfig, SessionContext
from arrow_ballista_tpu.catalog import MemoryTable
from arrow_ballista_tpu.exec import expressions as pe
from arrow_ballista_tpu.exec.operators import Metrics, Partitioning, TaskContext
from arrow_ballista_tpu.obs import trace
from arrow_ballista_tpu.obs.recorder import get_recorder
from arrow_ballista_tpu.ops import stage_compiler as SC
from arrow_ballista_tpu.parallel import mesh_stage
from arrow_ballista_tpu.parallel.mesh_stage import MeshRepartitionExec
from benchmark import compare, datagen, queries, reference

TABLES = ("lineitem", "orders", "customer")
Q3_PARAMS = {"segment": "BUILDING", "date": "1995-03-15"}
N_DEV = 4
OFF_ROUTE = ("join_fallback", "tpu_fallback", "cpu_fallback", "highcard_fallback",
             "device_error", "mesh_fallback")


def _cfg(devices: int, **extra) -> BallistaConfig:
    return BallistaConfig({
        "ballista.tpu.min_rows": "0", "ballista.mesh.devices": str(devices),
        "ballista.shuffle.partitions": "4", **extra,
    })


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    """The benchmark's q3 tables at SF0.02, written in this process."""
    out = str(tmp_path_factory.mktemp("tpch"))
    for name in TABLES:
        os.makedirs(os.path.join(out, name), exist_ok=True)
        for i in range(4):
            datagen._write_chunk((name, i, 4, 0.02, 2**31 + 36, out))
    return out


# ------------------------------------------------- (a) the served q3
@pytest.fixture(scope="module")
def served():
    """A local scheduler + one executor (four task slots, in-thread tasks)
    and its REST API; each client states its own mesh."""
    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.scheduler.api import ApiServerHandle

    polls = {"ballista.client.poll_interval_seconds": "0.05",
             "ballista.client.poll_max_interval_seconds": "0.05"}
    ctx = BallistaContext.standalone(config=_cfg(N_DEV, **polls), num_executors=1, concurrent_tasks=4)
    scheduler, _ = ctx._standalone_handles
    one = BallistaContext.remote(scheduler.host, scheduler.port, _cfg(1, **polls))
    api = ApiServerHandle(scheduler.server, "127.0.0.1", 0).start()
    try:
        yield {N_DEV: ctx, 1: one}, f"http://127.0.0.1:{api.port}"
    finally:
        api.stop()
        one.close()
        ctx.close()


def _served_q3(served, devices: int, data_dir: str):
    """(answer, [the job's stages: {operator: counters}]) of q3 on a client
    whose mesh is ``devices`` chips."""
    clients, base = served
    ctx = clients[devices]
    for t in TABLES:
        ctx.register_parquet(t, os.path.join(data_dir, t))
    seen = set(ctx._job_ids)
    answer = ctx.sql(queries.render(3, Q3_PARAMS)).collect()
    (job_id,) = set(ctx._job_ids) - seen
    detail = json.load(urllib.request.urlopen(f"{base}/api/job/{job_id}"))
    stages = [{op: vals for op, vals in st["metrics"].items() if not op.startswith("__")}
              for st in detail["stages"]]
    return answer, stages


def test_served_q3_on_four_chips_equals_the_reference_and_one_chip(served, data_dir, monkeypatch):
    # the executor's pools read the cores the process may use: enough for
    # a worker a chip beside the other three task slots
    monkeypatch.setattr(mesh_stage, "_usable_cores", lambda: 16)
    answer, stages = _served_q3(served, N_DEV, data_dir)
    ref = reference.answer(reference.Data(data_dir), 3, Q3_PARAMS)
    verdict = compare.judge([(answer, ref, queries.ORDER_BY[3])])
    assert verdict["correct"], verdict["numbers"]
    assert answer.num_rows == 10
    one, one_stages = _served_q3(served, 1, data_dir)
    assert answer.equals(one)
    for got, chips in ((stages, N_DEV), (one_stages, 1)):
        assert all(not vals.get(k) for st in got for vals in st.values() for k in OFF_ROUTE)
        exchanges = [st["MeshRepartitionExec"] for st in got if "MeshRepartitionExec" in st]
        assert len(exchanges) == 5 and all(ex["mesh_devices"] == chips for ex in exchanges)
        (device_stage,) = [st for st in got if "TpuStageExec" in st]
        tpu = device_stage["TpuStageExec"]
        assert tpu["join_build_groups"] == 1
        per_chip = {k: v for k, v in tpu.items() if k.startswith("tpu_stage_time_ns_dev")}
        # every chip ran a partition, and the chips' counters are the stage's
        assert sorted(per_chip) == [f"tpu_stage_time_ns_dev{d}" for d in range(chips)]
        assert all(v > 0 for v in per_chip.values()) and sum(per_chip.values()) == tpu["tpu_stage_time_ns"]
        # the build side: one host sort, one placement a chip
        assert tpu["join_build_rows"] > 0 and tpu["join_build_capacity"] >= tpu["join_build_rows"]
        assert device_stage["MeshRepartitionExec"]["exchange_workers"] == chips


# ---------------------------------- (b) placement, width, one device
def _join_stage(devices: int):
    """q3's folded join as a session with ``devices`` chips plans it, over
    the four partitions of its probe side."""
    from benchmarks.tpch import datagen as small
    from benchmarks.tpch.queries import QUERIES

    cfg = _cfg(devices)
    ctx = SessionContext(cfg)
    for t in TABLES:
        ctx.register_table(t, MemoryTable.from_table(small.gen_table(t, 0.005), 4))
    plan = ctx.sql(QUERIES[3].split("order by")[0]).physical_plan()
    stack, stages = [plan], []
    while stack:
        n = stack.pop()
        if isinstance(n, SC.TpuStageExec) and n.fused.join is not None:
            stages.append(n)
        stack.extend(n.children())
    (stage,) = stages
    assert stage.output_partitioning().n == 4
    return stage, TaskContext(config=cfg)


def _states_on(monkeypatch, stage) -> dict:
    """partition -> the devices its fetched states were on."""
    seen = {}
    real = stage._fetch_states
    owner = threading.local()

    def fetch(acc, n_groups=None):
        seen[owner.partition] = {d for a in acc for d in a.devices()}
        return real(acc, n_groups)

    real_execute = stage.execute

    def execute(partition, ctx):
        owner.partition = partition
        yield from real_execute(partition, ctx)

    monkeypatch.setattr(stage, "_fetch_states", fetch)
    monkeypatch.setattr(stage, "execute", execute)
    return seen


def test_each_partitions_build_and_states_live_on_its_chip(monkeypatch):
    stage, tctx = _join_stage(N_DEV)
    devices = jax.devices()[:N_DEV]
    assert [SC.partition_device(p, tctx) for p in range(6)] == [
        (p % N_DEV, devices[p % N_DEV]) for p in range(6)]
    seen = _states_on(monkeypatch, stage)
    rows = sum(b.num_rows for p in range(4) for b in stage.execute(p, tctx))
    assert rows > 0
    assert seen == {p: {devices[p]} for p in range(4)}
    assert set(stage._build_states) == set(devices)
    for d, state in stage._build_states.items():
        assert state[0] == "dense"
        assert {x for a in [state[1], *state[2], *state[3]] for x in a.devices()} == {d}
    # the host sort is one: the padded host arrays and the answers' table
    assert stage._build_host[0] == "dense" and len(stage._build_host[4]) == stage.metrics.values["join_build_rows"]
    m = stage.metrics.to_dict()
    assert m["join_build_groups"] == 1 and m.get("dense_join") == 1
    per_chip = [m[f"tpu_stage_time_ns_dev{d}"] for d in range(N_DEV)]
    assert all(v > 0 for v in per_chip) and sum(per_chip) == m["tpu_stage_time_ns"]


@pytest.mark.parametrize("cores", [1, 8], ids=["width-1", "width-4"])
def test_width_one_and_four_give_the_same_batches(monkeypatch, cores):
    """Four chips either way; the pool's width from the cores it may use."""
    runs = {}
    for c in sorted({1, cores}):
        monkeypatch.setattr(mesh_stage, "_usable_cores", lambda c=c: c)
        stage, tctx = _join_stage(N_DEV)
        node = MeshRepartitionExec(stage, Partitioning("hash", 4, (pe.Col(0, "l_orderkey"),)))
        runs[c] = list(node.execute_exchanged(tctx))
        assert node.metrics.to_dict()["exchange_workers"] == min(c, N_DEV)
        assert set(stage._build_states) == set(jax.devices()[:N_DEV])
    assert not [t for t in threading.enumerate() if t.name.startswith("exchange")]
    want = runs[1]
    got = runs[cores]
    assert [p for p, _ in got] == [p for p, _ in want] and sum(b.num_rows for _, b in want) > 0
    for (_, g), (_, w) in zip(got, want):
        assert g.equals(w)


def test_one_device_places_nothing_and_stays_inline(monkeypatch):
    monkeypatch.setattr(mesh_stage, "_usable_cores", lambda: 8)
    stage, tctx = _join_stage(1)
    assert all(SC.partition_device(p, tctx) == (0, None) for p in range(4))
    seen = _states_on(monkeypatch, stage)
    node = MeshRepartitionExec(stage, Partitioning("hash", 4, (pe.Col(0, "l_orderkey"),)))
    assert list(node.execute_exchanged(tctx))
    assert node.metrics.to_dict()["exchange_workers"] == 1
    assert list(stage._build_states) == [None]  # the default device: device 0
    assert seen == {p: {jax.devices()[0]} for p in range(4)}
    m = stage.metrics.to_dict()
    assert [k for k in m if k.startswith("tpu_stage_time_ns_dev")] == ["tpu_stage_time_ns_dev0"]
    assert m["tpu_stage_time_ns_dev0"] == m["tpu_stage_time_ns"]
    # without a mesh, the default device too
    off = TaskContext(config=_cfg(N_DEV, **{"ballista.mesh.enable": "false"}))
    assert SC.partition_device(3, off) == (0, None)


# -------------------------------------------------------- (c) the spans
def test_probe_build_and_partition_spans_say_the_chip(monkeypatch):
    monkeypatch.setattr(mesh_stage, "_usable_cores", lambda: 8)
    stage, tctx = _join_stage(N_DEV)
    node = MeshRepartitionExec(stage, Partitioning("hash", 4, (pe.Col(0, "l_orderkey"),)))
    trace.configure(enabled=True, process="local")
    try:
        get_recorder().drain()
        trace_id = trace.new_id()
        with trace.root_span("job", trace_id), trace.span("task.execute"):
            list(node.execute_exchanged(tctx))
    finally:
        trace.configure(enabled=False)
    spans = [s for s in get_recorder().drain() if s["trace"] == trace_id]
    probes = {s["attrs"]["partition"]: s["attrs"] for s in spans if s["name"] == "join.probe"}
    assert {p: a["device"] for p, a in probes.items()} == {p: p % N_DEV for p in range(4)}
    assert all(a["gid"] == "build_row" for a in probes.values())
    builds = sorted(s["attrs"]["device"] for s in spans if s["name"] == "join.build")
    assert builds == list(range(N_DEV))  # one placement a chip
    parts = {s["attrs"]["partition"]: s["attrs"] for s in spans if s["name"] == "exchange.partition"}
    assert {p: a["device"] for p, a in parts.items()} == {p: p % N_DEV for p in range(4)}
    assert all(a["worker"].startswith("exchange_") for a in parts.values())


# ------------------------------------------ (d) compile or execute, per call
TRACE_S = 0.5


def _slow_program():
    """A fresh jitted program whose trace takes ``TRACE_S``, so that a
    second call arrives while the first is still compiling."""
    def f(x):
        time.sleep(TRACE_S)
        return x * 2 + 1

    return jax.jit(f)


@pytest.mark.parametrize("devices,compiles", [(1, 1), (2, 2)], ids=["one-device", "two-devices"])
def test_calls_meeting_in_a_first_compile_count_one_compile_each_where_each_compiled(devices, compiles):
    stage = types.SimpleNamespace(metrics=Metrics())
    call = SC.TpuStageExec._timed_jit(stage, _slow_program())
    args = [jax.device_put(jnp.arange(8), jax.devices()[i % devices]) for i in range(2)]
    meet = threading.Barrier(2)
    out = [None, None]

    def run(i):
        meet.wait()
        out[i] = np.asarray(call(args[i]))

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all((o == np.arange(8) * 2 + 1).all() for o in out)
    m = stage.metrics.to_dict()
    assert m["kernel_compiles"] == compiles
    assert ("tpu_execute_ns" in m) == (compiles < 2)
    # no call counts the time it waited for the other's compile: on one
    # device the second call is a dispatch, on two the second compile
    # traces nothing again
    if compiles == 1:
        assert m["tpu_execute_ns"] < TRACE_S / 2 * 1e9
    else:
        assert m["tpu_compile_ns"] < 1.5 * TRACE_S * 1e9
    # a later call on a device it compiled for is a dispatch
    call(args[0])
    assert stage.metrics.to_dict()["kernel_compiles"] == compiles
