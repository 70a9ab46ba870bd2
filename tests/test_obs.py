"""Observability acceptance tests (ISSUE 3).

Covers the ``arrow_ballista_tpu.obs`` subsystem: span API semantics and
the disabled fast path, the bounded recorder + scheduler trace store,
the unified metrics registry and Prometheus exposition, Chrome-trace /
profile exports, trace-context propagation across a real standalone
cluster (one stitched trace id spanning scheduler and executor
processes, surviving a task retry), the monotonic-clock hardening of
quarantine/liveness, and the disabled-path overhead bound against the
shuffle fetch leg.
"""

import json
import threading
import time
import urllib.request

import pyarrow as pa
import pytest

from arrow_ballista_tpu.config import BallistaConfig
from arrow_ballista_tpu.obs import trace
from arrow_ballista_tpu.obs.export import chrome_trace, job_profile
from arrow_ballista_tpu.obs.recorder import SpanRecorder, TraceStore, get_recorder, trace_store
from arrow_ballista_tpu.obs.registry import MetricsRegistry
from arrow_ballista_tpu.testing import faults

pytestmark = pytest.mark.obs

# CPU-only operator path for cluster tests (this environment's jax lacks
# shard_map; the pyarrow sort kernel is broken at seed) — obs is about
# the scheduler/executor/shuffle planes, which these settings exercise
OBS_CONFIG = {
    "ballista.obs.enabled": "true",
    "ballista.mesh.enable": "false",
    "ballista.shuffle.partitions": "2",
    "ballista.tpu.min_rows": "0",
}


@pytest.fixture(autouse=True)
def _obs_state():
    """Isolate process-global obs state per test."""
    faults.clear()
    get_recorder().set_forward(None)
    get_recorder().drain()
    yield
    faults.clear()
    trace.configure(enabled=False, sample_rate=1.0)
    get_recorder().set_forward(None)
    get_recorder().drain()


def _rows(table: pa.Table):
    cols = sorted(table.column_names)
    d = table.to_pydict()
    return sorted(zip(*(d[c] for c in cols)))


# =====================================================================
# span API
# =====================================================================
def test_disabled_span_api_is_shared_noop():
    trace.configure(enabled=False)
    s = trace.span("anything", key="value")
    assert s is trace.NOOP
    with s as sp:
        sp.set_attr("x", 1)  # no-op surface exists
    assert get_recorder().drain() == []
    # propagation headers are empty when disabled
    assert trace.propagation_headers() == []


def test_span_nesting_and_ids():
    trace.configure(enabled=True, process="test-proc")
    tid = trace.new_id()
    with trace.activate(tid):
        with trace.span("outer", job="j1") as outer:
            with trace.span("inner") as inner:
                assert trace.current_context().span_id == inner.span_id
            assert trace.current_context().span_id == outer.span_id
    spans = {s["name"]: s for s in get_recorder().drain()}
    assert set(spans) == {"outer", "inner"}
    assert spans["outer"]["trace"] == spans["inner"]["trace"] == tid
    assert spans["outer"]["parent"] == tid  # root adoption
    assert spans["inner"]["parent"] == spans["outer"]["span"]
    assert spans["inner"]["proc"] == "test-proc"
    assert spans["outer"]["dur"] >= spans["inner"]["dur"] >= 0
    assert spans["outer"]["attrs"]["job"] == "j1"


def test_span_records_error_attr():
    trace.configure(enabled=True)
    with pytest.raises(ValueError):
        with trace.activate(trace.new_id()), trace.span("boom"):
            raise ValueError("kapow")
    (s,) = get_recorder().drain()
    assert "ValueError: kapow" in s["attrs"]["error"]


def test_positionless_span_is_noop_even_when_enabled():
    """Sampling end-to-end: with no activated context and no explicit
    parent, span()/manual_span() collapse to the no-op — an unsampled
    job (empty trace id -> activate installs nothing) records NOTHING
    on executors instead of minting orphan local traces."""
    trace.configure(enabled=True)
    assert trace.span("orphan") is trace.NOOP
    assert trace.manual_span("orphan") is trace.NOOP_MANUAL
    with trace.activate(""):  # what an unsampled TaskDefinition carries
        assert trace.span("task.execute") is trace.NOOP
    assert get_recorder().drain() == []


def test_traced_decorator_and_cross_thread_parent():
    trace.configure(enabled=True)

    @trace.traced("helper")
    def helper():
        return 42

    activation = trace.activate(trace.new_id())
    activation.__enter__()
    with trace.span("parent") as p:
        assert helper() == 42
        # explicit parent hop (worker-thread pattern used by the fetcher)
        out = {}

        def worker(ctx):
            with trace.span("in-thread", parent=ctx):
                out["ctx"] = trace.current_context().trace_id

        t = threading.Thread(target=worker, args=(trace.current_context(),))
        t.start()
        t.join()
    activation.__exit__(None, None, None)
    spans = {s["name"]: s for s in get_recorder().drain()}
    assert spans["helper"]["parent"] == spans["parent"]["span"]
    assert spans["in-thread"]["parent"] == spans["parent"]["span"]
    assert out["ctx"] == spans["parent"]["trace"]


def test_sampling_zero_never_samples():
    trace.configure(enabled=True, sample_rate=0.0)
    assert not any(trace.sampled() for _ in range(64))
    trace.configure(sample_rate=1.0)
    assert all(trace.sampled() for _ in range(64))


# =====================================================================
# recorder + trace store
# =====================================================================
def test_recorder_ring_is_bounded():
    r = SpanRecorder(cap=4)
    for i in range(10):
        r.record({"span": f"s{i}", "trace": "t", "ts": i})
    spans = r.drain()
    assert [s["span"] for s in spans] == ["s6", "s7", "s8", "s9"]
    assert r.dropped == 6
    assert r.drain() == []


def test_recorder_requeue_after_failed_ship():
    r = SpanRecorder(cap=4)
    for i in range(3):
        r.record({"span": f"s{i}", "trace": "t", "ts": i})
    drained = r.drain()
    r.record({"span": "s3", "trace": "t", "ts": 3})
    r.requeue(drained)  # transport failed: spans come back, order kept
    assert [s["span"] for s in r.drain()] == ["s0", "s1", "s2", "s3"]
    # overflowing requeue keeps the NEWEST of the returned batch
    r2 = SpanRecorder(cap=2)
    r2.record({"span": "live", "trace": "t", "ts": 9})
    r2.requeue([{"span": f"old{i}", "trace": "t", "ts": i} for i in range(3)])
    assert [s["span"] for s in r2.drain()] == ["old2", "live"]
    assert r2.dropped == 2


def test_manual_span_never_touches_thread_context():
    """Generator-safe span (ShuffleReaderExec): children parent via .ctx,
    the thread-local current context stays untouched."""
    trace.configure(enabled=True)
    with trace.activate(trace.new_id()), trace.span("task") as outer:
        ms = trace.manual_span("gen", rows=0)
        assert trace.current_context().span_id == outer.span_id  # unchanged
        with trace.span("child", parent=ms.ctx):
            pass
        ms.set_attr("rows", 7)
        ms.finish()
        ms.finish()  # idempotent
    spans = {s["name"]: s for s in get_recorder().drain()}
    assert set(spans) == {"task", "gen", "child"}
    assert spans["gen"]["parent"] == spans["task"]["span"]
    assert spans["child"]["parent"] == spans["gen"]["span"]
    assert spans["gen"]["attrs"]["rows"] == 7
    # disabled path exposes the same surface
    trace.configure(enabled=False)
    noop = trace.manual_span("x")
    assert noop.ctx is None
    noop.set_attr("a", 1)
    noop.finish()


def test_trace_store_routes_dedups_and_binds():
    ts = TraceStore(max_jobs=2)
    ts.bind("tr1", "job1")
    # span w/o job attr routes through the binding; duplicate span ids drop
    assert ts.add([{"span": "a", "trace": "tr1", "ts": 1}]) == 1
    assert ts.add([{"span": "a", "trace": "tr1", "ts": 1}]) == 0
    # job attr on a span teaches the binding for its trace
    assert ts.add(
        [{"span": "b", "trace": "tr2", "ts": 2, "attrs": {"job": "job2"}}]
    ) == 1
    assert ts.add([{"span": "c", "trace": "tr2", "ts": 3}]) == 1
    assert [s["span"] for s in ts.for_job("job2")] == ["b", "c"]
    # job eviction is LRU by insertion, bounded at max_jobs
    ts.add([{"span": "d", "trace": "tr3", "ts": 4, "attrs": {"job": "job3"}}])
    assert ts.for_job("job1") == []
    # json round trip tolerates garbage
    assert ts.add_json(b"not-json") == 0
    assert ts.add_json(b"") == 0


# =====================================================================
# registry
# =====================================================================
def test_registry_counters_gauges_histograms():
    r = MetricsRegistry()
    c = r.counter("task_retries_total", "retries")
    c.inc()
    c.inc(2)
    g = r.gauge("alive_executors", fn=lambda: 3)
    h = r.histogram("latency", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5)
    h.observe(100)
    snap = r.snapshot()
    assert snap["task_retries_total"] == 3
    assert snap["alive_executors"] == 3
    assert snap["latency"]["count"] == 3
    assert snap["latency"]["buckets"]["+Inf"] == 3
    # same name returns the same metric; wrong kind raises
    assert r.counter("task_retries_total") is c
    with pytest.raises(TypeError):
        r.gauge("task_retries_total")
    assert g.value == 3


def test_registry_prometheus_text_format():
    r = MetricsRegistry()
    r.counter("jobs_total", "jobs seen").inc(7)
    r.histogram("wait_seconds", buckets=(0.1, 1.0)).observe(0.5)
    text = r.prometheus_text()
    assert "# TYPE ballista_jobs_total counter" in text
    assert "ballista_jobs_total 7" in text
    assert 'ballista_wait_seconds_bucket{le="1"} 1' in text
    assert "ballista_wait_seconds_count 1" in text
    assert text.endswith("\n")


# =====================================================================
# exports
# =====================================================================
def _mk_span(name, trace_id, span_id, parent, proc, ts, dur, **attrs):
    return {
        "name": name, "trace": trace_id, "span": span_id, "parent": parent,
        "proc": proc, "tid": 1, "ts": ts, "dur": dur, "attrs": attrs,
    }


def test_chrome_trace_export_shape():
    spans = [
        _mk_span("job", "t1", "t1", "", "scheduler", 1_000_000, 5_000_000, job="j"),
        _mk_span("task.execute", "t1", "s2", "t1", "executor:e1", 2_000_000,
                 1_000_000, job="j", stage=1),
    ]
    out = chrome_trace(spans, "j")
    metas = [e for e in out["traceEvents"] if e["ph"] == "M"]
    slices = [e for e in out["traceEvents"] if e["ph"] == "X"]
    proc_metas = [m for m in metas if m["name"] == "process_name"]
    assert {m["args"]["name"] for m in proc_metas} == {
        "scheduler", "executor:e1",
    }
    # every (pid, tid) also carries thread_name metadata (ISSUE 13)
    thread_metas = [m for m in metas if m["name"] == "thread_name"]
    assert {(m["pid"], m["tid"]) for m in thread_metas} == {
        (e["pid"], e["tid"]) for e in slices
    }
    assert len(slices) == 2
    # ts is microseconds
    assert slices[0]["ts"] == 1000.0 and slices[0]["dur"] == 5000.0
    assert out["otherData"]["job_id"] == "j"
    # distinct processes get distinct pids
    assert len({e["pid"] for e in slices}) == 2


def test_job_profile_rollup():
    detail = {
        "job_id": "j", "state": "completed", "task_retries": 1,
        "attempt_histogram": {0: 3, 1: 1},
        "stages": [
            {"stage_id": 1, "state": "Completed", "partitions": 2,
             "output_links": [2], "task_attempts": {0: 1},
             "task_retries": 1,
             "metrics": {"TpuStageExec": {
                 "tpu_compile_ns": 4_000_000, "tpu_execute_ns": 2_000_000,
                 "compile_cache_hits": 3, "compile_cache_misses": 1}}},
            {"stage_id": 2, "state": "Completed", "partitions": 1,
             "output_links": [], "fetch_retries": 2,
             "metrics": {"ShuffleReaderExec": {"bytes_fetched": 1234}}},
        ],
    }
    t0 = 1_000_000_000
    spans = [
        _mk_span("job", "t", "t", "", "scheduler", t0, 60_000_000, job="j"),
        _mk_span("task.execute", "t", "a", "t", "executor:e", t0 + 10_000_000,
                 20_000_000, job="j", stage=1),
        _mk_span("task.execute", "t", "b", "t", "executor:e", t0 + 35_000_000,
                 10_000_000, job="j", stage=2),
    ]
    prof = job_profile(detail, spans)
    s1, s2 = prof["stages"]
    assert s1["tpu"] == {
        "compile_ms": 4.0, "execute_ms": 2.0,
        "compile_cache_hits": 3, "compile_cache_misses": 1,
    }
    assert s1["attempts"] == 3  # 2 partitions + 1 retry
    # stage 1 queue wait = first task start - job root ts = 10ms
    assert s1["queue_wait_ms"] == pytest.approx(10.0)
    # stage 2 ready when stage 1's last task span ends (t0+30ms), starts 35ms
    assert s2["queue_wait_ms"] == pytest.approx(5.0)
    assert s2["shuffle_bytes_fetched"] == 1234
    assert s2["fetch_retries"] == 2
    assert prof["span_count"] == 3


# =====================================================================
# end-to-end: stitched trace across a real standalone cluster
# =====================================================================
def _wait_for_job_span(job_id: str, timeout_s: float = 20.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        spans = trace_store().for_job(job_id)
        if any(s["name"] == "job" for s in spans):
            return spans
        time.sleep(0.1)
    return trace_store().for_job(job_id)


def test_e2e_one_stitched_trace_and_profile():
    """Acceptance: a multi-stage aggregate on the standalone cluster
    yields ONE trace containing scheduler- and executor-process spans
    under a single trace id, and the profile reports the TPU
    compile-vs-execute split for compiled stages."""
    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.context import MemoryTable
    from arrow_ballista_tpu.scheduler.api import ApiServerHandle

    ctx = BallistaContext.standalone(
        config=BallistaConfig(dict(OBS_CONFIG)),
        num_executors=2,
        concurrent_tasks=2,
    )
    try:
        ctx.register_table(
            "t",
            MemoryTable.from_table(
                pa.table(
                    {
                        "g": ["a", "b", "c", "d"] * 500,
                        "x": [1.0, 2.0, 3.0, 4.0] * 500,
                    }
                ),
                2,
            ),
        )
        out = ctx.sql(
            "select g, sum(x) as s, count(x) as n from t group by g"
        ).collect()
        assert dict(
            zip(out.column("g").to_pylist(), out.column("s").to_pylist())
        ) == {"a": 500.0, "b": 1000.0, "c": 1500.0, "d": 2000.0}

        (job_id,) = ctx._job_ids
        scheduler, _executors = ctx._standalone_handles
        scheduler.server.drain()
        spans = _wait_for_job_span(job_id)

        # one trace id across >= 2 processes, scheduler + executor both in
        traces = {s["trace"] for s in spans}
        assert len(traces) == 1
        procs = {s["proc"] for s in spans}
        assert "scheduler" in procs
        assert any(p.startswith("executor:") for p in procs)
        names = {s["name"] for s in spans}
        assert {"job", "job.plan", "task.execute", "shuffle.write",
                "shuffle.fetch"} <= names
        # every span reachable from the root (stitched, not orphaned)
        by_id = {s["span"]: s for s in spans}
        (root_id,) = traces
        for s in spans:
            cur, hops = s, 0
            while cur["parent"] and hops < 20:
                assert cur["parent"] in by_id or cur["parent"] == root_id
                cur = by_id.get(cur["parent"]) or by_id[root_id]
                hops += 1

        # REST: trace + profile + metrics over real HTTP
        api = ApiServerHandle(scheduler.server, "127.0.0.1", 0).start()
        try:
            base = f"http://127.0.0.1:{api.port}"
            tr = json.load(
                urllib.request.urlopen(f"{base}/api/jobs/{job_id}/trace")
            )
            slices = [e for e in tr["traceEvents"] if e["ph"] == "X"]
            assert len({e["pid"] for e in slices}) >= 2
            prof = json.load(
                urllib.request.urlopen(f"{base}/api/jobs/{job_id}/profile")
            )
            tpu_stages = [s for s in prof["stages"] if s.get("tpu")]
            assert tpu_stages, "no stage reported a TPU compile/execute split"
            for s in tpu_stages:
                assert s["tpu"]["compile_ms"] >= 0
                assert s["tpu"]["execute_ms"] > 0
                assert (
                    s["tpu"]["compile_cache_hits"]
                    + s["tpu"]["compile_cache_misses"]
                ) > 0
            mets = json.load(urllib.request.urlopen(f"{base}/api/metrics"))
            for key in (
                "available_slots", "alive_executors", "active_jobs",
                "task_retries", "executors_quarantined", "quarantines_total",
            ):
                assert key in mets, f"legacy /api/metrics key {key} missing"
            prom = urllib.request.urlopen(
                f"{base}/api/metrics/prometheus"
            ).read().decode()
            assert "# TYPE ballista_task_retries_total counter" in prom
            assert "ballista_shuffle_bytes_fetched_total" in prom
        finally:
            api.stop()
    finally:
        ctx.close()


def test_sample_rate_zero_records_no_spans():
    """obs.sample_rate=0: metrics stay on, but no job is traced — neither
    scheduler-side nor on executors (the empty trace id shipped in
    TaskDefinition collapses every executor span to the no-op)."""
    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.context import MemoryTable

    cfg = dict(OBS_CONFIG)
    cfg["ballista.obs.sample_rate"] = "0.0"
    ctx = BallistaContext.standalone(
        config=BallistaConfig(cfg), num_executors=1, concurrent_tasks=2
    )
    try:
        ctx.register_table(
            "t",
            MemoryTable.from_table(
                pa.table({"g": ["a", "b"] * 100, "x": [1.0, 2.0] * 100}), 2
            ),
        )
        out = ctx.sql("select g, sum(x) as s from t group by g").collect()
        assert out.num_rows == 2
        (job_id,) = ctx._job_ids
        scheduler, _ = ctx._standalone_handles
        scheduler.server.drain()
        assert trace_store().for_job(job_id) == []
        assert all(
            (s.get("attrs") or {}).get("job") != job_id
            for s in get_recorder().snapshot()
        )
    finally:
        ctx.close()


def test_trace_survives_task_retry():
    """Satellite: spans from attempt 0 (failed) and attempt 1 (retry)
    of the same partition share one trace id with distinct span ids,
    both parented under the job root (PR 2 faults harness)."""
    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.context import MemoryTable

    killed = {}
    lock = threading.Lock()

    def first_attempt_fails(job_id="", stage_id=0, partition_id=0, attempt=0, **_):
        with lock:
            if attempt == 0 and not killed:
                killed["key"] = (job_id, stage_id, partition_id)
                return True
        return False

    faults.arm("executor.execute_task", times=-1, match=first_attempt_fails)

    ctx = BallistaContext.standalone(
        config=BallistaConfig(dict(OBS_CONFIG)),
        num_executors=2,
        concurrent_tasks=2,
    )
    try:
        ctx.register_table(
            "t",
            MemoryTable.from_table(
                pa.table({"g": ["a", "b"] * 200, "x": [1.0, 2.0] * 200}), 2
            ),
        )
        out = ctx.sql("select g, sum(x) as s from t group by g").collect()
        assert dict(
            zip(out.column("g").to_pylist(), out.column("s").to_pylist())
        ) == {"a": 200.0, "b": 400.0}
        assert faults.hits("executor.execute_task") == 1

        (job_id,) = ctx._job_ids
        scheduler, _ = ctx._standalone_handles
        scheduler.server.drain()
        spans = _wait_for_job_span(job_id)

        _job, stage_id, partition_id = killed["key"]
        attempts = [
            s
            for s in spans
            if s["name"] == "task.execute"
            and s["attrs"].get("stage") == stage_id
            and s["attrs"].get("partition") == partition_id
        ]
        by_attempt = {s["attrs"]["attempt"]: s for s in attempts}
        assert {0, 1} <= set(by_attempt), f"attempts seen: {sorted(by_attempt)}"
        a0, a1 = by_attempt[0], by_attempt[1]
        assert "error" in a0["attrs"] and "FaultInjected" in a0["attrs"]["error"]
        assert "error" not in a1["attrs"]
        # one trace, two distinct spans, both children of the job root
        assert a0["trace"] == a1["trace"]
        assert a0["span"] != a1["span"]
        root = a0["trace"]
        assert a0["parent"] == root and a1["parent"] == root
    finally:
        ctx.close()


# =====================================================================
# monotonic-clock hardening (satellite)
# =====================================================================
def test_quarantine_and_liveness_ignore_wall_clock_jumps(monkeypatch):
    from arrow_ballista_tpu.scheduler.backend import MemoryBackend
    from arrow_ballista_tpu.scheduler.executor_manager import ExecutorManager
    from arrow_ballista_tpu.serde.scheduler_types import (
        ExecutorMetadata,
        ExecutorSpecification,
    )

    em = ExecutorManager(
        MemoryBackend(),
        liveness_window_s=60.0,
        quarantine_threshold=2,
        quarantine_window_s=60.0,
        quarantine_backoff_s=300.0,
    )
    try:
        e1 = ExecutorMetadata("e1", "127.0.0.1", 1, 2, ExecutorSpecification(1))
        e2 = ExecutorMetadata("e2", "127.0.0.1", 3, 4, ExecutorSpecification(1))
        em.register_executor(e1)
        em.register_executor(e2)
        assert em.get_alive_executors() == {"e1", "e2"}
        assert em.record_task_failure("e1") is False
        assert em.record_task_failure("e1") is True
        assert em.is_quarantined("e1")

        # a 6-hour wall-clock jump must neither expire liveness nor lift
        # the quarantine backoff (both run on time.monotonic now)
        import arrow_ballista_tpu.scheduler.executor_manager as emod

        real_time = time.time
        monkeypatch.setattr(
            emod.time, "time", lambda: real_time() + 6 * 3600
        )
        assert em.get_alive_executors() == {"e1", "e2"}
        assert em.is_quarantined("e1")
        assert em.quarantined_executors() == ["e1"]
        assert not em.get_expired_executors(timeout_s=180.0)
    finally:
        em.close()


# =====================================================================
# disabled-path overhead (satellite)
# =====================================================================
def test_disabled_span_overhead_under_2pct_of_shuffle_leg():
    """The span API must stay <2% of the bench_suite shuffle leg when
    disabled.  Measured, not assumed: time the instrumented fetch path
    (obs off) the way benchmarks/shuffle_fetch.py drives it, count the
    disabled span-API entries that path makes, and price them with a
    measured per-call cost."""
    from arrow_ballista_tpu.shuffle.fetcher import FetchPolicy, ShuffleFetcher

    trace.configure(enabled=False)

    class _Loc:
        path = ""

    n_locations, batches_per_loc = 32, 8
    batch = pa.record_batch([pa.array(list(range(256)))], names=["x"])

    def fetch_fn(loc):
        for _ in range(batches_per_loc):
            yield batch

    class _M:
        def add(self, *a):
            pass

    def run_leg() -> float:
        t0 = time.perf_counter_ns()
        fetcher = ShuffleFetcher(
            [_Loc() for _ in range(n_locations)],
            FetchPolicy(concurrency=8),
            _M(),
            fetch_fn=fetch_fn,
        )
        n = sum(b.num_rows for b in fetcher)
        assert n == n_locations * batches_per_loc * 256
        return time.perf_counter_ns() - t0

    run_leg()  # warm
    leg_ns = min(run_leg() for _ in range(3))

    # price the disabled span API: per-call cost x the entries this leg
    # makes (1 reader span + 1 parent-check per location + 1 header probe
    # per Flight fetch; be conservative and charge 3 per location + 8)
    calls = 100_000
    t0 = time.perf_counter_ns()
    for _ in range(calls):
        trace.span("x")
    per_call_ns = (time.perf_counter_ns() - t0) / calls
    charged = (3 * n_locations + 8) * per_call_ns

    ratio = charged / leg_ns
    assert ratio < 0.02, (
        f"disabled span API projected at {ratio:.2%} of the shuffle leg "
        f"({per_call_ns:.0f}ns/call, leg {leg_ns/1e6:.1f}ms)"
    )


def test_process_registry_tees_fetch_counters():
    """Satellite: PR 1's fetcher metric dict now also lands in the
    process-wide registry (Prometheus-scrapable totals)."""
    from arrow_ballista_tpu.obs.registry import process_registry
    from arrow_ballista_tpu.shuffle.fetcher import FetchPolicy, ShuffleFetcher

    class _Loc:
        path = ""

    batch = pa.record_batch([pa.array([1, 2, 3])], names=["x"])

    def fetch_fn(loc):
        yield batch

    class _M:
        def __init__(self):
            self.values = {}

        def add(self, k, v):
            self.values[k] = self.values.get(k, 0) + v

    reg = process_registry()
    before = reg.value("shuffle_bytes_fetched_total")
    m = _M()
    fetcher = ShuffleFetcher(
        [_Loc(), _Loc()], FetchPolicy(concurrency=2), m, fetch_fn=fetch_fn
    )
    assert sum(b.num_rows for b in fetcher) == 6
    # operator metrics unchanged AND registry total advanced in lockstep
    assert m.values["bytes_fetched"] > 0
    assert (
        reg.value("shuffle_bytes_fetched_total") - before
        == m.values["bytes_fetched"]
    )
    assert m.values["locations_fetched"] == 2


# =====================================================================
# inside the gang task (PR 26): phase counters always, spans only when on
# =====================================================================
GANG_SQL = "select g, sum(x) as s, count(x) as n from t group by g"
GANG_COUNTERS = (
    "gang_scan_ns", "key_encode_time_ns", "gang_convert_ns", "gang_upload_ns",
    "gang_uploads", "gang_upload_bytes", "gang_assemble_ns", "gang_step_ns",
    "gang_materialize_ns", "mesh_stage_time_ns", "gang_cpu_ns", "gang_batches",
    "gang_partitions", "gang_workers", "gang_wait_ns", "gang_merge_ns",
)


def _gang_table(partitions: int):
    from arrow_ballista_tpu.catalog import MemoryTable

    t = pa.table({"g": ["a", "b", "c", "d"] * 3000, "x": [1.0, 2.0, 3.0, 4.0] * 3000})
    per = t.num_rows // partitions
    return MemoryTable([
        t.slice(i * per, per).to_batches(max_chunksize=1000) for i in range(partitions)
    ])


def _run_local_gang(partitions: int = 3) -> dict:
    """One local gang aggregate; returns MeshGangExec's counters."""
    from arrow_ballista_tpu import SessionContext
    from arrow_ballista_tpu.parallel.mesh_stage import MeshGangExec

    ctx = SessionContext(BallistaConfig({
        "ballista.tpu.min_rows": "0", "ballista.shuffle.partitions": "2",
    }))
    ctx.register_table("t", _gang_table(partitions))
    plan = ctx.sql(GANG_SQL).physical_plan()
    out = ctx.execute(plan)
    assert sorted(out.column("s").to_pylist()) == [3000.0, 6000.0, 9000.0, 12000.0]
    stack, gangs = [plan], []
    while stack:
        node = stack.pop()
        if isinstance(node, MeshGangExec):
            gangs.append(node)
        stack.extend(node.children())
    (gang,) = gangs
    return gang.metrics.to_dict()


def test_gang_spans_nest_inside_the_trace_when_obs_is_on():
    trace.configure(enabled=True, process="local")
    trace_id = trace.new_id()
    with trace.root_span("job", trace_id):
        counters = _run_local_gang(partitions=3)
    spans = get_recorder().drain()
    assert {s["trace"] for s in spans} == {trace_id}
    by_id = {s["span"]: s for s in spans}
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    for name in ("gang.stage", "gang.assemble", "gang.step", "gang.fetch", "gang.materialize"):
        assert len(by_name[name]) == 1, name
    parts, hands = by_name["gang.partition"], by_name["gang.handover"]
    assert len(parts) == len(hands) == 3 == counters["gang_partitions"]
    (stage,) = by_name["gang.stage"]
    assert stage["parent"] == trace_id and stage["attrs"]["rows"] == 12000
    assert stage["attrs"]["groups"] == 4 and stage["attrs"]["capacity"] >= 4
    assert "compiled" in by_name["gang.step"][0]["attrs"]
    for s in spans:
        if not s["name"].startswith("gang.") or s is stage:
            continue
        parent = by_id[s["parent"]]
        assert parent is stage, s["name"]
        # wall-clock anchors: a child starts inside its parent's interval
        assert parent["ts"] <= s["ts"] <= parent["ts"] + parent["dur"], s["name"]
    # the spans carry the same numbers the counters sum: a partition's
    # (opened by the worker that prepared it) what the workers count, its
    # hand-over's (the task thread) what the task thread counts; the route
    # probe of the stage's first batch is the rest of scan, encode and wait
    for key, counter in (("convert_ns", "gang_convert_ns"), ("batches", "gang_batches")):
        assert sum(p["attrs"][key] for p in parts) == counters[counter], key
    for key, counter in (("scan_ns", "gang_scan_ns"), ("encode_ns", "key_encode_time_ns")):
        assert 0 < sum(p["attrs"][key] for p in parts) <= counters[counter], key
    for key, counter in (("merge_ns", "gang_merge_ns"), ("upload_ns", "gang_upload_ns"),
                         ("upload_bytes", "gang_upload_bytes")):
        assert sum(h["attrs"][key] for h in hands) == counters[counter], key
    assert 0 < sum(h["attrs"]["wait_ns"] for h in hands) <= counters["gang_wait_ns"]
    for p in parts:
        assert {"rows", "device", "worker", "cpu_ns", "cpu_start", "cpu_end"} <= set(p["attrs"])
    assert [h["attrs"]["partition"] for h in sorted(hands, key=lambda h: h["ts"])] == [0, 1, 2]
    if counters["gang_workers"] > 1:
        assert {p["tid"] for p in parts}.isdisjoint({h["tid"] for h in hands})


def test_gang_stage_makes_no_span_object_when_obs_is_off(monkeypatch):
    made = []

    class CountingSpan(trace.Span):
        def __init__(self, *a, **kw):
            made.append(a[0])
            super().__init__(*a, **kw)

    monkeypatch.setattr(trace, "Span", CountingSpan)
    assert not trace.is_enabled()
    off = _run_local_gang(partitions=3)
    assert made == [] and get_recorder().drain() == []
    # the counters do not depend on the switch
    trace.configure(enabled=True, process="local")
    with trace.root_span("job", trace.new_id()):
        on = _run_local_gang(partitions=3)
    assert "gang.stage" in made
    for counters in (off, on):
        assert set(GANG_COUNTERS) <= set(counters)
    for k in ("gang_uploads", "gang_upload_bytes", "gang_batches", "gang_partitions",
              "mesh_rows_in"):
        assert off[k] == on[k], k


def test_standalone_gang_job_reports_task_run_time_phase_split_and_spans():
    """Through a real scheduler and executor: every stage's root operator
    carries task_run_ns within the scheduler's finish - dispatch, the
    profile's gang stage shows the phase split (from counters), and the
    exported trace holds the gang.* spans under task.execute."""
    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.scheduler.api import ApiServerHandle

    config = dict(OBS_CONFIG)
    config["ballista.mesh.enable"] = "true"
    ctx = BallistaContext.standalone(
        config=BallistaConfig(config), num_executors=1, concurrent_tasks=2,
    )
    try:
        ctx.register_table("t", _gang_table(3))
        out = ctx.sql(GANG_SQL).collect()
        assert sorted(out.column("s").to_pylist()) == [3000.0, 6000.0, 9000.0, 12000.0]
        (job_id,) = ctx._job_ids
        scheduler, _executors = ctx._standalone_handles
        scheduler.server.drain()
        _wait_for_job_span(job_id)
        api = ApiServerHandle(scheduler.server, "127.0.0.1", 0).start()
        try:
            base = f"http://127.0.0.1:{api.port}"
            detail = json.load(urllib.request.urlopen(f"{base}/api/job/{job_id}"))
            prof = json.load(urllib.request.urlopen(f"{base}/api/jobs/{job_id}/profile"))
            tr = json.load(urllib.request.urlopen(f"{base}/api/jobs/{job_id}/trace"))
        finally:
            api.stop()
    finally:
        ctx.close()

    assert len(detail["stages"]) >= 2
    gang_stage_ids = []
    for st in detail["stages"]:
        ops = {k: v for k, v in st["metrics"].items() if not k.startswith("__")}
        root = ops["ShuffleWriterExec"]
        timing = st["timing"]
        scheduler_ns = sum(
            (timing["finish_us"][p] - timing["dispatch_us"][p]) * 1000
            for p in timing["finish_us"]
        )
        # task_run_ns sums over the stage's tasks, like every counter; the
        # scheduler's stamps are whole microseconds
        assert 0 < root["task_run_ns"] <= scheduler_ns + 1000 * len(timing["finish_us"]), st["stage_id"]
        assert [op for op, v in ops.items() if "task_run_ns" in v] == ["ShuffleWriterExec"]
        if "MeshGangExec" in ops:
            gang_stage_ids.append(st["stage_id"])
            assert set(GANG_COUNTERS) <= set(ops["MeshGangExec"])
    (gang_id,) = gang_stage_ids

    (row,) = [s for s in prof["stages"] if s["stage_id"] == gang_id]
    tpu = row["tpu"]
    task_phases = ("gang_wait_ms", "gang_merge_ms", "gang_upload_ms",
                   "gang_assemble_ms", "gang_step_ms", "gang_materialize_ms")
    worker_phases = ("gang_scan_ms", "gang_encode_ms", "gang_convert_ms")
    assert all(tpu[k] >= 0 for k in task_phases + worker_phases) and "compile_ms" in tpu
    assert sum(tpu[k] for k in task_phases) <= tpu["gang_stage_ms"] + 0.01
    assert 1 <= tpu["gang_workers"] <= 3
    assert sum(tpu[k] for k in worker_phases) <= tpu["gang_workers"] * tpu["gang_stage_ms"] + 0.01
    assert tpu["gang_partitions"] == 3 and tpu["gang_uploads"] > 0
    assert tpu["gang_upload_bytes"] > 0
    for other in prof["stages"]:
        if other["stage_id"] != gang_id:
            assert "gang_stage_ms" not in (other.get("tpu") or {})

    slices = {e["args"]["span_id"]: e for e in tr["traceEvents"] if e["ph"] == "X"}
    gang = [e for e in slices.values() if e["name"].startswith("gang.")]
    assert sum(e["name"] == "gang.partition" for e in gang) == 3
    assert sum(e["name"] == "gang.handover" for e in gang) == 3
    assert {"gang.stage", "gang.assemble", "gang.step", "gang.fetch",
            "gang.materialize"} <= {e["name"] for e in gang}
    for e in gang:
        cur, names = e, []
        while cur["args"].get("parent_span_id") in slices:
            cur = slices[cur["args"]["parent_span_id"]]
            names.append(cur["name"])
        assert "task.execute" in names, (e["name"], names)


# =====================================================================
# the profiler's trace: every span reaches it, obs on or off
# =====================================================================
def _host_events(trace_dir) -> list:
    """(name, line, metadata) of every event on the profile's host plane;
    a line is one thread id's."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    return [
        (e.name, i, dict(e.stats))
        for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
        for i, line in enumerate(plane.lines) for e in line.events
    ]


EXCHANGE_SQL = "select a.k, sum(b.v) as s from a join b on a.k = b.k group by a.k"


def _keyed_table(n: int, parts: int, seed: int):
    import numpy as np

    from arrow_ballista_tpu.catalog import MemoryTable

    rng = np.random.default_rng(seed)
    t = pa.table({"k": rng.integers(0, 50, n), "v": rng.random(n)})
    per = -(-n // parts)
    return MemoryTable([t.slice(i * per, per).to_batches() for i in range(parts)])


def test_profiler_trace_holds_the_programs_spans_with_obs_off(tmp_path, monkeypatch):
    """A join whose two inputs go through the device exchange, served by
    a local scheduler and executor with obs OFF, under a profiler
    session: the host plane holds task.execute and the exchange's spans,
    each with its task's job and stage, the pool workers' too; the
    recorder holds nothing."""
    import jax

    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.parallel import mesh_stage

    monkeypatch.setattr(mesh_stage, "_usable_cores", lambda: 4)
    config = {**OBS_CONFIG, "ballista.obs.enabled": "false", "ballista.mesh.enable": "true",
              "ballista.mesh.devices": "4"}
    ctx = BallistaContext.standalone(config=BallistaConfig(config), num_executors=1, concurrent_tasks=2)
    try:
        ctx.register_table("a", _keyed_table(3000, 3, 1))
        ctx.register_table("b", _keyed_table(2000, 3, 2))
        ctx.sql(EXCHANGE_SQL).collect()  # compiled outside the session
        seen = set(ctx._job_ids)
        jax.profiler.start_trace(str(tmp_path))
        try:
            out = ctx.sql(EXCHANGE_SQL).collect()
        finally:
            jax.profiler.stop_trace()
        (job_id,) = set(ctx._job_ids) - seen
    finally:
        ctx.close()
    assert out.num_rows == 50
    assert not trace.is_enabled() and get_recorder().drain() == []

    events = [e for e in _host_events(tmp_path) if e[2].get("job") == job_id]
    names = {name for name, _, _ in events}
    assert {"task.execute", "exchange.device", "exchange.pad", "exchange.upload",
            "exchange.program", "exchange.fetch", "exchange.partition", "shuffle.write"} <= names
    for name, _, meta in events:
        assert "stage" in meta, name
    # the workers prepare partitions on threads of their own, under the
    # job and stage of the task that started them
    workers = [meta for name, _, meta in events if name == "exchange.partition"]
    stages = {meta["stage"] for name, _, meta in events if name == "exchange.device"}
    assert any(meta["worker"].startswith("exchange_") for meta in workers)
    assert {meta["stage"] for meta in workers} == stages
    (up,) = {meta["bytes"] > 0 for name, _, meta in events if name == "exchange.upload"}
    assert up
    # attrs set before the span closed ride along
    assert all("capacity" in meta for name, _, meta in events if name == "exchange.device")


def test_span_is_the_shared_noop_once_the_profiler_stops(tmp_path):
    import jax

    trace.configure(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        on = trace.span("x")
        assert on is not trace.NOOP
        assert trace.manual_span("y") is not trace.NOOP_MANUAL
    finally:
        jax.profiler.stop_trace()
    assert trace.span("x", key="value") is trace.NOOP
    assert trace.manual_span("y") is trace.NOOP_MANUAL
    with on:
        on.set_attr("after", 1)  # a span made while profiling stays usable
    assert get_recorder().drain() == []


def test_importing_the_span_api_loads_no_jax():
    import subprocess
    import sys

    code = ("import sys; import arrow_ballista_tpu.obs.trace as t; "
            "assert t.span('x') is t.NOOP; assert 'jax' not in sys.modules, 'jax'")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_obs_on_spans_reach_the_recorder_and_the_profiler(tmp_path):
    """With obs on, one span reaches both: the recorder as before and the
    profiler with its attrs, late ones too, and its task's job and stage,
    across an explicit parent on another thread and a manual span."""
    import jax

    trace.configure(enabled=True, process="test-proc")
    tid = trace.new_id()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.activate(tid), trace.span("task.execute", job="j7", stage=4) as task:
            task.set_attr("late", 5)
            ms = trace.manual_span("gen", rows=0)

            def worker(ctx):
                with trace.span("in-thread", parent=ctx):
                    pass

            t = threading.Thread(target=worker, args=(trace.current_context(),))
            t.start()
            t.join()
            ms.set_attr("rows", 3)
            ms.finish()
    finally:
        jax.profiler.stop_trace()
    recorded = {s["name"]: s for s in get_recorder().drain()}
    assert set(recorded) == {"task.execute", "gen", "in-thread"}
    assert {s["trace"] for s in recorded.values()} == {tid}
    assert recorded["in-thread"]["parent"] == recorded["task.execute"]["span"]
    seen = {name: (line, meta) for name, line, meta in _host_events(tmp_path)
            if name in recorded}
    assert set(seen) == set(recorded)
    assert seen["task.execute"][1]["late"] == 5
    assert seen["gen"][1]["rows"] == 3
    for name in recorded:
        assert (seen[name][1]["job"], seen[name][1]["stage"]) == ("j7", 4), name
    assert seen["in-thread"][0] != seen["task.execute"][0]
