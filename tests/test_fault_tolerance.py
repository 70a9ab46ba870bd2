"""Fault-tolerance acceptance tests (ISSUE 2).

Uses the deterministic fault-injection harness
(``arrow_ballista_tpu.testing.faults``) to prove that:

* a multi-stage aggregate completes with byte-identical results while
  every stage loses at least one task attempt AND one executor dies
  mid-stage;
* fatal (plan-class) errors still fail fast on attempt 1 with no retry;
* an executor failing ``quarantine_threshold`` tasks in-window receives
  no new reservations until its backoff expires;
* a worker-process crash surfaces as a transient failure and the task
  retries to completion (single-executor exclusion escape hatch).

All injection is seeded/armed explicitly — nothing here is random, and
``BALLISTA_FAULTS`` stays unset outside the one subprocess test, so
tier-1 runs flake-free.
"""

import random
import threading
import time

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from arrow_ballista_tpu.config import BallistaConfig, TaskSchedulingPolicy
from arrow_ballista_tpu.context import SessionContext
from arrow_ballista_tpu.scheduler.backend import MemoryBackend
from arrow_ballista_tpu.scheduler.executor_manager import ExecutorManager
from arrow_ballista_tpu.serde.scheduler_types import (
    ExecutorMetadata,
    ExecutorSpecification,
)
from arrow_ballista_tpu.testing import faults

pytestmark = pytest.mark.faults

SEED = 0xBA11157A  # deterministic job ids etc. (pytest.ini `faults` marker)

EXEC1 = ExecutorMetadata("exec-1", "127.0.0.1", 50051, 50052, ExecutorSpecification(4))
EXEC2 = ExecutorMetadata("exec-2", "127.0.0.2", 50051, 50052, ExecutorSpecification(4))

# CPU-only operator path: the fault machinery under test is
# scheduler/executor-level, not device-level
CPU_CONFIG = {
    "ballista.tpu.enable": "false",
    "ballista.mesh.enable": "false",
    "ballista.shuffle.partitions": "2",
}


@pytest.fixture(autouse=True)
def _clean_faults():
    random.seed(SEED)
    faults.clear()
    yield
    faults.clear()


@pytest.fixture()
def sales_parquet(tmp_path):
    table = pa.table(
        {
            "g": pa.array([f"g{i % 7}" for i in range(400)]),
            "v": pa.array([float(i % 113) for i in range(400)]),
        }
    )
    path = str(tmp_path / "sales.parquet")
    pq.write_table(table, path)
    return path


@pytest.fixture()
def dims_parquet(tmp_path):
    table = pa.table(
        {
            "g": pa.array([f"g{i}" for i in range(7)]),
            "region": pa.array(["north" if i % 2 else "south" for i in range(7)]),
        }
    )
    path = str(tmp_path / "dims.parquet")
    pq.write_table(table, path)
    return path


def _rows(table: pa.Table):
    """Order-independent canonical form (python-level, avoids the broken
    pyarrow sort in this environment)."""
    cols = sorted(table.column_names)
    d = table.to_pydict()
    return sorted(zip(*(d[c] for c in cols)))


# =====================================================================
# 1. end-to-end: task kills every stage + executor dropped mid-stage
# =====================================================================
def test_multistage_job_survives_task_kills_and_executor_drop(
    sales_parquet, dims_parquet
):
    from arrow_ballista_tpu.client.context import BallistaContext

    # join + aggregate: >= 3 shuffle-bounded stages, no sort operator
    # (this environment's pyarrow sort kernel is broken — a pre-existing
    # seed failure unrelated to fault tolerance)
    sql = (
        "SELECT dims.region, SUM(sales.v) AS sv, COUNT(sales.v) AS n "
        "FROM sales JOIN dims ON sales.g = dims.g GROUP BY dims.region"
    )
    local = SessionContext(BallistaConfig(dict(CPU_CONFIG)))
    local.register_parquet("sales", sales_parquet)
    local.register_parquet("dims", dims_parquet)
    expected = local.sql(sql).collect()

    # kill the FIRST attempt of every (job, stage, partition): >=1 task
    # attempt dies per stage, every retry must succeed elsewhere
    seen = set()
    seen_lock = threading.Lock()
    first_task_started = threading.Event()

    def first_attempt_fails(
        job_id="", stage_id=0, partition_id=0, attempt=0, **_
    ):
        first_task_started.set()
        with seen_lock:
            key = (job_id, stage_id, partition_id)
            if attempt == 0 and key not in seen:
                seen.add(key)
                return True
        return False

    faults.arm("executor.execute_task", times=-1, match=first_attempt_fails)
    # and make the shuffle plane limp too: two fetch attempts die mid-job
    faults.arm("shuffle.fetch", times=2)

    ctx = BallistaContext.standalone(
        config=BallistaConfig(dict(CPU_CONFIG)),
        num_executors=2,
        concurrent_tasks=2,
    )
    scheduler, executors = ctx._standalone_handles
    em = scheduler.server.state.executor_manager
    # this test wants retries, not quarantine stalls
    em.quarantine_threshold = 1000
    try:
        ctx.register_parquet("sales", sales_parquet)
        ctx.register_parquet("dims", dims_parquet)

        result = {}

        def run():
            try:
                result["table"] = ctx.sql(sql).collect()
            except Exception as e:  # noqa: BLE001
                result["error"] = e

        t = threading.Thread(target=run, daemon=True)
        t.start()
        # drop one executor mid-stage, deterministically AFTER the first
        # task attempt started (and was killed by injection)
        assert first_task_started.wait(60), "no task ever started"
        victim = executors[1]
        scheduler.server.executor_lost(victim.id, "injected executor drop")
        victim.shutdown()
        t.join(300)
        assert not t.is_alive(), "job did not finish"
        assert "error" not in result, result.get("error")

        assert _rows(result["table"]) == _rows(expected)
        assert faults.hits("executor.execute_task") >= 1

        # retry/quarantine decisions surfaced as metrics on the job table
        tm = scheduler.server.state.task_manager
        assert tm.task_retries_total >= 1
        (job_id,) = ctx._job_ids
        detail = tm.get_job_detail(job_id)
        histogram = detail["attempt_histogram"]
        assert sum(n for a, n in histogram.items() if a >= 1) >= 1
    finally:
        ctx.close()


# =====================================================================
# 2. fatal errors fail fast: attempt 1, no retry
# =====================================================================
def test_fatal_error_fails_fast_without_retry():
    from arrow_ballista_tpu.scheduler.event_loop import EventLoop
    from arrow_ballista_tpu.scheduler.execution_stage import TaskInfo
    from arrow_ballista_tpu.scheduler.executor_manager import (
        ExecutorReservation,
    )
    from arrow_ballista_tpu.scheduler.query_stage_scheduler import (
        JobQueued,
        QueryStageScheduler,
        TaskUpdating,
    )
    from arrow_ballista_tpu.scheduler.state import SchedulerState
    from arrow_ballista_tpu.scheduler.task_manager import NoopLauncher

    state = SchedulerState(
        MemoryBackend(),
        "sched-ft",
        launcher=NoopLauncher(),
        work_dir="/tmp/abt-ft-test",
    )
    loop = EventLoop("ft", 1000, QueryStageScheduler(state))
    loop.start()
    try:
        state.executor_manager.register_executor(EXEC1)
        ctx = state.session_manager.create_session(dict(CPU_CONFIG))
        ctx.register_arrow_table(
            "t",
            pa.table({"g": ["a", "b", "a"], "v": [1.0, 2.0, 3.0]}),
            partitions=2,
        )
        plan = ctx.sql("select g, sum(v) as s from t group by g").logical_plan()
        loop.get_sender().post(JobQueued("job-fatal", ctx.session_id, plan))
        assert loop.drain(5.0)

        assignments, _, _ = state.task_manager.fill_reservations(
            [ExecutorReservation("exec-1")]
        )
        _, task = assignments[0]
        assert task.attempt == 0
        loop.get_sender().post(
            TaskUpdating(
                EXEC1,
                [
                    TaskInfo(
                        task.partition,
                        "failed",
                        "exec-1",
                        error="PlanError: deterministic plan bug",
                        attempt=0,
                    )
                ],
            )
        )
        assert loop.drain(5.0)
        status = state.task_manager.get_job_status("job-fatal")
        assert status["state"] == "failed"
        assert "fatal error" in status["error"]
        assert "deterministic plan bug" in status["error"]
        # attempt 1, zero retries, and the host was NOT blamed
        assert state.task_manager.task_retries_total == 0
        assert not state.executor_manager.is_quarantined("exec-1")
    finally:
        loop.stop()
        state.executor_manager.close()


# =====================================================================
# 3. quarantine: threshold failures in-window -> no reservations until
#    the backoff expires
# =====================================================================
def test_quarantined_executor_gets_no_reservations_until_backoff_expires():
    em = ExecutorManager(
        MemoryBackend(),
        quarantine_threshold=3,
        quarantine_window_s=60.0,
        quarantine_backoff_s=0.4,
    )
    try:
        em.register_executor(EXEC1)
        em.register_executor(EXEC2)

        assert not em.record_task_failure("exec-1")
        assert not em.record_task_failure("exec-1")
        assert em.record_task_failure("exec-1")  # 3rd in-window: quarantined
        assert em.is_quarantined("exec-1")
        assert em.quarantined_executors() == ["exec-1"]
        assert em.quarantines_total == 1

        res = em.reserve_slots(8)
        assert {r.executor_id for r in res} == {"exec-2"}
        em.cancel_reservations(res)

        time.sleep(0.5)  # backoff expired
        assert not em.is_quarantined("exec-1")
        res2 = em.reserve_slots(8)
        assert {r.executor_id for r in res2} == {"exec-1", "exec-2"}
        em.cancel_reservations(res2)
    finally:
        em.close()


def test_quarantine_slide_window_expires_old_failures():
    em = ExecutorManager(
        MemoryBackend(),
        quarantine_threshold=3,
        quarantine_window_s=0.2,
        quarantine_backoff_s=30.0,
    )
    try:
        em.register_executor(EXEC1)
        em.register_executor(EXEC2)
        now = time.time()
        assert not em.record_task_failure("exec-1", now=now)
        assert not em.record_task_failure("exec-1", now=now)
        # the first two failures age out of the window before the third
        assert not em.record_task_failure("exec-1", now=now + 0.5)
        assert not em.is_quarantined("exec-1")
    finally:
        em.close()


def test_sole_alive_executor_never_quarantined():
    """Sidelining the only live executor would deadlock the cluster; its
    failures stay bounded by the per-task attempt budget instead."""
    em = ExecutorManager(
        MemoryBackend(), quarantine_threshold=2, quarantine_backoff_s=30.0
    )
    try:
        em.register_executor(EXEC1)
        for _ in range(5):
            assert not em.record_task_failure("exec-1")
        assert not em.is_quarantined("exec-1")
        # a second executor appears: the already-full window now sticks
        em.register_executor(EXEC2)
        assert em.record_task_failure("exec-1")
        assert em.is_quarantined("exec-1")
    finally:
        em.close()


def test_launch_failures_feed_quarantine_and_expel():
    em = ExecutorManager(
        MemoryBackend(),
        quarantine_threshold=100,  # isolate the launch-failure path
        launch_failure_threshold=3,
    )
    try:
        em.register_executor(EXEC1)
        assert not em.record_launch_failure("exec-1")
        assert not em.record_launch_failure("exec-1")
        # a success in between resets the consecutive counter
        em.record_launch_success("exec-1")
        assert not em.record_launch_failure("exec-1")
        assert not em.record_launch_failure("exec-1")
        assert em.record_launch_failure("exec-1")  # 3rd consecutive
        assert em.take_pending_expulsions() == ["exec-1"]
        assert em.take_pending_expulsions() == []  # drained once
    finally:
        em.close()


def test_launch_failure_requeues_with_exclusion_and_counts():
    """task_manager.launch_tasks failing must hand the tasks back excluded
    from the failing executor and report it to the ExecutorManager."""
    from arrow_ballista_tpu.errors import SchedulerError
    from arrow_ballista_tpu.scheduler.state import SchedulerState
    from arrow_ballista_tpu.scheduler.task_manager import TaskLauncher

    class ExplodingLauncher(TaskLauncher):
        def launch(self, executor, tasks, scheduler_id):
            raise RuntimeError("connection refused")

    state = SchedulerState(
        MemoryBackend(),
        "sched-lf",
        policy=TaskSchedulingPolicy.PUSH_STAGED,
        launcher=ExplodingLauncher(),
        work_dir="/tmp/abt-lf-test",
    )
    try:
        state.executor_manager.register_executor(EXEC1)
        state.executor_manager.register_executor(EXEC2)
        ctx = state.session_manager.create_session(dict(CPU_CONFIG))
        ctx.register_arrow_table(
            "t",
            pa.table({"g": ["a", "b"], "v": [1.0, 2.0]}),
            partitions=2,
        )
        plan = ctx.sql("select g, sum(v) as s from t group by g").logical_plan()
        state.submit_job("job-lf", ctx, plan)

        graph = state.task_manager._cache["job-lf"].graph
        task = graph.pop_next_task("exec-1")
        with pytest.raises(SchedulerError, match="launching"):
            state.task_manager.launch_tasks(EXEC1, [task])
        # the task went back to the pool, excluded from exec-1
        stage = graph.stages[task.partition.stage_id]
        assert stage.task_statuses[task.partition.partition_id] is None
        assert (
            stage.task_exclusions[task.partition.partition_id] == "exec-1"
        )
        # and the failure was routed into the quarantine accounting
        assert len(state.executor_manager._failure_times["exec-1"]) == 1
    finally:
        state.executor_manager.close()


def test_quarantine_resets_in_flight_tasks():
    """An executor quarantined by a failure batch has its other in-flight
    tasks reset (with exclusion) so they re-dispatch immediately."""
    from arrow_ballista_tpu.scheduler.execution_stage import TaskInfo
    from arrow_ballista_tpu.scheduler.state import SchedulerState
    from arrow_ballista_tpu.scheduler.task_manager import NoopLauncher

    state = SchedulerState(
        MemoryBackend(),
        "sched-q",
        launcher=NoopLauncher(),
        work_dir="/tmp/abt-q-test",
    )
    try:
        em = state.executor_manager
        em.quarantine_threshold = 1  # first transient failure quarantines
        em.register_executor(EXEC1)
        em.register_executor(EXEC2)
        ctx = state.session_manager.create_session(dict(CPU_CONFIG))
        ctx.register_arrow_table(
            "t",
            pa.table({"g": ["a", "b", "c"], "v": [1.0, 2.0, 3.0]}),
            partitions=2,
        )
        plan = ctx.sql("select g, sum(v) as s from t group by g").logical_plan()
        state.submit_job("job-q", ctx, plan)
        graph = state.task_manager._cache["job-q"].graph
        t1 = graph.pop_next_task("exec-1")
        t2 = graph.pop_next_task("exec-1")  # second in-flight task
        assert t1 is not None and t2 is not None

        state.update_task_statuses(
            EXEC1,
            [
                TaskInfo(
                    t1.partition, "failed", "exec-1",
                    error="OSError: flaky disk", attempt=0,
                )
            ],
        )
        assert em.is_quarantined("exec-1")
        # BOTH tasks are back in the pool: t1 via retry, t2 via the
        # quarantine reset — and neither can land on exec-1
        stage = graph.stages[t1.partition.stage_id]
        assert stage.task_statuses[t1.partition.partition_id] is None
        assert stage.task_statuses[t2.partition.partition_id] is None
        assert stage.task_exclusions[t2.partition.partition_id] == "exec-1"
        # fill for both executors: the quarantined one gets nothing
        from arrow_ballista_tpu.scheduler.executor_manager import (
            ExecutorReservation,
        )

        assignments, free, _ = state.task_manager.fill_reservations(
            [ExecutorReservation("exec-1"), ExecutorReservation("exec-2")]
        )
        assert {eid for eid, _ in assignments} == {"exec-2"}
        assert [r.executor_id for r in free] == ["exec-1"]
    finally:
        state.executor_manager.close()


# =====================================================================
# 4. worker-process crash: transient, retried, single-executor fallback
# =====================================================================
def test_worker_crash_retries_to_completion(sales_parquet, monkeypatch):
    """Process-isolation worker hard-crashes (os._exit) on every FIRST
    attempt; the parent reports a transient 'worker terminated' failure
    and the retry — necessarily on the same, only executor — succeeds."""
    from arrow_ballista_tpu.client.context import BallistaContext

    monkeypatch.setenv(
        "BALLISTA_FAULTS", "executor.task_runner:-1:exit:attempt=0"
    )
    sql = "SELECT g, SUM(v) AS s FROM sales GROUP BY g"
    local = SessionContext(BallistaConfig(dict(CPU_CONFIG)))
    local.register_parquet("sales", sales_parquet)
    expected = local.sql(sql).collect()

    config = dict(CPU_CONFIG)
    config["ballista.shuffle.partitions"] = "1"
    ctx = BallistaContext.standalone(
        config=BallistaConfig(config),
        num_executors=1,
        concurrent_tasks=1,
        task_isolation="process",
    )
    scheduler, _executors = ctx._standalone_handles
    scheduler.server.state.executor_manager.quarantine_threshold = 1000
    try:
        ctx.register_parquet("sales", sales_parquet)
        out = ctx.sql(sql).collect()
        assert _rows(out) == _rows(expected)
        assert scheduler.server.state.task_manager.task_retries_total >= 1
    finally:
        ctx.close()


# =====================================================================
# 5. harness unit tests
# =====================================================================
def test_fault_point_default_off():
    # nothing armed: free and silent
    faults.fault_point("some.path", anything=1)
    assert faults.hits("some.path") == 0


def test_arm_times_and_hits():
    faults.arm("unit.point", times=2)
    for _ in range(2):
        with pytest.raises(faults.FaultInjected):
            faults.fault_point("unit.point")
    faults.fault_point("unit.point")  # budget spent: no-op
    assert faults.hits("unit.point") == 2


def test_arm_match_predicate():
    faults.arm(
        "unit.match", times=-1, match=lambda stage_id=0, **_: stage_id == 2
    )
    faults.fault_point("unit.match", stage_id=1)
    with pytest.raises(faults.FaultInjected):
        faults.fault_point("unit.match", stage_id=2)
    assert faults.hits("unit.match") == 1


def test_inject_context_manager_and_env_spec():
    with faults.inject("unit.scoped", times=1, message="scoped boom"):
        with pytest.raises(faults.FaultInjected, match="scoped boom"):
            faults.fault_point("unit.scoped")
    faults.fault_point("unit.scoped")  # disarmed on exit

    faults._load_env("unit.env:2,unit.env2,unit.gated:1:raise:attempt=1")
    with pytest.raises(faults.FaultInjected):
        faults.fault_point("unit.env")
    with pytest.raises(faults.FaultInjected):
        faults.fault_point("unit.env2")
    faults.fault_point("unit.gated", attempt=0)  # gated off
    with pytest.raises(faults.FaultInjected):
        faults.fault_point("unit.gated", attempt=1)


def test_fault_injected_classified_transient():
    from arrow_ballista_tpu.scheduler.failure import classify_failure

    assert classify_failure("FaultInjected: fault injected at x") == "transient"
    assert classify_failure("ExecutionError: task worker terminated") == "transient"
    assert classify_failure("PlanError: nope") == "fatal"


# =====================================================================
# 6. attempt / fetch_retries proto serde
# =====================================================================
def test_task_status_serde_carries_attempt_and_fetch_retries():
    from arrow_ballista_tpu.scheduler.execution_stage import TaskInfo
    from arrow_ballista_tpu.scheduler.task_status import (
        task_info_from_proto,
        task_info_to_proto,
    )
    from arrow_ballista_tpu.serde.scheduler_types import PartitionId

    pid = PartitionId("job-s", 1, 0)
    info = TaskInfo(
        pid, "failed", "exec-1", error="OSError: x", attempt=2, fetch_retries=5
    )
    back = task_info_from_proto(task_info_to_proto(info))
    assert back.attempt == 2
    assert back.fetch_retries == 5
    assert back.error == "OSError: x"

    done = TaskInfo(pid, "completed", "exec-1", attempt=1, fetch_retries=3)
    back2 = task_info_from_proto(task_info_to_proto(done))
    assert back2.attempt == 1 and back2.fetch_retries == 3
