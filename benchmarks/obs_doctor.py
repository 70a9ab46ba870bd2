"""Observability overhead + query-doctor smoke (ISSUE 13).

Two entry points:

* :func:`run_obs_bench` — re-measures the obs planes' cost with the new
  attribution pass in the picture (PR 3 methodology: price the code the
  hot path actually runs against the bench_suite shuffle leg, rather
  than trusting a noisy wall-clock A/B):

  - **disabled path**: the per-call cost of the disabled span API plus
    the scheduler's new per-task timestamp anchors (two ``time.time_ns``
    reads per task), charged at the shuffle leg's call counts;
  - **enabled path**: the full attribution pass (``obs.doctor.
    job_report`` — profile + critical path + doctor) timed over a real
    completed job's detail.  The pass runs ON DEMAND (REST/explain
    requests), never per task, so its cost is reported both absolute and
    relative to the shuffle leg.

  Emits ``obs_overhead_pct`` (acceptance: < 2% of the shuffle leg) with
  a ``breakdown`` field carrying the measured job's category breakdown —
  the trajectory report renders its dominant categories.

* :func:`run_doctor_smoke` — tier-1 ``--bench-smoke`` gate: a tiny
  standalone job whose ``/api/jobs/{id}/critical_path`` must return a
  path whose category sum is within tolerance of wall-clock, and at
  least one doctor finding on a manufactured skewed input.
"""

from __future__ import annotations

import json
import time
import urllib.request

import pyarrow as pa

CLUSTER_CONFIG = {
    "ballista.obs.enabled": "true",
    "ballista.mesh.enable": "false",
    "ballista.shuffle.partitions": "2",
    "ballista.tpu.min_rows": "0",
}


def _run_cluster_job(extra_config=None, straggler_ms: int = 0):
    """One tiny standalone group-by; returns (cp, profile, wall_info)
    read over real HTTP.  ``straggler_ms`` arms a task.run delay fault
    on partition 1 (the manufactured skew input)."""
    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.config import BallistaConfig
    from arrow_ballista_tpu.context import MemoryTable
    from arrow_ballista_tpu.scheduler.api import ApiServerHandle
    from arrow_ballista_tpu.testing import faults

    cfg = dict(CLUSTER_CONFIG)
    cfg.update(extra_config or {})
    ctx = BallistaContext.standalone(
        config=BallistaConfig(cfg), num_executors=2, concurrent_tasks=2
    )
    try:
        ctx.register_table(
            "t",
            MemoryTable.from_table(
                pa.table(
                    {
                        "g": ["a", "b", "c", "d"] * 250,
                        "x": [1.0, 2.0, 3.0, 4.0] * 250,
                    }
                ),
                2,
            ),
        )
        if straggler_ms:
            faults.arm(
                "task.run",
                times=1,
                action="delay",
                delay_ms=straggler_ms,
                match=lambda partition_id=0, speculative=False, **_:
                    partition_id == 1 and not speculative,
            )
        ctx.sql("select g, sum(x) as s from t group by g").collect()
        (job_id,) = ctx._job_ids
        scheduler, _ = ctx._standalone_handles
        scheduler.server.drain()
        detail = scheduler.server.state.task_manager.get_job_detail(job_id)
        api = ApiServerHandle(scheduler.server, "127.0.0.1", 0).start()
        try:
            base = f"http://127.0.0.1:{api.port}"
            cp = json.load(
                urllib.request.urlopen(
                    f"{base}/api/jobs/{job_id}/critical_path"
                )
            )
            prof = json.load(
                urllib.request.urlopen(f"{base}/api/jobs/{job_id}/profile")
            )
        finally:
            api.stop()
        return cp, prof, detail
    finally:
        faults.clear()
        ctx.close()


def _shuffle_leg_ns() -> tuple:
    """The PR 3 pricing denominator: the instrumented fetch path driven
    the way benchmarks/shuffle_fetch.py does, obs off.  Returns
    (leg_ns, n_locations)."""
    from arrow_ballista_tpu.obs import trace
    from arrow_ballista_tpu.shuffle.fetcher import FetchPolicy, ShuffleFetcher

    trace.configure(enabled=False)

    class _Loc:
        path = ""

    class _M:
        def add(self, *a):
            pass

    n_locations, batches_per_loc = 32, 8
    batch = pa.record_batch([pa.array(list(range(256)))], names=["x"])

    def fetch_fn(loc):
        for _ in range(batches_per_loc):
            yield batch

    def run_leg() -> float:
        t0 = time.perf_counter_ns()
        fetcher = ShuffleFetcher(
            [_Loc() for _ in range(n_locations)],
            FetchPolicy(concurrency=8),
            _M(),
            fetch_fn=fetch_fn,
        )
        sum(b.num_rows for b in fetcher)
        return time.perf_counter_ns() - t0

    run_leg()  # warm
    return min(run_leg() for _ in range(3)), n_locations


def run_obs_bench() -> dict:
    from arrow_ballista_tpu.obs import trace
    from arrow_ballista_tpu.obs.doctor import job_report

    leg_ns, n_locations = _shuffle_leg_ns()

    # disabled span API per-call cost (one global read + return NOOP)
    calls = 100_000
    t0 = time.perf_counter_ns()
    for _ in range(calls):
        trace.span("x")
    span_call_ns = (time.perf_counter_ns() - t0) / calls
    # the new timestamp anchors: two wall-clock reads + dict stores per
    # task attempt (dispatch + commit), always on
    t0 = time.perf_counter_ns()
    anchors: dict = {}
    for i in range(calls):
        anchors[i & 63] = time.time_ns()
    anchor_ns = (time.perf_counter_ns() - t0) / calls
    # charge like PR 3: 3 span entries per location + 8, plus 2 anchor
    # writes per location-as-task (a leg task is at most one location)
    disabled_ns = (3 * n_locations + 8) * span_call_ns + (
        2 * n_locations
    ) * anchor_ns
    disabled_pct = 100.0 * disabled_ns / leg_ns

    # enabled path: the full attribution pass over a real completed job
    cp, prof, detail = _run_cluster_job()
    t0 = time.perf_counter_ns()
    iters = 50
    for _ in range(iters):
        job_report(detail, [], [])
    attribution_ms = (time.perf_counter_ns() - t0) / iters / 1e6
    attribution_pct = 100.0 * (attribution_ms * 1e6) / leg_ns

    return {
        "metric": "obs_overhead_pct",
        "value": round(disabled_pct, 4),
        "unit": "% of shuffle leg",
        "disabled_span_call_ns": round(span_call_ns, 1),
        "timestamp_anchor_ns": round(anchor_ns, 1),
        "shuffle_leg_ms": round(leg_ns / 1e6, 3),
        "attribution_pass_ms": round(attribution_ms, 3),
        "attribution_pct_of_shuffle_leg": round(attribution_pct, 3),
        "job_wall_clock_ms": cp.get("wall_clock_ms"),
        "coverage": cp.get("coverage"),
        # the measured job's category breakdown rides the record, to be
        # read next to the overhead number
        "breakdown": cp.get("breakdown"),
    }


def run_doctor_smoke(tolerance: float = 0.05) -> dict:
    """Tier-1 gate: breakdown sums to wall-clock within ``tolerance``
    and the doctor fires on a manufactured skewed input.  The straggler
    delay must dominate the fast task's runtime INCLUDING its first-run
    XLA compile (~300ms on a slow box), or max/median can land under the
    skew coefficient and the gate flakes."""
    cp, prof, _detail = _run_cluster_job(straggler_ms=1500)
    assert cp.get("complete") is True, f"incomplete attribution: {cp}"
    wall = cp["wall_clock_ms"]
    total = cp["breakdown_total_ms"]
    assert wall > 0 and abs(total - wall) <= tolerance * wall, (
        f"breakdown {total}ms vs wall {wall}ms outside {tolerance:.0%}"
    )
    assert cp["breakdown"]["scheduling_delay_ms"] > 0
    skew = [f for f in cp.get("doctor", []) if f["code"] == "skewed_stage"]
    assert skew, f"manufactured straggler produced no skew finding: {cp['doctor']}"
    stage_ids = {s["stage_id"] for s in prof["stages"]}
    assert skew[0]["stage_id"] in stage_ids
    assert skew[0]["evidence"]["slowest_partition"] == 1
    return {
        "wall_clock_ms": wall,
        "breakdown_total_ms": total,
        "coverage": cp.get("coverage"),
        "findings": [f["code"] for f in cp.get("doctor", [])],
        "skew_stage": skew[0]["stage_id"],
    }
