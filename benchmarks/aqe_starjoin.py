"""AQE benchmark legs: skewed star join + tiny-partition aggregate.

Two workloads on a real standalone cluster (scheduler + executors over
gRPC/Flight), each run twice on IDENTICAL inputs — ``ballista.aqe.
enabled=false`` (static plans, the A/B baseline) vs ``true`` — so the
emitted ``vs_baseline`` isolates exactly the re-planning effect:

* ``run_aqe_starjoin`` — a fact table whose join key is heavily skewed
  (a tunable fraction of all rows share one hot key) joined against a
  small dim and aggregated.  Static plans serialize the hot reduce
  partition into one straggler task (BENCH_SUITE_r05's starjoin at
  0.592x vs CPU is exactly this shape).  The ``on`` config is the full
  production policy with skew splitting opted in — default-on
  coalescing packs the many near-empty reduce partitions (usually the
  bigger win at bench scale) and skew splitting spreads the hot
  partition's map-side fragments across tasks; the emitted record
  carries the most-rewritten stage's task counts plus a separate
  ``skew_splits`` count so the two rewrites stay distinguishable.
* ``run_aqe_tiny_agg`` — a small group-by shuffled over many reduce
  partitions; AQE coalescing collapses the reduce side to
  ceil(total_bytes / target_partition_bytes) tasks.

Both verify bit-identical results between the two runs (multiset of
rows) and report the before/after reduce-task counts read from the
job's AQE stage summary.

Usage: via ``bench_suite.py aqe`` (measurement) or ``dev/tier1.sh
--bench-smoke`` (tiny-input compile/regression smoke via
:func:`run_aqe_smoke`, NOT a measurement).
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

BASE = {
    "ballista.tpu.enable": "false",
}


def _write_parts(table: pa.Table, d: str, n_parts: int) -> None:
    os.makedirs(d, exist_ok=True)
    per = (table.num_rows + n_parts - 1) // n_parts
    for i in range(n_parts):
        pq.write_table(table.slice(i * per, per), os.path.join(d, f"p{i}.parquet"))


def _gen_star(root: str, n_fact: int, n_dim: int, skew: float, seed: int = 7):
    rng = np.random.default_rng(seed)
    hot = np.where(
        rng.random(n_fact) < skew, 0, rng.integers(0, n_dim, n_fact)
    ).astype(np.int64)
    fact = pa.table(
        {
            "k": hot,
            "v": rng.random(n_fact),
            "g": pa.array((np.arange(n_fact) % 13).astype(np.int64)),
        }
    )
    dim = pa.table(
        {
            "k": pa.array(np.arange(n_dim, dtype=np.int64)),
            "w": pa.array([f"w{i % 29}" for i in range(n_dim)]),
        }
    )
    fact_dir, dim_dir = os.path.join(root, "fact"), os.path.join(root, "dim")
    _write_parts(fact, fact_dir, 4)
    _write_parts(dim, dim_dir, 1)
    return fact_dir, dim_dir


def _rows_fingerprint(tbl: pa.Table) -> str:
    import hashlib

    rows = sorted(
        tuple(round(x, 9) if isinstance(x, float) else x for x in r)
        for r in zip(*[c.to_pylist() for c in tbl.columns])
    )
    return hashlib.sha256(repr(rows).encode()).hexdigest()[:16]


def _run_once(
    tables: dict,
    sql: str,
    settings: dict,
    executors: int,
    slots: int,
):
    """One clustered run; returns (elapsed_s, result table, aqe summary
    of the most-rewritten stage or None)."""
    from arrow_ballista_tpu.client import BallistaContext
    from arrow_ballista_tpu.config import BallistaConfig

    ctx = BallistaContext.standalone(
        config=BallistaConfig(settings),
        num_executors=executors,
        concurrent_tasks=slots,
    )
    try:
        for name, path in tables.items():
            ctx.register_parquet(name, path)
        t0 = time.perf_counter()
        out = ctx.sql(sql).collect()
        elapsed = time.perf_counter() - t0
        sched, _ = ctx._standalone_handles
        detail = sched.server.state.task_manager.get_job_detail(
            next(iter(ctx._job_ids))
        )
        aqe = [
            row["aqe"] for row in detail.get("stages", []) if row.get("aqe")
        ]
        return elapsed, out, aqe
    finally:
        ctx.close()


def _ab(tables, sql, on_settings, off_settings, executors, slots, iters):
    """A/B the two configs; best-of-``iters`` wall time each."""
    best_off = best_on = None
    fp_off = fp_on = None
    aqe = None
    for _ in range(iters):
        t, out, _ = _run_once(tables, sql, off_settings, executors, slots)
        best_off = t if best_off is None else min(best_off, t)
        fp_off = _rows_fingerprint(out)
    for _ in range(iters):
        t, out, info = _run_once(tables, sql, on_settings, executors, slots)
        best_on = t if best_on is None else min(best_on, t)
        fp_on = _rows_fingerprint(out)
        aqe = info or aqe
    return best_off, best_on, fp_off == fp_on, aqe


def run_aqe_starjoin(
    n_fact: int = 300_000,
    n_dim: int = 2_000,
    skew: float = 0.5,
    partitions: int = 24,
    executors: int = 2,
    slots: int = 2,
    iters: int = 2,
    data_dir: str | None = None,
) -> dict:
    root = data_dir or tempfile.mkdtemp(prefix="aqe-starjoin-")
    made = data_dir is None
    try:
        fact_dir, dim_dir = _gen_star(root, n_fact, n_dim, skew)
        sql = (
            "select d.w, sum(f.v) as s, count(*) as c "
            "from fact f join dim d on f.k = d.k group by d.w"
        )
        common = {**BASE, "ballista.shuffle.partitions": str(partitions)}
        on = {
            **common,
            "ballista.aqe.enabled": "true",
            "ballista.aqe.skew_enabled": "true",
            "ballista.aqe.skew_factor": "2.0",
            # the hot partition should split well below the default
            # 16 MiB on bench-sized inputs
            "ballista.aqe.target_partition_bytes": str(256 << 10),
        }
        off = {**common, "ballista.aqe.enabled": "false"}
        t_off, t_on, identical, aqe = _ab(
            {"fact": fact_dir, "dim": dim_dir}, sql, on, off,
            executors, slots, iters,
        )
        out = {
            "metric": "aqe_starjoin_rows_per_sec",
            "value": round(n_fact / t_on),
            "unit": "rows/sec",
            "vs_baseline": round(t_off / t_on, 3),
            "baseline_s": round(t_off, 3),
            "aqe_s": round(t_on, 3),
            "rows": n_fact,
            "skew": skew,
            "identical": identical,
        }
        if aqe:
            top = max(
                aqe,
                key=lambda i: abs(i["tasks_after"] - i["tasks_before"]),
            )
            out["tasks_before"] = top["tasks_before"]
            out["tasks_after"] = top["tasks_after"]
            # most of the task-count delta above is coalescing; report
            # the split rewrite separately so it isn't conflated
            splits = sum(i.get("skew_splits", 0) for i in aqe)
            if splits:
                out["skew_splits"] = splits
                out["skewed_partitions"] = sum(
                    i.get("skewed_partitions", 0) for i in aqe
                )
        return out
    finally:
        if made:
            shutil.rmtree(root, ignore_errors=True)


def run_aqe_tiny_agg(
    n_rows: int = 60_000,
    partitions: int = 64,
    executors: int = 2,
    slots: int = 2,
    iters: int = 2,
    data_dir: str | None = None,
) -> dict:
    root = data_dir or tempfile.mkdtemp(prefix="aqe-tinyagg-")
    made = data_dir is None
    try:
        rng = np.random.default_rng(3)
        tbl = pa.table(
            {
                "g": pa.array(rng.integers(0, 500, n_rows).astype(np.int64)),
                "v": rng.random(n_rows),
            }
        )
        td = os.path.join(root, "t")
        _write_parts(tbl, td, 2)
        sql = "select g, sum(v) as s, count(*) as c from t group by g"
        common = {**BASE, "ballista.shuffle.partitions": str(partitions)}
        on = {**common, "ballista.aqe.enabled": "true"}
        off = {**common, "ballista.aqe.enabled": "false"}
        t_off, t_on, identical, aqe = _ab(
            {"t": td}, sql, on, off, executors, slots, iters
        )
        out = {
            "metric": "aqe_tiny_agg_rows_per_sec",
            "value": round(n_rows / t_on),
            "unit": "rows/sec",
            "vs_baseline": round(t_off / t_on, 3),
            "baseline_s": round(t_off, 3),
            "aqe_s": round(t_on, 3),
            "rows": n_rows,
            "identical": identical,
        }
        if aqe:
            top = max(
                aqe,
                key=lambda i: abs(i["tasks_after"] - i["tasks_before"]),
            )
            out["tasks_before"] = top["tasks_before"]
            out["tasks_after"] = top["tasks_after"]
        return out
    finally:
        if made:
            shutil.rmtree(root, ignore_errors=True)


def run_aqe_smoke() -> dict:
    """Tiny-input smoke for dev/tier1.sh --bench-smoke: both legs must
    produce IDENTICAL results with and without AQE and at least one
    replan must fire.  A compile/regression check, not a measurement."""
    star = run_aqe_starjoin(
        n_fact=20_000, n_dim=200, partitions=12, executors=1, slots=2,
        iters=1,
    )
    agg = run_aqe_tiny_agg(
        n_rows=8_000, partitions=16, executors=1, slots=2, iters=1
    )
    assert star["identical"], "AQE starjoin results diverged from static"
    assert agg["identical"], "AQE tiny-agg results diverged from static"
    assert agg.get("tasks_after", 99) < agg.get("tasks_before", 0), (
        "tiny-partition aggregate did not coalesce"
    )
    return {
        "starjoin_vs_baseline": star["vs_baseline"],
        "starjoin_tasks": f"{star.get('tasks_before')}→{star.get('tasks_after')}",
        "tiny_agg_vs_baseline": agg["vs_baseline"],
        "tiny_agg_tasks": f"{agg.get('tasks_before')}→{agg.get('tasks_after')}",
        "identical": True,
    }


if __name__ == "__main__":
    import json

    print(json.dumps(run_aqe_starjoin()))
    print(json.dumps(run_aqe_tiny_agg()))
