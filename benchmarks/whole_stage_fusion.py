"""Whole-stage fusion A/B: one jitted dispatch per map task vs the
per-batch dispatch sequence (ISSUE 19).

Two workloads, each run on IDENTICAL inputs across two configurations:

* ``fused``  — ``ballista.tpu.whole_stage_fusion=true``: the fusion
  planner (``ops/fusion.py``) walks the stage's operator list, finds no
  cut, and ``_run_fused`` executes every retained batch's kernel, the
  cross-batch combine tree and the state pack as ONE ``_timed_jit``
  dispatch (``fused_dispatches == 1`` per task).
* ``per_op`` — knob off: today's sequence, one kernel dispatch + one
  combine per batch, then the separate pack/fetch.  This is the knob
  A/B the acceptance criterion names.

``ballista.tpu.cache_columns=false`` keeps both legs off the
device-resident result cache (whose retained path was already fused for
cache-ELIGIBLE stages) so the A/B isolates exactly what ISSUE 19
generalizes: whole-stage fusion for ordinary, non-cacheable map stages.

Workloads:

* ``run_fusion_q3_bench`` — q3's map-stage shape: scan → date filter →
  revenue projection (``v * (1 - d)``) → partial agg grouped by small
  keys.  Fusion-eligible end to end, so the planner emits ONE segment.
* ``run_fusion_scan_bench`` — scan-heavy scalar shape: selective filter
  + arithmetic projection feeding a global sum/count/min (no groups),
  many small batches — the dispatch-overhead-dominated profile where
  per-batch dispatch costs the most.

Both verify bit-identical results across the legs via a sha-256 row
fingerprint.  Runs on the CPU JAX backend (CI) and on chip unchanged.

Usage: via ``bench_suite.py fusion`` (measurement) or ``dev/tier1.sh
--bench-smoke`` (tiny-input identity/compile smoke via
:func:`run_fusion_smoke`, NOT a measurement).
"""

from __future__ import annotations

import hashlib
import time

import numpy as np
import pyarrow as pa

BASE = {
    "ballista.tpu.enable": "true",
    "ballista.tpu.min_rows": "0",
    # keep both legs off the device result cache: its retained path was
    # already one fused dispatch, and the A/B measures the GENERALIZED
    # fusion for non-cache-eligible stages
    "ballista.tpu.cache_columns": "false",
    "ballista.shuffle.partitions": "1",
}

LEGS = {
    "fused": {"ballista.tpu.whole_stage_fusion": "true"},
    "per_op": {"ballista.tpu.whole_stage_fusion": "false"},
}

_METRIC_KEYS = (
    "fused_segments",
    "fused_ops_per_dispatch",
    "fused_dispatches",
    "fused_degraded",
    "device_time_ns",
    "bridge_time_ns",
    "tpu_stage_time_ns",
    "tpu_fallback",
)


def _canon(tbl: pa.Table):
    cols = [
        np.ascontiguousarray(c.to_numpy(zero_copy_only=False))
        for c in tbl.columns
    ]
    keys = [v for v in cols if v.dtype.kind != "f"]
    if not keys:  # scalar-agg shapes: single row, any order is total
        return cols
    order = np.lexsort(tuple(reversed(keys)))
    return [v[order] for v in cols]


def _fingerprint(tbl: pa.Table) -> str:
    """Order-independent sha of the EXACT row bytes (floats included
    bit-for-bit): equal fingerprints mean bit-identical results."""
    h = hashlib.sha256()
    for v in _canon(tbl):
        h.update(v.tobytes())
    return h.hexdigest()[:16]


def _collect_metrics(plan) -> dict:
    from arrow_ballista_tpu.ops.stage_compiler import TpuStageExec

    agg: dict = {}
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, TpuStageExec):
            for k, v in node.metrics.values.items():
                agg[k] = agg.get(k, 0) + v
        stack.extend(node.children())
    return agg


def _run_leg(tables: dict, sql: str, settings: dict, batch_rows: int,
             iters: int):
    """(best_s, result table, last-iter stage metrics) for one config."""
    from arrow_ballista_tpu import BallistaConfig, SessionContext
    from arrow_ballista_tpu.catalog import MemoryTable

    ctx = SessionContext(
        BallistaConfig({**BASE, "ballista.batch.size": str(batch_rows),
                        **settings})
    )
    for name, t in tables.items():
        ctx.register_table(
            name,
            MemoryTable([t.to_batches(max_chunksize=batch_rows)], t.schema),
        )
    best = None
    out = None
    metrics: dict = {}
    for _ in range(iters):
        plan = ctx.sql(sql).physical_plan()
        t0 = time.perf_counter()
        out = ctx.execute(plan)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
        metrics = _collect_metrics(plan)
    return best, out, {
        k: metrics[k] for k in _METRIC_KEYS if k in metrics
    }


def _ab(tables: dict, sql: str, n_rows: int, metric: str,
        batch_rows: int, iters: int, extra: dict) -> dict:
    times: dict = {}
    outs: dict = {}
    mets: dict = {}
    for leg, settings in LEGS.items():
        times[leg], outs[leg], mets[leg] = _run_leg(
            tables, sql, settings, batch_rows, iters
        )
    # both legs run the SAME per-batch kernels and the same combine tree
    # (fusion changes how many dispatches carry them, not the math), so
    # the sha row fingerprints must match EXACTLY
    identical = _fingerprint(outs["fused"]) == _fingerprint(outs["per_op"])
    return {
        "metric": metric,
        "value": round(n_rows / times["fused"]),
        "unit": "rows/s",
        "vs_baseline": round(times["per_op"] / times["fused"], 3),
        "fused_s": round(times["fused"], 4),
        "per_op_s": round(times["per_op"], 4),
        "rows": n_rows,
        "identical": identical,
        "fused_metrics": mets["fused"],
        "per_op_metrics": mets["per_op"],
        **extra,
    }


def run_fusion_q3_bench(
    n_rows: int = 131_072,
    batch_rows: int = 4_096,
    iters: int = 3,
    seed: int = 7,
) -> dict:
    """q3's map-stage shape: date filter → revenue projection → grouped
    partial agg, in one fused segment.  Small batches on purpose — the
    per-batch leg pays one dispatch + one combine per batch, the fused
    leg pays one dispatch total (<= _FUSED_MAX_ENTRIES batches so the
    unroll discipline admits the whole partition)."""
    rng = np.random.default_rng(seed)
    t = pa.table({
        "p": pa.array(rng.integers(0, 7, n_rows).astype(np.int64)),
        "d": pa.array(rng.uniform(0, 0.1, n_rows)),
        "v": pa.array(rng.uniform(1, 100, n_rows)),
        "ship": pa.array(rng.integers(9000, 9400, n_rows).astype(np.int64)),
    })
    sql = (
        "select p, sum(v * (1 - d)) as revenue, count(*) as c "
        "from t where ship < 9200 group by p"
    )
    return _ab(
        {"t": t}, sql, n_rows, "fusion_q3_rows_per_sec", batch_rows,
        iters, {"shape": "q3_map"},
    )


def run_fusion_scan_bench(
    n_rows: int = 32_768,
    batch_rows: int = 1_024,
    iters: int = 3,
    seed: int = 11,
) -> dict:
    """Scan-heavy scalar shape: selective filter + projection into a
    global aggregate — no groups, dispatch overhead dominates."""
    rng = np.random.default_rng(seed)
    t = pa.table({
        "q": pa.array(rng.integers(1, 50, n_rows).astype(np.float64)),
        "v": pa.array(rng.uniform(-100, 100, n_rows)),
        "w": pa.array(rng.uniform(0, 1, n_rows)),
    })
    sql = (
        "select sum(v * w) as s, count(*) as c, min(v) as mn "
        "from t where q < 24"
    )
    return _ab(
        {"t": t}, sql, n_rows, "fusion_scan_rows_per_sec", batch_rows,
        iters, {"shape": "scan_heavy"},
    )


def run_fusion_smoke() -> dict:
    """Tiny-input smoke for dev/tier1.sh --bench-smoke: the fused and
    per-op legs must be BIT-identical, the fused leg must plan ONE
    segment covering >1 operator and execute it as ONE dispatch per task
    (zero host round-trips between fused ops — a second segment or a
    degrade counter would betray one), with no CPU fallback.  A
    compile/regression check, not a measurement."""
    q3 = run_fusion_q3_bench(n_rows=24_576, batch_rows=4_096, iters=1)
    scan = run_fusion_scan_bench(n_rows=24_576, batch_rows=4_096, iters=1)
    for rec in (q3, scan):
        assert rec["identical"], f"{rec['metric']}: legs diverged"
        fm = rec["fused_metrics"]
        # one segment, one dispatch: no host hop between fused operators
        assert fm.get("fused_segments", 0) == 1, fm
        assert fm.get("fused_ops_per_dispatch", 0) > 1, fm
        assert fm.get("fused_dispatches", 0) == 1, fm
        assert fm.get("fused_degraded", 0) == 0, fm
        assert fm.get("tpu_fallback", 0) == 0, fm
        # knob off: the planner never ran
        assert rec["per_op_metrics"].get("fused_segments", 0) == 0, rec
    return {
        "fusion_q3_vs_per_op": q3["vs_baseline"],
        "fusion_scan_vs_per_op": scan["vs_baseline"],
        "fused_ops_per_dispatch": (
            q3["fused_metrics"]["fused_ops_per_dispatch"]
        ),
        "identical": True,
    }


if __name__ == "__main__":
    import json

    print(json.dumps(run_fusion_q3_bench()))
    print(json.dumps(run_fusion_scan_bench()))
