"""Benchmark: TPC-H q1 fused TPU stage vs the CPU operator path.

Prints ONE JSON line:
  {"metric": ..., "value": rows/sec on the accelerated path, "unit": "rows/s",
   "vs_baseline": speedup over the CPU (reference-architecture) path,
   "platform": ..., "dtype": ..., "breakdown": {...}}

Failure policy: the device leg measures on the platform that was asked for
(``benchmarks/device_guard.py``) or the run fails.  When the device leg
throws, the line carries ``"error"``, ``value`` stays null and the process
exits non-zero — a CPU number is never printed under the device metric's
name.  ``JAX_PLATFORMS=cpu`` is an intentional, labelled CPU run.

Scale factor via BENCH_SF (default 10 -> 60M lineitem rows); iterations via
BENCH_ITERS (default 5, best-of).
"""

import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

RESULT = {
    "metric": "tpch_q1_rows_per_sec",
    "value": None,
    "unit": "rows/s",
    "vs_baseline": None,
}


def _emit() -> None:
    print(json.dumps(RESULT), flush=True)


def _collect_stage_metrics(plan) -> dict:
    """Walk the executed physical plan and sum device-stage metric timers."""
    from arrow_ballista_tpu.ops.stage_compiler import TpuStageExec
    from arrow_ballista_tpu.parallel.mesh_stage import MeshGangExec

    agg: dict = {}
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, (TpuStageExec, MeshGangExec)):
            for k, v in node.metrics.values.items():
                agg[k] = agg.get(k, 0) + v
        stack.extend(node.children())
    return agg


def main() -> None:
    # default SF10 = BASELINE.md config #2 (q1 SF10): the per-row rate is
    # only meaningful at a scale where dispatch overhead is amortized
    sf = float(os.environ.get("BENCH_SF", "10"))
    iters = int(os.environ.get("BENCH_ITERS", "5"))

    from arrow_ballista_tpu import BallistaConfig, SessionContext
    from arrow_ballista_tpu.catalog import MemoryTable
    from benchmarks.tpch.datagen import gen_lineitem
    from benchmarks.tpch.queries import QUERIES

    lineitem = gen_lineitem(sf)
    n_rows = lineitem.num_rows
    RESULT["rows"] = n_rows

    def run(tpu: bool):
        """Return (best seconds, result table, executed plan)."""
        cfg = BallistaConfig(
            {
                "ballista.tpu.enable": "true" if tpu else "false",
                # one big batch per partition: the fused kernel wants large
                # device invocations; the CPU path is batch-size agnostic
                "ballista.batch.size": str(1 << 23),
                "ballista.shuffle.partitions": "1",
            }
        )
        ctx = SessionContext(cfg)
        ctx.register_table("lineitem", MemoryTable.from_table(lineitem, 1))
        df = ctx.sql(QUERIES[1])
        best = float("inf")
        result = None
        plan = None
        for _ in range(iters):
            plan = df.physical_plan()
            t0 = time.perf_counter()
            result = ctx.execute(plan)
            dt = time.perf_counter() - t0
            best = min(best, dt)
        assert result is not None and result.num_rows > 0
        return best, result, plan

    # the platform asked for, or no run at all (first backend touch)
    from benchmarks.device_guard import require_device

    platform = require_device()
    # the metric is named for the platform that produced it
    RESULT["metric"] = "tpch_q1_sf%g_%s_rows_per_sec" % (sf, platform)

    # ---- CPU (reference-architecture) leg: the baseline, never `value`
    cpu_t, cpu_table, _ = run(False)
    RESULT["cpu_rows_per_sec"] = round(n_rows / cpu_t)

    import numpy as np

    from arrow_ballista_tpu.ops import kernels as K

    RESULT["platform"] = RESULT["device_platform"] = platform
    RESULT["precision_mode"] = K.precision_mode()
    RESULT["dtype"] = np.dtype(K.value_dtype()).name

    # ---- device leg: a failure here propagates (error + exit 1)
    run(True)  # first call pays jit compile
    tpu_t, tpu_table, plan = run(True)

    RESULT["value"] = round(n_rows / tpu_t)
    RESULT["vs_baseline"] = round(cpu_t / tpu_t, 3)

    # correctness oracle on-chip: q1 result must match the CPU path
    try:
        import pyarrow.compute as pc

        a = cpu_table.sort_by([(cpu_table.column_names[0], "ascending")])
        b = tpu_table.sort_by([(tpu_table.column_names[0], "ascending")])
        ok = a.num_rows == b.num_rows
        if ok:
            for name in a.column_names:
                ca, cb = a[name].to_pylist(), b[name].to_pylist()
                for x, y in zip(ca, cb):
                    if isinstance(x, float) and isinstance(y, float):
                        scale = max(abs(x), abs(y), 1.0)
                        if abs(x - y) / scale > 1e-6:
                            ok = False
                            break
                    elif x != y:
                        ok = False
                        break
                if not ok:
                    break
        RESULT["matches_cpu_1e-6"] = bool(ok)
    except Exception as e:
        RESULT["matches_cpu_1e-6"] = "check failed: %s" % str(e)[:200]

    # host-prep vs device breakdown
    if plan is not None:
        m = _collect_stage_metrics(plan)
        if m:
            RESULT["breakdown"] = {
                k: m[k]
                for k in (
                    "bridge_time_ns",
                    "key_encode_time_ns",
                    "device_time_ns",
                    "tpu_stage_time_ns",
                    "tpu_fallback",
                    "cpu_fallback",
                    "device_error",
                )
                if k in m
            }


if __name__ == "__main__":
    try:
        main()
    except Exception as e:
        RESULT["value"] = RESULT["vs_baseline"] = None
        RESULT["error"] = "fatal: %s" % str(e)[:400]
        traceback.print_exc(file=sys.stderr)
        _emit()
        sys.exit(1)
    _emit()
