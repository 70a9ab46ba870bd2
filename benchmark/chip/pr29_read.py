"""What PR 29's chip runs are read with: per run of a phase directory (the
``<tag>.out`` files ``pr29.sh`` leaves), the end-to-end numbers, whether the
run was correct, and the gang stage's per-layer readings of a traced run;
then, per kept run directory and query kind, the client latency beside the
gang stage's wall as the task saw it and every phase counter (window means).

    python3 benchmark/chip/pr29_read.py chiprun_out/pr29/<phase>
"""

import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.chip.phases import PHASES, window_queries  # noqa: E402
from benchmark.metrics import _gang  # noqa: E402

TASK_THREAD = ("gang_wait_ns", "gang_merge_ns", "gang_upload_ns", "gang_assemble_ns",
               "gang_step_ns", "gang_materialize_ns")
WORKERS = ("gang_scan_ns", "key_encode_time_ns", "gang_convert_ns")

SHOWN = (
    "query_geomean_s", "scan_rows_rate", "setup_s", "gang_workers", "gang_wait_ms", "gang_merge_ms",
    "gang_upload_ms", "gang_uploads", "gang_assemble_ms", "gang_step_ms", "gang_materialize_ms",
    "gang_scan_ms", "gang_encode_ms", "gang_convert_ms", "gang_unaccounted_share", "gang_cpu_share",
    "task_overhead_ms", "sched_delay_ms", "client_notice_delay_ms", "device_idle_share",
    "scan_roofline", "peak_hbm_GB", "window_xla_compiles", "warmup_compile_s", "device_route_share",
    "cpu_ops_geomean_s", "collective_ms", "exchange_ms", "device_stage_ms", "shuffle_ms",
)


def main(out_dir: str) -> None:
    for path in sorted(glob.glob(os.path.join(out_dir, "*.out"))):
        lines = open(path).read().strip().splitlines()
        tag = os.path.basename(path)[:-4]
        if not lines or not lines[-1].startswith("{"):
            print(json.dumps({"run": tag, "result": None}))
            continue
        r = json.loads(lines[-1])
        m = {k: v["value"] for k, v in r["metrics"].items()}
        row = {"run": tag, "correct": r["correct"], "failed": r["failed"], "attempted": r["attempted"],
               "rel_gap_max": r["compared"]["rel_gap_max"][0], "cells_wrong": r["compared"]["cells_wrong"][0],
               "device": f"{r['device']['kind']} x{r['device']['count']}",
               "memory_peak_bytes": r["device"].get("memory_peak_bytes"),
               "busy_s/window_s": [r["device"].get("busy_s"), r["device"].get("window_s")]}
        row.update({k: m[k] for k in SHOWN if k in m})
        lat = r["window"].get("latencies_s", {})
        row["latency_mean_s"] = {k: sum(v) / len(v) for k, v in lat.items() if v}
        print(json.dumps(row))
    for kept in sorted(d for d in glob.glob(os.path.join(out_dir, "*")) if os.path.isdir(d)):
        by_kind(kept)


def by_kind(kept: str) -> None:
    queries = window_queries(kept)
    for kind in sorted({q["kind"] for q in queries}):
        qs = [q for q in queries if q["kind"] == kind]
        run = {"window": qs}
        wall = _gang.per_query(run, _gang.WALL, 1e6)
        if wall is None:
            continue
        row = {"run": os.path.basename(kept), "kind": f"q{kind}", "n": len(qs),
               "latency_s": sum(q["latency_s"] for q in qs) / len(qs), "gang_wall_ms": wall,
               "gang_workers": _gang.per_query(run, "gang_workers")}
        for k in TASK_THREAD + WORKERS + ("gang_cpu_ns",):
            row[k.replace("_time_ns", "_ms").replace("_ns", "_ms")] = _gang.per_query(run, k, 1e6)
        task = [_gang.per_query(run, k, 1e6) for k in TASK_THREAD]
        if None not in task:  # the change: what the task thread's six phases leave of the wall
            row["task_thread_rest_%"] = 100.0 * (wall - sum(task)) / wall
        else:  # the parent: its seven self times
            row["seven_phases_rest_%"] = _gang.share_of_wall(run, PHASES, rest=True)
        stages: dict = {}
        for q in qs:
            for st in q["job"]["stages"]:
                if st["start_us"] is not None and st["end_us"] is not None:
                    stages[st["stage_id"]] = stages.get(st["stage_id"], 0.0) + (st["end_us"] - st["start_us"]) / 1e3 / len(qs)
        row["stage_wall_ms"] = {k: round(v, 1) for k, v in sorted(stages.items())}
        print(json.dumps({k: round(v, 3) if isinstance(v, float) else v for k, v in row.items()}))


if __name__ == "__main__":
    main(sys.argv[1])
