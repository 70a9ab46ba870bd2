"""What PR 33's chip runs are read with: per run of a phase directory (the
``<tag>.out`` files ``pr33.sh`` leaves) one line with the end-to-end or
per-layer numbers, what was compared, the completions by kind and by client
and each kind's mean latency; then, per kept run, each kind apart: the gang
stage's phases of its q1s and q6s (``pr29_read.by_kind``), the stages of its
q3s (``q3_phases``), and each job's dispatch-to-finish against the time its
tasks ran (what a task waited around its run, summed over the job).

    python3 benchmark/chip/pr33_read.py chiprun_out/pr33/<phase>
"""

import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import jobstats  # noqa: E402
from benchmark.chip import pr29_read, q3_phases  # noqa: E402
from benchmark.chip.phases import window_queries  # noqa: E402


def main(out_dir: str) -> None:
    for path in sorted(glob.glob(os.path.join(out_dir, "*.out"))):
        lines = open(path).read().strip().splitlines()
        tag = os.path.basename(path)[:-4]
        if not lines or not lines[-1].startswith('{"correct"'):
            print(json.dumps({"run": tag, "result": None}))
            continue
        r = json.loads(lines[-1])
        w = r["window"]
        print(json.dumps({
            "run": tag, "correct": r["correct"], "failed": r["failed"], "attempted": r["attempted"],
            "compared": r["compared"], "memory_peak_bytes": r["device"].get("memory_peak_bytes"),
            "busy_s/window_s": [r["device"].get("busy_s"), r["device"].get("window_s")],
            "window_s": w["window_s"], "by_client": w.get("completions_by_client"), "paired_by_time": w.get("paired_by_time"),
            "by_kind": {k: len(v) for k, v in w["latencies_s"].items()},
            "latency_mean_s": {k: round(sum(v) / len(v), 4) for k, v in w["latencies_s"].items() if v},
            "latency_max_s": {k: round(max(v), 4) for k, v in w["latencies_s"].items() if v},
            **{k: v["value"] for k, v in r["metrics"].items()},
        }))
        if "breakdown" in r:
            print(json.dumps({"run": tag, **r["breakdown"]}))
    for kept in sorted(d for d in glob.glob(os.path.join(out_dir, "*")) if os.path.isdir(d)):
        pr29_read.by_kind(kept)
        q3_phases.main(kept)
        around_the_tasks(kept)


def around_the_tasks(kept: str) -> None:
    """Per kind: the job's wall at the scheduler, its tasks' dispatch to
    finish summed, and the time the tasks ran (``task_run_ns``): the gap is
    what tasks waited in the executor before they ran or before their end
    was reported."""
    queries = window_queries(kept)
    for kind in sorted({q["kind"] for q in queries}):
        qs = [q for q in queries if q["kind"] == kind]
        wall = sum((q["job"]["end_us"] - q["job"]["submitted_us"]) / 1e3 for q in qs) / len(qs)
        held = sum(st["task_us"] for q in qs for st in q["job"]["stages"]) / 1e3 / len(qs)
        ran = sum(jobstats.op_sum(q["job"], "task_run_ns") for q in qs) / 1e6 / len(qs)
        worst = max(qs, key=lambda q: q["latency_s"])
        print(json.dumps({"run": os.path.basename(kept), "kind": f"q{kind}", "n": len(qs),
                          "job_wall_ms": round(wall, 1), "tasks_dispatch_to_finish_ms": round(held, 1),
                          "tasks_ran_ms": round(ran, 1), "slowest_s": round(worst["latency_s"], 3),
                          "slowest_job": worst["job"]["job_id"], "slowest_client": worst.get("client")}))


if __name__ == "__main__":
    main(sys.argv[1])
