"""What PR 26's chip runs are read with: per run (a directory kept by
``obs_run.py``/``run.py --keep``) and query kind, the client latency beside
the gang stage's phase counters, its CPU share, the cores its partitions ran
on (spans, where obs was on), and each stage's wall less ``task_run_ns``;
with ``--clock``, where the device's ``jit_sharded_step`` events fall against
the program's ``gang.step`` spans.

    python3 benchmark/chip/phases.py [--clock] <kept dir> [<kept dir> ...]
"""

import gzip
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import jobstats  # noqa: E402
from benchmark.metrics import _gang  # noqa: E402

COUNTS = ("gang_uploads", "gang_batches")
# the seven self times of PR 26: they sum to the stage's wall on a program
# from before PR 29 only (since then three of them are the workers' sums)
PHASES = (
    "gang_scan_ns", "key_encode_time_ns", "gang_convert_ns", "gang_upload_ns",
    "gang_assemble_ns", "gang_step_ns", "gang_materialize_ns",
)


def window_queries(kept: str) -> list:
    """The window's query records (after the warm-up and CPU-operator
    reads), each with its summarized job."""
    with open(os.path.join(kept, "queries.json")) as f:
        records = [r for r in json.load(f) if "seq" in r and r.get("error") is None]
    with open(os.path.join(kept, "job_details.json")) as f:
        jobs = [jobstats.summarize(d) for d in json.load(f) if d.get("stages") is not None]
    jobstats.match(records, jobs)
    return [r for r in records if r.get("job")]


def spans_of(kept: str) -> dict:
    path = os.path.join(kept, "spans.json")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return {
            job: [e for e in tr["traceEvents"] if e.get("ph") == "X"]
            for job, tr in json.load(f).items()
        }


def table(kept: str) -> None:
    queries, spans = window_queries(kept), spans_of(kept)
    for kind in sorted({q["kind"] for q in queries}):
        qs = [q for q in queries if q["kind"] == kind]
        run = {"window": qs}
        row = {"n": len(qs), "latency_s": sum(q["latency_s"] for q in qs) / len(qs)}
        gang_wall = _gang.per_query(run, _gang.WALL, 1e6)
        row["gang_wall_ms"] = gang_wall
        for k in PHASES:
            row[k.replace("_time_ns", "_ms").replace("_ns", "_ms")] = _gang.per_query(run, k, 1e6)
        for k in COUNTS:
            row[k] = _gang.per_query(run, k)
        row["unaccounted_%"] = _gang.share_of_wall(run, PHASES, rest=True)
        row["cpu_share_%"] = _gang.share_of_wall(run, ("gang_cpu_ns",))
        stages: dict = {}
        for q in qs:
            for st in q["job"]["stages"]:
                ran = sum(int(v.get("task_run_ns", 0) or 0) for v in st["ops"].values())
                if ran and st["start_us"] is not None and st["end_us"] is not None:
                    s = stages.setdefault(st["stage_id"], [0.0, 0.0])
                    s[0] += (st["end_us"] - st["start_us"]) / 1e3 / len(qs)
                    s[1] += ran / 1e6 / max(1, int(st["partitions"] or 1)) / len(qs)
        row["stage_wall_ms,task_run_ms"] = {k: [round(a, 1), round(b, 1)] for k, (a, b) in sorted(stages.items())}
        cpus: list = []
        for q in qs:
            parts = [e for e in spans.get(q["job"]["job_id"], []) if e["name"] == "gang.partition"]
            cpus.append(sorted({c for e in parts for c in (e["args"]["cpu_start"], e["args"]["cpu_end"])}))
        if any(cpus):
            row["cores_per_query"] = cpus
        print(json.dumps({"run": os.path.basename(kept.rstrip("/")), "kind": f"q{kind}", **{
            k: round(v, 2) if isinstance(v, float) else v for k, v in row.items()
        }}))


def clock(kept: str) -> None:
    """Each ``jit_sharded_step`` module event of the device trace, shifted by
    the trace's zero (``unix_ns_before`` of the launcher's ack), against the
    ``gang.step`` + ``gang.fetch`` spans of the query it belongs to."""
    with open(os.path.join(kept, "trace_marks.json")) as f:
        marks = json.load(f)
    with gzip.open(os.path.join(kept, "trace_events.json.gz"), "rt") as f:
        events = json.load(f)
    steps = sorted(
        (s, s + n) for dev, line, name, s, n in events
        if line == "XLA Modules" and name.startswith("jit_sharded_step") and dev == 0
    )
    spans = sorted(
        (e["ts"] * 1e3, (e["ts"] + e["dur"]) * 1e3, e["name"])
        for evs in spans_of(kept).values() for e in evs if e["name"] in ("gang.step", "gang.fetch")
    )
    zero = marks["unix_ns_before"]
    print(json.dumps({"run": os.path.basename(kept.rstrip("/")),
                      "start_trace_took_ms": (marks["unix_ns_after"] - zero) / 1e6}))
    for a, b in steps:
        a, b = a + zero, b + zero
        near = [s for s in spans if s[2] == "gang.step" and abs(s[0] - a) < 2e9]
        for s0, s1, _ in near:
            fetch = next((f for f in spans if f[2] == "gang.fetch" and 0 <= f[0] - s1 < 1e8), None)
            print(json.dumps({
                "device_step_ms": round((b - a) / 1e6, 3),
                "gang.step_ms": round((s1 - s0) / 1e6, 3),
                "device_start_after_span_start_ms": round((a - s0) / 1e6, 3),
                "device_end_before_step_span_end_ms": round((s1 - b) / 1e6, 3),
                "device_end_before_fetch_span_end_ms": round((fetch[1] - b) / 1e6, 3) if fetch else None,
                "inside_step_span": s0 <= a and b <= s1,
                "inside_step_plus_fetch": bool(fetch) and s0 <= a and b <= fetch[1],
            }))


if __name__ == "__main__":
    args = sys.argv[1:]
    fn = clock if "--clock" in args else table
    for d in [a for a in args if a != "--clock"]:
        fn(d)
