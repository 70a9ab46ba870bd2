"""Where a q3's time goes, from a kept run (``run.py --keep DIR``): per
stage the scheduler's wall against the time its task ran, and inside the
task the exchange's encode / device / decode, the join's build, the device
stage, shuffle write and fetch; means over the run's window queries.

    python3 benchmark/chip/q3_phases.py DIR [DIR ...]

In a window of mixed kinds (``loadtest4``) only the q3s are read.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from benchmark import jobstats  # noqa: E402

MS = (
    "exchange_encode_ns", "device_time_ns", "exchange_decode_ns", "repart_time_ns", "join_build_ns", "tpu_stage_time_ns",
    "bridge_time_ns", "key_encode_time_ns", "scan_time_ns", "filter_time_ns", "join_time_ns", "agg_time_ns", "sort_time_ns",
    "write_time_ns", "fetch_time_ns", "fetch_wait_time_ns", "mesh_stage_time_ns",
)
COUNTS = (
    "mesh_exchange_rows", "mesh_exchange_padded_rows", "mesh_exchange_bytes", "join_build_rows", "join_build_capacity",
    "join_probe_rows", "stage_pad_rows", "stage_batches", "stage_uploads", "capacity_growths", "xla_compiles", "output_rows",
)


def main(keep_dir: str) -> None:
    with open(os.path.join(keep_dir, "job_details.json")) as f:
        jobs = [jobstats.summarize(d) for d in json.load(f) if d.get("stages") is not None]
    with open(os.path.join(keep_dir, "queries.json")) as f:
        records = json.load(f)
    jobstats.match(records, jobs)
    window = [r for r in records if r.get("seq") is not None and r.get("job") and r["kind"] == 3]
    print(f"{keep_dir}: {len(window)} window queries, mean latency "
          f"{sum(r['latency_s'] for r in window) / max(1, len(window)):.3f} s")
    n = len(window)
    by_stage: dict = {}
    for r in window:
        j = r["job"]
        for st in j["stages"]:
            row = by_stage.setdefault(st["stage_id"], {"chain": st["chain"], "wall": 0.0, "start": 0.0, "ran": 0.0, "ops": {}})
            if st["start_us"] is not None and st["end_us"] is not None:
                row["wall"] += (st["end_us"] - st["start_us"]) / 1e3 / n
                row["start"] += (st["start_us"] - j["submitted_us"]) / 1e3 / n
            for op, vals in st["ops"].items():
                row["ran"] += int(vals.get("task_run_ns", 0) or 0) / 1e6 / n
                for k in MS + COUNTS:
                    if k in vals:
                        row["ops"][f"{op}.{k}"] = row["ops"].get(f"{op}.{k}", 0) + int(vals[k] or 0) / n
    for sid, row in sorted(by_stage.items()):
        print(f"  stage {sid} [{row['chain']}]: starts +{row['start']:.0f} ms, wall {row['wall']:.1f} ms, task(s) ran {row['ran']:.1f} ms")
        parts = []
        for k, v in row["ops"].items():
            name = k.replace("Exec.", ".").replace("_time_ns", "").replace("_ns", "")
            parts.append(f"{name} {v / 1e6:.1f} ms" if k.endswith("_ns") else f"{name} {v:,.0f}")
        print("     " + "; ".join(parts))
    end = sum((r["job"]["end_us"] - r["job"]["submitted_us"]) / 1e3 for r in window) / max(1, n)
    print(f"  job at the scheduler {end:.0f} ms; planning {sum(r['job']['planning_us'] for r in window) / 1e3 / max(1, n):.1f} ms; "
          f"untasked {sum(r['job'].get('untasked_us', 0) for r in window) / 1e3 / max(1, n):.1f} ms")
    trace = os.path.join(keep_dir, "trace.json")
    if os.path.exists(trace):
        with open(trace) as f:
            tr = json.load(f)
        print(f"  trace: window {tr['window_s']:.2f} s, busy {tr['busy_s']:.3f} s; programs {[[n_, round(s, 4)] for n_, s in tr['device_ops'][:8]]}")
        print(f"  idle gaps {[[g, round(s, 3)] for g, s in tr['idle_gaps']]}")


if __name__ == "__main__":
    for d in sys.argv[1:]:
        main(d)
