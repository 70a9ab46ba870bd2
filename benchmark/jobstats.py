"""From the scheduler's REST job detail to one record per query.

What is read: the job's submit and planning anchors, every stage's task
dispatch and finish times, and the operators' host timers and counters as
the program reports them.  ``device_time_ns`` is a HOST timer around
dispatch and fetch (it includes compile on a first call) and is never
reported as a device time.
"""

from __future__ import annotations

DEVICE_OPS = ("TpuStageExec", "MeshGangExec", "TpuWindowExec")
# route counters that say a device-eligible stage did not end on the device
OFF_DEVICE = ("device_error", "cpu_fallback", "tpu_fallback", "mesh_fallback",
              "join_fallback", "highcard_fallback")


def _union(intervals) -> float:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def summarize(detail: dict) -> dict:
    """One job detail -> the numbers the metric readers use (times in
    microseconds on the scheduler's unix clock, sums in nanoseconds as
    the program counts them)."""
    stages = []
    intervals = []
    for st in detail.get("stages", []):
        timing = st.get("timing") or {}
        disp, fin = timing.get("dispatch_us") or {}, timing.get("finish_us") or {}
        start = min(disp.values()) if disp else None
        end = max(fin.values()) if fin else None
        for p, f in fin.items():
            if p in disp:
                intervals.append((disp[p], f))
        ops = {
            op: vals for op, vals in (st.get("metrics") or {}).items()
            if not op.startswith("__")
        }
        stages.append({
            "stage_id": st["stage_id"], "partitions": st.get("partitions"),
            "start_us": start, "end_us": end, "ops": ops,
            "chain": "_".join(op for op in ops if op != "ShuffleWriterExec")[:60],
        })
    ends = [s["end_us"] for s in stages if s["end_us"] is not None]
    submitted = detail.get("submitted_us")
    out = {
        "job_id": detail.get("job_id"), "state": detail.get("state"),
        "submitted_us": submitted, "planning_us": detail.get("planning_us", 0),
        "end_us": max(ends) if ends else None, "stages": stages,
    }
    if ends and submitted:
        out["untasked_us"] = max(
            0, out["end_us"] - submitted - _union(intervals) - out["planning_us"]
        )
    return out


def op_sum(job: dict, key: str, ops=None) -> int:
    """Sum of one counter over the job's stages (over ``ops`` only, if given)."""
    return sum(
        int(vals.get(key, 0) or 0)
        for st in job["stages"]
        for op, vals in st["ops"].items()
        if ops is None or op in ops
    )


def gang_stages(job: dict) -> list:
    return [st for st in job["stages"] if "MeshGangExec" in st["ops"]]


def device_stages(job: dict) -> list:
    return [st for st in job["stages"] if any(op in DEVICE_OPS for op in st["ops"])]


def stage_off_device(st: dict) -> bool:
    return any(int(v.get(k, 0) or 0) for v in st["ops"].values() for k in OFF_DEVICE)


def wrong_route(job: dict, chips: int, kinds_need_gang: bool) -> str:
    """Why this query did not run the path the cell is for ('' if it did)."""
    if job.get("state") != "completed":
        return f"job state {job.get('state')}"
    if op_sum(job, "device_error"):
        return "device_error"
    gangs = gang_stages(job)
    if kinds_need_gang:
        if not gangs:
            return "no gang stage"
        if op_sum(job, "mesh_fallback"):
            return "mesh_fallback"
        if any(stage_off_device(st) for st in gangs):
            return "gang stage fell back"
        devices = max(int(st["ops"]["MeshGangExec"].get("mesh_devices", 0) or 0) for st in gangs)
        if devices != chips:
            return f"mesh_devices {devices} != {chips}"
    elif not device_stages(job):
        return "no device stage"
    return ""


def match(records: list, jobs: list) -> None:
    """Give each query record its job: the job submitted soonest after the
    client's call (queries of one client are sequential, so this is exact
    there; concurrent clients with equal texts may swap, which changes no
    sum).  Sets ``rec["job"]`` (or None)."""
    free = sorted((j for j in jobs if j.get("submitted_us")), key=lambda j: j["submitted_us"])
    for rec in sorted(records, key=lambda r: r["unix_submit"]):
        rec["job"] = None
        lo, hi = rec["unix_submit"] * 1e6 - 5e3, rec["unix_done"] * 1e6 + 5e3
        for i, j in enumerate(free):
            if lo <= j["submitted_us"] <= hi:
                rec["job"] = free.pop(i)
                break


def gang_timer_share(queries: list, key: str):
    """One of the gang stage's host timers over the gang stage's wall (first
    task dispatch to last finish), in percent, over ``queries``; None where
    there is no gang stage or no such timer."""
    timer = wall = 0
    for q in queries:
        for st in gang_stages(q["job"]) if q.get("job") else ():
            if st["start_us"] is not None and st["end_us"] is not None:
                timer += sum(int(v.get(key, 0) or 0) for v in st["ops"].values())
                wall += (st["end_us"] - st["start_us"]) * 1000
    return 100.0 * timer / wall if wall and timer else None
