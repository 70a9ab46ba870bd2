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
        tasks = [(disp[p], f) for p, f in fin.items() if p in disp]
        intervals += tasks
        ops = {
            op: vals for op, vals in (st.get("metrics") or {}).items()
            if not op.startswith("__")
        }
        stages.append({
            "stage_id": st["stage_id"], "partitions": st.get("partitions"),
            "start_us": start, "end_us": end, "ops": ops,
            "task_us": sum(f - d for d, f in tasks),  # dispatch to finish, summed over the stage's tasks
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
        return ""
    stages = device_stages(job)
    if not stages:
        return "no device stage"
    if any(stage_off_device(st) for st in stages):
        return "device stage fell back"
    return ""


def match(records: list, jobs: list) -> list:
    """Give each query record its job (``rec["job"]``, or None).  A record
    that carries the id of the job its own ``collect()`` ran (``job_id``)
    takes that job, or none where the scheduler has no detail of it: exact
    whoever else was submitting.  A record with no id (a stand-in for the
    served system keeps none) takes, of the jobs no id claims, the one
    submitted soonest after the client's call.  That rule is exact for ONE
    client only: a client plans its query before the scheduler stamps
    ``submitted_us``, a q3 plans longer than a q6, and with several clients
    the two orders cross.  Returns the records that took it, for the caller
    to report."""
    by_id = {j["job_id"]: j for j in jobs if j.get("job_id")}
    claimed = {rec["job_id"] for rec in records if rec.get("job_id")}
    for rec in records:
        rec["job"] = by_id.get(rec.get("job_id"))
    by_time = [rec for rec in records if not rec.get("job_id")]
    free = sorted((j for j in jobs if j.get("submitted_us") and j.get("job_id") not in claimed),
                  key=lambda j: j["submitted_us"])
    for rec in sorted(by_time, key=lambda r: r["unix_submit"]):
        lo, hi = rec["unix_submit"] * 1e6 - 5e3, rec["unix_done"] * 1e6 + 5e3
        for i, j in enumerate(free):
            if lo <= j["submitted_us"] <= hi:
                rec["job"] = free.pop(i)
                break
    return by_time
