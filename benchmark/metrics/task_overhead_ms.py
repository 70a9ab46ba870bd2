"""Per stage the scheduler's wall (first task dispatch to last finish) less
the time the executor ran a task of it (``task_run_ns`` over the stage's
partitions), summed over a query's stages, mean over the window's queries:
what the executor's poll step and the status report cost around the tasks."""

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "executor", "query_geomean_s"


def stage_overhead_us(st: dict):
    """One summarized stage's overhead in microseconds; None where the
    program counts no ``task_run_ns`` or the stage has no times."""
    ran = [int(v["task_run_ns"] or 0) for v in st["ops"].values() if "task_run_ns" in v]
    if not ran or st.get("start_us") is None or st.get("end_us") is None:
        return None
    return (st["end_us"] - st["start_us"]) - sum(ran) / 1e3 / max(1, int(st.get("partitions") or 1))


def read(run):
    per_query = []
    for q in run["window"]:
        stages = [stage_overhead_us(st) for st in q["job"]["stages"]] if q.get("job") else []
        stages = [s for s in stages if s is not None]
        if stages:
            per_query.append(sum(stages) / 1e3)
    return sum(per_query) / len(per_query) if per_query else None
