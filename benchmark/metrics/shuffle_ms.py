"""Shuffle write plus fetch and fetch-wait timers, summed over a query's
tasks, mean per query."""

from benchmark import jobstats

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER, MOVES = "shuffle and Flight", "query_geomean_s"
KEYS = ("write_time_ns", "fetch_time_ns", "fetch_wait_time_ns")


def read(run):
    jobs = [q["job"] for q in run["window"] if q.get("job")]
    if not jobs:
        return None
    return sum(jobstats.op_sum(j, k) for j in jobs for k in KEYS) / 1e6 / len(jobs)
