"""Planning time per query as the scheduler's job detail records it, mean."""

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER, MOVES = "scheduler", "query_geomean_s"


def read(run):
    v = [q["job"]["planning_us"] / 1e3 for q in run["window"] if q.get("job")]
    return sum(v) / len(v) if v else None
