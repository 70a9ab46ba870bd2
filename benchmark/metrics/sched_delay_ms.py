"""Time inside a job's wall (submit to last task finish) in which no task of
it ran, less planning: submit to first task plus the gaps between stages."""

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER, MOVES = "scheduler", "query_geomean_s"


def read(run):
    v = [q["job"]["untasked_us"] / 1e3 for q in run["window"]
         if q.get("job") and "untasked_us" in q["job"]]
    return sum(v) / len(v) if v else None
