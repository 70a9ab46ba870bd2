"""Executables compiled by the window's tasks (``xla_compiles``).  A window
should compile nothing; what this reads above 0 is the program's to explain."""

from benchmark import jobstats

UNIT, BETTER, SOURCE = "count", "lower", "program_counter"
LAYER, MOVES = "executor", "query_geomean_s"


def read(run):
    jobs = [q["job"] for q in run["window"] if q.get("job")]
    return float(sum(jobstats.op_sum(j, "xla_compiles") for j in jobs)) if jobs else None
