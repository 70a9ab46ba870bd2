"""Executables the warm-up queries' tasks COMPILED (``xla_compiles`` less
``xla_cache_hits``: obtained, and not loaded from the disk cache).  With
shapes that do not follow the data it reads the same on a seed never seen
as on one seen before."""

from benchmark import jobstats

UNIT, BETTER, SOURCE = "count", "lower", "program_counter"
LAYER, MOVES = "executor", "setup_s"


def read(run):
    jobs = [q["job"] for q in run["warmup"] if q.get("job")]
    if not jobs:
        return None
    return float(sum(
        jobstats.op_sum(j, "xla_compiles") - jobstats.op_sum(j, "xla_cache_hits") for j in jobs
    ))
