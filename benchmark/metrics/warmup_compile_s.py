"""XLA compile time summed over the warm-up queries' tasks
(``xla_compile_ns``, ``ops/xla_meter.py``): what set-up pays to compile, or
to load from the persistent cache."""

from benchmark import jobstats

UNIT, BETTER, SOURCE = "s", "lower", "program_counter"
LAYER, MOVES = "executor", "setup_s"


def read(run):
    jobs = [q["job"] for q in run["warmup"] if q.get("job")]
    return sum(jobstats.op_sum(j, "xla_compile_ns") for j in jobs) / 1e9 if jobs else None
