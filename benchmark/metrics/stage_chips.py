"""How many chips a query's device stage ran on: the chips whose
``tpu_stage_time_ns_dev<chip>`` of ``TpuStageExec`` is non-zero, the most over
the query's device stages, mean over the window's queries.  4 where each of
four partitions ran on its own chip; 1 where they all ran on one.  None where
the program counts no chip (a parent commit)."""

from benchmark.metrics import _chips, _exchange

UNIT, BETTER, SOURCE = "count", "higher", "program_counter"
LAYER, MOVES = "device stage", "query_geomean_s"


def _of(job):
    stages = _chips.stages_per_chip(job)
    return max(sum(ns > 0 for ns in c.values()) for c in stages) if stages else None


def read(run):
    return _exchange.mean_over_queries(run, _of)
