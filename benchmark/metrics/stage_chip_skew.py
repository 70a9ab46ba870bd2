"""How evenly a query's device stages kept the chips busy: the chips'
``tpu_stage_time_ns_dev<chip>`` of ``TpuStageExec`` summed over the query, the
largest over the mean of the chips that ran anything; mean over the window's
queries.  1.0 is even; hash skew across the partitions, or a chip whose
partition waited on a lock, reads above it.  None where the program counts no
chip (a parent commit)."""

from benchmark.metrics import _chips, _exchange

UNIT, BETTER, SOURCE = "ratio", "lower", "program_counter"
LAYER, MOVES = "device stage", "query_geomean_s"


def _of(job):
    sums: dict = {}
    for c in _chips.stages_per_chip(job):
        for chip, ns in c.items():
            sums[chip] = sums.get(chip, 0) + ns
    busy = [ns for ns in sums.values() if ns > 0]
    return max(busy) * len(busy) / sum(busy) if busy else None


def read(run):
    return _exchange.mean_over_queries(run, _of)
