"""``tpu_stage_time_ns`` of the per-partition device stages
(``TpuStageExec``: the folded join's probe and the device aggregate, batch
by batch, to the fetch of the states), summed over a query's partitions,
mean per query.  A host timer."""

from benchmark.metrics import _exchange

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "device stage", "query_geomean_s"


def read(run):
    return _exchange.per_query(run, "tpu_stage_time_ns", 1e6)
