"""``gang_uploads``: how many ``jax.device_put`` calls a query's gang stage
made (batches x columns)."""

from benchmark.metrics import _gang

UNIT, BETTER, SOURCE = "count", "lower", "program_counter"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return _gang.per_query(run, "gang_uploads")
