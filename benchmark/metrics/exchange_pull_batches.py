"""``exchange_pull_batches`` of ``MeshRepartitionExec``: the non-empty
batches the exchange's workers pulled from its input, summed over a query's
exchanges, mean over the queries that have one.  Over host operators a
batch is a parquet row group (``mesh_stage._PULL_ROWS`` rows), so each
return of the scan and the filter to Python, and to the GIL, is counted
here.  None on a program without the counter, and where no stage
exchanges (q1, q6)."""

from benchmark.metrics import _exchange

UNIT, BETTER, SOURCE = "count", "lower", "program_counter"
LAYER, MOVES = "exchange", "query_geomean_s"


def read(run):
    return _exchange.per_query(run, "exchange_pull_batches")
