"""``exchange_fetch_ns`` of ``MeshRepartitionExec``: ``np.asarray`` of every
output and of the dropped-row count: the copy back. Summed over a query's
exchanges that moved rows, mean over the queries that have one; one of the
four phases that sum to ``exchange_ms`` exactly.  None on a program without
the counter."""

from benchmark.metrics import _exchange_phase

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "exchange", "query_geomean_s"


def read(run):
    return _exchange_phase.read(run, "exchange_fetch_ns")
