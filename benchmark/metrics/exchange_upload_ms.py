"""``exchange_upload_ns`` of ``MeshRepartitionExec``: the ``jax.device_put`` of
every padded array onto the mesh, then one wait until all are there: the
copy up. Summed over a query's exchanges that moved rows, mean over the
queries that have one; one of the four phases that sum to ``exchange_ms``
exactly.  None on a program without the counter."""

from benchmark.metrics import _exchange_phase

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "exchange", "query_geomean_s"


def read(run):
    return _exchange_phase.read(run, "exchange_upload_ns")
