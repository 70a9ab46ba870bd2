"""``exchange_pad_ns`` of ``MeshRepartitionExec``: ``kernels._pad`` of every
input array (each row's partition, its validity and the encoded columns) to
``mesh.exchange_rows`` rows; a capacity growth's set-up goes here too.
Summed over a query's exchanges that moved rows, mean over the queries that
have one; one of the four phases that sum to ``exchange_ms`` exactly.  None
on a program without the counter."""

from benchmark.metrics import _exchange_phase

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "exchange", "query_geomean_s"


def read(run):
    return _exchange_phase.read(run, "exchange_pad_ns")
