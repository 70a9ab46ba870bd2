"""``exchange_decode_ns`` of ``MeshRepartitionExec`` (PR 35): the task
thread's time from the exchange program's return to the last output
partition's RecordBatch (slices of the received buffers where the program
placed rows by output partition, ``exchange_grouped``; else a mask, a sort
and a gather), summed over those of a query's operators that repartitioned
rows through the device exchange (``mesh_exchange_rows`` > 0), mean over the
queries that have one.  Nothing to read where no stage exchanges (q1, q6)."""

from benchmark.metrics import _exchange

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "exchange", "query_geomean_s"


def _of(job):
    ops = [v for v in _exchange.ops_with(job, "exchange_decode_ns") if int(v.get("mesh_exchange_rows") or 0) > 0]
    if not ops:
        return None
    return sum(int(v["exchange_decode_ns"] or 0) for v in ops) / 1e6


def read(run):
    return _exchange.mean_over_queries(run, _of)
