"""``exchange_workers`` of ``MeshRepartitionExec`` (PR 32): how many input
partitions an exchange prepared side by side (pull, filter, destination
hash, column encode); 1 says it ran them inline, one after the other (as it
does over a device stage).  The widest pool among a query's exchanges, mean
over the window's queries that have an exchange."""

from benchmark.metrics import _exchange

UNIT, BETTER, SOURCE = "count", "higher", "program_counter"
LAYER, MOVES = "exchange", "query_geomean_s"


def _of(job):
    ops = _exchange.ops_with(job, "exchange_workers")
    return max(int(v["exchange_workers"] or 0) for v in ops) if ops else None


def read(run):
    return _exchange.mean_over_queries(run, _of)
