"""``exchange_workers`` of ``MeshRepartitionExec`` (PR 32): how many input
partitions an exchange prepared side by side (pull, filter, destination
hash, column encode); 1 says it ran them inline, one after the other (as it
does over a device stage).  The widest pool among a query's exchanges, mean
over the window's queries."""

from benchmark.metrics import _exchange

UNIT, BETTER, SOURCE = "count", "higher", "program_counter"
LAYER, MOVES = "exchange", "query_geomean_s"


def read(run):
    widest = [
        max(int(v["exchange_workers"] or 0) for v in ops)
        for q in run["window"] if q.get("job")
        for ops in [_exchange.ops_with(q["job"], "exchange_workers")] if ops
    ]
    return sum(widest) / len(widest) if widest else None
