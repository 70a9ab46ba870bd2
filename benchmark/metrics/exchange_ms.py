"""Host-timed ``device_time_ns`` of the operators that repartitioned rows
through the device exchange (``mesh_exchange_rows`` > 0), per query that
has one.  A host timer, named so.  Nothing to read where no stage exchanges
(q1, q6)."""

from benchmark.metrics import _exchange

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER, MOVES = "exchange", "query_geomean_s"


def _of(job):
    ns = sum(
        int(v.get("device_time_ns", 0) or 0)
        for v in _exchange.ops_with(job, "mesh_exchange_rows") if int(v["mesh_exchange_rows"] or 0) > 0
    )
    return ns / 1e6 or None


def read(run):
    return _exchange.mean_over_queries(run, _of)
