"""``exchange_wait_ns`` of ``MeshRepartitionExec`` (PR 32): the task thread's
time blocked until the next input partition IN ORDER is prepared, summed over
those of a query's exchanges that ran a pool (``exchange_workers`` > 1): what
is still exposed of the workers' pull, filter, hash and encode.  An exchange
prepared inline is left out: its wait is its input's own run (over a device
stage, that stage's: ``device_stage_ms`` reads it), not time the exchange
can hide."""

from benchmark.metrics import _exchange

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "exchange", "query_geomean_s"


def _of(job):
    ops = _exchange.ops_with(job, "exchange_wait_ns")
    if not ops:
        return None
    return sum(int(v["exchange_wait_ns"] or 0) for v in ops if int(v.get("exchange_workers") or 0) > 1) / 1e6


def read(run):
    return _exchange.mean_over_queries(run, _of)
