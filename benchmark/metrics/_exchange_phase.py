"""Shared by the four readers of the exchange call's phases (``exchange_pad_ms``,
``exchange_upload_ms``, ``exchange_program_ms``, ``exchange_fetch_ms``): the
device call of ``MeshRepartitionExec`` (``device_time_ns``, which
``exchange_ms`` reads) timed as four consecutive reads of one clock, so the
four sum to it exactly.  A file whose name starts with ``_`` is no reader."""

from benchmark.metrics import _exchange


def read(run, counter: str):
    """``counter`` summed over those of a query's operators that
    repartitioned rows through the device exchange (``mesh_exchange_rows``
    > 0), in ms, mean over the queries that have one; None on a program
    without the counter."""

    def of(job):
        ops = [v for v in _exchange.ops_with(job, counter) if int(v.get("mesh_exchange_rows") or 0) > 0]
        return sum(int(v[counter] or 0) for v in ops) / 1e6 if ops else None

    return _exchange.mean_over_queries(run, of)
