"""Shared by the exchange and join readers (PR 28): the always-on counters
of ``MeshRepartitionExec`` (the device exchange) and ``TpuStageExec`` (the
folded join and the per-partition device stage), as the job detail carries
them.  A file whose name starts with ``_`` is no reader."""


def ops_with(job: dict, key: str) -> list:
    """Counter dicts of the job's operators that count ``key``."""
    return [vals for st in job["stages"] for vals in st["ops"].values() if key in vals]


def mean_over_queries(run, per_job):
    """Mean of ``per_job(job)`` over the window's queries for which it is
    not None: a query with nothing to read (a q1 beside a q3 has no exchange
    and no join) is left out, not averaged in as 0.  None where none has."""
    found = [x for q in run["window"] if q.get("job") for x in [per_job(q["job"])] if x is not None]
    return sum(found) / len(found) if found else None


def per_query(run, key: str, scale: float = 1.0):
    """One counter summed over a query's operators, mean over the window's
    queries that count it; None where the program has no such counter."""
    def of(job):
        ops = ops_with(job, key)
        return sum(int(v[key] or 0) for v in ops) / scale if ops else None

    return mean_over_queries(run, of)


def pad_share(run, pad_key: str, rows_key: str):
    """100 x rows of padding / rows sent to the device (real + padding), over
    the operators of the window's queries that count ``pad_key``."""
    ops = [v for q in run["window"] if q.get("job") for v in ops_with(q["job"], pad_key)]
    pad = sum(int(v[pad_key] or 0) for v in ops)
    sent = pad + sum(int(v.get(rows_key, 0) or 0) for v in ops)
    return 100.0 * pad / sent if sent else None


def exchange_bytes(job: dict):
    """Bytes the exchange programs of one query had to move at least: every
    input array read once (``mesh_exchange_bytes``: destination, validity and
    the encoded columns at the padded row count) and every output written
    once (``mesh_exchange_recv_bytes``: the columns and one validity at
    devices x capacity slots a device).  None where the program has no such
    counters."""
    ops = ops_with(job, "mesh_exchange_bytes")
    if not ops or any("mesh_exchange_recv_bytes" not in v for v in ops):
        return None
    return sum(int(v["mesh_exchange_bytes"]) + int(v["mesh_exchange_recv_bytes"]) for v in ops)
