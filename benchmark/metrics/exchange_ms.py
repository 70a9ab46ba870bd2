"""Host-timed ``device_time_ns`` of the operators that repartitioned rows
through the device exchange (``mesh_exchange_rows`` > 0), per query.  A
host timer, named so.  Nothing to read where no stage exchanges (q1, q6)."""

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER, MOVES = "exchange", "query_geomean_s"


def read(run):
    jobs = [q["job"] for q in run["window"] if q.get("job")]
    ns = sum(
        int(vals.get("device_time_ns", 0) or 0)
        for j in jobs for st in j["stages"] for vals in st["ops"].values()
        if int(vals.get("mesh_exchange_rows", 0) or 0) > 0
    )
    return ns / 1e6 / len(jobs) if ns else None
