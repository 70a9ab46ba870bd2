"""Scheduler's job end (last task finish) to ``collect()`` returning, mean
over the window's queries: the client's poll step plus the result fetch."""

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER, MOVES = "client", "query_geomean_s"


def read(run):
    gaps = [
        (q["unix_done"] * 1e6 - q["job"]["end_us"]) / 1e3
        for q in run["window"] if q.get("job") and q["job"].get("end_us")
    ]
    return sum(gaps) / len(gaps) if gaps else None
