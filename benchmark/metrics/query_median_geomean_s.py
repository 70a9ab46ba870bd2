"""Per kind the MEDIAN client latency of the window's completions, geometric
mean over kinds: the steadier statistic beside `query_geomean_s`, whose
per-kind MEAN one stalled query moves.  The two apart say a stall, not the
code, moved the end-to-end number."""

import math
import statistics

UNIT, BETTER, SOURCE = "s", "lower", "host_clock"
LAYER, MOVES = "client", "query_geomean_s"


def read(run):
    by_kind: dict = {}
    for q in run["window"]:
        by_kind.setdefault(q["kind"], []).append(q["latency_s"])
    if not by_kind:
        return None
    meds = [statistics.median(v) for v in by_kind.values()]
    return math.exp(sum(map(math.log, meds)) / len(meds))
