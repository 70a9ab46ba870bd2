"""Device time of collective operations in the trace (all-reduce,
all-gather, all-to-all, collective-permute, reduce-scatter), averaged over
chips, per traced query.  Only a cell across chips has any."""

UNIT, BETTER, SOURCE = "ms", "lower", "device_trace"
LAYER, MOVES = "device", "scan_rows_rate"


def read(run):
    tr = run.get("trace")
    if not tr or not tr["queries"] or not tr["collective_s"]:
        return None
    return 1e3 * tr["collective_s"] / len(tr["queries"])
