"""Least time the chip's memory could take to move what the build-row
program reads and writes (``_join.agg_bytes`` of the traced queries, over the
cell's chips and the table of peaks' bytes/s), over the summed device time of
that program (``jit_join_aggregate``) in the trace.  Bound: memory (a segment
sum does an add or two a row).  A program without the build-row route has
neither the program nor its counters: None.  With several clients, a q3 is
in flight when the trace ends: the bytes are of the queries that COMPLETED
inside the trace and the time is of every such program in it, so the share
reads low there, never high."""

from benchmark.metrics import _exchange, _join

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "scan_rows_rate"


def read(run):
    tr = run.get("trace")
    if not tr or not tr["queries"] or not run.get("peaks"):
        return None
    program_s = sum(s for name, s in tr["device_ops"] if "join_aggregate" in name)
    moved = [_join.agg_bytes(q["job"]) for q in tr["queries"]
             if _exchange.ops_with(q["job"], "join_build_groups")]
    if not program_s or not moved or None in moved:
        return None
    least_s = sum(moved) / run["chips"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / program_s
