"""Least time the chip's memory could take to read the exchange programs'
inputs and write their outputs once (``_exchange.exchange_bytes`` of the
traced queries, over the cell's chips and the table of peaks' bytes/s), over
the summed device time of the exchange program (``jit_local_exchange``) in
the trace.  Bound: memory (an exchange computes nothing; its sort is what
the share shows).  With several clients, a q3 is in flight when the trace
ends: the bytes are of the queries that COMPLETED inside the trace and the
time is of every exchange program in it, so the share reads low there, never
high."""

from benchmark.metrics import _exchange

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER, MOVES = "exchange", "scan_rows_rate"


def read(run):
    tr = run.get("trace")
    if not tr or not tr["queries"] or not run.get("peaks"):
        return None
    program_s = sum(s for name, s in tr["device_ops"] if "local_exchange" in name)
    # of the traced queries, those that exchange (a q1 or q6 beside them does not)
    moved = [_exchange.exchange_bytes(q["job"]) for q in tr["queries"]
             if _exchange.ops_with(q["job"], "mesh_exchange_bytes")]
    if not program_s or not moved or None in moved:
        return None
    least_s = sum(moved) / run["chips"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / program_s
