"""Least time the chip's memory could take to read the columns the traced
queries scan (bytes from the data's shapes: rows x the columns' widths as
stored, whatever kernel reads them; divided over the cell's chips; over the
table of peaks' bytes/s), over the summed device time of the programs in the
trace.  Bound: memory (a scan-aggregate does a few operations a byte).
With several clients, queries are in flight when the trace ends: the bytes
are of the queries that COMPLETED inside the trace and the time is of every
program in it, so the share reads low there, never high."""

from benchmark import queries

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER, MOVES = "kernels", "scan_rows_rate"


def read(run):
    tr = run.get("trace")
    if not tr or not tr["programs_s"] or not tr["queries"] or not run.get("peaks"):
        return None
    nbytes = 0
    for q in tr["queries"]:
        for table, cols in queries.COLUMNS_OF[q["kind"]].items():
            nbytes += run["data"]["rows"][table] * sum(queries.COLUMN_BYTES[c] for c in cols)
    least_s = nbytes / run["chips"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["programs_s"]
