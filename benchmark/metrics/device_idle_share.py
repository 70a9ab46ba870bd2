"""1 - (union of the device's op intervals, averaged over the chips used)
over the traced window."""

UNIT, BETTER, SOURCE = "%", "lower", "device_trace"
LAYER, MOVES = "device", "scan_rows_rate"


def read(run):
    tr = run.get("trace")
    if not tr or not tr["window_s"] or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
