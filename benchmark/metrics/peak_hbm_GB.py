"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read in the
executor process once the window has closed."""

UNIT, BETTER, SOURCE = "GB", "lower", "program_counter"
LAYER, MOVES = "device", "scan_rows_rate"


def read(run):
    peaks = [p for p in run["memory"].get("peak_bytes_in_use", []) if p]
    return max(peaks) / 1e9 if peaks else None
