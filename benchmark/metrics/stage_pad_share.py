"""Rows of padding over the rows ``TpuStageExec`` sent to the device
(``stage_pad_rows`` / (``input_rows`` + padding)): what bucketing the
batches of a partition costs in device rows."""

from benchmark.metrics import _exchange

UNIT, BETTER, SOURCE = "%", "lower", "program_counter"
LAYER, MOVES = "device stage", "query_geomean_s"


def read(run):
    return _exchange.pad_share(run, "stage_pad_rows", "input_rows")
