"""Rows of padding over the rows handed to the exchange program
(``mesh_exchange_padded_rows`` / (``mesh_exchange_rows`` + padding)): what
bucketing the exchange's input costs in device rows."""

from benchmark.metrics import _exchange

UNIT, BETTER, SOURCE = "%", "lower", "program_counter"
LAYER, MOVES = "exchange", "query_geomean_s"


def read(run):
    return _exchange.pad_share(run, "mesh_exchange_padded_rows", "mesh_exchange_rows")
