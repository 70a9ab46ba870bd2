"""``join_build_ns``: the folded join's build (sort by key, pad to the
bucket, upload, dense table), without the child's execute that feeds it, per
query."""

from benchmark.metrics import _exchange

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "join", "query_geomean_s"


def read(run):
    return _exchange.per_query(run, "join_build_ns", 1e6)
