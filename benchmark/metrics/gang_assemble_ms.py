"""``gang_assemble_ns``: host time of ``assemble_shards`` (dispatching the
per-device concatenates and pads), per query."""

from benchmark.metrics import _gang

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return _gang.per_query(run, "gang_assemble_ns", 1e6)
