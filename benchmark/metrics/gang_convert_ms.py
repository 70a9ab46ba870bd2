"""``gang_convert_ns``: Arrow -> numpy (``build_env``, casts, building the
column list) in the gang stage, per query."""

from benchmark.metrics import _gang

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return _gang.per_query(run, "gang_convert_ns", 1e6)
