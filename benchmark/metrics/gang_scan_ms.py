"""``gang_scan_ns`` of ``MeshGangExec``: time inside ``next()`` on the gang stage's
source (parquet read, decode, ``from_arrays``), per query."""

from benchmark.metrics import _gang

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return _gang.per_query(run, "gang_scan_ns", 1e6)
