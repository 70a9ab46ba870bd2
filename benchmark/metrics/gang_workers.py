"""``gang_workers`` of ``MeshGangExec`` (PR 29): how many partitions the gang
stage prepared side by side, per query; 1 says it ran them inline, one after
the other."""

from benchmark.metrics import _gang

UNIT, BETTER, SOURCE = "count", "higher", "program_counter"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return _gang.per_query(run, "gang_workers")
