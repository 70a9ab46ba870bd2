"""``gang_wait_ns`` of ``MeshGangExec`` (PR 29): the task thread's time until
the next partition in order is prepared, per query: the route probe of the
stage's first batch, then blocked on the workers that scan, key-encode and
convert partitions side by side (at ``gang_workers`` 1, that work itself,
inline).  What is still exposed of the workers' work."""

from benchmark.metrics import _gang

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return _gang.per_query(run, "gang_wait_ns", 1e6)
