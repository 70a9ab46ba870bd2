"""``gang_merge_ns`` of ``MeshGangExec`` (PR 29): the task thread maps each
partition's own dictionaries and groups into the stage's, in partition order,
and rewrites its segment ids with one gather, per query."""

from benchmark.metrics import _gang

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return _gang.per_query(run, "gang_merge_ns", 1e6)
