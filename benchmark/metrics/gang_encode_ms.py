"""``key_encode_time_ns`` of ``MeshGangExec``: the host's group-key encode in the
gang stage, per query (q6 has no key and adds 0)."""

from benchmark.metrics import _gang

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return _gang.per_query(run, "key_encode_time_ns", 1e6)
