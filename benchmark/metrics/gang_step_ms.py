"""``gang_step_ns``: step lookup or compile, the one sharded step's dispatch and
the fetch that syncs (the host blocked on the device), per query.  A host
time, never a device time."""

from benchmark.metrics import _gang

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return _gang.per_query(run, "gang_step_ns", 1e6)
