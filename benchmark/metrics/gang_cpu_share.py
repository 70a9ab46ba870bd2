"""``gang_cpu_ns`` (the task thread's ``thread_time_ns``) over the gang stage's
wall: under 100 the one host thread waited (for a core, the device, I/O)."""

from benchmark.metrics import _gang

UNIT, BETTER, SOURCE = "%", "higher", "program_counter"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return _gang.share_of_wall(run, ("gang_cpu_ns",))
