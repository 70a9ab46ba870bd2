"""What the seven phase counters leave of the gang stage's wall
(``mesh_stage_time_ns``): loop overhead, mesh set-up, cancellation checks."""

from benchmark.metrics import _gang

UNIT, BETTER, SOURCE = "%", "lower", "program_counter"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return _gang.share_of_wall(run, _gang.PHASES, rest=True)
