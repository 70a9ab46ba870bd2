"""What the task thread's six phases (wait, merge, upload, assemble, step,
materialize: ``_gang.TASK_PHASES``) leave of the gang stage's wall
(``mesh_stage_time_ns``): loop overhead, mesh set-up, cancellation checks.
Nothing to read on a program from before PR 29, which has no wait counter."""

from benchmark.metrics import _gang

UNIT, BETTER, SOURCE = "%", "lower", "program_counter"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return _gang.share_of_wall(run, _gang.TASK_PHASES, rest=True)
