"""The gang stage's host-to-device bridge timer (``bridge_time_ns``) over
the gang stage's wall, over the window's queries."""

from benchmark import jobstats

UNIT, BETTER, SOURCE = "%", "lower", "program_span"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return jobstats.gang_timer_share(run["window"], "bridge_time_ns")
