"""The gang stage's host scan timer (``scan_time_ns``) over the gang stage's
wall, over the window's queries.  Host timers overlap: the shares of one
stage need not add up to 100."""

from benchmark import jobstats

UNIT, BETTER, SOURCE = "%", "lower", "program_span"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return jobstats.gang_timer_share(run["window"], "scan_time_ns")
