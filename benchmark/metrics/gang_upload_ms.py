"""``gang_upload_ns``: the gang stage's ``jax.device_put`` calls (host time to
hand the columns to the device), per query."""

from benchmark.metrics import _gang

UNIT, BETTER, SOURCE = "ms", "lower", "program_counter"
LAYER, MOVES = "gang stage", "query_geomean_s"


def read(run):
    return _gang.per_query(run, "gang_upload_ns", 1e6)
