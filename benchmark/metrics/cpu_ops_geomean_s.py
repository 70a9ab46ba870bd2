"""The cell's kinds with ``ballista.tpu.enable=false`` through the same
cluster, once each before the window (traced runs only): the CPU operators
share every layer but the device stage with the path under test."""

import math

UNIT, BETTER, SOURCE = "s", "lower", "host_clock"
LAYER, MOVES = "CPU operator reference", "query_geomean_s"


def read(run):
    v = [q["latency_s"] for q in run["cpu_ops"] if q["error"] is None]
    return math.exp(sum(map(math.log, v)) / len(v)) if v else None
