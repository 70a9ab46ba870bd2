"""Device-eligible stages of the window's queries that ended on the device:
no fallback counter and no ``device_error`` on the stage."""

from benchmark import jobstats

UNIT, BETTER, SOURCE = "%", "higher", "program_counter"
LAYER, MOVES = "device stage", "query_geomean_s"


def read(run):
    stages = [st for q in run["window_all"] if q.get("job")
              for st in jobstats.device_stages(q["job"])]
    if not stages:
        return None
    return 100.0 * sum(not jobstats.stage_off_device(st) for st in stages) / len(stages)
