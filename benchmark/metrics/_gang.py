"""Shared by the gang-stage phase readers (PR 26): ``MeshGangExec``'s
always-on phase counters, as the job detail carries them.  Since PR 29 they
are of two kinds.  ``TASK_PHASES`` are self times of the ONE thread that
runs the task and sum to the stage's wall ``mesh_stage_time_ns`` up to loop
overhead.  The workers' three (``gang_scan_ns``, ``key_encode_time_ns``,
``gang_convert_ns``) are summed over the ``gang_workers`` threads that
prepare partitions side by side, inside the task thread's wait: up to
``gang_workers`` x the wall, never a share of it.  A file whose name starts
with ``_`` is no reader."""

from benchmark import jobstats

TASK_PHASES = (
    "gang_wait_ns", "gang_merge_ns", "gang_upload_ns", "gang_assemble_ns",
    "gang_step_ns", "gang_materialize_ns",
)
WALL = "mesh_stage_time_ns"


def gang_ops(run) -> list:
    """Per query of the window that has a gang stage, the ``MeshGangExec``
    counter dicts of its gang stages."""
    out = []
    for q in run["window"]:
        ops = [st["ops"]["MeshGangExec"] for st in jobstats.gang_stages(q["job"])] if q.get("job") else []
        if ops:
            out.append(ops)
    return out


def total(ops: list, key: str):
    """Sum of one counter over ``gang_ops``; None where no stage counts it."""
    found = [int(op[key] or 0) for query in ops for op in query if key in op]
    return sum(found) if found else None


def per_query(run, key: str, scale: float = 1.0):
    """One counter summed over a query's gang stage, mean over the window's
    queries that have one (a query that never counts it, as q6 never encodes
    a key, adds 0); None where the program has no such counter."""
    ops = gang_ops(run)
    counted = total(ops, key)
    return None if counted is None else counted / scale / len(ops)


def share_of_wall(run, keys, rest: bool = False):
    """100 x the sum of ``keys`` over the summed stage walls (or, with
    ``rest``, the wall they leave); None unless every key and the wall are
    counted."""
    ops = gang_ops(run)
    parts = [total(ops, k) for k in keys]
    wall = total(ops, WALL)
    if not wall or any(p is None for p in parts):
        return None
    return 100.0 * ((wall - sum(parts)) if rest else sum(parts)) / wall
