"""Shared by the join readers (PR 34): the bytes the build-row program
(``join_aggregate``: the folded join's aggregate keyed on the matched build
row) had to move at least, from the always-on counters of ``TpuStageExec``
as the job detail carries them.  A file whose name starts with ``_`` is no
reader."""

from benchmark.metrics import _exchange

# what a probe row reads of the join's table: the dense table's i32 slot,
# or a key of the sorted build side at each step of the binary search
KEY_BYTES = 4
COUNTERS = ("join_probe_bytes", "join_probe_rows", "join_matched_rows",
            "join_state_bytes", "join_state_slots")


def agg_bytes(job: dict):
    """Least bytes of one query's build-row programs: the padded probe rows'
    input columns read once (``join_probe_bytes``: the row mask and each
    probe array as handed); the join's table read once a padded row (4 B
    dense; 4 B a step of the sorted form's search over ``join_build_capacity``
    keys); a read and a write of every state a matched row touches
    (``join_matched_rows`` x the bytes of one slot's states); the states
    fetched (``join_state_bytes``).  None where the program has no such
    counters."""
    ops = _exchange.ops_with(job, "join_build_groups")
    if not ops or any(k not in v for v in ops for k in COUNTERS):
        return None
    total = 0.0
    for v in ops:
        rows = int(v["join_probe_rows"]) + int(v.get("stage_pad_rows", 0) or 0)
        steps = 1 if v.get("dense_join") else max(1, int(v.get("join_build_capacity", 1))).bit_length()
        slots = int(v["join_state_slots"])
        slot_bytes = int(v["join_state_bytes"]) / slots if slots else 0.0
        total += (int(v["join_probe_bytes"]) + KEY_BYTES * steps * rows
                  + 2 * int(v["join_matched_rows"]) * slot_bytes + int(v["join_state_bytes"]))
    return total
