"""Shared by the per-chip readers: ``tpu_stage_time_ns_dev<chip>`` of
``TpuStageExec``, the device stage's host-timed partitions counted on the chip
each ran on (the chips' counters sum to ``tpu_stage_time_ns``).  A program
that counts no such key gives every reader None.  A file whose name starts
with ``_`` is no reader."""

import re

KEY = re.compile(r"tpu_stage_time_ns_dev(\d+)$")


def per_chip(vals: dict) -> dict:
    """chip -> ns, of one operator's counters (empty where it has none)."""
    return {int(m.group(1)): int(v or 0) for k, v in vals.items() for m in [KEY.match(k)] if m}


def stages_per_chip(job: dict) -> list:
    """chip -> ns of each of the job's operators that count per chip."""
    return [c for st in job["stages"] for vals in st["ops"].values() for c in [per_chip(vals)] if c]
