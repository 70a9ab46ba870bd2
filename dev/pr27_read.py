"""Beside ``benchmark/chip/phases.py`` (which this PR may not edit): per kept
run and query kind, the bytes and arrays the gang stage handed to
``device_put`` and the host rate they give; for a traced run, the device's
program times of its traced cycle.

    python3 dev/pr27_read.py <kept dir> [<kept dir> ...]
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.chip.phases import window_queries  # noqa: E402
from benchmark.metrics import _gang  # noqa: E402


def main(kept: str) -> None:
    name = os.path.basename(kept.rstrip("/"))
    queries = window_queries(kept)
    for kind in sorted({q["kind"] for q in queries}):
        run = {"window": [q for q in queries if q["kind"] == kind]}
        row = {
            k: _gang.per_query(run, k)
            for k in ("gang_uploads", "gang_upload_bytes", "gang_upload_ns", "gang_partitions", "gang_batches")
        }
        if row["gang_upload_bytes"] and row["gang_upload_ns"]:
            row["upload_GB/s"] = round(row["gang_upload_bytes"] / row["gang_upload_ns"], 3)
        print(json.dumps({"run": name, "kind": f"q{kind}", **row}))
    path = os.path.join(kept, "trace.json")
    if os.path.exists(path):
        with open(path) as f:
            tr = json.load(f)
        print(json.dumps({"run": name, **{k: tr[k] for k in ("busy_s", "window_s", "device_ops", "idle_gaps")}}))


if __name__ == "__main__":
    for d in sys.argv[1:]:
        main(d)
