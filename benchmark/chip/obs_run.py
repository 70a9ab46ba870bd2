"""``run.py`` with the program's own spans kept (PR 26's chip measurements).

    python3 benchmark/chip/obs_run.py --obs <0|1> <run.py's arguments, --keep DIR among them>

``--obs 1`` switches ``ballista.obs.enabled`` on in the cell's session, in
memory: no configuration file is written and the program gets no new knob.
Beside what ``--keep`` keeps, DIR gets ``spans.json`` (with obs on: every job's
``GET /api/jobs/{id}/trace``, Chrome-trace JSON: ``ts`` in microseconds of
the unix clock) and ``trace_marks.json`` (the launcher's ``trace_start``
ack: the device trace's zero).  Nothing else of a run differs.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import cluster, harness, run  # noqa: E402


def main(argv) -> int:
    obs = argv[argv.index("--obs") + 1] == "1"
    argv = [a for i, a in enumerate(argv) if a != "--obs" and (i == 0 or argv[i - 1] != "--obs")]
    dest = argv[argv.index("--keep") + 1]
    os.makedirs(dest, exist_ok=True)
    resolve, job_details, start_trace = harness.resolve, cluster.Cluster.job_details, cluster.Cluster.start_trace

    def resolve_with_obs(*a, **kw):
        resolved = resolve(*a, **kw)
        if obs:
            resolved["config"].setdefault("session", {})["ballista.obs.enabled"] = "true"
        return resolved

    def job_details_and_spans(self):
        details = job_details(self)
        if not obs:  # the scheduler answers 404 for a job it kept no span of
            return details
        spans = {
            d["job_id"]: cluster.rest(self.rest_port, f"/api/jobs/{d['job_id']}/trace")
            for d in details if d.get("job_id")
        }
        with open(os.path.join(dest, "spans.json"), "w") as f:
            json.dump(spans, f)
        return details

    def start_trace_and_keep(self):
        ack = start_trace(self)
        with open(os.path.join(dest, "trace_marks.json"), "w") as f:
            json.dump(ack, f)
        return ack

    harness.resolve = resolve_with_obs
    cluster.Cluster.job_details = job_details_and_spans
    cluster.Cluster.start_trace = start_trace_and_keep
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
