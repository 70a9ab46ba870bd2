"""Where the device sat idle inside a stage, by the program's own spans.

    # a traced run that keeps its profile beside what --keep keeps
    python3 benchmark/chip/span_gaps.py run <run.py's arguments, --trace 1 and --keep DIR among them>
    # the table, from a kept run
    python3 benchmark/chip/span_gaps.py read DIR [--stage 4] [--json OUT] [--rehearsal]

``run`` is ``run.py`` unchanged, with the profile (``.xplane.pb``) copied
into DIR as ``trace.xplane.pb`` and the launcher's ``trace_start`` ack kept as
``trace_marks.json`` (the trace's zero on the unix clock).  Nothing else of a
run differs: the program's spans reach the profile whether
``ballista.obs.enabled`` is on or off.

``read`` takes the window of stage ``--stage`` of every traced query from
the scheduler's stamps (``job_details.json``: first dispatch to last finish,
as the harness's ``idle_gaps`` does) and, inside it, every instant at which a
chip was idle (the device planes' op events, merged) goes to the innermost
program span open at that instant on any executor thread: the one that
started last among the host plane's events named ``<layer>.<phase>`` whose
``job`` metadata is that query's (or which carry none; one of another
stage of the query, running beside it, is named with its stage).  An
instant with no such span goes to ``(no span)``.  Idle time is averaged over the chips, like
``idle_gaps``.  It prints, per query and summed, the table by span name,
the share of the idle time that named spans cover, and the stage's
exchange-call phases as the counters read them (``exchange_<phase>_ns``)
beside ``jit_local_exchange``'s device time in the same window.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import trace_reduce  # noqa: E402

SPAN_NAME = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")
NO_SPAN = "(no span)"
PHASES = ("pad", "upload", "program", "fetch")


# ------------------------------------------------------------------- run
def run_and_keep(argv: list) -> int:
    from benchmark import cluster, run

    dest = argv[argv.index("--keep") + 1]
    os.makedirs(dest, exist_ok=True)
    find_xplane, start_trace = trace_reduce.find_xplane, cluster.Cluster.start_trace

    def find_and_keep(trace_dir):
        path = find_xplane(trace_dir)
        shutil.copy(path, os.path.join(dest, "trace.xplane.pb"))
        return path

    def start_trace_and_keep(self):
        ack = start_trace(self)
        with open(os.path.join(dest, "trace_marks.json"), "w") as f:
            json.dump(ack, f)
        return ack

    trace_reduce.find_xplane = find_and_keep
    cluster.Cluster.start_trace = start_trace_and_keep
    return run.main(argv)


# ------------------------------------------------------------------ read
def host_spans(path: str) -> list:
    """(name, start_ns, end_ns, metadata) of the program's spans on the
    profile's host plane, on the trace's clock."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if SPAN_NAME.match(e.name):
                    start = int(e.start_ns)
                    out.append((e.name, start, start + int(e.duration_ns), dict(e.stats)))
    return out


def attribute(window: tuple, spans: list, busy: dict, chips: int, stage=None) -> dict:
    """Idle seconds inside ``window`` by innermost open span (a span of
    another stage than ``stage`` is named with its stage); ``busy``:
    chip -> merged busy intervals.  A sweep over every boundary."""
    w0, w1 = window
    marks = []  # (time, order, kind, payload): ends before starts at one instant
    for i, (_, a, b, _) in enumerate(spans):
        a, b = max(a, w0), min(b, w1)
        if b > a:
            marks += [(a, 1, "open", i), (b, 0, "close", i)]
    for chip, intervals in busy.items():
        for a, b in intervals:
            a, b = max(a, w0), min(b, w1)
            if b > a:
                marks += [(a, 1, "busy", chip), (b, 0, "free", chip)]
    marks += [(w0, 2, "edge", None), (w1, 2, "edge", None)]
    marks.sort(key=lambda m: (m[0], m[1]))
    open_spans: set = set()
    busy_now: dict = {}
    out: dict = {}
    last = w0
    for t, _, kind, payload in marks:
        if t > last:
            idle = chips - sum(1 for n in busy_now.values() if n > 0)
            if idle > 0:
                inner = max(open_spans, key=lambda i: (spans[i][1], -spans[i][2]), default=None)
                name = NO_SPAN if inner is None else _label(spans[inner], stage)
                out[name] = out.get(name, 0.0) + (t - last) * idle / chips / 1e9
            last = t
        if kind == "open":
            open_spans.add(payload)
        elif kind == "close":
            open_spans.discard(payload)
        elif kind == "busy":
            busy_now[payload] = busy_now.get(payload, 0) + 1
        elif kind == "free":
            busy_now[payload] -= 1
    return out


def _label(span: tuple, stage) -> str:
    other = span[3].get("stage")
    return span[0] if other in (None, stage) else f"{span[0]} (stage {other})"


def read_kept(dest: str, stage: int, rehearsal: bool = False) -> dict:
    with open(os.path.join(dest, "trace_marks.json")) as f:
        zero = json.load(f)["unix_ns_before"]
    with open(os.path.join(dest, "queries.json")) as f:
        records = json.load(f)
    path = os.path.join(dest, "trace.xplane.pb")
    events = trace_reduce.read_xplane(path, rehearsal)
    chips = max(1, len({e[0] for e in events}))
    busy = trace_reduce.reduce(events, chips)["busy_intervals"]
    spans = host_spans(path)
    end = max([b for _, _, b, _ in spans] + [s + n for *_, s, n in events] + [0])
    queries = []
    for r in records:
        job = r.get("job")
        if not job or r.get("error"):
            continue
        (st,) = [s for s in job["stages"] if s["stage_id"] == stage] or [None]
        if st is None or st["start_us"] is None or st["end_us"] is None:
            continue
        window = (st["start_us"] * 1e3 - zero, st["end_us"] * 1e3 - zero)
        if window[0] < 0 or window[1] > end:
            continue  # outside the profile
        mine = [s for s in spans if s[3].get("job") in (job["job_id"], None)]
        gaps = attribute(window, mine, busy, chips, stage)
        exchange = [v for op, v in st["ops"].items() if op.startswith("MeshRepartition")]
        program_s = sum(
            trace_reduce.overlap([[s, s + n]], *window)
            for _, line, name, s, n in events
            if line == trace_reduce.MODULES and trace_reduce.program_name(name) == "jit_local_exchange"
        ) / 1e9 / chips
        queries.append({
            "job_id": job["job_id"], "kind": r.get("kind"), "window_s": (window[1] - window[0]) / 1e9,
            "idle_s": sum(gaps.values()), "by_span": dict(sorted(gaps.items(), key=lambda kv: -kv[1])),
            "phases_ms": {p: sum(int(v.get(f"exchange_{p}_ns", 0) or 0) for v in exchange) / 1e6 for p in PHASES},
            "device_time_ms": sum(int(v.get("device_time_ns", 0) or 0) for v in exchange) / 1e6,
            "jit_local_exchange_s": program_s,
        })
    total: dict = {}
    for q in queries:
        for k, v in q["by_span"].items():
            total[k] = total.get(k, 0.0) + v
    idle = sum(total.values())
    return {
        "stage": stage, "chips": chips, "queries": queries,
        "by_span": dict(sorted(total.items(), key=lambda kv: -kv[1])),
        "idle_s": idle,
        "named_share": (1 - total.get(NO_SPAN, 0.0) / idle) if idle else None,
    }


def main(argv: list) -> int:
    if argv and argv[0] == "run":
        return run_and_keep(argv[1:])
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("read",))
    ap.add_argument("dest")
    ap.add_argument("--stage", type=int, default=4)
    ap.add_argument("--json", default="")
    ap.add_argument("--rehearsal", action="store_true",
                    help="a --platform cpu run: the CPU client's threads stand in for the device")
    args = ap.parse_args(argv)
    out = read_kept(args.dest, args.stage, args.rehearsal)
    n = max(1, len(out["queries"]))
    print(f"stage {out['stage']}, {len(out['queries'])} traced queries, {out['chips']} chip(s): "
          f"idle {out['idle_s']:.3f} s, named spans cover {100 * (out['named_share'] or 0):.1f}%")
    print(f"{'span':<36}{'idle s a query':>16}{'share':>9}")
    for name, s in out["by_span"].items():
        print(f"{name:<36}{s / n:>16.3f}{100 * s / out['idle_s']:>8.1f}%")
    for q in out["queries"]:
        ph = " ".join(f"{p} {q['phases_ms'][p]:.1f}" for p in PHASES)
        print(f"{q['job_id']}: window {q['window_s']:.3f} s, idle {q['idle_s']:.3f} s; exchange call "
              f"{q['device_time_ms']:.1f} ms = {ph}; jit_local_exchange {1e3 * q['jit_local_exchange_s']:.1f} ms")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
