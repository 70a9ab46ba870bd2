"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts the program as a user does (scheduler, ONE executor holding the
cell's chips, remote clients), generates the cell's tables from the seed,
warms up the cell's query kinds, drives the cell's traffic for a window of
whole queries timed on the client's clock around
``BallistaContext.sql(text).collect()``, then compares every answer of the
window with the plain reference and prints one JSON object as the last line
of stdout.  See ``benchmark/README.md``.

Without a TPU (or with fewer chips than the cell asks) it exits non-zero
and prints nothing on stdout.  ``--platform cpu`` is a labelled rehearsal,
never the driver's command.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # set-up counts from process start

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmark import (  # noqa: E402
    compare, harness, jobstats, reference, suites, trace_reduce, window,
)
from benchmark.cluster import Cluster, ClusterFailure, new_job_id  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="'cpu' is the labelled rehearsal, never a fallback")
    ap.add_argument("--sf", type=float, default=None,
                    help="rehearsal only: another scale than the configuration's, as its suite reads it")
    ap.add_argument("--keep", default="", help="copy logs, job details and the trace's reduction here")
    args = ap.parse_args(argv)
    if args.sf is not None and args.platform != "cpu":
        ap.error("--sf is for --platform cpu rehearsals only")
    return args


def collect(ctx, seen: set, suite, kind, params: dict) -> window.WithJob:
    """The timed call, ``sql(text).collect()`` of the suite's text, and the
    id of the job it ran, from the client's own books (``seen``: the ids this
    context had before).  A record finds its job by that id, whoever else
    was submitting."""
    try:
        answer = ctx.sql(suite.render(kind, params)).collect()
    except Exception as e:
        e.job_id = new_job_id(ctx, seen)
        raise
    return window.WithJob(answer, new_job_id(ctx, seen))


def timed(ctx, seen: set, suite, kind, params: dict) -> dict:
    """One query outside the window (warm-up, CPU-operator read)."""
    rec = {"kind": kind, "params": params, "unix_submit": time.time(), "error": None,
           "answer": None, "job_id": None}
    t = time.monotonic()
    try:
        rec["answer"], rec["job_id"] = collect(ctx, seen, suite, kind, params)
    except Exception as e:  # noqa: BLE001 - reported with the run's failure
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["job_id"] = getattr(e, "job_id", None)
    rec["latency_s"] = time.monotonic() - t
    rec["unix_done"] = time.time()
    return rec


def measure(served, resolved: dict, data: dict, seed: int, seconds: float, trace: bool) -> dict:
    """Warm-up, window and read-out against ``served`` (a ``Cluster``, or a
    test's stand-in with its five methods).  Everything a run does between
    the cluster's start and its stop; tests drive it with the timed path
    broken underneath."""
    cell, config, traffic, suite = (resolved[k] for k in ("cell", "config", "traffic", "suite"))
    chips = int(cell["chips"])
    kinds = list(dict.fromkeys(traffic["kinds"]))
    clients = int(traffic.get("clients", 1))
    settings = dict(config.get("session", {}))
    ctxs = [served.client(settings) for _ in range(clients)]
    seen = [set() for _ in ctxs]  # per client, the job ids already given to a record
    fixed = traffic.get("requests")
    draws = suites.Draws(suite, seed, kinds, traffic.get("parameter_sets", 1))

    warmup = []
    # the cell's own kinds, and every set of literals the window will send
    # (the program compiles for each), once each
    todo = [(r["kind"], dict(r["params"])) for r in fixed] if fixed else [
        (kind, p) for kind in kinds for p in draws.window_sets(kind)
    ]
    for kind, params in todo:
        rec = timed(ctxs[0], seen[0], suite, kind, params)
        log(f"warm-up {suite.label(kind)}: {rec['latency_s']:.2f}s {rec['error'] or ''}")
        if rec["error"]:
            raise ClusterFailure(f"warm-up of {suite.label(kind)} failed: {rec['error']}")
        warmup.append(rec)
    cpu_ops = []
    if trace:
        ref_ctx, ref_seen = served.client({**settings, "ballista.tpu.enable": "false"}), set()
        for kind in kinds:
            rec = timed(ref_ctx, ref_seen, suite, kind, draws.aside(kind))
            log(f"cpu operators {suite.label(kind)}: {rec['latency_s']:.2f}s {rec['error'] or ''}")
            cpu_ops.append(rec)
        ref_ctx.close()
    trace_marks: dict = {}
    if trace:
        trace_marks["start"] = served.start_trace()
        if trace_marks["start"].get("error"):
            raise ClusterFailure(f"profiler did not start: {trace_marks['start']['error']}")

    def stop_trace() -> None:
        if trace and "stop" not in trace_marks:
            trace_marks["stop"] = served.stop_trace()

    setup_s = time.monotonic() - T0
    log(f"set-up done in {setup_s:.1f}s; window of {seconds:g}s")
    result = window.run_window(
        suite, traffic, seed, seconds,
        lambda c, kind, params: collect(ctxs[c], seen[c], suite, kind, params),
        after_first_cycle=stop_trace,
    )
    stop_trace()
    memory = served.memory()
    for ctx in ctxs:
        ctx.close()

    records = result["queries"]
    details = [d for d in served.job_details() if d.get("stages") is not None]
    jobs = [jobstats.summarize(d) for d in details]
    by_time = jobstats.match(warmup + cpu_ops + records, jobs)
    if by_time:
        # sound only for one client (a test's stand-in keeps no ids): with
        # several, the clients' order and the scheduler's cross
        log(f"{len(by_time)} record(s) carry no job id and took the job submitted soonest after "
            f"their call ({clients} client(s))")
    for r in records:
        if r["error"] is None:
            why = (
                jobstats.wrong_route(r["job"], chips, r["kind"] in config.get("gang_kinds", []))
                if r["job"] else "no job detail"
            )
            if why:
                r["wrong_route"] = why
                log(f"{suite.label(r['kind'])} client {r['client']} #{r['seq']} job {r['job_id']} "
                    f"off the cell's path: {why}")
    return {
        "setup_s": setup_s, "records": records, "window_s": result["window_s"],
        "warmup": warmup, "cpu_ops": cpu_ops, "memory": memory,
        "trace_marks": trace_marks, "chips": chips, "job_details": details, "paired_by_time": len(by_time),
        "rows_of_kind": {
            k: sum(data["rows"][t] for t in suite.TABLES_OF[k]) for k in kinds
        },
        "suite": suite,
    }


def judge(measured: dict, data_dir: str) -> dict:
    """Every answer the window completed against the suite's plain reference."""
    suite = measured["suite"]
    ref = reference.Data(data_dir)
    answers: dict = {}  # a window repeats its parameter sets: one reference answer each
    pairs = []
    for r in measured["records"]:
        if r["error"] is None:
            key = (r["kind"], json.dumps(r["params"], sort_keys=True))
            if key not in answers:
                answers[key] = suite.answer(ref, r["kind"], r["params"])
            pairs.append((r["answer"], answers[key], suite.ORDER_BY[r["kind"]]))
    return compare.judge(pairs)


def reduce_trace(measured: dict, trace_dir: str, rehearsal: bool) -> dict:
    marks = measured["trace_marks"]
    zero = marks["start"]["unix_ns_before"]
    end = marks["stop"]["unix_ns_before"]
    events = trace_reduce.read_xplane(trace_reduce.find_xplane(trace_dir), rehearsal)
    red = trace_reduce.reduce(events, measured["chips"])
    red["events"] = events
    red["window_s"] = (end - zero) / 1e9
    traced = [
        r for r in measured["records"]
        if r["error"] is None and r.get("job") and r["unix_done"] * 1e9 <= end + 5e8
    ]
    red["queries"] = traced
    spans, covered = [], []
    for r in traced:
        j, tag = r["job"], measured["suite"].label(r["kind"])
        a = j["submitted_us"] * 1e3 - zero
        b = (j["end_us"] or j["submitted_us"]) * 1e3 - zero
        covered.append((a, b))
        spans.append((f"{tag}:planning", a, a + j["planning_us"] * 1e3))
        in_stages = 0
        for st in j["stages"]:
            if st["start_us"] is None or st["end_us"] is None:
                continue
            s, e = st["start_us"] * 1e3 - zero, st["end_us"] * 1e3 - zero
            spans.append((f"{tag}:stage_{st['stage_id']}_{st['chain']}", s, e))
            in_stages += e - s
        # between stages: lumped at the job's start (tasks are host-bound; the device is idle there)
        rest = max(0, (b - a) - in_stages - j["planning_us"] * 1e3)
        spans.append((f"{tag}:between_stages_scheduler", a, a + rest))
    gaps = trace_reduce.idle_gaps(red, spans, measured["chips"])
    between = red["window_s"] - sum(b - a for a, b in trace_reduce.merge(covered)) / 1e9
    gaps.append(["between_jobs:client_notice_result_fetch_submit", max(0.0, between)])
    red["idle_gaps"] = sorted(gaps, key=lambda kv: -kv[1])[:10]
    return red


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = harness.benchmark_json()
    resolved = harness.resolve(args.workload, bench)
    cell, config, suite = resolved["cell"], resolved["config"], resolved["suite"]
    readers = harness.load_readers()
    peaks_table = harness.load_json(os.path.join(HERE, "peaks.json"))
    try:
        import arrow_ballista_tpu.client  # noqa: F401 - the program has to be there
    except ImportError as e:
        log(f"the program is not in this checkout: {e}")
        return 2
    rehearsal = args.platform == "cpu"
    scale = suite.scale(config, args.sf)
    kinds = list(dict.fromkeys(resolved["traffic"]["kinds"]))
    tables = [t for t in config["tables"] if any(t in suite.TABLES_OF[k] for k in kinds)]

    work = tempfile.mkdtemp(prefix="abt_bench_")
    cluster = Cluster(work, args.platform)
    try:
        # the cluster first: with no chip the executor's start-up fails
        # while the data is still being written
        cluster.start()
        data = suites.generate(work, suite, tables, scale, args.seed, int(config["files_per_table"]))
        log(f"data: scale {scale} {data['rows']} {data['parquet_bytes'] / 1e6:.0f} MB in {data['seconds']:.1f}s")
        info = cluster.wait_ready()
        log(f"executor up: {info}")
        if info["platform"] != args.platform or int(info["device_count"]) != int(cell["chips"]):
            raise ClusterFailure(
                f"cell asks {cell['chips']} {args.platform} chip(s); the executor holds "
                f"{info['device_count']} x {info['platform']}"
            )
        if not rehearsal and info["device_kind"] not in peaks_table:
            raise ClusterFailure(f"device kind {info['device_kind']!r} is not in peaks.json")
        cluster.tables = {t: os.path.join(work, t) for t in tables}
        measured = measure(cluster, resolved, data, args.seed, args.seconds, bool(args.trace))
        exits = cluster.stop()
        log(f"cluster stopped: {exits}")
        if any(e["sigkill"] for e in exits.values()):
            raise ClusterFailure("a child had to be SIGKILLed")
        verdict = judge(measured, work)
        trace = None
        if args.trace:
            trace_dir = os.path.join(cluster.ctl_dir, "trace")
            trace = reduce_trace(measured, trace_dir, rehearsal)
            if args.keep:
                xplane = trace_reduce.find_xplane(trace_dir)
                trace["described"] = trace_reduce.describe(xplane)
                trace["xplane_bytes"] = os.path.getsize(xplane)
        if args.keep:
            keep(args.keep, work, measured, trace)
    except ClusterFailure as e:
        log(f"FAILED: {e}")
        return 1
    finally:
        cluster.stop()
        shutil.rmtree(work, ignore_errors=True)

    records = measured["records"]
    good = [r for r in records if r["error"] is None and not r.get("wrong_route")]
    if not good:
        log("FAILED: the window completed no query on the cell's path")
        return 1
    e2e = window.end_to_end(records, measured["window_s"], measured["rows_of_kind"], measured["chips"])
    e2e["setup_s"] = measured["setup_s"]
    peak = max([p for p in measured["memory"].get("peak_bytes_in_use", []) if p] or [0])
    out = {
        "correct": verdict["correct"],
        "attempted": len(records),
        "failed": len(records) - len(good),
        "metrics": {},
        "device": {
            "platform": str(info["platform"]), "kind": str(info["device_kind"]),
            "count": int(info["device_count"]), "memory_peak_bytes": int(peak),
        },
    }
    if args.trace:
        run = {
            "cell": cell, "config": config, "traffic": resolved["traffic"], "suite": suite,
            "chips": measured["chips"], "window": good, "window_all": records,
            "window_s": measured["window_s"],
            "warmup": measured["warmup"], "cpu_ops": measured["cpu_ops"],
            "trace": trace, "memory": measured["memory"], "data": data,
            "peaks": peaks_table.get(info["device_kind"]),  # None only in a rehearsal
        }
        out["metrics"] = harness.read_per_layer(bench, cell["name"], run, readers)
        out["device"]["busy_s"] = trace["busy_s"]
        out["device"]["window_s"] = trace["window_s"]
        out["breakdown"] = {"device_ops": trace["device_ops"][:10], "idle_gaps": trace["idle_gaps"]}
    else:
        for m in harness.metrics_of_cell(bench, cell["name"], "end_to_end"):
            if e2e.get(m["name"]) is not None:
                out["metrics"][m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}
    out["window"] = {
        "window_s": measured["window_s"], "seconds": args.seconds,
        "latencies_s": {suite.label(k): [r["latency_s"] for r in good if r["kind"] == k] for k in kinds},
        "compared": verdict["compared"],
        "completions_by_client": [
            sum(r["client"] == c for r in good) for c in range(int(resolved["traffic"].get("clients", 1)))
        ],
        "paired_by_time": measured["paired_by_time"],
    }
    if rehearsal:
        out["rehearsal"] = f"--platform cpu at scale {scale}: no number here is a device's"
    out["compared"] = {k: [v["value"], v["limit"]] for k, v in verdict["numbers"].items()}
    log(f"widest gap in column {verdict['widest']!r}")
    for name, v in verdict["numbers"].items():
        print(f"compared {name} = {v['value']:.6g} (limit {v['limit']:g})", file=sys.stderr)
    print(f"correct = {verdict['correct']} over {verdict['compared']} answers", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


def keep(dest: str, work: str, measured: dict, trace) -> None:
    os.makedirs(dest, exist_ok=True)
    for f in os.listdir(work):
        if f.endswith(".log"):
            shutil.copy(os.path.join(work, f), dest)
    slim = [
        {k: v for k, v in r.items() if k != "answer"}
        for r in measured["warmup"] + measured["cpu_ops"] + measured["records"]
    ]
    with open(os.path.join(dest, "queries.json"), "w") as f:
        json.dump(slim, f, indent=1, default=str)
    with open(os.path.join(dest, "job_details.json"), "w") as f:
        json.dump(measured["job_details"], f, default=str)
    if trace:
        with open(os.path.join(dest, "trace.json"), "w") as f:
            json.dump({k: v for k, v in trace.items() if k not in ("queries", "events")}, f, default=str)
        import gzip

        with gzip.open(os.path.join(dest, "trace_events.json.gz"), "wt") as f:
            json.dump(trace["events"], f)


if __name__ == "__main__":
    sys.exit(main())
