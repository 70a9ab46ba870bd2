"""Chip smoke: the served TPC-H path, end to end, on the accelerator.

    python chip_smoke.py                       # one v5e chip (or a 4-chip host), SF10
    python chip_smoke.py --platform cpu --sf 0.01   # rehearsal, labelled cpu

What it drives is what a user starts: ``python -m arrow_ballista_tpu.scheduler``
and ONE ``python -m arrow_ballista_tpu.executor`` with their default flags
(pull-staged, 4 slots, task_isolation=process), a ``BallistaContext.remote``
client with session defaults (device on, mesh on), tables registered as
parquet paths.  lineitem/orders/customer are generated from ``--seed`` at
``--sf`` (full schemas, ``--files`` parquet files per table, outside the
checkout); q1, q6 and q3 run twice on the device path, once with
``ballista.tpu.enable=false`` through the same cluster as the plain
reference, and once more on a SECOND executor process so the persistent
compile cache's hit shows next to the cold start.

One process holds the chip: the executor.  This parent never touches a jax
backend (it reports so), the scheduler is pinned to the CPU platform, and
the executor is TOLD its platform so jax raises instead of quietly choosing
the CPU.  There is no fallback: without ``--platform cpu`` a machine with
no chip fails at executor start-up and nothing is printed on stdout.

stdout: the full report (one JSON line, ending ``"claim": null``), then as
the LAST line exactly ``{"ok": ..., "device": {"platform", "kind", "count"}}``
with the device as the executor process reported it from jax.  Exit 0 only
if every check held.  Wall times in the report are smoke observations on a shared
host, not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
QUERY_IDS = (1, 6, 3)
TABLES = ("lineitem", "orders", "customer")
BUDGET_S = 1200  # the contract's limit, compilation included
# operator rows of the job detail that are device stages
DEVICE_OPS = ("TpuStageExec", "MeshGangExec", "TpuWindowExec")
# a device stage's time on the device: TpuStageExec's device_time_ns, the
# gang's two device phases (assemble + step)
DEVICE_NS = ("device_time_ns", "gang_assemble_ns", "gang_step_ns")
# counters lifted from the raw operator metrics into each run's summary
COUNTERS = (
    "device_error", "cpu_fallback", "tpu_fallback", "mesh_fallback",
    "join_fallback", "highcard_fallback", "keyed_path",
    "mesh_exchange_fallback", "mesh_exchange_rows", "mesh_devices",
    "mesh_rows_in", "cache_hits", "capacity_growths",
    "device_time_ns", "gang_assemble_ns", "gang_step_ns",
    "tpu_execute_ns", "tpu_compile_ns", "kernel_compiles",
    "compile_cache_misses", "xla_compiles", "xla_compile_ns",
    "xla_cache_hits",
)


T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[chip_smoke {time.monotonic() - T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ data
def _write_chunk(job) -> tuple:
    """Pool worker: generate one slice of one table and write it."""
    name, i, k, sf, seed, out_dir = job
    import pyarrow.parquet as pq

    from benchmarks.tpch import datagen

    gen = getattr(datagen, f"gen_{name}")
    tbl = gen(sf, seed=seed + TABLES.index(name), chunk=(i, k))
    path = os.path.join(out_dir, name, f"part-{i:03d}.parquet")
    pq.write_table(tbl, path)
    return name, tbl.num_rows, os.path.getsize(path)


def generate(data_dir: str, sf: float, seed: int, files: int) -> dict:
    for name in TABLES:
        os.makedirs(os.path.join(data_dir, name), exist_ok=True)
    jobs = [
        (name, i, files, sf, seed, data_dir)
        for name in TABLES  # lineitem first: the long jobs start first
        for i in range(files)
    ]
    # spawn: workers re-import this file as a module and never see jax
    ctx = multiprocessing.get_context("spawn")
    procs = max(1, min(files, (os.cpu_count() or 2) - 1))
    t0 = time.monotonic()
    rows = dict.fromkeys(TABLES, 0)
    size = 0
    with ctx.Pool(procs) as pool:
        for name, n, nbytes in pool.imap_unordered(_write_chunk, jobs):
            rows[name] += n
            size += nbytes
    return {
        "rows": rows,
        "parquet_bytes": size,
        "files_per_table": files,
        "seconds": round(time.monotonic() - t0, 1),
        "processes": procs,
    }


# --------------------------------------------------------------- cluster
def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env(platform: str) -> dict:
    """The host's environment (libtpu reads its TPU_* settings from it)
    with the jax platform stated, never inherited."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME")
    }
    env["JAX_PLATFORMS"] = platform
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


class Child:
    def __init__(self, name: str, args: list, platform: str, log_path: str):
        self.name = name
        self.log_path = log_path
        self._sink = open(log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", *args],
            env=_child_env(platform),
            stdout=self._sink,
            stderr=subprocess.STDOUT,
            cwd=REPO,
        )

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def wait_for(self, pattern: str, timeout_s: float):
        """First regex match in the child's log; fails when the child
        exits first (its log tail goes to stderr) or the wait runs out."""
        deadline = time.monotonic() + timeout_s
        rx = re.compile(pattern)
        while True:
            m = rx.search(self.log_text())
            if m:
                return m
            rc = self.proc.poll()
            if rc is not None:
                raise SmokeFailure(
                    f"{self.name} exited with code {rc} before it was ready:\n"
                    + self.log_text()[-3000:]
                )
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f"{self.name} not ready after {timeout_s:.0f}s:\n"
                    + self.log_text()[-3000:]
                )
            time.sleep(0.2)

    def terminate(self, grace_s: float = 30.0) -> dict:
        """SIGTERM and wait.  A holder of the chip that has to be SIGKILLed
        can leave the libtpu lock behind for the next process, so needing
        the kill is a failed check, not a detail."""
        killed = False
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                killed = True
                self.proc.kill()
                self.proc.wait()
        self._sink.close()
        return {"returncode": self.proc.returncode, "sigkill": killed}


class SmokeFailure(Exception):
    pass


def start_scheduler(work: str) -> tuple:
    port, rest = _free_port(), _free_port()
    child = Child(
        "scheduler",
        [
            "arrow_ballista_tpu.scheduler",
            "--bind-host", "127.0.0.1",
            "--bind-port", str(port),
            "--rest-port", str(rest),
            "--work-dir", os.path.join(work, "scheduler"),
        ],
        "cpu",
        os.path.join(work, "scheduler.log"),
    )
    child.wait_for(r"REST API on ", 60)
    return child, port, rest


def start_executor(work: str, sched_port: int, platform: str, tag: str) -> tuple:
    child = Child(
        f"executor{tag}",
        [
            "arrow_ballista_tpu.executor",
            "--scheduler-host", "127.0.0.1",
            "--scheduler-port", str(sched_port),
            "--bind-host", "127.0.0.1",
            "--bind-port", str(_free_port()),
            "--work-dir", os.path.join(work, f"executor{tag}"),
        ],
        platform,
        os.path.join(work, f"executor{tag}.log"),
    )
    t0 = time.monotonic()
    m = child.wait_for(r"executor \S+ starting: (\{.*\})", 180)
    info = json.loads(m.group(1))
    info["pid"] = child.proc.pid
    info["startup_seconds"] = round(time.monotonic() - t0, 1)
    return child, info


def _descendants(pid: int) -> list:
    """[(pid, cmdline)] of ``pid`` and every process below it."""
    kids: dict = {}
    for d in os.listdir("/proc"):
        if not d.isdigit():
            continue
        try:
            with open(f"/proc/{d}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
        kids.setdefault(ppid, []).append(int(d))
    out, stack = [], [pid]
    while stack:
        p = stack.pop()
        try:
            with open(f"/proc/{p}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace").strip()
        except OSError:
            continue
        out.append((p, cmd))
        stack.extend(kids.get(p, []))
    return out


def chip_holders(root_pid: int) -> list:
    """Processes at or below ``root_pid`` with an accelerator device node
    open (``/dev/vfio/<n>`` on v5e, ``/dev/accel<n>`` on older hosts)."""
    held = []
    for pid, cmd in _descendants(root_pid):
        try:
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue
        devices = set()
        for fd in fds:
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if re.match(r"/dev/(vfio/\d+|accel\d+)$", target):
                devices.add(target)
        if devices:
            held.append({"pid": pid, "cmd": cmd[-120:], "devices": sorted(devices)})
    return held


# --------------------------------------------------------------- queries
def _rest(rest_port: int, path: str):
    with urllib.request.urlopen(f"http://127.0.0.1:{rest_port}{path}", timeout=30) as r:
        return json.loads(r.read().decode())


def _job_ids(rest_port: int) -> set:
    return {j["job_id"] for j in _rest(rest_port, "/api/jobs")["jobs"]}


def run_query(ctx, rest_port: int, sql: str) -> tuple:
    """collect() as a user would, then the job's raw operator metrics from
    the scheduler's REST job detail.  Returns (table, summary)."""
    before = _job_ids(rest_port)
    t0 = time.monotonic()
    table = ctx.sql(sql).collect()
    wall = time.monotonic() - t0
    new = _job_ids(rest_port) - before
    if len(new) != 1:
        raise SmokeFailure(f"expected one new job, scheduler lists {sorted(new)}")
    job_id = new.pop()
    detail = _rest(rest_port, f"/api/job/{job_id}")
    totals = dict.fromkeys(COUNTERS, 0)
    by_op: dict = {}
    stages = []
    for st in detail["stages"]:
        ops = {}
        for op, vals in (st.get("metrics") or {}).items():
            picked = {k: v for k, v in vals.items() if k in COUNTERS and v}
            if op in DEVICE_OPS or picked:
                ops[op] = picked
            agg = by_op.setdefault(op, {})
            for k, v in picked.items():
                totals[k] += v
                agg[k] = agg.get(k, 0) + v
        stages.append(
            {"stage_id": st["stage_id"], "partitions": st["partitions"], "ops": ops}
        )
    summary = {
        "job_id": job_id,
        "wall_seconds": round(wall, 3),
        "rows": table.num_rows,
        # device stages that spent time on the device
        "device_stages": [
            f"stage{s['stage_id']}:{op}"
            for s in stages
            for op, vals in s["ops"].items()
            if op in DEVICE_OPS and sum(vals.get(k, 0) for k in DEVICE_NS) > 0
        ],
        "counters": {k: v for k, v in totals.items() if v},
        "gang_mesh_devices": by_op.get("MeshGangExec", {}).get("mesh_devices", 0),
        "stages": stages,
    }
    summary["route"] = route_of(summary)
    return table, summary


def route_of(s: dict) -> str:
    """Name the route a query's stages took, from its counters."""
    c = s["counters"]
    parts = []
    if s["gang_mesh_devices"]:
        parts.append(f"mesh gang over {s['gang_mesh_devices']} device(s)")
    if c.get("mesh_exchange_rows"):
        parts.append("ICI repartition exchange")
    if c.get("mesh_exchange_fallback"):
        parts.append("exchange fell back to the host hash split")
    if c.get("join_fallback"):
        parts.append("join on CPU operators, aggregate on device (join_fallback)")
    if c.get("keyed_path"):
        parts.append("device-keyed aggregate")
    if c.get("highcard_fallback"):
        parts.append("CPU hash aggregate (highcard_fallback)")
    if c.get("tpu_fallback"):
        parts.append("capacity/type route to CPU operators (tpu_fallback)")
    if c.get("cpu_fallback"):
        parts.append("small partitions on CPU operators (cpu_fallback)")
    if c.get("mesh_fallback"):
        parts.append("gang re-ran sequentially (mesh_fallback)")
    if c.get("device_error"):
        parts.append("DEVICE ERROR degradation")
    if not parts:
        parts.append(
            "sequential device stage" if s["device_stages"] else "CPU operators only"
        )
    return "; ".join(parts)


# --------------------------------------------------------------- verdict
DEVICE_RUNS = ("first", "repeat", "second_process")


def evaluate(report: dict) -> dict:
    """Every condition the smoke passes on, from the collected report
    alone (so each can be shown to fail without a cluster)."""
    asked = report["asked_platform"]
    info = report["executor"]
    checks = {
        "executor_platform": info["platform"] == asked
        and report["executor_second_process"]["platform"] == asked,
        "native_partitioner_loaded": info["native_partitioner"] == "loaded",
    }
    for q in QUERY_IDS:
        name = f"q{q}"
        runs = report["queries"][name]
        for label in DEVICE_RUNS:
            r, c = runs[label], runs[label]["counters"]
            checks[f"{name}_{label}_matches_reference"] = r["matches_reference"]
            checks[f"{name}_{label}_no_device_error"] = not c.get("device_error")
            if q == 3:
                continue  # q3's route is reported, not asserted
            checks[f"{name}_{label}_ran_on_device"] = bool(
                r["device_stages"]
                and c.get("tpu_execute_ns", 0) + c.get("tpu_compile_ns", 0) > 0
                and not any(
                    c.get(k)
                    for k in ("cpu_fallback", "tpu_fallback", "mesh_fallback")
                )
            )
            # one gang task over every device the executor holds
            checks[f"{name}_{label}_mesh_devices"] = (
                r["gang_mesh_devices"] == info["device_count"]
            )
        # Nothing new compiled (or loaded) on the repeat in a warm
        # process.  Asserted off the cpu platform only: there device
        # tasks stay in the executor process, while on cpu they run in
        # pooled task-runner workers and the repeat may land in a worker
        # that has not seen the query.  Reported, not asserted, for q3:
        # its streamed aggregate compiles one kernel per (capacity, rows)
        # pair it meets, and which pairs it meets depends on the order
        # shuffle fragments arrive in.
        rc = runs["repeat"]["counters"]
        runs["repeat_compiled_nothing"] = not (
            rc.get("xla_compiles") or rc.get("kernel_compiles")
        )
        if asked != "cpu" and q != 3:
            checks[f"{name}_repeat_compiled_nothing"] = runs["repeat_compiled_nothing"]
    holders = report["chip_holders"]
    if asked == "cpu":
        checks["nobody_holds_a_chip"] = not any(holders.values())
    else:
        # one holder per phase, and it is the executor's main process (not
        # the scheduler, a task-runner worker or the heartbeat sidecar)
        checks["only_executor_holds_chip"] = all(
            [h["pid"] for h in holders[phase]] == [report[key]["pid"]]
            for phase, key in (
                ("first_process", "executor"),
                ("second_process", "executor_second_process"),
            )
        )
        checks["second_process_hit_compile_cache"] = any(
            report["queries"][f"q{q}"]["second_process"]["counters"].get("xla_cache_hits")
            for q in QUERY_IDS
        )
    checks["parent_touched_no_backend"] = not report["parent_jax_backends"]
    checks["children_exited_cleanly_on_sigterm"] = all(
        e["returncode"] == 0 and not e["sigkill"]
        for e in report["children_exit"].values()
    )
    checks["within_time_limit"] = report["seconds"] <= BUDGET_S
    return checks


# ------------------------------------------------------------------ main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--platform", choices=("tpu", "cpu"), default="tpu",
        help="platform the executor is told to use; 'cpu' is the labelled "
        "rehearsal (never a fallback)",
    )
    ap.add_argument("--sf", type=float, default=10.0, help="TPC-H scale factor")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--files", type=int, default=12, help="parquet files per table (>= 8)")
    ap.add_argument("--data-dir", default="", help="scratch root outside the checkout (default: a fresh temp dir, removed afterwards)")
    ap.add_argument("--report", default="", help="also write the full report, and copy the children's logs, here")
    args = ap.parse_args()
    if args.files < 8:
        ap.error("--files must be >= 8 (partitions are the mesh shards)")

    from arrow_ballista_tpu import BallistaConfig
    from arrow_ballista_tpu.client import BallistaContext
    from bench_suite import _tables_match
    from benchmarks.tpch.queries import QUERIES

    work = args.data_dir or tempfile.mkdtemp(prefix="chip_smoke_")
    os.makedirs(work, exist_ok=True)
    if os.path.commonpath([REPO, os.path.realpath(work)]) == REPO:
        raise SystemExit("--data-dir must be outside the checkout")

    report: dict = {
        "sf": args.sf, "seed": args.seed, "asked_platform": args.platform,
        "jax_compilation_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(REPO, ".jax_cache"),
    }
    if args.sf != 10.0:
        report["scale_cut"] = f"SF{args.sf:g} instead of the SF10 headline (BASELINE.md config 2)"
    children: list = []
    exits: dict = {}

    def stop(child: Child) -> None:
        children.remove(child)
        exits[child.name] = child.terminate()
        m = re.search(r"shutting down: (\{.*\})", child.log_text())
        if m:  # the executor's peak device memory, as its backend saw it
            exits[child.name].update(json.loads(m.group(1)))
        log(f"{child.name} stopped: {exits[child.name]}")

    try:
        # the cluster first: on a machine with no chip the executor's
        # start-up fails here, before any data is generated
        sched, port, rest = start_scheduler(work)
        children.append(sched)
        executor, report["executor"] = start_executor(work, port, args.platform, "1")
        children.append(executor)
        log(f"executor up: {report['executor']}")

        report["data"] = generate(work, args.sf, args.seed, args.files)
        log(f"data: {report['data']}")

        def connect(settings: dict) -> BallistaContext:
            # the client's own wait is the one setting that is not a
            # session default: a cold q3 at SF10 may pass its 300 s
            settings = {"ballista.client.job_timeout_seconds": "600", **settings}
            ctx = BallistaContext.remote("127.0.0.1", port, BallistaConfig(settings))
            for name in TABLES:
                ctx.register_parquet(name, os.path.join(work, name))
            return ctx

        dev_ctx = connect({})
        ref_ctx = connect({"ballista.tpu.enable": "false"})
        queries: dict = {f"q{q}": {} for q in QUERY_IDS}
        tables: dict = {}

        def device_pass(labels) -> None:
            for q in QUERY_IDS:
                for label in labels:
                    tables[(q, label)], s = run_query(dev_ctx, rest, QUERIES[q])
                    queries[f"q{q}"][label] = s
                    log(f"q{q} {label}: {s['wall_seconds']}s {s['route']} {s['counters']}")

        device_pass(("first", "repeat"))
        holders = {"first_process": chip_holders(os.getpid())}
        for q in QUERY_IDS:
            tables[(q, "reference")], s = run_query(ref_ctx, rest, QUERIES[q])
            queries[f"q{q}"]["reference"] = s
            log(f"q{q} reference: {s['wall_seconds']}s")

        # second executor PROCESS, same cache directory: set-up time next
        # to the cold one, and proof the first holder released the chip
        stop(executor)
        executor, report["executor_second_process"] = start_executor(
            work, port, args.platform, "2"
        )
        children.append(executor)
        device_pass(("second_process",))
        holders["second_process"] = chip_holders(os.getpid())
        dev_ctx.close()
        ref_ctx.close()
        stop(executor)
        stop(sched)

        for q in QUERY_IDS:
            runs = queries[f"q{q}"]
            for label in DEVICE_RUNS:
                runs[label]["matches_reference"] = bool(
                    _tables_match(tables[(q, "reference")], tables[(q, label)])
                )
            runs["compile_setup_ms"] = {
                label: round(runs[label]["counters"].get("xla_compile_ns", 0) / 1e6, 1)
                for label in ("first", "second_process")
            }
        report["queries"] = queries
        report["chip_holders"] = holders
        xb = sys.modules.get("jax._src.xla_bridge")
        report["parent_jax_backends"] = sorted(getattr(xb, "_backends", {}) or {})
        report["children_exit"] = exits
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    finally:
        for child in list(children):
            log(f"{child.name} left running; stopping: {child.terminate(10)}")
        if args.report:
            # the children's logs are the evidence when a run goes wrong
            out = os.path.dirname(os.path.abspath(args.report))
            os.makedirs(out, exist_ok=True)
            for f in os.listdir(work):
                if f.endswith(".log"):
                    shutil.copy(os.path.join(work, f), out)
        if not args.data_dir:
            shutil.rmtree(work, ignore_errors=True)

    report["seconds"] = round(time.monotonic() - T0, 1)
    report["checks"] = evaluate(report)
    ok = report["ok"] = all(report["checks"].values())
    report["claim"] = None  # bring-up: the program runs; no gain is claimed
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=1)
    for name, held in report["checks"].items():
        if not held:
            log(f"check failed: {name}")
    info = report["executor"]
    print(json.dumps(report))
    # the last line is the contract's object: these two keys, nothing else
    device = {
        "platform": str(info["platform"]),
        "kind": str(info["device_kind"]),
        "count": int(info["device_count"]),
    }
    print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
