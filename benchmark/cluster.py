"""The system under test, started as a user starts it.

One ``python -m arrow_ballista_tpu.scheduler`` pinned to the CPU platform,
ONE executor process that holds the cell's chips (the program's own
``executor.__main__.main``, started through ``executor_launcher.py`` so that
a traced run can switch ``jax.profiler`` on and off around a part of the
window), and remote clients.  Copied from ``chip_smoke.py`` (PR 21), not
imported: later PRs may change the smoke.  This process never touches a jax
backend.
"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class ClusterFailure(Exception):
    pass


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(platform: str) -> dict:
    """The host's environment (libtpu reads its TPU_* settings from it)
    with the jax platform stated, never inherited, the compile cache at
    ``<checkout>/.jax_cache`` unless the machine sets one, and jax's
    persistent-cache threshold at 0 s: 89 of q1's 90 executables compile in
    under the default 1 s and would be compiled again in every process."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME")
    }
    env["JAX_PLATFORMS"] = platform
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    return env


class Child:
    def __init__(self, name: str, argv: list, platform: str, log_path: str):
        self.name = name
        self.log_path = log_path
        self._sink = open(log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, *argv],
            env=child_env(platform),
            stdout=self._sink,
            stderr=subprocess.STDOUT,
            cwd=REPO,
        )

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def wait_for(self, pattern: str, timeout_s: float):
        """First regex match in the child's log; fails when the child exits
        first or the wait runs out (its log tail goes into the error)."""
        deadline = time.monotonic() + timeout_s
        rx = re.compile(pattern)
        while True:
            m = rx.search(self.log_text())
            if m:
                return m
            rc = self.proc.poll()
            if rc is not None or time.monotonic() > deadline:
                why = f"exited with code {rc}" if rc is not None else f"not ready after {timeout_s:.0f}s"
                raise ClusterFailure(f"{self.name} {why}:\n{self.log_text()[-3000:]}")
            time.sleep(0.1)

    def terminate(self, grace_s: float = 60.0) -> dict:
        """SIGTERM and wait; SIGKILL only past the grace (and say so: a
        killed holder of the chip can leave libtpu's lock behind)."""
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


def new_job_id(ctx, seen: set):
    """The id of the job that the context's last ``collect()`` ran: what the
    client has submitted (``BallistaContext._job_ids``, the program's private
    name, read here and nowhere else) less ``seen``, which takes the new ids.
    None where the call submitted no job, or more than one, or the context
    keeps no ids (a test's stand-in)."""
    new = set(getattr(ctx, "_job_ids", ())) - seen
    seen |= new
    return new.pop() if len(new) == 1 else None


def rest(rest_port: int, path: str):
    with urllib.request.urlopen(f"http://127.0.0.1:{rest_port}{path}", timeout=30) as r:
        return json.loads(r.read().decode())


class Cluster:
    """Scheduler + one executor.  ``start`` returns as soon as both
    processes are launched; ``wait_ready`` blocks until the executor has
    claimed its backend and says which one."""

    def __init__(self, work: str, platform: str):
        self.work = work
        self.platform = platform
        self.children: list = []
        self.port = self.rest_port = 0
        self.executor = None
        self.executor_info: dict = {}
        self.exits: dict = {}
        self.ctl_dir = os.path.join(work, "ctl")
        self.tables: dict = {}  # name -> parquet directory, registered on every client

    def start(self) -> None:
        os.makedirs(self.ctl_dir, exist_ok=True)
        self.port, self.rest_port = _free_port(), _free_port()
        sched = Child(
            "scheduler",
            ["-m", "arrow_ballista_tpu.scheduler",
             "--bind-host", "127.0.0.1", "--bind-port", str(self.port),
             "--rest-port", str(self.rest_port),
             "--work-dir", os.path.join(self.work, "scheduler")],
            "cpu",
            os.path.join(self.work, "scheduler.log"),
        )
        self.children.append(sched)
        self.executor = Child(
            "executor",
            [os.path.join(HERE, "executor_launcher.py"), self.ctl_dir,
             "--scheduler-host", "127.0.0.1", "--scheduler-port", str(self.port),
             "--bind-host", "127.0.0.1", "--bind-port", str(_free_port()),
             "--work-dir", os.path.join(self.work, "executor")],
            self.platform,
            os.path.join(self.work, "executor.log"),
        )
        self.children.append(self.executor)

    def wait_ready(self) -> dict:
        self.children[0].wait_for(r"REST API on ", 120)
        m = self.executor.wait_for(r"executor \S+ starting: (\{.*\})", 300)
        self.executor_info = json.loads(m.group(1))
        return self.executor_info

    # ---- what a client sees
    def client(self, settings: dict):
        from arrow_ballista_tpu import BallistaConfig
        from arrow_ballista_tpu.client import BallistaContext

        ctx = BallistaContext.remote(
            "127.0.0.1", self.port, BallistaConfig({k: str(v) for k, v in settings.items()})
        )
        for name, path in self.tables.items():
            ctx.register_parquet(name, path)
        return ctx

    def job_details(self) -> list:
        """Job detail of every job the scheduler lists."""
        return [
            rest(self.rest_port, f"/api/job/{j['job_id']}")
            for j in rest(self.rest_port, "/api/jobs")["jobs"]
        ]

    # ---- profiler control (the launcher's thread watches ctl_dir)
    def _ctl(self, cmd: str, timeout_s: float = 120.0) -> dict:
        ack = os.path.join(self.ctl_dir, cmd + ".ack")
        with open(os.path.join(self.ctl_dir, cmd), "w") as f:
            f.write(cmd)
        deadline = time.monotonic() + timeout_s
        while not os.path.exists(ack):
            if self.executor.proc.poll() is not None or time.monotonic() > deadline:
                raise ClusterFailure(f"executor did not acknowledge {cmd!r}")
            time.sleep(0.02)
        with open(ack) as f:
            return json.load(f)

    def start_trace(self) -> dict:
        return self._ctl("trace_start")

    def stop_trace(self) -> dict:
        return self._ctl("trace_stop", 300.0)

    def memory(self) -> dict:
        return self._ctl(f"memory_{time.monotonic_ns()}")

    def stop(self) -> dict:
        for child in reversed(self.children):
            self.exits[child.name] = child.terminate()
        self.children = []
        return self.exits
