"""Start the program's executor, with a profiler switch for traced runs.

    python executor_launcher.py <ctl_dir> <the executor's own flags...>

Runs ``arrow_ballista_tpu.executor.__main__.main`` unchanged in this
process (the one that holds the chip: only it can trace the device).  A
daemon thread watches ``ctl_dir`` for command files written by the harness
and answers each with ``<command>.ack`` (JSON):

- ``trace_start``: ``jax.profiler.start_trace(<ctl_dir>/trace)``; the ack
  carries the unix time just before the call, which is the trace's zero.
- ``trace_stop``: ``jax.profiler.stop_trace()``.
- ``memory_<n>``: ``memory_stats()`` peak and live bytes of every device.

Nothing here alters what the executor does with a task.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def _ack(ctl_dir: str, cmd: str, body: dict) -> None:
    tmp = os.path.join(ctl_dir, cmd + ".ack.tmp")
    with open(tmp, "w") as f:
        json.dump(body, f)
    os.replace(tmp, os.path.join(ctl_dir, cmd + ".ack"))


def _watch(ctl_dir: str) -> None:
    seen: set = set()
    while True:
        time.sleep(0.02)
        try:
            names = [n for n in os.listdir(ctl_dir) if "." not in n and n not in seen]
        except OSError:
            return
        for cmd in sorted(names):
            seen.add(cmd)
            body: dict = {}
            try:
                import jax

                if cmd == "trace_start":
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0  # host python frames: large, unread
                    opts.host_tracer_level = 1
                    body["unix_ns_before"] = time.time_ns()
                    jax.profiler.start_trace(
                        os.path.join(ctl_dir, "trace"), profiler_options=opts
                    )
                    body["unix_ns_after"] = time.time_ns()
                elif cmd == "trace_stop":
                    body["unix_ns_before"] = time.time_ns()
                    jax.profiler.stop_trace()
                    body["unix_ns_after"] = time.time_ns()
                elif cmd.startswith("memory_"):
                    stats = [d.memory_stats() or {} for d in jax.local_devices()]
                    body["peak_bytes_in_use"] = [s.get("peak_bytes_in_use") for s in stats]
                    body["bytes_in_use"] = [s.get("bytes_in_use") for s in stats]
            except Exception as e:  # noqa: BLE001 - the harness reads the error and fails the run
                body["error"] = f"{type(e).__name__}: {e}"
            _ack(ctl_dir, cmd, body)


def main() -> None:
    ctl_dir, argv = sys.argv[1], sys.argv[2:]
    from arrow_ballista_tpu.executor.__main__ import main as executor_main

    threading.Thread(target=_watch, args=(ctl_dir,), daemon=True, name="bench-ctl").start()
    executor_main(argv)


if __name__ == "__main__":
    main()
