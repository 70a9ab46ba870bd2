"""From a profiler trace (``.xplane.pb``) to device metrics.

``read_xplane`` needs jax only as a protobuf reader (no backend is
touched); ``reduce`` works on plain event tuples, so it is checked on a
small recorded trace (``tests/data``).  Event times are nanoseconds from
the start of the trace.

A device plane is ``/device:TPU:<n>``.  Its ``XLA Modules`` line holds one
event per executed program (named ``jit_<function>(<id>)``), its ``XLA Ops``
line one per HLO operation.  Busy time is the union of the op events (of
the module events where a plane has no op line).  In a ``--platform cpu``
rehearsal there is no device plane and the CPU client's executor threads
stand in, so that the code path runs; such numbers are labelled a
rehearsal by the harness and never reported as a device's.
"""

from __future__ import annotations

import glob
import os
import re

# an op event is named by its HLO text, "%all-reduce.3 = f32[...] all-reduce(...)":
# the instruction's own name decides, not an operand that mentions one
COLLECTIVE = re.compile(
    r"%?(all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter|collective-broadcast)"
)
MODULES, OPS = "XLA Modules", "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str, rehearsal: bool = False) -> list:
    """[(device, line, name, start_ns, duration_ns)] of the device planes."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            device = int(m.group(1))
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    for e in line.events:
                        events.append((device, line.name, e.name, int(e.start_ns), int(e.duration_ns)))
        elif rehearsal and plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("tf_XLAPjRtCpuClient"):
                    for e in line.events:
                        if e.duration_ns > 0:
                            events.append((0, OPS, e.name, int(e.start_ns), int(e.duration_ns)))
    return events


def describe(path: str) -> list:
    """[(plane, line, events)] of a trace: what to look at by hand first."""
    from jax.profiler import ProfileData

    return [
        (plane.name, line.name, sum(1 for _ in line.events))
        for plane in ProfileData.from_file(path).planes for line in plane.lines
    ]


def merge(intervals) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def overlap(merged: list, a: float, b: float) -> float:
    return sum(max(0, min(b, y) - max(a, x)) for x, y in merged if y > a and x < b)


def program_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce(events: list, chips: int) -> dict:
    """Busy seconds (averaged over the chips used), seconds by program,
    collective seconds, and each device's merged busy intervals."""
    devices = sorted({e[0] for e in events})
    busy: dict = {}
    by_program: dict = {}
    collective = 0
    for d in devices:
        ops = [(s, s + n) for dev, line, _, s, n in events if dev == d and line == OPS]
        if not ops:
            ops = [(s, s + n) for dev, line, _, s, n in events if dev == d and line == MODULES]
        busy[d] = merge(ops)
    for dev, line, name, _, n in events:
        if line == MODULES:
            by_program[program_name(name)] = by_program.get(program_name(name), 0) + n
        elif COLLECTIVE.match(name):
            collective += n
    chips = max(1, chips)
    return {
        "devices": devices,
        "busy_s": sum(b - a for m in busy.values() for a, b in m) / 1e9 / chips,
        "programs_s": sum(by_program.values()) / 1e9 / chips,
        "device_ops": sorted(
            ([k, v / 1e9 / chips] for k, v in by_program.items()), key=lambda kv: -kv[1]
        ),
        "collective_s": collective / 1e9 / chips,
        "busy_intervals": busy,
    }


def idle_gaps(reduced: dict, spans: list, chips: int) -> list:
    """``spans``: (name, start_ns, end_ns) of what the host was doing, on
    the trace's clock.  Each span's idle time is its length less the
    device's busy time inside it (averaged over chips); spans of one name
    add up.  Longest first."""
    out: dict = {}
    for name, a, b in spans:
        if b <= a:
            continue
        busy = sum(overlap(m, a, b) for m in reduced["busy_intervals"].values()) / max(1, chips)
        out[name] = out.get(name, 0.0) + max(0.0, (b - a) - busy) / 1e9
    return sorted(([k, v] for k, v in out.items()), key=lambda kv: -kv[1])
