"""The traffic generator and the window's accounting.

One general loop reads a traffic file's parameters:

- ``kinds``: the query kinds of the mix (TPC-H query numbers).
- ``clients``: closed-loop clients, each a thread with its own remote
  context and session; a client sends its next query when its last one has
  returned, with no think time.
- ``order``: ``cycle`` (kinds in the listed order, every client) or
  ``shuffle`` (each round of kinds in an order drawn from the seed and the
  client's number, so that clients at once send different kinds).
- ``parameter_sets``: how many parameter sets of each kind a window cycles
  through (default 1); warm-up runs every one, since the program compiles
  for each new set of literals.
- ``requests``: optional fixed list of ``{"kind", "params"}`` replayed in
  order instead of drawn parameters.

Whole-query windows: a client submits its next query only while the time
elapsed plus that kind's last latency fits in the window's seconds.  No
query is cut and none is counted in part; the window's time is from its
start to the last completion.
"""

from __future__ import annotations

import collections
import random
import threading
import time

from . import queries

# What ``submit`` may return in place of the bare answer: the answer and the
# id of the job that ran it (``cluster.new_job_id``).  An exception may carry
# the id as its ``job_id`` attribute.
WithJob = collections.namedtuple("WithJob", "answer job_id")


def plan(traffic: dict, seed: int, client: int):
    """Endless iterator of (kind, params) for one client."""
    kinds = list(traffic["kinds"])
    fixed = traffic.get("requests")
    draws = queries.Draws(seed, kinds, traffic.get("parameter_sets", 1))
    rng = random.Random(f"{seed}/order/{client}")
    used = dict.fromkeys(kinds, 0)
    clients = int(traffic.get("clients", 1))
    order = traffic.get("order", "cycle")
    if order not in ("cycle", "shuffle"):
        raise ValueError(f"traffic order {order!r}: cycle or shuffle")
    i = client
    while fixed:  # a fixed set, dealt round-robin to the clients
        r = fixed[i % len(fixed)]
        yield int(r["kind"]), dict(r["params"])
        i += clients
    while True:
        round_ = list(kinds)
        if order == "shuffle":
            rng.shuffle(round_)
        for kind in round_:
            yield kind, draws.window(kind, used[kind] * clients + client)
            used[kind] += 1


def run_window(traffic: dict, seed: int, seconds: float, submit, after_first_cycle=None) -> dict:
    """Drive the window.  ``submit(client, kind, params)`` returns the
    answer, or a ``WithJob`` (or raises); a record keeps the job's id as
    ``job_id``.  A kind with no completion yet is submitted while
    the window is open (warm-up latencies hold compile time and say nothing).
    Returns {"queries": [...], "window_s": start to last completion}."""
    clients = int(traffic.get("clients", 1))
    if traffic.get("loop", "closed") != "closed":
        raise ValueError("traffic loop: only 'closed' is understood yet")
    records: list = []
    lock = threading.Lock()
    last: dict = {}  # kind -> latency of its last completion
    t0 = time.monotonic()

    def client_loop(c: int) -> None:
        per_cycle = len(traffic["kinds"])
        for n, (kind, params) in enumerate(plan(traffic, seed, c)):
            if c == 0 and n == per_cycle and after_first_cycle is not None:
                after_first_cycle()
            with lock:
                expect = last.get(kind, 0.0)  # no completion of this kind yet: it goes
            if time.monotonic() - t0 + expect >= seconds:
                return
            rec = {"client": c, "seq": n, "kind": kind, "params": params,
                   "unix_submit": time.time(), "t_submit": time.monotonic() - t0,
                   "answer": None, "job_id": None, "error": None}
            try:
                got = submit(c, kind, params)
                rec["answer"], rec["job_id"] = got if isinstance(got, WithJob) else (got, None)
            except Exception as e:  # noqa: BLE001 - a failed query is counted, not fatal
                rec["error"] = f"{type(e).__name__}: {e}"
                rec["job_id"] = getattr(e, "job_id", None)
            rec["t_done"] = time.monotonic() - t0
            rec["unix_done"] = time.time()
            rec["latency_s"] = rec["t_done"] - rec["t_submit"]
            with lock:
                records.append(rec)
                if rec["error"] is None:
                    last[kind] = rec["latency_s"]

    if clients == 1:
        client_loop(0)
    else:
        threads = [threading.Thread(target=client_loop, args=(c,)) for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    records.sort(key=lambda r: r["t_submit"])
    window_s = max((r["t_done"] for r in records), default=0.0)
    return {"queries": records, "window_s": window_s}


def end_to_end(records: list, window_s: float, rows_of_kind: dict, chips: int) -> dict:
    """The end-to-end metrics, from the completions that count (no error,
    right route).  None where nothing completed."""
    import math

    good = [r for r in records if r["error"] is None and not r.get("wrong_route")]
    by_kind: dict = {}
    for r in good:
        by_kind.setdefault(r["kind"], []).append(r["latency_s"])
    out = {"query_geomean_s": None, "scan_rows_rate": None}
    if by_kind:
        means = [sum(v) / len(v) for v in by_kind.values()]
        out["query_geomean_s"] = math.exp(sum(math.log(m) for m in means) / len(means))
    if good and window_s > 0:
        rows = sum(rows_of_kind[r["kind"]] for r in good)
        out["scan_rows_rate"] = rows / 1e6 / window_s / chips
    return out
