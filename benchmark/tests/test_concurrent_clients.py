"""Four clients at once against one executor with the device on (PR 33).

Four threads, a context and session each, send q1/q6/q3 in a seeded
shuffle, two rounds, at SF0.01 against a local scheduler + one executor
(four task slots, tasks in-thread, XLA's CPU backend as the device).  Every
answer is equal to the one the same context got alone and correct against
the numpy reference; no job fails; no fallback counter is set that a single
client does not set.  Written as the tier-1 test ISSUE 33 asks under
``tests/``; a `benchmark` PR may add no file there, so it lives here until
a later PR moves it."""

import json
import os
import random
import threading
import urllib.request

import pytest

from benchmark import cluster, compare, jobstats, queries, reference

KINDS = (1, 6, 3)
PARAMS = {1: {"delta": 90}, 6: {"year": 1994, "discount": 0.06, "quantity": 24},
          3: {"segment": "BUILDING", "date": "1995-03-15"}}
CLIENTS, ROUNDS, SEED = 4, 2, 2**31 + 33


@pytest.fixture(scope="module")
def served(small_data):
    """(contexts, REST base, data dir): a standalone cluster and four
    contexts on it, the device route on for partitions of any size."""
    from arrow_ballista_tpu import BallistaConfig
    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.scheduler.api import ApiServerHandle

    data_dir, _ = small_data
    config = BallistaConfig({
        "ballista.tpu.min_rows": "0",
        "ballista.client.poll_interval_seconds": "0.05",
        "ballista.client.poll_max_interval_seconds": "0.05",
    })
    first = BallistaContext.standalone(config=config, num_executors=1, concurrent_tasks=4)
    ctxs = [first] + [BallistaContext.remote(first.host, first.port, config) for _ in range(CLIENTS - 1)]
    for ctx in ctxs:
        for t in ("lineitem", "orders", "customer"):
            ctx.register_parquet(t, os.path.join(data_dir, t))
    scheduler, _ = first._standalone_handles
    api = ApiServerHandle(scheduler.server, "127.0.0.1", 0).start()
    try:
        yield ctxs, f"http://127.0.0.1:{api.port}", data_dir
    finally:
        api.stop()
        for ctx in reversed(ctxs):
            ctx.close()


def _ask(ctx, seen, kind):
    answer = ctx.sql(queries.render(kind, PARAMS[kind])).collect()
    return answer, cluster.new_job_id(ctx, seen)


def _fallbacks(base: str, job_id: str) -> tuple:
    """(state, the fallback counters set anywhere in the job)."""
    job = jobstats.summarize(json.load(urllib.request.urlopen(f"{base}/api/job/{job_id}")))
    return job["state"], {k for k in jobstats.OFF_DEVICE if jobstats.op_sum(job, k)}


def test_four_clients_answer_as_each_does_alone(served):
    ctxs, base, data_dir = served
    seen = [set() for _ in ctxs]
    alone, alone_fallbacks = {}, {}
    for c, ctx in enumerate(ctxs):
        for kind in KINDS:
            alone[c, kind], job_id = _ask(ctx, seen[c], kind)
            state, fell = _fallbacks(base, job_id)
            assert state == "completed"
            alone_fallbacks[kind] = alone_fallbacks.get(kind, set()) | fell
    got, errors = [], []

    def client(c: int) -> None:
        rng = random.Random(f"{SEED}/order/{c}")
        try:
            for _ in range(ROUNDS):
                for kind in rng.sample(KINDS, len(KINDS)):
                    answer, job_id = _ask(ctxs[c], seen[c], kind)
                    got.append((c, kind, answer, job_id))
        except Exception as e:  # noqa: BLE001 - reported below, with the client
            errors.append((c, repr(e)))

    threads = [threading.Thread(target=client, args=(c,)) for c in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors and not any(t.is_alive() for t in threads), errors
    assert len(got) == CLIENTS * ROUNDS * len(KINDS) and len({job_id for *_, job_id in got}) == len(got)
    ref = reference.Data(data_dir)
    oracle = {kind: reference.answer(ref, kind, PARAMS[kind]) for kind in KINDS}
    for c, kind, answer, job_id in got:
        assert answer.equals(alone[c, kind]), (c, kind, job_id)
        state, fell = _fallbacks(base, job_id)
        assert state == "completed" and fell <= alone_fallbacks[kind], (c, kind, job_id, state, fell)
    verdict = compare.judge([(a, oracle[kind], queries.ORDER_BY[kind]) for _, kind, a, _ in got])
    assert verdict["correct"] and verdict["compared"] == len(got), verdict["numbers"]
    assert not set().union(*alone_fallbacks.values()), alone_fallbacks  # nothing falls back at SF0.01 with min_rows 0
