"""BASELINE.md config sweep.

Runs the measured configs beyond bench.py's default (q1 SF10 = config #2):

  #1 q6 SF1 from PARQUET (scan->HBM bridge cost is in the wall time)
  #3 q3 SF10 (join + aggregate; mesh gang + exchange paths)
  #4 full 22 TPC-H distributed (2 executors over gRPC/Flight) at
     tractable scale (BENCH_FULL22_SF, default 1)
  #5 h2o groupby G1_1e8 (high-cardinality aggregate), TPU vs CPU
  plus a star-join showcase for the fused device PK-FK join and a window
  showcase (ranking + running sum + lag on TpuWindowExec)

Each config emits one JSON line (same shape as bench.py) and everything
is appended to BENCH_SUITE_r05.json (or ``BENCH_SUITE_OUT``), which git
ignores: the repo's record of speed is PERF_LEDGER.jsonl.

  plus shuffle data-plane micro-benches: shuffle_fetch_mb_per_sec
  (pipelined vs sequential reduce-side read), shuffle_write_mb_per_sec
  (slab-buffered async map-side write vs the synchronous baseline, with
  the zstd wire-compression ratio), and the locality A/B
  (shuffle_local_fetch_mb_per_sec: identity-gated same-host zero-copy
  vs forced-remote Flight loopback on identical inputs, sha-fingerprint
  identity enforced; shuffle_batched_fetch_round_trips: the batched
  multi-partition DoGet leg)

  plus an AQE A/B leg (aqe_starjoin_rows_per_sec /
  aqe_tiny_agg_rows_per_sec): skewed star join + tiny-partition
  aggregate with ballista.aqe.enabled true vs false on identical
  inputs, reporting before/after reduce-task counts

  plus the keyed device-path A/B (keyed_path_rows_per_sec /
  keyed_starjoin_rows_per_sec): device-encoded fused
  encode→sort→segment-reduce vs the host-encode keyed baseline
  (ballista.tpu.device_encode knob) and the gid-table GroupTable route,
  on identical inputs with a sha row-fingerprint identity check

  plus the multi-tenant concurrency leg
  (concurrent_interactive_p99_s / concurrent_weighted_throughput_ratio
  / concurrent_shed_jobs): N open-loop clients of mixed priority
  against one standalone cluster at >=4x slot oversubscription,
  admission control A/B'd via ballista.admission.enabled — interactive
  p99 with priority lanes vs the FIFO free-for-all, two tenants at
  weights 2:1 vs the 2:1 completed-throughput target, and a burst past
  max_queued_jobs shedding with structured ClusterSaturated errors

  plus the obs leg (obs_overhead_pct): disabled-path span-API +
  timestamp-anchor cost and the enabled-path query-doctor attribution
  pass, both priced against the shuffle leg (PR 3 methodology,
  acceptance < 2%), with the measured job's wall-clock category
  breakdown riding the record

  plus the pipelined-execution A/B (pipelined_stage_speedup): a
  barrier-dominated shuffle query (manufactured straggler map task +
  reduce-side work) with ballista.shuffle.pipelined off vs on on
  identical inputs — sha fingerprint identity enforced, wall-clock and
  the doctor's measured barrier_wait before/after in the record

  plus the whole-stage fusion A/B (fusion_q3_rows_per_sec /
  fusion_scan_rows_per_sec): q3's map-stage shape and a scan-heavy
  scalar shape with ballista.tpu.whole_stage_fusion on vs off on
  identical inputs — ONE jitted dispatch per map task vs the per-batch
  dispatch sequence, sha row-fingerprint identity enforced, with the
  fused_segments / fused_ops_per_dispatch plan shape in the record

Usage: python bench_suite.py
[q6|q3|starjoin|full22|window|h2o|shuffle|aqe|keyed|concurrent|pipelined|obs|fusion|all]
(default all)
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

OUT_PATH = os.environ.get("BENCH_SUITE_OUT") or os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_SUITE_r05.json"
)


def _emit(rec: dict) -> None:
    if "metric" in rec:
        # label every record with the leg that actually produced it — a
        # CPU fallback must not ship CPU numbers under *_tpu_* names
        # without a trace in the artifact
        import jax

        rec.setdefault("device_platform", jax.default_backend())
    print(json.dumps(rec), flush=True)
    with open(OUT_PATH, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _collect_stage_metrics(plan) -> dict:
    from arrow_ballista_tpu.ops.stage_compiler import TpuStageExec
    from arrow_ballista_tpu.parallel.mesh_stage import MeshGangExec

    agg: dict = {}
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, (TpuStageExec, MeshGangExec)):
            for k, v in node.metrics.values.items():
                agg[k] = agg.get(k, 0) + v
        stack.extend(node.children())
    return agg


def _tables_match(a, b, rel: float = 1e-6) -> bool:
    """CPU-vs-TPU oracle comparison: align rows on the non-float columns
    first, then on floats ROUNDED to ~8 significant digits (sub-tolerance
    float diffs between the paths must not scramble tie ordering when
    rows agree on every non-float key), then compare floats to ``rel``
    and everything else exactly."""
    import pyarrow as pa

    if a.num_rows != b.num_rows:
        return False
    if a.num_rows and a.column_names:

        def sorted_rounded(t):
            keys = []
            drop = []
            for c in t.column_names:
                if not pa.types.is_floating(t.schema.field(c).type):
                    keys.append((c, "ascending"))
                    continue
                kc = f"__sortkey_{c}"
                t = t.append_column(
                    kc,
                    pa.array(
                        [
                            None if x is None else "%.8e" % x
                            for x in t.column(c).to_pylist()
                        ]
                    ),
                )
                keys.append((kc, "ascending"))
                drop.append(kc)
            t = t.sort_by(keys)
            return t.drop_columns(drop) if drop else t

        a, b = sorted_rounded(a), sorted_rounded(b)
    for name in a.column_names:
        for x, y in zip(a.column(name).to_pylist(), b.column(name).to_pylist()):
            if isinstance(x, float) and isinstance(y, float):
                if abs(x - y) > rel * max(abs(x), abs(y), 1.0):
                    return False
            elif x != y:
                return False
    return True


def _run_both(make_ctx, sql: str, n_rows: int, iters: int = 5):
    """(cpu_best_s, tpu_best_s, tpu_metrics, match_1e6)"""
    results = {}
    metrics = {}
    for tpu in (False, True):
        ctx = make_ctx(tpu)
        df = ctx.sql(sql)
        best = float("inf")
        table = None
        plan = None
        for _ in range(iters):
            plan = df.physical_plan()
            t0 = time.perf_counter()
            table = ctx.execute(plan)
            best = min(best, time.perf_counter() - t0)
        results[tpu] = (best, table)
        if tpu and plan is not None:
            metrics = _collect_stage_metrics(plan)

    ok = _tables_match(results[False][1], results[True][1])
    return results[False][0], results[True][0], metrics, ok


def bench_q6_parquet() -> None:
    """Config #1: q6 SF1 from Parquet — exercises the scan bridge.
    BENCH_Q6_SF shrinks the scale for CI smoke runs."""
    import tempfile

    import pyarrow.parquet as pq

    from arrow_ballista_tpu import BallistaConfig, SessionContext
    from benchmarks.tpch.datagen import gen_lineitem
    from benchmarks.tpch.queries import QUERIES

    sf = float(os.environ.get("BENCH_Q6_SF", "1"))
    li = gen_lineitem(sf)
    n = li.num_rows
    tmp = tempfile.mkdtemp(prefix="bench_q6_")
    path = os.path.join(tmp, "lineitem.parquet")
    pq.write_table(li, path)
    del li

    def make_ctx(tpu: bool):
        ctx = SessionContext(
            BallistaConfig(
                {
                    "ballista.tpu.enable": str(tpu).lower(),
                    "ballista.batch.size": str(1 << 23),
                    "ballista.shuffle.partitions": "1",
                }
            )
        )
        ctx.sql(
            "create external table lineitem stored as parquet "
            f"location '{path}'"
        )
        return ctx

    cpu_s, tpu_s, m, ok = _run_both(make_ctx, QUERIES[6], n)
    _emit(
        {
            "metric": "tpch_q6_sf%g_parquet_tpu_rows_per_sec" % sf,
            "value": round(n / tpu_s),
            "unit": "rows/s",
            "vs_baseline": round(cpu_s / tpu_s, 3),
            "rows": n,
            "cpu_rows_per_sec": round(n / cpu_s),
            "matches_cpu_1e-6": ok,
            "breakdown": {
                k: m[k]
                for k in (
                    "bridge_time_ns", "key_encode_time_ns", "device_time_ns",
                    "tpu_stage_time_ns", "tpu_fallback", "cpu_fallback",
                )
                if k in m
            },
        }
    )


def bench_q3_sf10() -> None:
    """Config #3: q3 SF10 — join + aggregate."""
    from arrow_ballista_tpu import BallistaConfig, SessionContext
    from arrow_ballista_tpu.catalog import MemoryTable
    from benchmarks.tpch.datagen import gen_customer, gen_lineitem, gen_orders
    from benchmarks.tpch.queries import QUERIES

    sf = float(os.environ.get("BENCH_Q3_SF", "10"))
    li, od, cu = gen_lineitem(sf), gen_orders(sf), gen_customer(sf)
    n = li.num_rows

    def make_ctx(tpu: bool):
        settings = {
            "ballista.tpu.enable": str(tpu).lower(),
            "ballista.batch.size": str(1 << 22),
            "ballista.shuffle.partitions": "1",
        }
        # A/B hook (tpu leg only — the CPU oracle is mode-independent):
        # 'device' pins the keyed path, 'gid'/'cpu' pin the alternatives
        mode = os.environ.get("BENCH_HIGHCARD_MODE")
        if tpu and mode:
            settings["ballista.tpu.highcard_mode"] = mode
        ctx = SessionContext(BallistaConfig(settings))
        ctx.register_table("lineitem", MemoryTable.from_table(li, 1))
        ctx.register_table("orders", MemoryTable.from_table(od, 1))
        ctx.register_table("customer", MemoryTable.from_table(cu, 1))
        return ctx

    cpu_s, tpu_s, m, ok = _run_both(make_ctx, QUERIES[3], n, iters=3)
    _emit(
        {
            "metric": "tpch_q3_sf%g_tpu_rows_per_sec" % sf,
            "highcard_mode": os.environ.get("BENCH_HIGHCARD_MODE", "auto"),
            "value": round(n / tpu_s),
            "unit": "rows/s",
            "vs_baseline": round(cpu_s / tpu_s, 3),
            "rows": n,
            "cpu_rows_per_sec": round(n / cpu_s),
            "matches_cpu_1e-6": ok,
            "breakdown": {
                k: m[k]
                for k in (
                    "bridge_time_ns", "key_encode_time_ns", "device_time_ns",
                    "tpu_stage_time_ns", "tpu_fallback", "cpu_fallback",
                )
                if k in m
            },
        }
    )


def bench_starjoin() -> None:
    """Device PK-FK join showcase: star-schema probe⋈dim aggregate with
    LOW-cardinality groups — the join runs on device via searchsorted +
    gather and the joined relation never materializes (the CPU path must
    materialize a 60M-row join first)."""
    import numpy as np

    from arrow_ballista_tpu import BallistaConfig, SessionContext
    from arrow_ballista_tpu.catalog import MemoryTable

    n = int(float(os.environ.get("BENCH_STAR_N", "6e7")))
    m = int(float(os.environ.get("BENCH_STAR_M", "1e6")))
    rng = np.random.default_rng(9)
    import pyarrow as pa

    dim = pa.table(
        {
            "dk": pa.array(np.arange(1, m + 1), pa.int64()),
            "dv": pa.array(rng.uniform(0.5, 1.5, m)),
            "dtag": pa.array(rng.integers(0, 25, m), pa.int32()),
        }
    )
    fact = pa.table(
        {
            "fk": pa.array(rng.integers(1, int(m * 1.2), n), pa.int64()),
            "g": pa.array(rng.integers(0, 8, n), pa.int32()),
            "v": pa.array(rng.uniform(0, 100, n)),
        }
    )
    sql = (
        "select g, sum(v * dv) as s, count(*) as c "
        "from dim, fact where dk = fk group by g order by g"
    )

    def make_ctx(tpu: bool):
        ctx = SessionContext(
            BallistaConfig(
                {
                    "ballista.tpu.enable": str(tpu).lower(),
                    "ballista.batch.size": str(1 << 23),
                    "ballista.shuffle.partitions": "1",
                }
            )
        )
        ctx.register_table("dim", MemoryTable.from_table(dim, 1))
        ctx.register_table("fact", MemoryTable.from_table(fact, 1))
        return ctx

    cpu_s, tpu_s, mets, ok = _run_both(make_ctx, sql, n, iters=3)
    _emit(
        {
            "metric": "starjoin_%.0e_x_%.0e_tpu_rows_per_sec" % (n, m),
            "value": round(n / tpu_s),
            "unit": "rows/s",
            "vs_baseline": round(cpu_s / tpu_s, 3),
            "rows": n,
            "dim_rows": m,
            "cpu_rows_per_sec": round(n / cpu_s),
            "matches_cpu_1e-6": ok,
            "breakdown": {
                k: mets[k]
                for k in (
                    "bridge_time_ns", "key_encode_time_ns", "device_time_ns",
                    "tpu_stage_time_ns", "tpu_fallback", "join_fallback",
                )
                if k in mets
            },
        }
    )


def bench_full22() -> None:
    """BASELINE config #4's shape at tractable scale: all 22 TPC-H
    queries through the DISTRIBUTED path (standalone scheduler + 2
    executors over real gRPC/Flight), TPU path vs CPU path."""
    from arrow_ballista_tpu import BallistaConfig
    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.catalog import MemoryTable
    from arrow_ballista_tpu.shuffle import memory_store
    from benchmarks.tpch.datagen import ALL_TABLES, gen_table
    from benchmarks.tpch.queries import QUERIES

    sf = float(os.environ.get("BENCH_FULL22_SF", "1"))
    # cold-compile-heavy sweep: a single job must never hit the client's
    # default 300s ceiling just because XLA is compiling 22 queries'
    # worth of kernels on a busy host
    os.environ.setdefault("BALLISTA_JOB_TIMEOUT_S", "1800")
    # register PARQUET paths, not in-memory tables: inline MemoryTable
    # data rides the ExecuteQuery proto, and at SF1 the serialized plan
    # (1.5 GB) blows the 256 MiB gRPC message cap (BENCH_SUITE_r05
    # full22 failure) — the reference harness registers parquet dirs for
    # the same reason (tpch.rs: register_tables); executors scan the
    # files themselves and only shuffle/result bytes cross the wire
    import tempfile

    import pyarrow.parquet as _pq

    pq_dir = tempfile.mkdtemp(prefix="bench_full22_")
    n_lineitem = 0
    for name in ALL_TABLES:
        tbl = gen_table(name, sf)
        if name == "lineitem":
            n_lineitem = tbl.num_rows
        _pq.write_table(tbl, os.path.join(pq_dir, f"{name}.parquet"))
        del tbl

    def run(tpu: bool):
        cfg = BallistaConfig(
            {
                "ballista.tpu.enable": str(tpu).lower(),
                "ballista.shuffle.partitions": "2",
                "ballista.batch.size": str(1 << 22),
                "ballista.shuffle.to_memory": "true",
            }
        )
        bctx = BallistaContext.standalone(
            config=cfg, num_executors=2, concurrent_tasks=2
        )
        times = {}
        outputs = {}
        try:
            for name in ALL_TABLES:
                bctx.register_parquet(
                    name, os.path.join(pq_dir, f"{name}.parquet")
                )
            for qno in sorted(QUERIES):
                t0 = time.perf_counter()
                out = bctx.sql(QUERIES[qno]).collect()
                times[f"q{qno}"] = round(time.perf_counter() - t0, 3)
                outputs[qno] = out
        finally:
            bctx.close()
            memory_store.clear()
        return times, outputs

    cpu_times, cpu_out = run(False)
    tpu_times, tpu_out = run(True)
    mismatched = [f"q{q}" for q in sorted(QUERIES)
                  if not _tables_match(cpu_out[q], tpu_out[q])]
    total_cpu = round(sum(cpu_times.values()), 3)
    total_tpu = round(sum(tpu_times.values()), 3)
    _emit(
        {
            "metric": "tpch_full22_sf%g_distributed_total_sec_tpu" % sf,
            "value": total_tpu,
            "unit": "s",
            "vs_baseline": round(total_cpu / total_tpu, 3),
            "lineitem_rows": n_lineitem,
            "cpu_total_sec": total_cpu,
            "executors": 2,
            "matches_cpu_1e-6": not mismatched,
            "mismatched_queries": mismatched,
            "per_query_sec": {
                q: {"cpu": cpu_times[q], "tpu": tpu_times[q]}
                for q in cpu_times
            },
        }
    )


def bench_window() -> None:
    """Device window showcase (capability the reference lacks: its
    planner raises NotImplemented for WindowAggExec): ranking + running
    sum + lag over partitioned data, TpuWindowExec vs the CPU window
    operator."""
    import numpy as np
    import pyarrow as pa

    from arrow_ballista_tpu import BallistaConfig, SessionContext
    from arrow_ballista_tpu.catalog import MemoryTable

    n = int(float(os.environ.get("BENCH_WINDOW_N", "2e7")))
    parts = int(float(os.environ.get("BENCH_WINDOW_PARTS", "5e4")))
    rng = np.random.default_rng(3)
    t = pa.table(
        {
            "g": pa.array(rng.integers(0, parts, n).astype(np.int64)),
            "o": pa.array(rng.integers(0, 1 << 30, n).astype(np.int64)),
            "v": pa.array(rng.uniform(0, 100, n)),
        }
    )
    sql = (
        "select g, o, "
        "row_number() over (partition by g order by o) rn, "
        "rank() over (partition by g order by o) rk, "
        "sum(v) over (partition by g order by o) rs, "
        "lag(v) over (partition by g order by o) lg "
        "from t"
    )

    def make_ctx(tpu: bool):
        ctx = SessionContext(
            BallistaConfig(
                {
                    "ballista.tpu.enable": str(tpu).lower(),
                    "ballista.batch.size": str(1 << 23),
                    "ballista.shuffle.partitions": "1",
                }
            )
        )
        ctx.register_table("t", MemoryTable.from_table(t, 1))
        return ctx

    def cheap_match(a, b) -> bool:
        """Numpy oracle: the 2e7-row x 6-col window output would cost
        more to compare via _tables_match (per-value Python strings)
        than the whole measurement — lexsort ints exactly, allclose
        floats with aligned NaN masks."""
        if a.num_rows != b.num_rows:
            return False
        ints = ("g", "o", "rn", "rk")
        ka = [a.column(c).to_numpy(zero_copy_only=False) for c in ints]
        kb = [b.column(c).to_numpy(zero_copy_only=False) for c in ints]
        oa = np.lexsort(tuple(reversed(ka)))
        ob = np.lexsort(tuple(reversed(kb)))
        for ca, cb in zip(ka, kb):
            if not np.array_equal(ca[oa], cb[ob]):
                return False
        for c in ("rs", "lg"):
            va = a.column(c).to_numpy(zero_copy_only=False)[oa]
            vb = b.column(c).to_numpy(zero_copy_only=False)[ob]
            na, nb_ = np.isnan(va), np.isnan(vb)
            if not np.array_equal(na, nb_):
                return False
            if not np.allclose(va[~na], vb[~nb_], rtol=1e-6):
                return False
        return True

    results = {}
    for tpu in (False, True):
        ctx = make_ctx(tpu)
        df = ctx.sql(sql)
        best = float("inf")
        table = None
        for _ in range(3):
            plan = df.physical_plan()
            t0 = time.perf_counter()
            table = ctx.execute(plan)
            best = min(best, time.perf_counter() - t0)
        results[tpu] = (best, table)
    cpu_s, tpu_s = results[False][0], results[True][0]
    ok = cheap_match(results[False][1], results[True][1])
    _emit(
        {
            "metric": "window_rank_runsum_%.0e_tpu_rows_per_sec" % n,
            "value": round(n / tpu_s),
            "unit": "rows/s",
            "vs_baseline": round(cpu_s / tpu_s, 3),
            "rows": n,
            "partitions": parts,
            "cpu_rows_per_sec": round(n / cpu_s),
            "matches_cpu_1e-6": ok,
        }
    )


def bench_h2o() -> None:
    """Config #5: h2o groupby G1_1e8, TPU vs CPU, via the real harness."""
    import io

    from benchmarks.h2o.__main__ import run_groupby

    n = int(float(os.environ.get("BENCH_H2O_N", "1e8")))
    k = int(os.environ.get("BENCH_H2O_K", "100"))
    iters = int(os.environ.get("BENCH_H2O_ITERS", "2"))
    # A/B hygiene: BENCH_HIGHCARD_MODE only affects the tpu leg, so a
    # mode sweep can skip re-running the identical CPU-engine oracle
    skip_cpu = bool(os.environ.get("BENCH_H2O_SKIP_CPU"))
    per_engine = {}
    questions = {}
    for tpu in ((True,) if skip_cpu else (False, True)):
        buf = io.StringIO()
        summary = run_groupby(
            n=n, k=k, partitions=2, tpu=tpu, iters=iters, out=buf
        )
        per_engine[tpu] = summary
        for line in buf.getvalue().splitlines():
            rec = json.loads(line)
            if "question" in rec and "skipped" not in rec:
                qid = rec["question"].split(":")[0]
                questions.setdefault(qid, {})[
                    "tpu" if tpu else "cpu"
                ] = rec["time_sec"]
    total_cpu = per_engine[False]["total_sec"] if not skip_cpu else None
    total_tpu = per_engine[True]["total_sec"]
    _emit(
        {
            "metric": "h2o_groupby_G1_%.0e_total_sec_tpu" % n,
            "value": total_tpu,
            "unit": "s",
            "vs_baseline": (
                round(total_cpu / total_tpu, 3) if total_cpu else None
            ),
            "rows": n,
            "k": k,
            # the record must say WHICH route produced it: the A/B legs
            # would otherwise be indistinguishable in the artifact
            "highcard_mode": os.environ.get("BENCH_HIGHCARD_MODE", "auto"),
            "cpu_total_sec": total_cpu,
            "per_question_sec": questions,
        }
    )


def bench_shuffle_fetch() -> None:
    """Config #6: shuffle fetch data plane — MB/s through the concurrent
    pipelined reader vs the sequential location-by-location path, over
    real IPC partition files (no query plan in the way)."""
    from benchmarks.shuffle_fetch import run_fetch_bench

    n_loc = int(os.environ.get("BENCH_SHUFFLE_LOCATIONS", "16"))
    mb = float(os.environ.get("BENCH_SHUFFLE_MB_PER_LOC", "4"))
    conc = int(os.environ.get("BENCH_SHUFFLE_CONCURRENCY", "8"))
    rec = run_fetch_bench(
        n_locations=n_loc, mb_per_location=mb, concurrency=conc
    )
    _emit(
        {
            "metric": "shuffle_fetch_mb_per_sec",
            "value": rec["pipelined_mb_per_sec"],
            "unit": "MB/s",
            "vs_baseline": round(
                rec["sequential_s"] / rec["pipelined_s"], 3
            ),
            **rec,
        }
    )


def bench_shuffle_write() -> None:
    """Config #7: shuffle write data plane — MB/s through the
    slab-buffered async writer pool vs the pre-pipelining synchronous
    path (argsort + one uncoalesced sink write per split run), plus the
    zstd wire-compression ratio."""
    from benchmarks.shuffle_write import run_write_bench

    rec = run_write_bench(
        n_batches=int(os.environ.get("BENCH_SHUFFLE_WRITE_BATCHES", "32")),
        rows_per_batch=int(
            os.environ.get("BENCH_SHUFFLE_WRITE_ROWS", "65536")
        ),
        n_out=int(os.environ.get("BENCH_SHUFFLE_WRITE_PARTITIONS", "8")),
        compression=os.environ.get("BENCH_SHUFFLE_COMPRESSION", "zstd"),
    )
    _emit(
        {
            "metric": "shuffle_write_mb_per_sec",
            "value": rec["pipelined_mb_per_sec"],
            "unit": "MB/s",
            "vs_baseline": rec["speedup"],
            **rec,
        }
    )


def bench_shuffle_locality() -> None:
    """Config #8: shuffle data-plane locality A/B (ISSUE 10) — same-host
    zero-copy (identity-gated pa.memory_map) vs forced-remote Flight
    loopback on identical inputs (sha row-fingerprint identity enforced
    inside the bench), plus the batched multi-partition DoGet leg
    (fewer round trips at no MB/s regression)."""
    from benchmarks.shuffle_locality import run_locality_bench

    rec = run_locality_bench(
        n_locations=int(os.environ.get("BENCH_SHUFFLE_LOCATIONS", "16")),
        mb_per_location=float(os.environ.get("BENCH_SHUFFLE_MB_PER_LOC", "4")),
        concurrency=int(os.environ.get("BENCH_SHUFFLE_CONCURRENCY", "8")),
    )
    _emit(
        {
            "metric": "shuffle_local_fetch_mb_per_sec",
            "value": rec["local_mb_per_sec"],
            "unit": "MB/s",
            # acceptance: >= 2x the Flight-loopback fetch throughput
            "vs_baseline": rec["local_vs_remote"],
            **rec,
        }
    )
    _emit(
        {
            "metric": "shuffle_batched_fetch_round_trips",
            "value": rec["batched_round_trips"],
            "unit": "round trips",
            "vs_baseline": round(
                rec["unbatched_round_trips"]
                / max(1, rec["batched_round_trips"]),
                3,
            ),
            "batched_mb_per_sec": rec["remote_batched_mb_per_sec"],
            "unbatched_mb_per_sec": rec["remote_unbatched_mb_per_sec"],
        }
    )


def bench_aqe() -> None:
    """Adaptive query execution A/B (ISSUE 8): a skewed star join and a
    tiny-partition aggregate, each measured with ballista.aqe.enabled
    true vs false on identical inputs over a real 2-executor standalone
    cluster.  ``vs_baseline`` is static-time / adaptive-time; the
    records carry the before/after reduce-task counts so the bench
    report shows the plan shape alongside the throughput."""
    from benchmarks.aqe_starjoin import run_aqe_starjoin, run_aqe_tiny_agg

    star = run_aqe_starjoin(
        n_fact=int(os.environ.get("BENCH_AQE_FACT_ROWS", "300000")),
        skew=float(os.environ.get("BENCH_AQE_SKEW", "0.5")),
        partitions=int(os.environ.get("BENCH_AQE_PARTITIONS", "24")),
    )
    _emit(star)
    _emit(run_aqe_tiny_agg(partitions=64))


def bench_keyed() -> None:
    """Keyed device-path A/B (ISSUE 9): q3-shaped keyed aggregate and
    starjoin, fused device-encode vs the host-encode keyed baseline
    (``ballista.tpu.device_encode``) vs the gid-table GroupTable route,
    bit-identical results enforced per record."""
    from benchmarks.keyed_path import (
        run_keyed_agg_bench,
        run_keyed_starjoin_bench,
    )

    _emit(
        run_keyed_agg_bench(
            n_rows=int(float(os.environ.get("BENCH_KEYED_ROWS", "2e6"))),
            n_groups=int(
                float(os.environ.get("BENCH_KEYED_GROUPS", "1e6"))
            ),
        )
    )
    _emit(
        run_keyed_starjoin_bench(
            n_fact=int(float(os.environ.get("BENCH_KEYED_FACT", "2e6"))),
            n_dim=int(float(os.environ.get("BENCH_KEYED_DIM", "2e5"))),
        )
    )


def bench_pipelined() -> None:
    """Streaming pipelined execution A/B (ISSUE 15): a barrier-dominated
    shuffle query (manufactured straggler map task + reduce-side work)
    with ballista.shuffle.pipelined off vs on over a real 2-executor
    standalone cluster on identical inputs — sha row-fingerprint
    identity enforced, wall-clock speedup and the doctor's measured
    barrier_wait for both legs in the record (pipelined leg's
    barrier_wait collapsing toward zero is the expected signature)."""
    from benchmarks.pipelined_stage import run_pipelined_bench

    _emit(
        run_pipelined_bench(
            n_rows=int(
                float(os.environ.get("BENCH_PIPELINED_ROWS", "2e5"))
            ),
            straggler_ms=int(
                os.environ.get("BENCH_PIPELINED_STRAGGLER_MS", "3000")
            ),
            reduce_delay_ms=int(
                os.environ.get("BENCH_PIPELINED_REDUCE_MS", "1800")
            ),
        )
    )


def bench_fusion() -> None:
    """Whole-stage fusion A/B (ISSUE 19): q3-shaped grouped map stage
    and a scan-heavy scalar shape, ballista.tpu.whole_stage_fusion on vs
    off on identical inputs — the fused leg plans one segment and runs
    each task's kernels + combine + pack as ONE jitted dispatch, with
    bit-identical results enforced per record."""
    from benchmarks.whole_stage_fusion import (
        run_fusion_q3_bench,
        run_fusion_scan_bench,
    )

    _emit(
        run_fusion_q3_bench(
            n_rows=int(float(os.environ.get("BENCH_FUSION_ROWS", "131072"))),
            batch_rows=int(
                os.environ.get("BENCH_FUSION_BATCH_ROWS", "4096")
            ),
            iters=int(os.environ.get("BENCH_FUSION_ITERS", "5")),
        )
    )
    _emit(
        run_fusion_scan_bench(
            n_rows=int(
                float(os.environ.get("BENCH_FUSION_SCAN_ROWS", "32768"))
            ),
            batch_rows=int(
                os.environ.get("BENCH_FUSION_SCAN_BATCH_ROWS", "1024")
            ),
            iters=int(os.environ.get("BENCH_FUSION_ITERS", "5")),
        )
    )


def bench_obs() -> None:
    """Obs leg (ISSUE 13): disabled-path + enabled-path overhead with
    the query-doctor attribution pass in the picture (PR 3 methodology —
    priced against the shuffle leg, acceptance < 2%), plus the measured
    job's wall-clock category breakdown riding the record."""
    from benchmarks.obs_doctor import run_obs_bench

    _emit(run_obs_bench())


def bench_concurrent() -> None:
    """Concurrency leg (ISSUE 12): N open-loop clients of mixed
    priority against one standalone cluster at >=4x slot
    oversubscription — admission-on vs admission-off interactive p99,
    two tenants at weights 2:1 vs the 2:1 completed-throughput target,
    and a burst past max_queued_jobs shedding with structured
    ClusterSaturated errors while every admitted job completes."""
    from benchmarks.concurrent_clients import run_concurrency_bench

    for rec in run_concurrency_bench():
        _emit(rec)


def main() -> None:
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if os.path.exists(OUT_PATH) and which == "all":
        os.remove(OUT_PATH)
    # the platform asked for, or no suite at all (first backend touch)
    from benchmarks.device_guard import require_device

    require_device()
    algo = os.environ.get("BENCH_AGG_ALGO")
    if algo:  # A/B hook: force matmul | sort | scatter on the TPU legs
        from arrow_ballista_tpu.ops import kernels as K

        K.set_agg_algorithm(algo)
    if which in ("q6", "all"):
        bench_q6_parquet()
    if which in ("q3", "all"):
        bench_q3_sf10()
    if which in ("starjoin", "all"):
        bench_starjoin()
    if which in ("full22", "all"):
        bench_full22()
    if which in ("window", "all"):
        bench_window()
    if which in ("h2o", "all"):
        bench_h2o()
    if which in ("shuffle", "all"):
        bench_shuffle_fetch()
        bench_shuffle_write()
        bench_shuffle_locality()
    if which in ("aqe", "all"):
        bench_aqe()
    if which in ("keyed", "all"):
        bench_keyed()
    if which in ("concurrent", "all"):
        bench_concurrent()
    if which in ("pipelined", "all"):
        bench_pipelined()
    if which in ("fusion", "all"):
        bench_fusion()
    if which in ("obs", "all"):
        bench_obs()


if __name__ == "__main__":
    main()
