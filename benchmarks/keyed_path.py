"""Keyed device-path A/B: device-encode fusion vs the host-encode baseline.

ISSUE 9's rescue of the keyed plans (BENCH_SUITE_r05: q3 SF10 at 0.036x
CPU with 12.6s of host GroupTable hashing) measured in isolation.  Two
workloads, each run on IDENTICAL inputs across three configurations:

* ``fused``    — ``ballista.tpu.device_encode=true`` + the keyed route:
  raw key columns cross the bridge once, group codes derive on device
  (bit-identical to the host encoders), and encode→packed-u64-sort runs
  as ONE jitted dispatch (``fused_keyed_dispatches``).
* ``baseline`` — ``ballista.tpu.device_encode=false`` + the keyed
  route: the host encodes per batch (``key_encode_time_ns``) and int64
  codes take the multi-operand device sort.  This is the knob A/B the
  acceptance criterion names.
* ``gid``      — ``ballista.tpu.highcard_mode=gid``: the gid-table
  device route whose host ``GroupTable`` hashing was the q3 cost
  center, recorded as a second reference point.

Workloads:

* ``run_keyed_agg_bench`` — q3-shaped keyed aggregate: GROUP BY a
  high-cardinality int64 key plus a date-like and a small int key
  (q3's ``l_orderkey, o_orderdate, o_shippriority`` shape),
  sum/count/min over multiple batches.  Multi-key is where the
  packed-u64 sort earns its keep: the fused path packs three i32 code
  fields + iota into two u64 words, the host-encode baseline sorts
  four i64 operands.
* ``run_keyed_starjoin_bench`` — starjoin shape: PK-FK dim join folded
  into the device stage, GROUP BY the high-cardinality probe key.

Both verify bit-identical results across every leg via a sha-256 row
fingerprint (numpy lexsort canonicalization — no ORDER BY, no pyarrow
sort).  Runs on the CPU JAX backend (CI) and on chip unchanged.

Usage: via ``bench_suite.py keyed`` (measurement) or ``dev/tier1.sh
--bench-smoke`` (tiny-input identity/compile smoke via
:func:`run_keyed_smoke`, NOT a measurement).
"""

from __future__ import annotations

import hashlib
import time

import numpy as np
import pyarrow as pa

BASE = {
    "ballista.tpu.enable": "true",
    "ballista.tpu.min_rows": "0",
    # the A/B isolates the execution path, not the device column cache
    "ballista.tpu.cache_columns": "false",
    "ballista.shuffle.partitions": "1",
}

LEGS = {
    "fused": {
        "ballista.tpu.highcard_mode": "device",
        "ballista.tpu.device_encode": "true",
    },
    "baseline": {
        "ballista.tpu.highcard_mode": "device",
        "ballista.tpu.device_encode": "false",
    },
    "gid": {
        "ballista.tpu.highcard_mode": "gid",
        "ballista.tpu.device_encode": "false",
    },
}

_METRIC_KEYS = (
    "key_encode_time_ns",
    "device_time_ns",
    "bridge_time_ns",
    "tpu_stage_time_ns",
    "device_encode_batches",
    "fused_keyed_dispatches",
    "keyed_path",
    "keyed_chunks",
    "tpu_fallback",
    "highcard_fallback",
    "join_fallback",
)


def _canon(tbl: pa.Table):
    """Columns canonicalized to one row order via the non-float columns
    (group keys/counts — unique per row here, so the order is total)."""
    cols = [
        np.ascontiguousarray(c.to_numpy(zero_copy_only=False))
        for c in tbl.columns
    ]
    keys = [v for v in cols if v.dtype.kind != "f"]
    order = np.lexsort(tuple(reversed(keys)))
    return [v[order] for v in cols]


def _fingerprint(tbl: pa.Table) -> str:
    """Order-independent sha of the EXACT row bytes (floats included
    bit-for-bit): equal fingerprints mean bit-identical results."""
    h = hashlib.sha256()
    for v in _canon(tbl):
        h.update(v.tobytes())
    return h.hexdigest()[:16]


def _tables_close(a: pa.Table, b: pa.Table, rel: float = 1e-9) -> bool:
    """Non-float columns exactly equal, floats within ``rel`` — for
    comparing against legs whose float REDUCTION ORDER differs (the
    gid-table route), where last-ulp drift is expected and a bitwise
    hash would flap."""
    if a.num_rows != b.num_rows:
        return False
    for va, vb in zip(_canon(a), _canon(b)):
        if va.dtype.kind == "f":
            if not np.allclose(va, vb, rtol=rel, atol=0, equal_nan=True):
                return False
        elif not np.array_equal(va, vb):
            return False
    return True


def _collect_metrics(plan) -> dict:
    from arrow_ballista_tpu.ops.stage_compiler import TpuStageExec

    agg: dict = {}
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, TpuStageExec):
            for k, v in node.metrics.values.items():
                agg[k] = agg.get(k, 0) + v
        stack.extend(node.children())
    return agg


def _run_leg(tables: dict, sql: str, settings: dict, batch_rows: int,
             iters: int):
    """(best_s, result table, last-iter stage metrics) for one config."""
    from arrow_ballista_tpu import BallistaConfig, SessionContext
    from arrow_ballista_tpu.catalog import MemoryTable

    ctx = SessionContext(
        BallistaConfig({**BASE, "ballista.batch.size": str(batch_rows),
                        **settings})
    )
    for name, t in tables.items():
        ctx.register_table(
            name,
            MemoryTable([t.to_batches(max_chunksize=batch_rows)], t.schema),
        )
    best = None
    out = None
    metrics: dict = {}
    for _ in range(iters):
        plan = ctx.sql(sql).physical_plan()
        t0 = time.perf_counter()
        out = ctx.execute(plan)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
        metrics = _collect_metrics(plan)
    return best, out, {
        k: metrics[k] for k in _METRIC_KEYS if k in metrics
    }


def _ab(tables: dict, sql: str, n_rows: int, metric: str,
        batch_rows: int, iters: int, extra: dict) -> dict:
    times: dict = {}
    outs: dict = {}
    mets: dict = {}
    for leg, settings in LEGS.items():
        times[leg], outs[leg], mets[leg] = _run_leg(
            tables, sql, settings, batch_rows, iters
        )
    # fused vs host-encode keyed share the sort/scan reduction order, so
    # the sha row fingerprints must match EXACTLY (bit-identical); the
    # gid route reduces in a different order, so floats get a 1e-9
    # relative bar instead of a flapping bitwise hash
    identical = _fingerprint(outs["fused"]) == _fingerprint(
        outs["baseline"]
    )
    rec = {
        "metric": metric,
        "value": round(n_rows / times["fused"]),
        "unit": "rows/s",
        # the knob A/B the acceptance names: host-encode keyed baseline
        "vs_baseline": round(times["baseline"] / times["fused"], 3),
        # the gid-table route whose GroupTable hashing was q3's cost
        # center, as a second reference
        "vs_gid_baseline": round(times["gid"] / times["fused"], 3),
        "fused_s": round(times["fused"], 3),
        "baseline_s": round(times["baseline"], 3),
        "gid_s": round(times["gid"], 3),
        "rows": n_rows,
        "identical": identical,
        "matches_gid_1e-9": _tables_close(outs["fused"], outs["gid"]),
        "fused_metrics": mets["fused"],
        "baseline_metrics": mets["baseline"],
        **extra,
    }
    return rec


def run_keyed_agg_bench(
    n_rows: int = 2_000_000,
    n_groups: int = 1_000_000,
    batch_rows: int = 262_144,
    iters: int = 3,
    seed: int = 7,
) -> dict:
    rng = np.random.default_rng(seed)
    k = rng.integers(0, n_groups, n_rows).astype(np.int64)
    t = pa.table(
        {
            "k": pa.array(k),
            # q3 shape: orderdate / shippriority ride along as group
            # keys functionally dependent-ish on the hot key
            "d": pa.array(9000 + (k % 121).astype(np.int64)),
            "p": pa.array((k % 7).astype(np.int64)),
            "v": pa.array(rng.uniform(0, 100, n_rows)),
            "w": pa.array(rng.integers(0, 1000, n_rows).astype(np.int64)),
        }
    )
    sql = (
        "select k, d, p, sum(v) as s, count(*) as c, min(w) as mn "
        "from t group by k, d, p"
    )
    return _ab(
        {"t": t}, sql, n_rows, "keyed_path_rows_per_sec", batch_rows,
        iters, {"groups": n_groups},
    )


def run_keyed_starjoin_bench(
    n_fact: int = 2_000_000,
    n_dim: int = 200_000,
    batch_rows: int = 262_144,
    iters: int = 3,
    seed: int = 11,
) -> dict:
    rng = np.random.default_rng(seed)
    dim = pa.table(
        {
            "dk": pa.array(np.arange(1, n_dim + 1).astype(np.int64)),
            "dv": pa.array(rng.uniform(0.5, 1.5, n_dim)),
        }
    )
    fact = pa.table(
        {
            "fk": pa.array(
                rng.integers(1, int(n_dim * 1.2), n_fact).astype(np.int64)
            ),
            "v": pa.array(rng.uniform(0, 100, n_fact)),
        }
    )
    sql = (
        "select fk, sum(v * dv) as s, count(*) as c "
        "from dim, fact where dk = fk group by fk"
    )
    return _ab(
        {"dim": dim, "fact": fact}, sql, n_fact,
        "keyed_starjoin_rows_per_sec", batch_rows, iters,
        {"dim_rows": n_dim},
    )


def run_keyed_smoke() -> dict:
    """Tiny-input smoke for dev/tier1.sh --bench-smoke: the fused and
    host-encode legs must be BIT-identical, the gid leg must match to
    1e-9, the fused leg must actually device-encode
    (``device_encode_batches`` >= 1, one fused dispatch) and must pay NO
    host group encode.  Shrinks the groups~rows detector (exactly like
    tests/test_keyed_agg.py) so the tiny inputs route keyed on the
    host-encode baseline leg too.  A compile/regression check, not a
    measurement."""
    from arrow_ballista_tpu.ops import stage_compiler as SC

    old = SC._HIGHCARD_MIN_GROUPS
    SC._HIGHCARD_MIN_GROUPS = 1024
    try:
        agg = run_keyed_agg_bench(
            n_rows=30_000, n_groups=6_000, batch_rows=8_192, iters=1
        )
        join = run_keyed_starjoin_bench(
            n_fact=20_000, n_dim=6_000, batch_rows=8_192, iters=1
        )
    finally:
        SC._HIGHCARD_MIN_GROUPS = old
    for rec in (agg, join):
        assert rec["identical"], f"{rec['metric']}: legs diverged"
        assert rec["matches_gid_1e-9"], f"{rec['metric']}: gid diverged"
        assert rec["baseline_metrics"].get("keyed_path", 0) >= 1, (
            "host-encode baseline leg did not route keyed",
            rec["baseline_metrics"],
        )
        fm = rec["fused_metrics"]
        assert fm.get("device_encode_batches", 0) >= 1, fm
        assert fm.get("fused_keyed_dispatches", 0) >= 1, fm
        assert fm.get("key_encode_time_ns", 0) == 0, (
            "fused leg paid a host group encode", fm,
        )
        assert fm.get("tpu_fallback", 0) == 0, fm
    return {
        "keyed_agg_vs_baseline": agg["vs_baseline"],
        "keyed_starjoin_vs_baseline": join["vs_baseline"],
        "device_encode_batches": (
            agg["fused_metrics"]["device_encode_batches"]
            + join["fused_metrics"]["device_encode_batches"]
        ),
        "identical": True,
    }


if __name__ == "__main__":
    import json

    print(json.dumps(run_keyed_agg_bench()))
    print(json.dumps(run_keyed_starjoin_bench()))
