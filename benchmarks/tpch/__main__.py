"""TPC-H benchmark harness: ``python -m benchmarks.tpch <subcommand>``.

Counterpart of the reference's ``benchmarks/src/bin/tpch.rs``:

* ``benchmark ballista|local`` — run queries 1-22 for N iterations and
  print a JSON summary with system info (`:69-113`, `:275-330`)
* ``data`` — generate the synthetic dataset as parquet/csv (stands in for
  dbgen; the reference assumes pre-generated .tbl files)
* ``convert`` — convert dbgen ``.tbl`` files to csv/parquet (`:245-249`
  convert subcommand)
* ``loadtest`` — concurrent query storm against a running cluster
  (`:249` loadtest subcommand)

Examples:
    python -m benchmarks.tpch data --path /tmp/tpch --sf 0.1
    python -m benchmarks.tpch benchmark local --path /tmp/tpch --query 6
    python -m benchmarks.tpch benchmark ballista --host localhost --port 50050 \
        --path /tmp/tpch --iterations 3
    python -m benchmarks.tpch convert --input /tmp/tbl --output /tmp/parquet \
        --format parquet
    python -m benchmarks.tpch loadtest --host localhost --port 50050 \
        --path /tmp/tpch --concurrency 4 --num-queries 16
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import pyarrow as pa
import pyarrow.csv as pacsv
import pyarrow.parquet as pq

from benchmarks.tpch.datagen import ALL_TABLES, gen_table
from benchmarks.tpch.queries import QUERIES

# dbgen .tbl column schemas (pipe-delimited, trailing delimiter)
TBL_SCHEMAS: dict[str, list[tuple[str, pa.DataType]]] = {
    "lineitem": [
        ("l_orderkey", pa.int64()), ("l_partkey", pa.int64()),
        ("l_suppkey", pa.int64()), ("l_linenumber", pa.int32()),
        ("l_quantity", pa.float64()), ("l_extendedprice", pa.float64()),
        ("l_discount", pa.float64()), ("l_tax", pa.float64()),
        ("l_returnflag", pa.string()), ("l_linestatus", pa.string()),
        ("l_shipdate", pa.date32()), ("l_commitdate", pa.date32()),
        ("l_receiptdate", pa.date32()), ("l_shipinstruct", pa.string()),
        ("l_shipmode", pa.string()), ("l_comment", pa.string()),
    ],
    "orders": [
        ("o_orderkey", pa.int64()), ("o_custkey", pa.int64()),
        ("o_orderstatus", pa.string()), ("o_totalprice", pa.float64()),
        ("o_orderdate", pa.date32()), ("o_orderpriority", pa.string()),
        ("o_clerk", pa.string()), ("o_shippriority", pa.int32()),
        ("o_comment", pa.string()),
    ],
    "customer": [
        ("c_custkey", pa.int64()), ("c_name", pa.string()),
        ("c_address", pa.string()), ("c_nationkey", pa.int64()),
        ("c_phone", pa.string()), ("c_acctbal", pa.float64()),
        ("c_mktsegment", pa.string()), ("c_comment", pa.string()),
    ],
    "part": [
        ("p_partkey", pa.int64()), ("p_name", pa.string()),
        ("p_mfgr", pa.string()), ("p_brand", pa.string()),
        ("p_type", pa.string()), ("p_size", pa.int32()),
        ("p_container", pa.string()), ("p_retailprice", pa.float64()),
        ("p_comment", pa.string()),
    ],
    "supplier": [
        ("s_suppkey", pa.int64()), ("s_name", pa.string()),
        ("s_address", pa.string()), ("s_nationkey", pa.int64()),
        ("s_phone", pa.string()), ("s_acctbal", pa.float64()),
        ("s_comment", pa.string()),
    ],
    "partsupp": [
        ("ps_partkey", pa.int64()), ("ps_suppkey", pa.int64()),
        ("ps_availqty", pa.int32()), ("ps_supplycost", pa.float64()),
        ("ps_comment", pa.string()),
    ],
    "nation": [
        ("n_nationkey", pa.int64()), ("n_name", pa.string()),
        ("n_regionkey", pa.int64()), ("n_comment", pa.string()),
    ],
    "region": [
        ("r_regionkey", pa.int64()), ("r_name", pa.string()),
        ("r_comment", pa.string()),
    ],
}


def _register_tables(ctx, path: str) -> None:
    """Register the 8 tables from a data dir (parquet dirs or csv files)."""
    for name in ALL_TABLES:
        pdir = os.path.join(path, name)
        csv = os.path.join(path, f"{name}.csv")
        if os.path.isdir(pdir):
            ctx.register_parquet(name, pdir)
        elif os.path.exists(csv):
            ctx.register_csv(name, csv)
        else:
            raise SystemExit(f"no data for table {name!r} under {path}")


def _make_context(args):
    if getattr(args, "host", None):
        from arrow_ballista_tpu import BallistaConfig
        from arrow_ballista_tpu.client.context import BallistaContext

        cfg = BallistaConfig(
            {
                "ballista.shuffle.partitions": str(args.partitions),
                "ballista.batch.size": str(args.batch_size),
                # session settings ship with every query, so the executors
                # honor --tpu in cluster mode too
                "ballista.tpu.enable": "true" if args.tpu else "false",
            }
        )
        return BallistaContext.remote(args.host, args.port, cfg)
    from arrow_ballista_tpu import BallistaConfig, SessionContext

    cfg = BallistaConfig(
        {
            "ballista.shuffle.partitions": str(args.partitions),
            "ballista.batch.size": str(args.batch_size),
            "ballista.tpu.enable": "true" if args.tpu else "false",
        }
    )
    return SessionContext(cfg)


def cmd_benchmark(args) -> None:
    ctx = _make_context(args)
    _register_tables(ctx, args.path)
    queries = [args.query] if args.query else sorted(QUERIES)
    results = {}
    for qn in queries:
        times = []
        rows = 0
        for i in range(args.iterations):
            t0 = time.perf_counter()
            out = ctx.sql(QUERIES[qn]).collect()
            dt = (time.perf_counter() - t0) * 1000.0
            rows = out.num_rows
            times.append(dt)
            if args.debug:
                print(f"q{qn} iter {i}: {dt:.1f} ms, {rows} rows", file=sys.stderr)
        results[f"q{qn}"] = {
            "iterations": args.iterations,
            "min_ms": round(min(times), 3),
            "max_ms": round(max(times), 3),
            "avg_ms": round(sum(times) / len(times), 3),
            "rows": rows,
        }
    # summary in the shape of the reference's BenchmarkRun JSON (tpch.rs
    # summary: engine/version/system info + per-query timings)
    summary = {
        "engine": "ballista-tpu" if getattr(args, "host", None) else "local",
        "benchmark_version": "0.7.0-tpu",
        "python_version": platform.python_version(),
        "system": {
            "machine": platform.machine(),
            "processor": platform.processor(),
            "platform": platform.platform(),
        },
        "data_path": args.path,
        "queries": results,
    }
    print(json.dumps(summary, indent=2 if args.debug else None))
    if args.output:
        with open(args.output, "w") as f:
            json.dump(summary, f, indent=2)


def cmd_data(args) -> None:
    os.makedirs(args.path, exist_ok=True)
    for name in ALL_TABLES:
        tbl = gen_table(name, args.sf)
        if args.format == "parquet":
            tdir = os.path.join(args.path, name)
            os.makedirs(tdir, exist_ok=True)
            n = args.partitions if name not in ("nation", "region") else 1
            per = (tbl.num_rows + n - 1) // n
            for i in range(n):
                pq.write_table(
                    tbl.slice(i * per, per),
                    os.path.join(tdir, f"part-{i}.parquet"),
                    compression=args.compression,
                )
        else:
            pacsv.write_csv(tbl, os.path.join(args.path, f"{name}.csv"))
        print(f"wrote {name}: {tbl.num_rows} rows", file=sys.stderr)


def cmd_convert(args) -> None:
    """dbgen .tbl → csv/parquet (reference: tpch.rs convert subcommand)."""
    os.makedirs(args.output, exist_ok=True)
    tables = [args.table] if args.table else ALL_TABLES
    for name in tables:
        tbl_path = os.path.join(args.input, f"{name}.tbl")
        if not os.path.exists(tbl_path):
            print(f"skipping {name}: {tbl_path} not found", file=sys.stderr)
            continue
        schema_cols = TBL_SCHEMAS[name]
        # dbgen emits a trailing '|' per row → one phantom column
        names = [c for c, _ in schema_cols] + ["__trailing"]
        table = pacsv.read_csv(
            tbl_path,
            read_options=pacsv.ReadOptions(column_names=names),
            parse_options=pacsv.ParseOptions(delimiter="|"),
            convert_options=pacsv.ConvertOptions(
                column_types={c: t for c, t in schema_cols},
                include_columns=[c for c, _ in schema_cols],
            ),
        )
        if args.format == "parquet":
            tdir = os.path.join(args.output, name)
            os.makedirs(tdir, exist_ok=True)
            pq.write_table(
                table,
                os.path.join(tdir, "part-0.parquet"),
                compression=args.compression,
            )
        else:
            pacsv.write_csv(table, os.path.join(args.output, f"{name}.csv"))
        print(f"converted {name}: {table.num_rows} rows", file=sys.stderr)


def cmd_loadtest(args) -> None:
    """Concurrent query storm (reference: tpch.rs loadtest subcommand)."""
    import threading

    queries = (
        [args.query] if args.query else sorted(set(QUERIES) & {1, 3, 5, 6, 10, 12})
    )
    errors: list[str] = []
    latencies: list[float] = []
    lock = threading.Lock()

    # distribute num_queries over workers exactly (remainder to the first)
    per_worker = [
        args.num_queries // args.concurrency
        + (1 if i < args.num_queries % args.concurrency else 0)
        for i in range(args.concurrency)
    ]

    def worker(wid: int) -> None:
        ctx = _make_context(args)
        _register_tables(ctx, args.path)
        import random

        rng = random.Random(wid)
        for _ in range(per_worker[wid]):
            qn = rng.choice(queries)
            t0 = time.perf_counter()
            try:
                ctx.sql(QUERIES[qn]).collect()
                with lock:
                    latencies.append((time.perf_counter() - t0) * 1000.0)
            except Exception as e:
                with lock:
                    errors.append(f"q{qn}: {e}")

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(args.concurrency)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    latencies.sort()
    n = len(latencies)
    print(
        json.dumps(
            {
                "completed": n,
                "errors": len(errors),
                "wall_seconds": round(wall, 2),
                "qps": round(n / wall, 2) if wall else 0,
                "p50_ms": round(latencies[n // 2], 1) if n else None,
                "p95_ms": round(latencies[int(n * 0.95)], 1) if n else None,
                "error_samples": errors[:3],
            }
        )
    )
    if errors:
        sys.exit(1)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser("tpch", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    b = sub.add_parser("benchmark", help="run TPC-H queries, print JSON summary")
    b.add_argument("mode", choices=["ballista", "local"], help="cluster or in-proc")
    b.add_argument("--host", default=None)
    b.add_argument("--port", type=int, default=50050)
    b.add_argument("--path", required=True, help="data directory")
    b.add_argument("--query", type=int, default=None, choices=sorted(QUERIES))
    b.add_argument("--iterations", type=int, default=3)
    b.add_argument("--partitions", type=int, default=2)
    b.add_argument("--batch-size", type=int, default=8192)
    b.add_argument("--tpu", action="store_true", help="enable the TPU stage compiler")
    b.add_argument("--debug", action="store_true")
    b.add_argument("--output", default=None, help="also write summary JSON here")

    d = sub.add_parser("data", help="generate the synthetic dataset (dbgen stand-in)")
    d.add_argument("--path", required=True)
    d.add_argument("--sf", type=float, default=0.1)
    d.add_argument("--partitions", type=int, default=2)
    d.add_argument("--format", choices=["parquet", "csv"], default="parquet")
    d.add_argument("--compression", default="snappy")

    c = sub.add_parser("convert", help="convert dbgen .tbl files")
    c.add_argument("--input", required=True)
    c.add_argument("--output", required=True)
    c.add_argument("--format", choices=["parquet", "csv"], default="parquet")
    c.add_argument("--compression", default="snappy")
    c.add_argument("--table", default=None, choices=ALL_TABLES)

    lt = sub.add_parser("loadtest", help="concurrent query storm")
    lt.add_argument("--host", default=None)
    lt.add_argument("--port", type=int, default=50050)
    lt.add_argument("--path", required=True)
    lt.add_argument("--query", type=int, default=None, choices=sorted(QUERIES))
    lt.add_argument("--concurrency", type=int, default=4)
    lt.add_argument("--num-queries", type=int, default=16)
    lt.add_argument("--partitions", type=int, default=2)
    lt.add_argument("--batch-size", type=int, default=8192)
    lt.add_argument("--tpu", action="store_true")

    args = ap.parse_args(argv)
    if args.cmd == "benchmark":
        if args.mode == "ballista" and not args.host:
            args.host = "localhost"
        if args.mode == "local":
            args.host = None
        cmd_benchmark(args)
    elif args.cmd == "data":
        cmd_data(args)
    elif args.cmd == "convert":
        cmd_convert(args)
    elif args.cmd == "loadtest":
        cmd_loadtest(args)


if __name__ == "__main__":
    main()
