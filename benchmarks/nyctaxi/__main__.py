"""NYC-taxi benchmark: ``python -m benchmarks.nyctaxi``.

Counterpart of the reference's ``benchmarks/src/bin/nyctaxi.rs``: registers
the yellow-tripdata table and runs the aggregate benchmark query
(min/max fare grouped by passenger count) against either a local context
or a cluster, printing per-iteration timings.  A ``data`` subcommand
generates a synthetic tripdata file in the 2022 yellow-taxi schema subset.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

BENCH_QUERY = """
select
    passenger_count,
    min(fare_amount) as min_fare,
    max(fare_amount) as max_fare,
    avg(fare_amount) as avg_fare,
    sum(total_amount) as total_revenue,
    count(*) as trips
from tripdata
group by passenger_count
order by passenger_count
"""


def gen_tripdata(n_rows: int, seed: int = 7) -> pa.Table:
    rng = np.random.default_rng(seed)
    distance = np.round(rng.gamma(2.0, 1.8, n_rows), 2)
    fare = np.round(2.5 + distance * 2.7 + rng.normal(0, 1.5, n_rows).clip(0), 2)
    tip = np.round(fare * rng.uniform(0, 0.35, n_rows), 2)
    return pa.table(
        {
            "vendor_id": pa.array(rng.integers(1, 3, n_rows).astype(np.int32)),
            "passenger_count": pa.array(
                rng.integers(1, 7, n_rows).astype(np.int32)
            ),
            "trip_distance": pa.array(distance),
            "fare_amount": pa.array(fare),
            "tip_amount": pa.array(tip),
            "total_amount": pa.array(np.round(fare + tip, 2)),
            "payment_type": pa.array(
                rng.choice(np.array(["CSH", "CRD", "DIS", "NOC"]), n_rows)
            ),
        }
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser("nyctaxi", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    d = sub.add_parser("data", help="generate synthetic tripdata parquet")
    d.add_argument("--path", required=True)
    d.add_argument("--rows", type=int, default=1_000_000)

    b = sub.add_parser("benchmark", help="run the aggregate benchmark")
    b.add_argument("mode", choices=["ballista", "local"])
    b.add_argument("--host", default="localhost")
    b.add_argument("--port", type=int, default=50050)
    b.add_argument("--path", required=True, help="tripdata parquet file/dir")
    b.add_argument("--iterations", type=int, default=3)
    b.add_argument("--partitions", type=int, default=2)
    b.add_argument("--tpu", action="store_true")

    args = ap.parse_args(argv)
    if args.cmd == "data":
        os.makedirs(os.path.dirname(os.path.abspath(args.path)), exist_ok=True)
        tbl = gen_tripdata(args.rows)
        pq.write_table(tbl, args.path)
        print(f"wrote {args.rows} rows to {args.path}", file=sys.stderr)
        return

    if args.mode == "ballista":
        from arrow_ballista_tpu import BallistaConfig
        from arrow_ballista_tpu.client.context import BallistaContext

        ctx = BallistaContext.remote(
            args.host,
            args.port,
            BallistaConfig(
                {
                    "ballista.shuffle.partitions": str(args.partitions),
                    "ballista.tpu.enable": "true" if args.tpu else "false",
                }
            ),
        )
    else:
        from arrow_ballista_tpu import BallistaConfig, SessionContext

        ctx = SessionContext(
            BallistaConfig(
                {
                    "ballista.shuffle.partitions": str(args.partitions),
                    "ballista.tpu.enable": "true" if args.tpu else "false",
                }
            )
        )
    ctx.register_parquet("tripdata", args.path)
    times = []
    rows = 0
    for i in range(args.iterations):
        t0 = time.perf_counter()
        out = ctx.sql(BENCH_QUERY).collect()
        dt = (time.perf_counter() - t0) * 1000.0
        times.append(dt)
        rows = out.num_rows
        print(f"iteration {i}: {dt:.1f} ms ({rows} groups)", file=sys.stderr)
    print(
        json.dumps(
            {
                "benchmark": "nyctaxi",
                "engine": args.mode,
                "min_ms": round(min(times), 2),
                "avg_ms": round(sum(times) / len(times), 2),
                "groups": rows,
            }
        )
    )


if __name__ == "__main__":
    main()
