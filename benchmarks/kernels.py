"""Kernel-level microbenchmarks (VERDICT r3 item 8 — the conbench slot).

Counterpart of the reference's criterion→conbench micro-bench bridge
(``/root/reference/conbench/benchmarks.py:38-46``,
``conbench/_criterion.py``): where the reference benches DataFusion
kernels via cargo-criterion, this grids the TPU segment-reduction
strategies directly — strategy × capacity × rows — plus the host-side
group-encode paths they compete against, emitting one JSON line per
cell.  This is the tuning tool for the routing constants: the
high-cardinality bounds ``stage_compiler.choose_route`` reads and the
segment-algorithm bounds ``kernels.segment_algo`` reads.

``keyed_fused`` is the ISSUE-9 production shape — prep (with in-kernel
key encode) and the packed-u64 sort in ONE jitted dispatch;
``keyed`` keeps the pre-fusion 3-dispatch form for comparison.

Usage:
    python benchmarks/kernels.py [--rows 1e6,8e6] [--caps 1024,65536,1048576]
        [--algos matmul,scatter,sort,keyed,keyed_fused] [--iters 3]
        [--out FILE]

Timing protocol: every timed run ends in the packed device→host fetch
the engine itself syncs on — times include queue + compute + result
fetch, matching the engine's device_time_ns accounting.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _emit(rec: dict, out_path: str | None) -> None:
    line = json.dumps(rec)
    print(line, flush=True)
    if out_path:
        with open(out_path, "a") as f:
            f.write(line + "\n")


def bench_segment_reduce(rows: int, capacity: int, algo: str, iters: int):
    """One grid cell: fused sum+count segment reduction at (rows, cap)."""
    import jax

    from arrow_ballista_tpu.ops import kernels as K

    mode = K.precision_mode()
    rng = np.random.default_rng(42)
    seg = rng.integers(0, capacity, rows).astype(np.int32)
    v = rng.uniform(0, 100, rows).astype(
        np.float32 if mode == "x32" else np.float64
    )
    valid = np.ones(rows, dtype=bool)
    specs = [K.KernelAggSpec("sum", True), K.KernelAggSpec("count_star", False)]
    flat_names = ["c0", "c0__valid"]
    closures = [lambda env: (env["c0"], env["c0__valid"]), None]

    if algo == "keyed":
        # keys ARE the segment ids: sort + boundary gids + scan + pack
        holder: dict = {}
        prep = jax.jit(
            K.make_keyed_prep_kernel(None, closures, specs, flat_names, holder)
        )
        sortk = K.keyed_sort_kernel(1)
        keys_d = jax.device_put(seg)
        valid_d = jax.device_put(valid)
        v_d = jax.device_put(v)

        def run():
            pre = prep((keys_d,), valid_d, v_d, valid_d)
            mask, key = pre[0], pre[1]
            flat = pre[2:]
            out = sortk(mask, key)
            s2, perm, sk = out[0], out[1], out[2:-1]
            n_groups = int(np.asarray(out[-1]))
            cap2 = max(64, 1 << (max(n_groups, 1) - 1).bit_length())
            finish = K.keyed_finish_kernel(
                holder["kinds"], holder["plan"], specs, 1, cap2, mode
            )
            packed = finish(s2, perm, tuple(sk), tuple(flat))
            return np.asarray(packed)

    elif algo == "keyed_fused":
        # ISSUE-9 production shape: device key encode + prep + packed
        # sort in ONE dispatch, then the capacity-sized finish — the
        # two-dispatch pipeline stage_compiler._keyed_reduce_fused runs
        holder: dict = {}
        prep_raw = K.make_keyed_prep_kernel(
            None, closures, specs, flat_names, holder,
            key_kinds=("ident",),
        )
        sort_body = K.keyed_sort_body(1)

        def fused(keys, valid_a, *args):
            pre = prep_raw(keys, valid_a, *args)
            return pre + sort_body(pre[0], pre[1])

        ffn = jax.jit(fused)
        # raw key values; identity codes (value+1) are the segment
        # ids shifted by one — same cardinality, same sort shape
        keys_d = jax.device_put(seg)
        valid_d = jax.device_put(valid)
        v_d = jax.device_put(v)

        def run():
            outs = ffn(((keys_d, valid_d),), valid_d, v_d, valid_d)
            flat = outs[2:-4]
            s2, perm, sk = outs[-4], outs[-3], (outs[-2],)
            n_groups = int(np.asarray(outs[-1]))
            cap2 = max(64, 1 << (max(n_groups, 1) - 1).bit_length())
            finish = K.keyed_finish_kernel(
                holder["kinds"], holder["plan"], specs, 1, cap2, mode
            )
            packed = finish(s2, perm, sk, tuple(flat))
            return np.asarray(packed)

    else:
        K.set_agg_algorithm(algo)
        try:
            kernel = jax.jit(
                K.make_partial_agg_kernel(
                    None, closures, specs, capacity, flat_names
                )
            )
        finally:
            K.set_agg_algorithm(None)
        seg_d = jax.device_put(seg)
        valid_d = jax.device_put(valid)
        v_d = jax.device_put(v)

        def run():
            K.set_agg_algorithm(algo)
            try:
                out = kernel(seg_d, valid_d, v_d, valid_d)
                packed = K.pack_for_fetch(specs, out, mode)
                return np.asarray(packed)
            finally:
                K.set_agg_algorithm(None)

    run()  # compile + warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_host_encode(rows: int, capacity: int, iters: int, strings: bool):
    """Host group-encode the keyed path replaces: GroupTable hash probe +
    factorize (ints) or DictEncoder (strings)."""
    from arrow_ballista_tpu.ops.bridge import DictEncoder
    from arrow_ballista_tpu.ops.groups import GroupTable

    import pyarrow as pa

    rng = np.random.default_rng(42)
    keys = rng.integers(0, capacity, rows)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        if strings:
            arr = pa.array(np.char.add("k", keys.astype("U10")))
            enc = DictEncoder()
            codes = enc.encode(arr)
            gt = GroupTable(1)
            gt.encode([codes])
        else:
            gt = GroupTable(1)
            gt.encode([keys.astype(np.int64)])
        best = min(best, time.perf_counter() - t0)
    return best


def bench_sort_operands(rows: int, n_operands: int, iters: int, u64: bool):
    """Pure lax.sort cost vs operand count — the r05 chip capture showed
    stream-wide multi-operand sorts losing 10-100x (q3 keyed 0.036x, the
    2e7 window sort never returning), and every sort-based path
    (keyed/window/median) carries 2+n_keys operands through each bitonic
    pass.  This family answers whether BYTES MOVED or per-pass overhead
    dominates, i.e. whether packing keys+iota into one u64 operand is
    worth building.  ``u64=True`` benches that packed candidate: one
    u64 key operand (num_keys=1) vs the same total key bits as i32s."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(42)
    if u64:
        ops = (rng.integers(0, 1 << 62, rows, dtype=np.uint64),)
        num_keys = 1
    else:
        ops = tuple(
            rng.integers(0, 1 << 30, rows).astype(np.int32)
            for _ in range(n_operands - 1)
        ) + (np.arange(rows, dtype=np.int32),)  # iota payload
        num_keys = n_operands - 1
    ops_d = tuple(jax.device_put(o) for o in ops)
    fn = jax.jit(lambda *a: jax.lax.sort(a, num_keys=num_keys))

    def run():
        out = fn(*ops_d)
        return np.asarray(out[0][:64])  # tiny fetch: sync without volume

    run()  # compile + warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_dispatch_latency(iters: int):
    """Dispatch + fetch round-trip floors (the q6 latency story): time a
    near-no-op jitted call synced by a 1-element fetch, and a chain of K
    dependent dispatches before one fetch — separates per-dispatch from
    per-fetch cost.  Returns (one_dispatch_fetch_s, chained8_fetch_s)."""
    import jax
    import jax.numpy as jnp

    x = jax.device_put(np.arange(1024, dtype=np.float32))
    one = jax.jit(lambda v: (v * 2.0).sum())
    step = jax.jit(lambda v: v * 1.000001)

    def run_one():
        return float(np.asarray(one(x)))

    def run_chain():
        v = x
        for _ in range(8):
            v = step(v)
        return float(np.asarray(v[0]))

    run_one(), run_chain()  # compile + warm
    best1 = best8 = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        run_one()
        best1 = min(best1, time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_chain()
        best8 = min(best8, time.perf_counter() - t0)
    return best1, best8


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="1e6,8e6")
    ap.add_argument("--caps", default="1024,65536,1048576")
    ap.add_argument(
        "--algos", default="matmul,scatter,sort,keyed,keyed_fused"
    )
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument(
        "--host-encode", action="store_true",
        help="also grid the host GroupTable/DictEncoder encode",
    )
    args = ap.parse_args()

    from benchmarks.device_guard import require_device

    platform = require_device()
    from arrow_ballista_tpu.ops import kernels as K

    base = {
        "device_platform": platform,
        "precision_mode": K.precision_mode(),
    }

    rows_list = [int(float(r)) for r in args.rows.split(",")]
    caps = [int(float(c)) for c in args.caps.split(",")]
    algos = args.algos.split(",")
    for rows in rows_list:
        for cap in caps:
            if cap > rows:
                continue
            for algo in algos:
                if (
                    algo == "matmul"
                    and (cap > K._MATMUL_MAX_CAP
                         or rows * cap > K._MATMUL_MAX_ELEMS)
                ):
                    continue  # outside the strategy's own applicability
                try:
                    s = bench_segment_reduce(rows, cap, algo, args.iters)
                    _emit(
                        dict(
                            base,
                            bench="segment_reduce",
                            algo=algo,
                            rows=rows,
                            capacity=cap,
                            sec=round(s, 6),
                            rows_per_sec=round(rows / s),
                        ),
                        args.out,
                    )
                except Exception as e:  # keep the grid going
                    _emit(
                        dict(
                            base,
                            bench="segment_reduce",
                            algo=algo,
                            rows=rows,
                            capacity=cap,
                            error=str(e)[:200],
                        ),
                        args.out,
                    )
            if args.host_encode:
                for strings in (False, True):
                    s = bench_host_encode(rows, cap, args.iters, strings)
                    _emit(
                        dict(
                            base,
                            bench="host_encode",
                            algo="dict" if strings else "group_table",
                            rows=rows,
                            capacity=cap,
                            sec=round(s, 6),
                            rows_per_sec=round(rows / s),
                        ),
                        args.out,
                    )

    # sort-cost vs operand count + the packed-u64 candidate
    for rows in rows_list:
        for n_ops, u64 in [(2, False), (3, False), (5, False), (1, True)]:
            try:
                s = bench_sort_operands(rows, n_ops, args.iters, u64)
                _emit(
                    dict(
                        base,
                        bench="sort_operands",
                        operands=("u64x1" if u64 else f"i32x{n_ops}"),
                        rows=rows,
                        sec=round(s, 6),
                        rows_per_sec=round(rows / s),
                    ),
                    args.out,
                )
            except Exception as e:
                _emit(
                    dict(
                        base,
                        bench="sort_operands",
                        operands=("u64x1" if u64 else f"i32x{n_ops}"),
                        rows=rows,
                        error=str(e)[:200],
                    ),
                    args.out,
                )

    # dispatch/fetch round-trip floors (the q6 latency story, versioned)
    try:
        one_s, chain8_s = bench_dispatch_latency(max(args.iters, 5))
        _emit(
            dict(base, bench="dispatch_latency", metric="dispatch_plus_fetch",
                 sec=round(one_s, 6)),
            args.out,
        )
        _emit(
            dict(base, bench="dispatch_latency", metric="chained8_plus_fetch",
                 sec=round(chain8_s, 6)),
            args.out,
        )
    except Exception as e:
        _emit(dict(base, bench="dispatch_latency", error=str(e)[:200]), args.out)


if __name__ == "__main__":
    main()
