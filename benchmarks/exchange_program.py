"""Exchange program micro-benchmark: the packed program
(``parallel.mesh.ici_batch_exchange``: one sort of the ``[W, rows]`` word
matrix and one ``all_to_all``) against the program it replaced, one
scatter and one ``all_to_all`` a column (:func:`per_column_exchange`, the
oracle of ``tests/test_exchange_packed.py``), on the same arrays.

The default shape is q3's lineitem exchange at SF10 (stage 4): 33,554,432
padded rows of which 30,460,000 are valid, two output partitions, and 7
int32 + 4 bool payload columns (the order key, price and discount as i32
pairs, the ship date, four validities), so W = 8.  The capacity follows
``MeshRepartitionExec``: the bucket of the fullest (source shard,
destination device) run.

    python3 benchmarks/exchange_program.py [--devices N] [--n-out P] [--reps K]

prints one JSON line: each program's compile and first call, its call
times and their median, the device's peak bytes after it (the packed
program runs first, so its peak is its own), and ``equal``: every output
of the two programs equal bit for bit.  It runs on whatever platform JAX
has; a time from it is the platform's it names.
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

import numpy as np


def per_column_exchange(mesh, n_cols, capacity, n_out):
    """The exchange program as it was: the same routing, then one scatter
    into a [n_dev, capacity + 1] buffer (a spill column for the rows that
    go nowhere) and one ``all_to_all`` a column, and one more for the
    delivered mask."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map

    from arrow_ballista_tpu.parallel import mesh as M

    n_dev = mesh.devices.size
    p = np.arange(n_out)
    below = (p[None, :] < p[:, None]) & (p[None, :] % n_dev == p[:, None] % n_dev)

    def local_exchange(part, valid, *cols):
        part_m = jnp.where(valid, part, n_out)
        bound = part_m[None, :] == jnp.arange(n_out, dtype=part_m.dtype)[:, None]
        run = jnp.cumsum(bound.astype(jnp.int32), axis=1)
        offset = jnp.sum(jnp.where(below, run[None, :, -1], 0), axis=1)
        idx_within = jnp.sum(jnp.where(bound, run - 1 + offset[:, None], 0), axis=0)
        real = part_m < n_out
        ok = real & (idx_within < capacity)
        overflow = real & (idx_within >= capacity)
        n_dropped = jax.lax.psum(jnp.sum(overflow.astype(jnp.int32)), M.DATA_AXIS)
        safe_dest = jnp.where(real, part_m % n_dev, n_dev - 1)
        slot = jnp.where(ok, idx_within, capacity)

        def route(c):
            stage = jnp.zeros((n_dev, capacity + 1), c.dtype)
            stage = stage.at[safe_dest, slot].set(c, mode="drop")[:, :capacity]
            return jax.lax.all_to_all(
                stage, M.DATA_AXIS, split_axis=0, concat_axis=0, tiled=False
            ).reshape(-1)

        return tuple(route(c) for c in cols) + (route(ok), n_dropped)

    return jax.jit(
        shard_map(
            local_exchange,
            mesh=mesh,
            in_specs=(M.P(M.DATA_AXIS),) * (2 + n_cols),
            out_specs=(M.P(M.DATA_AXIS),) * (n_cols + 1) + (M.P(),),
            check_vma=False,
        )
    )


def _inputs(rows, valid_rows, n_out, seed):
    """part, valid and the payload: the valid rows first, as the exchange
    pads its input at the end."""
    rng = np.random.default_rng(seed)
    host = [rng.integers(0, n_out, rows, dtype=np.int32), np.arange(rows) < valid_rows]
    host += [rng.integers(-(2**31), 2**31 - 1, rows, dtype=np.int32) for _ in range(7)]
    host += [rng.random(rows) < 0.9 for _ in range(4)]
    return host


def _capacity(part, valid, n_dev):
    from arrow_ballista_tpu.ops import kernels as K

    per = len(part) // n_dev
    need = max(
        int(np.sum(valid[s * per:(s + 1) * per] & (part[s * per:(s + 1) * per] % n_dev == d)))
        for s in range(n_dev) for d in range(n_dev)
    )
    return K.bucket_rows(need, floor=1)


def _time(fn, placed, reps):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*placed))
    first = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*placed))
        times.append(time.perf_counter() - t0)
    return out, {"compile_and_first_s": first, "times_s": times,
                 "median_s": statistics.median(times)}


def _same_bits(a, b) -> bool:
    import jax
    import jax.numpy as jnp

    if a.dtype != b.dtype or a.shape != b.shape:
        return False
    if a.dtype == jnp.bool_:
        return bool(jnp.array_equal(a, b))
    u = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}[a.dtype.itemsize]
    return bool(jnp.array_equal(
        jax.lax.bitcast_convert_type(a, u), jax.lax.bitcast_convert_type(b, u)
    ))


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--rows", type=int, default=1 << 25)
    ap.add_argument("--valid", type=int, default=30_460_000)
    ap.add_argument("--n-out", type=int, default=2)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=7)
    a = ap.parse_args(argv)

    import jax

    from arrow_ballista_tpu.parallel import mesh as M

    mesh = M.make_mesh(a.devices)
    host = _inputs(a.rows, min(a.valid, a.rows), a.n_out, a.seed)
    cap = _capacity(host[0], host[1], a.devices)
    placed = jax.block_until_ready(M.shard_batch(mesh, host))
    n_cols = len(host) - 2
    dev = mesh.devices.flat[0]
    res = {"device": dev.device_kind, "devices": a.devices, "rows": a.rows,
           "valid": min(a.valid, a.rows), "n_out": a.n_out, "capacity": cap,
           "row_words": M.exchange_row_words(h.dtype for h in host[2:])}
    outs = {}
    for name, build in (("packed", M.ici_batch_exchange), ("per_column", per_column_exchange)):
        outs[name], res[name] = _time(build(mesh, n_cols, cap, a.n_out), placed, a.reps)
        res[name]["peak_bytes"] = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        res[name]["n_dropped"] = int(outs[name][-1])
    res["equal"] = all(
        _same_bits(p, q) for p, q in zip(outs["packed"], outs["per_column"])
    )
    print(json.dumps(res))
    return res


if __name__ == "__main__":
    main()
