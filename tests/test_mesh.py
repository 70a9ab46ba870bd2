"""Multi-chip sharding tests on the 8-device virtual CPU mesh."""

import jax
import numpy as np
import pytest

from arrow_ballista_tpu.ops import kernels as K
from arrow_ballista_tpu.parallel import mesh as M


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) >= 8, "conftest should force 8 virtual devices"
    return M.make_mesh(8)


def test_distributed_partial_agg_psum(mesh8):
    # fused sum/count kernel sharded over 8 devices, psum over ICI
    capacity = 16
    specs = [K.KernelAggSpec("sum", True), K.KernelAggSpec("count_star", False)]

    def arg_closure(env):
        return env["v"], env["v__valid"]

    kernel = K.make_partial_agg_kernel(
        None, [arg_closure, None], specs, capacity, ["v", "v__valid"]
    )
    step = M.make_distributed_agg_step(kernel, specs, mesh8, capacity)

    n = 8 * 1000
    rng = np.random.default_rng(0)
    seg = rng.integers(0, 10, n).astype(np.int32)
    v = rng.normal(size=n)
    valid = np.ones(n, dtype=bool)
    seg_d, valid_d, v_d, vv_d = M.shard_batch(mesh8, [seg, valid, v, valid])
    out = step(seg_d, valid_d, v_d, vv_d)

    sums = np.asarray(out[0])[:10]
    counts = np.asarray(out[2])[:10]
    for g in range(10):
        assert sums[g] == pytest.approx(v[seg == g].sum(), rel=1e-12)
        assert counts[g] == (seg == g).sum()


def test_ici_all_to_all_repartition(mesh8):
    n_dev = 8
    cap = 64
    fn = M.ici_all_to_all_repartition(mesh8, cap)
    n = n_dev * 100
    rng = np.random.default_rng(1)
    values = rng.normal(size=n)
    dest = rng.integers(0, n_dev, n).astype(np.int32)
    valid = np.ones(n, dtype=bool)
    v_d, d_d, ok_d = M.shard_batch(mesh8, [values, dest, valid])
    recv_vals, recv_valid, n_dropped = fn(v_d, d_d, ok_d)
    assert int(n_dropped) == 0

    # device d's shard of the output must hold exactly the rows with dest==d
    rv = np.asarray(recv_vals).reshape(n_dev, n_dev * cap)
    rm = np.asarray(recv_valid).reshape(n_dev, n_dev * cap)
    for d in range(n_dev):
        got = np.sort(rv[d][rm[d]])
        want = np.sort(values[dest == d])
        assert len(got) == len(want)
        assert got == pytest.approx(want, rel=1e-12)


def test_sharded_agg_matches_single_device(mesh8):
    # the mesh path and the plain jit path produce identical states
    capacity = 8
    specs = [K.KernelAggSpec("min", True), K.KernelAggSpec("max", True)]

    def arg(env):
        return env["v"], env["v__valid"]

    kernel = K.make_partial_agg_kernel(
        None, [arg, arg], specs, capacity, ["v", "v__valid"]
    )
    step = M.make_distributed_agg_step(kernel, specs, mesh8, capacity)
    n = 8 * 64
    rng = np.random.default_rng(2)
    seg = rng.integers(0, 5, n).astype(np.int32)
    v = rng.normal(size=n)
    valid = np.ones(n, dtype=bool)
    args = M.shard_batch(mesh8, [seg, valid, v, valid])
    out_mesh = step(*args)
    out_single = jax.jit(kernel)(seg, valid, v, valid)
    for a, b in zip(out_mesh, out_single):
        assert np.asarray(a)[:5] == pytest.approx(np.asarray(b)[:5], rel=1e-12)


def test_repartition_with_invalid_rows(mesh8):
    # masked-out rows must not displace valid rows past the capacity bound
    n_dev = 8
    cap = 32
    fn = M.ici_all_to_all_repartition(mesh8, cap)
    n = n_dev * 64
    rng = np.random.default_rng(3)
    values = rng.normal(size=n)
    dest = rng.integers(0, n_dev, n).astype(np.int32)
    valid = rng.random(n) < 0.5  # half the rows are masked out
    v_d, d_d, ok_d = M.shard_batch(mesh8, [values, dest, valid])
    recv_vals, recv_valid, n_dropped = fn(v_d, d_d, ok_d)
    assert int(n_dropped) == 0
    rv = np.asarray(recv_vals).reshape(n_dev, n_dev * cap)
    rm = np.asarray(recv_valid).reshape(n_dev, n_dev * cap)
    for d in range(n_dev):
        got = np.sort(rv[d][rm[d]])
        want = np.sort(values[valid & (dest == d)])
        assert len(got) == len(want)
        assert got == pytest.approx(want, rel=1e-12)


def test_assemble_shards_one_chunk_per_partition_unequal_devices():
    """The gang stage's hand-over: one chunk a partition, devices dealt
    unequal rows (one of them none).  Every shard comes out at the longest
    device's length, chunks in partition order, pad rows zero."""
    mesh = M.make_mesh(4)
    devices = list(mesh.devices.flatten())
    rng = np.random.default_rng(5)
    rows = [[5, 3], [4], [], [7, 1, 2]]  # [device][partition]
    host = [
        [
            [rng.integers(1, 9, n).astype(np.int32), rng.uniform(1, 2, n).astype(np.float32)]
            for n in parts
        ]
        for parts in rows
    ]
    chunks = [
        [jax.device_put(cols, dev) for cols in parts]
        for parts, dev in zip(host, devices)
    ]
    out = M.assemble_shards(mesh, chunks, 2)
    L = 10
    assert len(out) == 2
    for c, arr in enumerate(out):
        assert arr.shape == (4 * L,) and arr.dtype == host[0][0][c].dtype
        shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start)
        assert [s.device for s in shards] == devices
        for d, shard in enumerate(shards):
            want = np.zeros(L, dtype=arr.dtype)
            n = sum(rows[d])
            if n:
                want[:n] = np.concatenate([cols[c] for cols in host[d]])
            assert shard.data.shape == (L,)
            np.testing.assert_array_equal(np.asarray(shard.data), want)
