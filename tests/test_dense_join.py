"""Dense-key direct-probe device join: build keys whose span fits the
slot cap are probed with ONE gather into a [span] table instead of
searchsorted's log2(m) sequential gather passes (measured dominant on
chip: round 5, star join).

Results must match the CPU join oracle exactly for dense, offset,
gappy, and wide-span (sorted-probe fallback) build keys.
"""

import numpy as np
import pyarrow as pa
import pytest

from arrow_ballista_tpu import BallistaConfig, SessionContext
from arrow_ballista_tpu.catalog import MemoryTable


def _ctx(tpu: bool, **extra) -> SessionContext:
    settings = {
        "ballista.tpu.enable": "true" if tpu else "false",
        "ballista.tpu.min_rows": "0",
        "ballista.shuffle.partitions": "1",
    }
    settings.update({k: str(v) for k, v in extra.items()})
    return SessionContext(BallistaConfig(settings))


def _assert_equal(a: pa.Table, b: pa.Table, rel=1e-9):
    assert a.num_rows == b.num_rows
    key = [(c, "ascending") for c in a.column_names
           if not pa.types.is_floating(a.schema.field(c).type)]
    a, b = a.sort_by(key), b.sort_by(key)
    for name in a.schema.names:
        for x, y in zip(a.column(name).to_pylist(), b.column(name).to_pylist()):
            if isinstance(x, float) and x is not None and y is not None:
                assert y == pytest.approx(x, rel=rel), name
            else:
                assert x == y, name


def _run_join(build_keys: np.ndarray, probe_lo: int, probe_hi: int,
              n: int = 4000, seed: int = 0):
    rng = np.random.default_rng(seed)
    m = len(build_keys)
    dim = pa.table({
        "pk": pa.array(build_keys, pa.int64()),
        "dv": pa.array(rng.uniform(0.5, 1.5, m)),
        "dg": pa.array((np.arange(m) % 5).astype(np.int64)),
    })
    fact = pa.table({
        "fk": pa.array(rng.integers(probe_lo, probe_hi, n), pa.int64()),
        "g": pa.array(rng.integers(0, 5, n), pa.int64()),
        "v": pa.array(rng.uniform(0, 100, n)),
    })
    sql = ("select g, sum(v * dv) as s, count(*) as c "
           "from dim, fact where pk = fk group by g")
    out = []
    for tpu in (False, True):
        ctx = _ctx(tpu)
        ctx.register_table("dim", MemoryTable.from_table(dim, 1))
        ctx.register_table("fact", MemoryTable.from_table(fact, 1))
        df = ctx.sql(sql)
        plan = df.physical_plan()
        out.append((ctx.execute(plan), plan))
    (cpu, _), (tpu_t, plan) = out
    _assert_equal(cpu, tpu_t)
    return plan


def _join_fallbacks(plan) -> int:
    from arrow_ballista_tpu.ops.stage_compiler import TpuStageExec

    n = 0
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, TpuStageExec):
            n += node.metrics.values.get("join_fallback", 0)
            n += node.metrics.values.get("tpu_fallback", 0)
        stack.extend(node.children())
    return n


def test_dense_contiguous_keys():
    plan = _run_join(np.arange(1, 1001), 1, 1200)
    assert _join_fallbacks(plan) == 0


def test_dense_offset_keys():
    # kmin far from zero: probe offset arithmetic must not assume 0-base
    plan = _run_join(np.arange(5_000_000, 5_001_000), 4_999_000, 5_002_000)
    assert _join_fallbacks(plan) == 0


def test_dense_gappy_keys():
    # every 7th key only: table slots between keys must stay misses
    plan = _run_join(np.arange(1, 7000, 7), 1, 7100)
    assert _join_fallbacks(plan) == 0


def test_dense_negative_probe_range():
    # probes below kmin exercise the rel<0 bound check
    plan = _run_join(np.arange(100, 600), -500, 700)
    assert _join_fallbacks(plan) == 0


def test_probe_key_overflow_degrades_to_cpu_join_device_agg():
    # The gid table of a join-fused stage holds every distinct PROBE key
    # pre-filter (q3 SF10: 15M orderkeys vs the 2M ceiling, only 1.26M
    # surviving groups).  On _CapacityExceeded the stage must retry the
    # round-2 shape — join on CPU, aggregate on device over POST-join
    # rows — not fall to full CPU.
    rng = np.random.default_rng(7)
    n = 5000
    dim = pa.table({
        "pk": pa.array(np.arange(100), pa.int64()),
        "dv": pa.array(rng.uniform(0.5, 1.5, 100)),
    })
    fact = pa.table({
        # 5000 distinct probe keys, only 100 join; group by the probe key
        "fk": pa.array(rng.permutation(5000), pa.int64()),
        "v": pa.array(rng.uniform(0, 100, n)),
    })
    sql = ("select fk, sum(v * dv) as s from dim, fact where pk = fk "
           "group by fk")
    out = []
    for tpu in (False, True):
        ctx = _ctx(tpu, **{"ballista.tpu.max_capacity": 1024,
                           "ballista.tpu.segment_capacity": 64})
        ctx.register_table("dim", MemoryTable.from_table(dim, 1))
        ctx.register_table("fact", MemoryTable.from_table(fact, 1))
        df = ctx.sql(sql)
        plan = df.physical_plan()
        out.append((ctx.execute(plan), plan))
    (cpu, _), (tpu_t, plan) = out
    _assert_equal(cpu, tpu_t)
    from arrow_ballista_tpu.ops.stage_compiler import TpuStageExec

    m = {}
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, TpuStageExec):
            for k, v in node.metrics.values.items():
                m[k] = m.get(k, 0) + v
        stack.extend(node.children())
    assert m.get("join_fallback", 0) >= 1, m   # degraded to round-2 shape
    assert m.get("device_time_ns", 0) > 0, m   # the aggregate still ran on device


def test_wide_span_falls_back_to_sorted_probe():
    # span beyond the slot cap: sorted searchsorted probe, same results
    keys = np.arange(0, 1 << 28, 1 << 18)  # span 2^28 > cap, m = 1024
    plan = _run_join(keys, 0, 1 << 28)
    assert _join_fallbacks(plan) == 0
