"""The reduction from a profiler trace to device metrics."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_reduce_on_hand_made_events():
    ev = [
        # device 0: two programs, the second's ops overlap; a collective
        (0, tr.MODULES, "jit_sharded_step(12)", 1000, 500),
        (0, tr.OPS, "fusion.1", 1000, 300),
        (0, tr.OPS, "all-reduce.2", 1300, 200),
        (0, tr.MODULES, "jit_concatenate(7)", 4000, 100),
        (0, tr.OPS, "concatenate.3", 4000, 100),
        (0, tr.OPS, "copy.4", 4050, 100),  # overlaps: the union counts 150, not 200
        # device 1: one program
        (1, tr.MODULES, "jit_sharded_step(12)", 1000, 500),
        (1, tr.OPS, "fusion.1", 1000, 500),
    ]
    red = tr.reduce(ev, chips=2)
    assert red["devices"] == [0, 1]
    assert red["busy_s"] == pytest.approx((300 + 200 + 150 + 500) / 1e9 / 2)
    assert red["device_ops"][0] == ["jit_sharded_step", pytest.approx(1000 / 1e9 / 2)]
    assert red["device_ops"][1] == ["jit_concatenate", pytest.approx(100 / 1e9 / 2)]
    assert red["collective_s"] == pytest.approx(200 / 1e9 / 2)
    assert red["programs_s"] == pytest.approx(1100 / 1e9 / 2)
    gaps = tr.idle_gaps(red, [("q1:stage_1", 0, 2000), ("q1:stage_2", 2000, 5000), ("q1:stage_1", 5000, 6000)], 2)
    assert gaps[0] == ["q1:stage_2", pytest.approx((3000 - 150 / 2) / 1e9)]
    assert gaps[1] == ["q1:stage_1", pytest.approx((2000 - (500 + 500) / 2 + 1000) / 1e9)]


def test_reduce_on_the_recorded_chip_trace():
    """Events cut from a traced run of tpch-sf1-1chip.scan-agg on a TPU v5
    lite (first cycle: one q1, one q6), with the totals the full trace gave."""
    path = os.path.join(DATA, "trace_events_1chip.json")
    rec = json.load(open(path))
    red = tr.reduce([tuple(e) for e in rec["events"]], chips=1)
    assert red["devices"] == [0]
    assert red["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert red["programs_s"] == pytest.approx(rec["expect"]["programs_s"], rel=1e-9)
    assert [n for n, _ in red["device_ops"]][:3] == rec["expect"]["top_programs"]
    assert 0 < red["busy_s"] <= red["programs_s"] * 1.001
    assert red["collective_s"] == 0.0  # one chip: nothing to exchange


def test_read_xplane_of_a_trace_made_here(tmp_path):
    """The .xplane.pb reader on a trace this test records on the CPU
    backend (rehearsal planes; never a device number)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    assert tr.read_xplane(path) == []  # no TPU plane here, and no stand-in unless asked
    ev = tr.read_xplane(path, rehearsal=True)
    assert ev and all(e[0] == 0 and e[1] == tr.OPS and e[4] > 0 for e in ev)
    assert tr.reduce(ev, 1)["busy_s"] > 0
    assert any(line.startswith("tf_XLA") or line == "python3" for _, line, _ in tr.describe(path))
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(str(tmp_path / "nothing"))
