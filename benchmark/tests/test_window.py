import datetime as dt
import time

import pytest

from benchmark import queries, window


def _fake_submit(latency):
    def submit(client, kind, params):
        time.sleep(latency[kind])
        return (kind, params)
    return submit


def test_window_counts_whole_queries_only_and_ends_at_last_completion():
    traffic = {"kinds": [1, 6], "clients": 1, "order": "cycle"}
    res = window.run_window(traffic, 3, 1.0, _fake_submit({1: 0.12, 6: 0.07}))
    recs = res["queries"]
    assert recs and all(r["error"] is None for r in recs)
    # no query is cut: every record has its whole latency, none ends past the window
    assert all(r["latency_s"] >= {1: 0.12, 6: 0.07}[r["kind"]] for r in recs)
    assert max(r["t_done"] for r in recs) <= 1.0 + 0.02
    # time to the last completion, not --seconds
    assert res["window_s"] == max(r["t_done"] for r in recs) < 1.0 + 0.02
    assert [r["kind"] for r in recs[:4]] == [1, 6, 1, 6]
    # the next one would not have fitted
    last = recs[-1]
    nxt = 6 if last["kind"] == 1 else 1
    assert last["t_done"] + {1: 0.12, 6: 0.07}[nxt] > 1.0 - 0.02


def test_rate_divides_by_time_to_last_completion_and_mean_moves_with_a_stall():
    recs = [
        {"kind": 1, "latency_s": 4.0, "error": None},
        {"kind": 6, "latency_s": 2.0, "error": None},
        {"kind": 1, "latency_s": 8.0, "error": None},  # one stalled q1
        {"kind": 6, "latency_s": 2.0, "error": None, "wrong_route": "mesh_fallback"},
        {"kind": 6, "latency_s": 9.0, "error": "boom"},
    ]
    m = window.end_to_end(recs, 20.0, {1: 6_000_000, 6: 6_000_000}, 4)
    assert m["query_geomean_s"] == pytest.approx((6.0 * 2.0) ** 0.5)
    assert m["scan_rows_rate"] == pytest.approx(18.0 / 20.0 / 4)


def test_failed_query_is_counted_not_fatal():
    def submit(c, kind, params):
        if kind == 6:
            raise RuntimeError("refused")
        time.sleep(0.05)
    res = window.run_window({"kinds": [1, 6], "clients": 1}, 1, 0.4, submit)
    assert any(r["error"] for r in res["queries"]) and any(r["error"] is None for r in res["queries"])


def test_after_first_cycle_hook_fires_once_between_cycles():
    seen = []
    res = window.run_window(
        {"kinds": [1, 6], "clients": 1}, 1, 0.6, _fake_submit({1: 0.05, 6: 0.05}),
        after_first_cycle=lambda: seen.append(time.monotonic()),
    )
    assert len(seen) == 1 and len(res["queries"]) > 2


def test_several_clients_shuffle_and_fixed_requests():
    traffic = {"kinds": [1, 6, 3], "clients": 3, "order": "shuffle", "parameter_sets": 2}
    res = window.run_window(traffic, 11, 0.5, _fake_submit({1: 0.03, 6: 0.02, 3: 0.04}))
    assert {r["client"] for r in res["queries"]} == {0, 1, 2}
    # a kind's queries cycle through its two parameter sets and no more
    for kind in (1, 6, 3):
        seen = {tuple(sorted(r["params"].items())) for r in res["queries"] if r["kind"] == kind}
        assert len(seen) == 2
    fixed = {"kinds": [1], "clients": 1, "requests": [
        {"kind": 1, "params": {"delta": 90}}, {"kind": 6, "params": {"year": 1994, "discount": 0.06, "quantity": 24}}]}
    res = window.run_window(fixed, 1, 0.2, _fake_submit({1: 0.03, 6: 0.02}))
    assert [r["kind"] for r in res["queries"][:3]] == [1, 6, 1]
    assert res["queries"][0]["params"] == {"delta": 90}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_parameter_draws_are_spec_valid_and_warmup_differs_from_window(seed):
    draws = queries.Draws(seed, [1, 6, 3], sets=3)
    for kind in (1, 6, 3):
        warm = draws.window_sets(kind)
        win = [draws.window(kind, i) for i in range(40)]
        # the window sends only literals that warm-up has run, and cycles them
        assert len(warm) == 3 and all(p in warm for p in win) and win[:6] == warm + warm
        assert draws.aside(kind) not in warm
        assert queries.Draws(seed, [kind]).window(kind, 5) == warm[0]  # one set by default
        for p in queries.parameter_sets(kind):
            if kind == 1:
                assert 60 <= p["delta"] <= 120
            elif kind == 6:
                assert 1993 <= p["year"] <= 1997 and p["quantity"] in (24, 25)
                assert 0.02 <= p["discount"] <= 0.09
                assert f"between {p['discount'] - 0.01:.2f} and {p['discount'] + 0.01:.2f}" in queries.render(6, p)
            else:
                assert p["segment"] in queries.SEGMENTS
                assert dt.date(1995, 3, 1) <= dt.date.fromisoformat(p["date"]) <= dt.date(1995, 3, 31)
    assert queries.Draws(seed, [1]).window(1, 0) == draws.window(1, 0)  # the same seed, the same inputs
    other = queries.Draws(seed + 1, [6], sets=8)
    assert [other.window(6, i) for i in range(8)] != [queries.Draws(seed, [6], sets=8).window(6, i) for i in range(8)]
