"""How an aggregate stage runs: the routing constants and the one function
that reads them (``stage_compiler.choose_route``).

The constants are pinned to the values the device paths were measured
with.  The decision is a table: each row gives the facts of a stage and
of its first batch, the route they have to give, and the questions about
the first batch (each costs host work) that may be asked on the way.  No
row touches a device.
"""

import pytest

from arrow_ballista_tpu.ops import kernels as K
from arrow_ballista_tpu.ops import stage_compiler as SC
from arrow_ballista_tpu.ops.stage_compiler import FirstBatch, Route, choose_route


def test_builtin_defaults_are_the_pre_table_constants():
    """The seven values the routing table used to hand out (six live on;
    ``auto`` never routes keyed, which the decision table below pins)."""
    assert K._MATMUL_MAX_CAP == 8192
    assert K._MATMUL_MAX_ELEMS == 1 << 36
    assert SC._HIGHCARD_MIN_GROUPS == 1 << 16
    assert SC._HIGHCARD_RATIO == 0.05
    assert SC._FUSION_MAX_OPS == 8
    assert SC._FUSION_MIN_ROWS == 2048
    # the two matmul bounds stay part of every compiled-kernel cache key
    assert K.algo_cache_token()[2:] == (8192, 1 << 36)


MAX_CAP = 1 << 21  # ballista.tpu.max_capacity's default
STAGE = dict(
    highcard_mode="auto", device_encode=True, grouped=True,
    needs_keyed=False, folded_join=False, max_capacity=MAX_CAP,
)
# what a gang stage's probe passes: no join, host-encoded keys taken as
# fitting, the batch already in a gid table
GANG = dict(STAGE, device_encode=False)
# a first batch of 1M rows; "groups ~ rows" is 100,000 groups of it
FIRST = dict(rows=1_000_000, fast=False, fit=True, groups=100_000)
NO_BATCH = None

CASES = [
    # id, stage facts, first-batch facts, route, questions asked in order
    ("small-input", dict(small_input=True), NO_BATCH, Route.CPU_SMALL, []),
    ("ungrouped", dict(grouped=False), NO_BATCH, Route.GID, []),
    ("ungrouped-with-batch", dict(grouped=False), {}, Route.GID, []),
    ("grouped-before-its-first-batch", {}, NO_BATCH, None, []),
    ("fast-encoders-median", dict(needs_keyed=True), dict(fast=True),
     Route.KEYED, ["fast"]),
    ("fast-encoders-mode-device", dict(highcard_mode="device"),
     dict(fast=True, groups=6), Route.KEYED, ["fast"]),
    ("device-encode-off-never-asks", dict(highcard_mode="device", device_encode=False),
     dict(fast=True, groups=6), Route.GID, ["groups"]),
    ("needs-keyed-keys-fit", dict(needs_keyed=True), dict(groups=6),
     Route.KEYED, ["fast", "fit"]),
    ("needs-keyed-keys-do-not-fit", dict(needs_keyed=True), dict(fit=False),
     Route.CPU_HASH, ["fast", "fit"]),
    ("low-cardinality", {}, dict(groups=6), Route.GID, ["groups"]),
    ("at-the-group-bound", {}, dict(groups=1 << 16), Route.GID, ["groups"]),
    ("many-groups-under-the-ratio", {}, dict(rows=10_000_000, groups=400_000),
     Route.GID, ["groups"]),
    ("low-cardinality-mode-device-no-device-key", dict(highcard_mode="device"),
     dict(groups=6), Route.GID, ["fast", "groups"]),
    ("table-overflow-on-batch-one", {}, dict(groups=None), Route.CPU_HASH,
     ["groups"]),
    ("table-overflow-on-batch-one-join", dict(folded_join=True),
     dict(groups=None), Route.NOJOIN, ["groups"]),
    ("groups~rows-auto", {}, {}, Route.CPU_HASH, ["groups"]),
    ("groups~rows-cpu", dict(highcard_mode="cpu"), {}, Route.CPU_HASH, ["groups"]),
    ("groups~rows-device-keys-fit", dict(highcard_mode="device"), {},
     Route.KEYED, ["fast", "groups", "fit"]),
    ("groups~rows-device-keys-do-not-fit", dict(highcard_mode="device"),
     dict(fit=False), Route.CPU_HASH, ["fast", "groups", "fit"]),
    ("table-overflow-device-keys-fit", dict(highcard_mode="device"),
     dict(groups=None), Route.KEYED, ["fast", "groups", "fit"]),
    ("groups~rows-gid", dict(highcard_mode="gid"), {}, Route.GID, ["groups"]),
    ("table-overflow-gid", dict(highcard_mode="gid"), dict(groups=None),
     Route.CPU_HASH, ["groups"]),
    # cell 3's stage 5: a first batch of 1.52M rows with several hundred
    # thousand order keys, join folded, the table at half its ceiling or under
    ("q3-folded-join-under-half-the-ceiling", dict(folded_join=True),
     dict(rows=1_520_000, groups=700_000), Route.GID, ["groups"]),
    ("folded-join-at-half-the-ceiling", dict(folded_join=True),
     dict(rows=4_000_000, groups=MAX_CAP // 2), Route.GID, ["groups"]),
    ("folded-join-over-half-the-ceiling", dict(folded_join=True),
     dict(rows=4_000_000, groups=MAX_CAP // 2 + 1), Route.NOJOIN, ["groups"]),
    ("folded-join-device-keys-do-not-fit", dict(folded_join=True, highcard_mode="device"),
     dict(fit=False), Route.GID, ["fast", "groups", "fit"]),
    ("gang-low-cardinality", GANG, dict(groups=6), Route.GID, ["groups"]),
    ("gang-groups~rows-gid-stays", dict(GANG, highcard_mode="gid"), {},
     Route.GID, ["groups"]),
    ("gang-groups~rows-device-mesh-keyed", dict(GANG, highcard_mode="device"), {},
     Route.KEYED, ["groups", "fit"]),
    ("gang-groups~rows-auto-mesh-fallback", GANG, {}, Route.CPU_HASH, ["groups"]),
    # a folded join whose groups are its build rows (PR 34): decided before
    # the first batch and before the probe-key rule, whatever the batch says
    ("build-rows-before-its-first-batch", dict(folded_join=True, build_rows=True),
     NO_BATCH, Route.BUILD_ROWS, []),
    ("build-rows-probe-keys-past-the-ceiling", dict(folded_join=True, build_rows=True),
     dict(groups=None), Route.BUILD_ROWS, []),
    ("build-rows-mode-cpu", dict(folded_join=True, build_rows=True, highcard_mode="cpu"),
     {}, Route.BUILD_ROWS, []),
    ("build-rows-small-input", dict(folded_join=True, build_rows=True, small_input=True),
     NO_BATCH, Route.CPU_SMALL, []),
    ("build-rows-ungrouped", dict(folded_join=True, build_rows=True, grouped=False),
     NO_BATCH, Route.GID, []),
    ("build-rows-mode-gid-pinned", dict(folded_join=True, build_rows=True, highcard_mode="gid"),
     dict(groups=6), Route.GID, ["groups"]),
    ("build-rows-mode-device-pinned", dict(folded_join=True, build_rows=True, highcard_mode="device"),
     dict(fast=True), Route.KEYED, ["fast"]),
]


@pytest.mark.parametrize(
    "stage,first,route,asks", [c[1:] for c in CASES], ids=[c[0] for c in CASES]
)
def test_choose_route(stage, first, route, asks):
    asked = []

    def answer(name, value):
        def ask():
            asked.append(name)
            return value

        return ask

    if first is not None:
        f = dict(FIRST, **first)
        first = FirstBatch(
            f["rows"], answer("fast", f["fast"]), answer("fit", f["fit"]),
            answer("groups", f["groups"]),
        )
    assert choose_route(**dict(STAGE, **stage), first=first) is route
    assert asked == asks


def test_choose_route_reads_the_bounds_when_called(monkeypatch):
    """Tests route small fixtures by setting the two bounds on the module."""
    first = FirstBatch(1000, lambda: False, lambda: True, lambda: 100)
    assert choose_route(**STAGE, first=first) is Route.GID
    monkeypatch.setattr(SC, "_HIGHCARD_MIN_GROUPS", 16)
    assert choose_route(**STAGE, first=first) is Route.CPU_HASH
    monkeypatch.setattr(SC, "_HIGHCARD_RATIO", 0.5)
    assert choose_route(**STAGE, first=first) is Route.GID
