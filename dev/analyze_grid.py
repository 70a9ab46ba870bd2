"""Turn KERNELBENCH grid rows into routing-threshold recommendations.

The r04/r05 verdict discipline: routing constants must cite a measured
artifact, not a guess.  This reads one or more KERNELBENCH_*.json files
and prints, per platform found in the rows:

* the matmul->sort capacity crossover per row count (tunes
  routing ``matmul_max_cap`` / ``matmul_max_elems``);
* the scatter/sort/keyed winner per (rows, capacity) cell (tunes
  segment_algo and the highcard route);
* sort cost vs operand count + the packed-u64 ratio (validates the
  packed-sort rework);
* dispatch/fetch latency floors (the q6 economics).

``--emit <path>`` additionally writes the recommendations as the
machine-readable routing table ``arrow_ballista_tpu/ops/routing.py``
loads at import (schema ``ballista.routing/v1``; the emit schema is
pinned by tests/test_routing_table.py).  Fields the grid has no
evidence for keep the builtin defaults, with the per-field basis
recorded under ``evidence`` so the artifact documents exactly what was
measured vs inherited.

Usage: python dev/analyze_grid.py KERNELBENCH_r05.json [more.json ...]
           [--emit arrow_ballista_tpu/ops/routing_table.json]
"""

from __future__ import annotations

import json
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(paths):
    rows = []
    for p in paths:
        with open(p) as f:
            for line in f:
                line = line.strip()
                if line:
                    rows.append(json.loads(line))
    return rows


def emit_routing_table(rows, inputs) -> dict:
    """Routing-table document (ballista.routing/v1) from grid rows.

    Per platform present in the rows:

    * ``matmul_max_cap`` / ``matmul_max_elems`` — largest capacity where
      the matmul segment reduction beat BOTH sort and scatter at EVERY
      measured row count (a capacity crossover must hold across row
      counts — one row-count outlier, e.g. BLAS threading kicking in at
      8M rows on the cpu box, must not move a threshold applied to every
      batch size); default when no capacity wins consistently.
    * ``keyed_route_auto`` — True only when the keyed reduction (the
      fused ``keyed_fused`` cell when the grid has it, else the
      pre-fusion ``keyed`` cell) beats every alternative at the
      high-cardinality cells (capacity >= highcard_min_groups); this is
      what lets ``auto`` route groups~rows plans to the fused keyed
      path on platforms where the measurement supports it.
    * detector bounds (``highcard_min_groups`` / ``highcard_ratio``)
      keep the builtin defaults — no grid bench measures the detector
      itself yet.
    * whole-stage fusion bounds (ISSUE 19): ``fusion_min_rows`` — the
      amortization floor below which one fused dispatch costs more than
      it saves — is judged from the keyed_fused-vs-keyed pairs (the
      one-dispatch vs 3-dispatch form of the SAME reduction, the grid's
      direct measurement of dispatch-fusion payoff): the floor becomes
      the smallest measured row count from which the fused form wins at
      every larger measured row count, and stays at the builtin when
      the fused form already wins at the grid's smallest cell (the grid
      cannot see below its own floor).  ``fusion_max_ops`` keeps the
      builtin default — it is the _FUSED_MAX_ENTRIES unroll discipline
      applied to operator count, and no grid cell measures op-count
      scaling yet.
    """
    from arrow_ballista_tpu.ops import routing

    by_platform = defaultdict(list)
    for r in rows:
        by_platform[r.get("device_platform", "?")].append(r)

    platforms = {}
    for platform, rs in sorted(by_platform.items()):
        vals = dict(routing._DEFAULTS)
        evidence = {
            k: "builtin default (no grid evidence)" for k in vals
        }
        cells = defaultdict(dict)
        for r in rs:
            if r.get("bench") == "segment_reduce" and "rows_per_sec" in r:
                cells[(r["rows"], r["capacity"])][r["algo"]] = r[
                    "rows_per_sec"
                ]
        # per-capacity verdict: matmul must win at EVERY measured row
        # count for that capacity to count toward the crossover — the
        # threshold steers every batch size, so one row-count outlier
        # cannot set it
        mm_by_cap: dict = {}
        for (n, cap), algos in sorted(cells.items()):
            others = [v for a, v in algos.items() if a != "matmul"]
            if "matmul" not in algos or not others:
                continue
            won = algos["matmul"] > max(others)
            all_won, elems = mm_by_cap.get(cap, (True, 0))
            mm_by_cap[cap] = (all_won and won, max(elems, n * cap))
        mm_caps = [c for c, (won, _e) in mm_by_cap.items() if won]
        if mm_caps:
            vals["matmul_max_cap"] = max(mm_caps)
            vals["matmul_max_elems"] = max(
                mm_by_cap[c][1] for c in mm_caps
            )
            evidence["matmul_max_cap"] = evidence["matmul_max_elems"] = (
                "largest capacity where matmul beat sort+scatter at "
                "every measured row count"
            )
        else:
            evidence["matmul_max_cap"] = evidence["matmul_max_elems"] = (
                "builtin default: matmul won no measured capacity "
                "consistently across row counts on this platform"
            )
        highcard = [
            (k, algos)
            for k, algos in cells.items()
            if k[1] >= vals["highcard_min_groups"] and len(algos) > 1
        ]
        if highcard:

            def keyed_best(algos: dict) -> bool:
                # the fused cell is the production shape; the pre-fusion
                # 'keyed' cell stands in on grids captured before it
                kv = algos.get("keyed_fused", algos.get("keyed"))
                return kv is not None and kv == max(algos.values())

            keyed_wins = all(keyed_best(algos) for _k, algos in highcard)
            vals["keyed_route_auto"] = bool(keyed_wins)
            evidence["keyed_route_auto"] = (
                "keyed(_fused) %s every alternative at the %d "
                "high-cardinality segment_reduce cell(s)"
                % ("beat" if keyed_wins else "lost to", len(highcard))
            )
        # whole-stage fusion amortization floor: keyed_fused vs keyed is
        # the grid's one-dispatch vs 3-dispatch pair for the same
        # reduction — where the fused form wins, a fused dispatch pays
        # for itself at that input size
        fused_won: dict = {}
        for (n, _cap), algos in cells.items():
            if "keyed_fused" in algos and "keyed" in algos:
                ok = algos["keyed_fused"] >= algos["keyed"]
                fused_won[n] = fused_won.get(n, True) and ok
        evidence["fusion_max_ops"] = (
            "builtin default: the _FUSED_MAX_ENTRIES unroll discipline "
            "applied to operator count (no grid cell measures op-count "
            "scaling)"
        )
        if fused_won:
            sizes = sorted(fused_won)
            # smallest size from which the fused form wins at every
            # larger measured size
            floor = None
            for i, n in enumerate(sizes):
                if all(fused_won[m] for m in sizes[i:]):
                    floor = n
                    break
            if floor is None:
                won = [n for n in sizes if fused_won[n]]
                lost = [n for n in sizes if not fused_won[n]]
                if won:
                    evidence["fusion_min_rows"] = (
                        "builtin default kept: no stable amortization "
                        "floor — keyed_fused beat the 3-dispatch keyed "
                        "form at %s rows but lost at %s rows, so the "
                        "win does not hold through the largest "
                        "measured size"
                        % (
                            ", ".join(str(n) for n in won),
                            ", ".join(str(n) for n in lost),
                        )
                    )
                else:
                    evidence["fusion_min_rows"] = (
                        "builtin default: keyed_fused never beat the "
                        "3-dispatch keyed form at any measured row "
                        "count (%s rows)"
                        % ", ".join(str(n) for n in sizes)
                    )
            elif floor == sizes[0]:
                evidence["fusion_min_rows"] = (
                    "builtin default kept: keyed_fused beat the "
                    "3-dispatch keyed form at every measured row count "
                    "(smallest cell %d rows; the grid cannot see below "
                    "its own floor)" % floor
                )
            else:
                vals["fusion_min_rows"] = int(floor)
                evidence["fusion_min_rows"] = (
                    "smallest measured row count from which keyed_fused "
                    "beat the 3-dispatch keyed form at every larger "
                    "size (lost below %d rows)" % floor
                )
        platforms[platform] = {**vals, "evidence": evidence}

    return {
        "schema": routing.SCHEMA,
        "generated_by": "dev/analyze_grid.py --emit",
        "inputs": [os.path.basename(p) for p in inputs],
        "platforms": platforms,
    }


def main() -> None:
    args = sys.argv[1:]
    emit_path = None
    if "--emit" in args:
        i = args.index("--emit")
        emit_path = args[i + 1]
        args = args[:i] + args[i + 2:]
    paths = args or ["KERNELBENCH_r05.json"]
    rows = load(paths)
    if emit_path:
        doc = emit_routing_table(rows, paths)
        with open(emit_path, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote routing table -> {emit_path}")
    by_platform = defaultdict(list)
    for r in rows:
        by_platform[r.get("device_platform", "?")].append(r)

    for platform, rs in by_platform.items():
        print(f"\n=== platform: {platform} "
              f"({'FALLBACK — not chip data' if any('error' in r for r in rs) else 'clean'}) ===")

        cells = defaultdict(dict)  # (rows, cap) -> algo -> rows/s
        for r in rs:
            if r.get("bench") == "segment_reduce" and "rows_per_sec" in r:
                cells[(r["rows"], r["capacity"])][r["algo"]] = r["rows_per_sec"]

        if cells:
            print("segment_reduce winner per (rows, capacity):")
            crossover = {}
            for (n, cap), algos in sorted(cells.items()):
                win = max(algos, key=algos.get)
                line = "  ".join(
                    f"{a}={v / 1e6:.1f}M" for a, v in sorted(algos.items())
                )
                print(f"  rows={n:>9} cap={cap:>8}: winner={win:<8} {line}")
                if "matmul" in algos and "sort" in algos:
                    better = algos["matmul"] > algos["sort"]
                    cur = crossover.get(n)
                    if better and (cur is None or cap > cur):
                        crossover[n] = cap
            for n, cap in sorted(crossover.items()):
                print(f"  -> matmul still wins at cap={cap} for rows={n}: "
                      f"set _MATMUL_MAX_CAP >= {cap} "
                      f"(_MATMUL_MAX_ELEMS >= {n * cap:.0e})")

        sorts = [r for r in rs if r.get("bench") == "sort_operands"
                 and "rows_per_sec" in r]
        if sorts:
            print("sort cost vs operands:")
            base = {}
            for r in sorted(sorts, key=lambda r: (r["rows"], r["operands"])):
                key = (r["rows"], "u64x1")
                if r["operands"] == "u64x1":
                    base[r["rows"]] = r["rows_per_sec"]
            for r in sorted(sorts, key=lambda r: (r["rows"], r["operands"])):
                rel = (
                    f"  ({base[r['rows']] / r['rows_per_sec']:.1f}x slower "
                    f"than u64x1)" if r["operands"] != "u64x1"
                    and r["rows"] in base else ""
                )
                print(f"  rows={r['rows']:>9} {r['operands']:>6}: "
                      f"{r['rows_per_sec'] / 1e6:6.1f}M rows/s{rel}")

        lat = [r for r in rs if r.get("bench") == "dispatch_latency"
               and "sec" in r]
        for r in lat:
            print(f"latency {r['metric']}: {r['sec'] * 1000:.2f} ms")
        if lat:
            one = next((r["sec"] for r in lat
                        if r["metric"] == "dispatch_plus_fetch"), None)
            if one:
                print(f"  -> per-query floor ~{one * 1000:.0f} ms: a query "
                      f"must beat the CPU by more than this to win; the "
                      f"fused runner exists to pay it exactly once")

        enc = [r for r in rs if r.get("bench") == "host_encode"
               and "rows_per_sec" in r]
        if enc:
            print("host encode:")
            for r in sorted(enc, key=lambda r: (r["rows"], r["algo"])):
                print(f"  rows={r['rows']:>9} {r['algo']:>12}: "
                      f"{r['rows_per_sec'] / 1e6:6.1f}M rows/s")


if __name__ == "__main__":
    main()
