"""Readings of the control, at a cell's own size.

    python3 benchmark/control.py --workload tpch-sf1-1chip.scan-agg --seeds 11 12 13

The control is the plain reference computed one precision below the
program's stated float32 (``reference.answer(..., precision="bfloat16")``)
and put in the program's place: for each seed it generates the cell's tables, draws as many
parameter sets of each kind as a window completes, and prints the numbers
the comparison would read for them.  The smallest ``rel_gap_max`` over the
seeds is the UPPER reading of that number's limit (``compare.LIMITS``); it
has to come out as not correct.  No chip is needed: it is plain numpy, run
on the chip's machine so that the reading is taken where the cell runs.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import compare, datagen, harness, queries, reference  # noqa: E402


def reading(workload: str, seed: int, per_kind: int, sf=None, precision="bfloat16") -> dict:
    resolved = harness.resolve(workload, harness.benchmark_json())
    config, kinds = resolved["config"], list(dict.fromkeys(resolved["traffic"]["kinds"]))
    tables = [t for t in config["tables"] if any(t in queries.TABLES_OF[k] for k in kinds)]
    work = tempfile.mkdtemp(prefix="abt_control_")
    try:
        datagen.generate(work, tables, sf or float(config["scale_factor"]), seed,
                         int(config["files_per_table"]))
        data = reference.Data(work)
        draws = queries.Draws(seed, kinds, per_kind)
        pairs = [
            (reference.answer(data, k, draws.window(k, i), precision),
             reference.answer(data, k, draws.window(k, i)))
            for k in kinds for i in range(per_kind)
        ]
        return compare.judge(pairs)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--per-kind", type=int, default=1, help="parameter sets of each kind, as a window sends")
    ap.add_argument("--sf", type=float, default=None)
    ap.add_argument("--precision", default="bfloat16", choices=("bfloat16", "float32"))
    args = ap.parse_args()
    gaps = []
    for seed in args.seeds:
        v = reading(args.workload, seed, args.per_kind, args.sf, args.precision)
        gaps.append(v["numbers"]["rel_gap_max"]["value"])
        print(json.dumps({"seed": seed, "precision": args.precision, "control_correct": v["correct"], "compared": v["compared"],
                          **{k: n["value"] for k, n in v["numbers"].items()}}), flush=True)
    print(json.dumps({"control_rel_gap_max_smallest": min(gaps), "limit": compare.LIMITS["rel_gap_max"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
