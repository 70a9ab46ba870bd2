"""Spreads of the runs ``sets.sh`` left in a directory, as the contract
measures them: per metric and set the distance between the first and third
quartile (``statistics.quantiles(values, n=4)``) as a share of the median;
the wider of the two sets; five times that as the bound to set."""

import glob
import json
import os
import statistics
import sys


def main(out_dir: str) -> None:
    sets: dict = {}
    for path in sorted(glob.glob(os.path.join(out_dir, "set*_run*.out"))):
        lines = open(path).read().strip().splitlines()
        if not lines:
            print("no result:", path)
            continue
        r = json.loads(lines[-1])
        tag = os.path.basename(path).split("_")[0]
        if not r["correct"] or r["failed"]:
            print("NOT CORRECT or failed:", path, r["compared"], r["failed"])
        for name, m in r["metrics"].items():
            sets.setdefault(name, {}).setdefault(tag, []).append(m["value"])
        sets.setdefault("rel_gap_max", {}).setdefault(tag, []).append(r["compared"]["rel_gap_max"][0])
    for name, by_set in sets.items():
        spreads = []
        for tag, v in sorted(by_set.items()):
            med = statistics.median(v)
            q = statistics.quantiles(v, n=4) if len(v) > 1 else [med, med, med]
            spreads.append((q[2] - q[0]) / med)
            shown = v[1:] if name == "setup_s" else v
            print(f"{name} {tag}: median {med:.6g} (without first run {statistics.median(shown):.6g}) "
                  f"spread {spreads[-1]:.4%} values {[float(f"{x:.6g}") for x in v]}")
        print(f"{name}: wider spread {max(spreads):.4%} -> bound of five times {5 * max(spreads):.4%}")


if __name__ == "__main__":
    main(sys.argv[1])
