#!/bin/bash
# PR 29's chip measurements (one phase a call; outputs under chiprun_out/pr29/<phase>/).  The parent is unpacked under
# _archive/parent_overlay (git archive of the parent commit with this PR's BENCHMARK.json and benchmark/ laid over it, as the
# driver does for traced runs and new metrics); the change is the tree itself, or _archive/final (git archive of the index).
#   bash benchmark/chip/pr29.sh cell1 <seed>           tpch-sf1-1chip.scan-agg: traced parent, traced change, then untraced
#                                                      change, parent, parent, change (the two sides of a pair share a seed)
#   bash benchmark/chip/pr29.sh cell2 <seed>           (--chips 4) the 2x2 cell: traced change, then parent and change untraced
#   bash benchmark/chip/pr29.sh cell3 <seed>           the q3 guard: parent, change, change, parent untraced on two seeds
#   bash benchmark/chip/pr29.sh prove <seed> [cell]    _archive/final: one traced run (the committed files are enough)
#   bash benchmark/chip/pr29.sh more <seed> [cell] [n] n more untraced runs of the change, a seed each (spread)
# python3 benchmark/chip/pr29_read.py chiprun_out/pr29/<phase> prints each run's end-to-end and gang-stage numbers.
PHASE=$1; SEED=${2:-2900000000}
S=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
ROOT=$(pwd); OUT=$ROOT/chiprun_out/pr29/$PHASE; PARENT=_archive/parent_overlay; mkdir -p $OUT
run() { # dir tag cell seed trace
  (cd $1 && python3 benchmark/run.py --workload $3 --seed $4 --seconds $S --trace $5 --keep $OUT/$2 > $OUT/$2.out 2> $OUT/$2.err)
  echo "rc=$? $2 $(tail -n 1 $OUT/$2.out | cut -c 1-400)"
  grep -E "^\[bench.*(set-up done|FAILED)" $OUT/$2.err
}
case $PHASE in
  cell1)
    W=tpch-sf1-1chip.scan-agg
    run $PARENT parent_traced $W $((SEED + 1)) 1
    run $ROOT change_traced $W $((SEED + 1)) 1
    run $ROOT change1 $W $((SEED + 2)) 0
    run $PARENT parent1 $W $((SEED + 2)) 0
    run $PARENT parent2 $W $((SEED + 3)) 0
    run $ROOT change2 $W $((SEED + 3)) 0 ;;
  cell2)
    W=tpch-sf1-4chip-gang.scan-agg
    run $ROOT change_traced $W $((SEED + 11)) 1
    run $PARENT parent1 $W $((SEED + 12)) 0
    run $ROOT change1 $W $((SEED + 12)) 0 ;;
  cell3)
    W=tpch-q3-sf1-1chip.join-agg
    run $PARENT parent1 $W $((SEED + 21)) 0
    run $ROOT change1 $W $((SEED + 21)) 0
    run $ROOT change2 $W $((SEED + 22)) 0
    run $PARENT parent2 $W $((SEED + 22)) 0 ;;
  prove)
    run _archive/final final_traced ${3:-tpch-sf1-1chip.scan-agg} $((SEED + 31)) 1 ;;
  more)
    for i in $(seq 1 ${4:-4}); do run $ROOT more$i ${3:-tpch-sf1-1chip.scan-agg} $((SEED + 40 + i)) 0; done ;;
esac
python3 benchmark/chip/pr29_read.py $OUT
