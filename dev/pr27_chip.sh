#!/bin/bash
# PR 27's chip measurements (one call a cell; outputs under chiprun_out/pr27/).  Lives in dev/ because
# this PR may not add to benchmark/.
#   bash dev/pr27_chip.sh cell <cell> <seed> [n]   on n seeds (default 3): parent, change, change, parent ... untraced,
#                                                  then one traced run a side on a seed of its own; the phase tables
#   bash dev/pr27_chip.sh prove <cell> <seed>      run.py untraced and traced from _archive/final (git archive of the index)
# The parent (git archive of the parent commit) is expected under _archive/parent; benchmark/ is the same on both
# sides.  One compile cache for both checkouts.
# (a rehearsal here: PR27_EXTRA="--platform cpu --sf 0.01" PR27_SECONDS=5)
PHASE=$1; W=${2:-tpch-sf1-1chip.scan-agg}; SEED=${3:-2700000000}; N=${4:-3}
S=${PR27_SECONDS:-$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")}
ROOT=$(pwd); OUT=$ROOT/chiprun_out/pr27/$PHASE-$W; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$ROOT/.jax_cache}
run() { # dir tag seed trace
  (cd $1 && python3 benchmark/run.py --workload $W --seed $3 --seconds $S --trace $4 --keep $OUT/$2 $PR27_EXTRA > $OUT/$2.out 2> $OUT/$2.err)
  echo "rc=$? $2 seed=$3 $(tail -n 1 $OUT/$2.out | cut -c 1-1700)"
}
case $PHASE in
  cell)
    for i in $(seq 1 $N); do
      if [ $((i % 2)) = 1 ]; then order="parent change"; else order="change parent"; fi
      for side in $order; do
        if [ $side = parent ]; then run $ROOT/_archive/parent parent$i $((SEED + i)) 0; else run $ROOT change$i $((SEED + i)) 0; fi
      done
    done
    run $ROOT change_traced $((SEED + 10)) 1
    run $ROOT/_archive/parent parent_traced $((SEED + 10)) 1
    python3 benchmark/chip/phases.py $OUT/parent*/ $OUT/change*/
    python3 dev/pr27_read.py $OUT/parent*/ $OUT/change*/ ;;
  prove)
    for t in 0 1; do
      (cd $ROOT/_archive/final && python3 benchmark/run.py --workload $W --seed $((SEED + 30 + t)) --seconds $S --trace $t $PR27_EXTRA > $OUT/final_t$t.out 2> $OUT/final_t$t.err)
      echo "rc=$? final_t$t $(tail -n 1 $OUT/final_t$t.out | cut -c 1-2400)"
    done ;;
esac
