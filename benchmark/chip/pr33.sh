#!/bin/bash
# PR 33's chip measurements (one phase a call; outputs under chiprun_out/pr33/<phase>/).  The parent is the parent commit
# whole, unpacked under _archive/parent (git archive HEAD): the program is the same on both sides, the harness is what
# changed.  Both sides keep their compiled programs in ONE cache (JAX_COMPILATION_CACHE_DIR below).
#   bash benchmark/chip/pr33.sh look <seed> [seconds]   the parent on the new cell's name (has to fail at once), then the
#                                                       change on it: two untraced runs and a traced one, kept
#   bash benchmark/chip/pr33.sh sets <seed>             the new cell as the contract measures it: two sets of six at
#                                                       run_seconds on the same seeds, then two traced runs, kept
#   bash benchmark/chip/pr33.sh guard <seed> <cell>...  cells 1-3 (cell 2 with --chips 4): parent and change traced on one
#                                                       seed, then change, parent untraced on the next
#   bash benchmark/chip/pr33.sh prove <seed>            _archive/final (git archive of the index): one traced run of the
#                                                       new cell from the committed files alone, then the control on three seeds
# python3 benchmark/chip/pr33_read.py chiprun_out/pr33/<phase> prints each run's numbers and, for kept runs, the kinds apart.
PHASE=$1; SEED=${2:-3300000000}
NEW=tpch-sf1-1chip.loadtest4
S=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
ROOT=$(pwd); OUT=$ROOT/chiprun_out/pr33/$PHASE; PARENT=_archive/parent; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$ROOT/.jax_cache}
run() { # dir tag cell seed trace [seconds] [keep]
  local keep=""; [ "${7:-keep}" = keep ] && keep="--keep $OUT/$2"
  local t0=$(date +%s)
  (cd $1 && python3 benchmark/run.py --workload $3 --seed $4 --seconds ${6:-$S} --trace $5 $keep > $OUT/$2.out 2> $OUT/$2.err)
  echo "rc=$? $2 $(( $(date +%s) - t0 ))s $(tail -n 1 $OUT/$2.out | cut -c 1-300)"
  grep -E "^\[bench.*(set-up done|FAILED|off the cell|carry no job id)" $OUT/$2.err | head -n 8
}
case $PHASE in
  look)
    T=${3:-25}
    run $PARENT parent_new_name $NEW $((SEED + 1)) 0 $T nokeep; tail -n 3 $OUT/parent_new_name.err
    run $ROOT look1 $NEW $((SEED + 1)) 0 $T
    run $ROOT look2 $NEW $((SEED + 2)) 0 $T
    run $ROOT look_traced $NEW $((SEED + 3)) 1 $T ;;
  sets)
    for set in 1 2; do for i in 1 2 3 4 5 6; do
      run $ROOT set${set}_run$i $NEW $((SEED + 10 + i)) 0 $S $([ $set = 1 ] && [ $i -le 2 ] && echo keep || echo nokeep)
    done; done
    run $ROOT traced1 $NEW $((SEED + 21)) 1
    run $ROOT traced2 $NEW $((SEED + 22)) 1
    python3 benchmark/chip/spread.py $OUT ;;
  guard)
    shift 2
    for W in "$@"; do
      run $PARENT ${W}_parent_traced $W $((SEED + 31)) 1
      run $ROOT ${W}_change_traced $W $((SEED + 31)) 1
      run $ROOT ${W}_change $W $((SEED + 32)) 0 $S nokeep
      run $PARENT ${W}_parent $W $((SEED + 32)) 0 $S nokeep
    done ;;
  prove)
    run _archive/final final_traced $NEW $((SEED + 41)) 1
    (cd _archive/final && python3 benchmark/control.py --workload $NEW --seeds $((SEED + 42)) $((SEED + 43)) $((SEED + 44))) | tee $OUT/control.out ;;
esac
python3 benchmark/chip/pr33_read.py $OUT
