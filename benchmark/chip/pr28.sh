#!/bin/bash
# PR 28's chip measurements (one phase a call; outputs under chiprun_out/pr28/<phase>/).
#   bash benchmark/chip/pr28.sh before <seed>   the parent (unpacked under _archive/parent, its OWN benchmark files): the new
#                                               cell's name (has to fail at once), then cells_later's tpch-sf1-1chip.join-agg
#                                               on two seeds with an empty compile cache, kept: what compiles on a second seed
#   bash benchmark/chip/pr28.sh after <seed>    the new cell from _archive/final (git archive of the index): the cold first
#                                               run on an empty compile cache, a second seed on the warm cache, one traced run
#   bash benchmark/chip/pr28.sh sets <seed>     sets.sh: two sets of six seeds untraced at run_seconds, then spread.py
#   bash benchmark/chip/pr28.sh all <seed>      after, sets and the one-chip guard, in one call
#   bash benchmark/chip/pr28.sh guard <seed> [cell]  a scan-agg cell must not move: traced parent, traced change, then
#                                               untraced change, parent, parent, change on one seed (the parent with this
#                                               PR's benchmark files laid over it, under _archive/parent_overlay)
#   bash benchmark/chip/pr28.sh guard4 <seed>  (--chips 4) the 2x2 cell: traced change, then parent and change untraced on one seed
# JAX_LOG_COMPILES=1 puts every program jax lowers into executor.log; compiles.py counts them.
PHASE=$1; SEED=${2:-2800000000}; NEW=tpch-q3-sf1-1chip.join-agg; OLD=tpch-sf1-1chip.join-agg
S=${PR28_SECONDS:-$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")}
ROOT=$(pwd); OUT=$ROOT/chiprun_out/pr28/$PHASE; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache_pr28_$PHASE JAX_LOG_COMPILES=1
run() { # dir tag cell seed trace
  (cd $1 && python3 benchmark/run.py --workload $3 --seed $4 --seconds $S --trace $5 --keep $OUT/$2 $PR28_EXTRA > $OUT/$2.out 2> $OUT/$2.err)
  echo "rc=$? $2 $(tail -n 1 $OUT/$2.out | cut -c 1-2600)"
  grep -E "^\[bench.*(warm-up|set-up done|FAILED)" $OUT/$2.err
}
case $PHASE in
  before)
    t0=$(date +%s)
    (cd _archive/parent && timeout 120 python3 benchmark/run.py --workload $NEW --seed $SEED --seconds $S --trace 0 > $OUT/newname.out 2> $OUT/newname.err)
    echo "rc=$? parent on $NEW after $(( $(date +%s) - t0 )) s: $(tail -n 1 $OUT/newname.err | cut -c 1-300)"
    run _archive/parent parent_seed1 $OLD $((SEED + 1)) 0
    run _archive/parent parent_seed2 $OLD $((SEED + 2)) 0
    python3 benchmark/chip/compiles.py $OUT/parent_seed1 $OUT/parent_seed2 ;;
  after)
    run _archive/final cold $NEW $((SEED + 11)) 0
    run _archive/final unseen $NEW $((SEED + 12)) 0
    run _archive/final traced $NEW $((SEED + 13)) 1
    python3 benchmark/chip/compiles.py $OUT/cold $OUT/unseen $OUT/traced
    du -sh $JAX_COMPILATION_CACHE_DIR; ls $JAX_COMPILATION_CACHE_DIR | wc -l ;;
  sets)
    unset JAX_LOG_COMPILES
    export JAX_COMPILATION_CACHE_DIR=$ROOT/.jax_cache_pr28_after
    bash benchmark/chip/sets.sh $NEW $((SEED + 20)) pr28/sets ;;
  all)  # while chips are scarce: after, sets and the one-chip guard in one call
    bash $0 after $SEED; bash $0 sets $SEED; bash $0 guard $SEED ;;
  guard)
    W=${3:-tpch-sf1-1chip.scan-agg}; unset JAX_LOG_COMPILES
    run _archive/parent_overlay parent_traced $W $((SEED + 41)) 1
    run $ROOT change_traced $W $((SEED + 41)) 1
    run $ROOT change1 $W $((SEED + 42)) 0
    run _archive/parent_overlay parent1 $W $((SEED + 42)) 0
    run _archive/parent_overlay parent2 $W $((SEED + 43)) 0
    run $ROOT change2 $W $((SEED + 43)) 0 ;;
  guard4)  # the four-chip cell at four times the cost: one traced run of the change, then parent and change untraced on one seed
    W=tpch-sf1-4chip-gang.scan-agg; unset JAX_LOG_COMPILES
    run $ROOT change_traced $W $((SEED + 51)) 1
    run _archive/parent_overlay parent1 $W $((SEED + 52)) 0
    run $ROOT change1 $W $((SEED + 52)) 0 ;;
esac
