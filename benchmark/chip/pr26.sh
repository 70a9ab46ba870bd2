#!/bin/bash
# PR 26's chip measurements (one phase a call; outputs under chiprun_out/pr26/).
#   bash benchmark/chip/pr26.sh look <cell> <seed>      traced runs: the change with obs on (clock check), the
#                                                       change as the driver runs it, the parent under this PR's benchmark files
#   bash benchmark/chip/pr26.sh all <cell> <seed> <n>   look + (parent, obs off, obs on) on n seeds + 8 obs-on runs on one seed
#   bash benchmark/chip/pr26.sh guard <cell> <seed>     one traced run, then parent, change, change+obs x2, change, parent on one seed
#   bash benchmark/chip/pr26.sh malloc <cell> <seed>    three obs-on runs on one seed, the first and third with glibc told to keep its memory
#   bash benchmark/chip/pr26.sh prove <cell> <seed>     run.py untraced and traced from _archive/final (git archive of the index)
# The parent is expected unpacked under _archive/parent with BENCHMARK.json and benchmark/ of this PR laid over it.
# One compile cache for both checkouts, so that a second checkout does not compile what the first did.
PHASE=$1; W=${2:-tpch-sf1-1chip.scan-agg}; SEED=${3:-2600000000}; N=${4:-3}
# (a rehearsal here: PR26_EXTRA="--platform cpu --sf 0.01" PR26_SECONDS=5)
S=${PR26_SECONDS:-$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")}
ROOT=$(pwd); OUT=$ROOT/chiprun_out/pr26/$PHASE-$W; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$ROOT/.jax_cache}
run() { # dir tag obs seed trace
  (cd $1 && python3 benchmark/chip/obs_run.py --obs $3 --workload $W --seed $4 --seconds $S --trace $5 --keep $OUT/$2 $PR26_EXTRA > $OUT/$2.out 2> $OUT/$2.err)
  echo "rc=$? $2 $(tail -n 1 $OUT/$2.out | cut -c 1-1500)"
}
case $PHASE in
  look)
    run $ROOT traced_obs 1 $((SEED + 1)) 1
    run $ROOT traced 0 $((SEED + 2)) 1
    run $ROOT/_archive/parent parent_traced 0 $((SEED + 2)) 1
    python3 benchmark/chip/phases.py --clock $OUT/traced_obs
    python3 benchmark/chip/phases.py $OUT/traced_obs $OUT/traced $OUT/parent_traced ;;
  all)  # one call while chips are scarce: look, then parent / obs off / obs on on N seeds, then 8 runs on one seed
    bash $0 look $W $SEED
    for i in $(seq 1 $N); do
      run $ROOT/_archive/parent parent$i 0 $((SEED + 10 + i)) 0
      run $ROOT off$i 0 $((SEED + 10 + i)) 0
      run $ROOT on$i 1 $((SEED + 10 + i)) 0
    done
    for i in $(seq 1 8); do run $ROOT mode$i 1 $((SEED + 20)) 0; done
    python3 benchmark/chip/phases.py $OUT/parent*/ $OUT/off*/ $OUT/on*/ $OUT/mode*/ ;;
  guard)  # the four-chip cell at four times the cost: one traced run as the driver makes it, then on ONE seed
          # parent, change, change with obs on twice (the partitions' cores), change, parent
    run $ROOT traced 0 $((SEED + 1)) 1
    run $ROOT/_archive/parent parent1 0 $((SEED + 2)) 0
    run $ROOT off1 0 $((SEED + 2)) 0
    run $ROOT on1 1 $((SEED + 2)) 0
    run $ROOT on2 1 $((SEED + 2)) 0
    run $ROOT off2 0 $((SEED + 2)) 0
    run $ROOT/_archive/parent parent2 0 $((SEED + 2)) 0
    python3 benchmark/chip/phases.py $OUT/traced/ $OUT/parent*/ $OUT/off*/ $OUT/on*/ ;;
  malloc)  # an experiment on the two modes, environment of this call only: does glibc giving memory back to the
           # kernel (trim, mmap) between batches make the slow mode?  with, without, with, on one seed
    for i in 1 2 3; do
      if [ $i = 2 ]; then run $ROOT plain$i 1 $SEED 0
      else MALLOC_TRIM_THRESHOLD_=1073741824 MALLOC_TOP_PAD_=268435456 MALLOC_MMAP_THRESHOLD_=33554432 run $ROOT kept$i 1 $SEED 0; fi
    done
    python3 benchmark/chip/phases.py $OUT/kept*/ $OUT/plain*/ ;;
  prove)  # the committed files are enough: the driver's own command, untraced and traced, from _archive/final
          # (a `git archive $(git write-tree)` unpacked there: no git repository, nothing uncommitted)
    for t in 0 1; do
      (cd $ROOT/_archive/final && python3 benchmark/run.py --workload $W --seed $((SEED + 30 + t)) --seconds $S --trace $t $PR26_EXTRA > $OUT/final_t$t.out 2> $OUT/final_t$t.err)
      echo "rc=$? final_t$t $(tail -n 1 $OUT/final_t$t.out | cut -c 1-1800)"
    done ;;
esac
