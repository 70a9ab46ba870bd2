#!/bin/bash
# The contract's measurement of one cell: two sets of six runs, the same
# six seeds in both sets, at BENCHMARK.json's run_seconds, in one call.
#   bash benchmark/chip/sets.sh <cell> <first seed> <out dir> [runs per set]
W=${1:-tpch-sf1-1chip.scan-agg}; SEED=${2:-3100000000}; OUT=chiprun_out/${3:-sets}; N=${4:-6}
S=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
mkdir -p $OUT
for set in 1 2; do
  for i in $(seq 1 $N); do
    tag=set${set}_run$i
    python3 benchmark/run.py --workload $W --seed $((SEED + i)) --seconds $S --trace 0 > $OUT/$tag.out 2> $OUT/$tag.err
    echo "rc=$? $tag $(tail -n 1 $OUT/$tag.out)"
  done
done
python3 benchmark/chip/spread.py $OUT
