#!/bin/bash
# More seeds for a cell's `correct`: N short untraced runs, each on a seed of its own.
#   bash benchmark/chip/seeds.sh <cell> <seconds> <first seed> <runs> <out dir>
W=$1; S=$2; SEED=$3; N=$4; OUT=chiprun_out/$5
mkdir -p $OUT
for i in $(seq 1 $N); do
  python3 benchmark/run.py --workload $W --seed $((SEED + i)) --seconds $S --trace 0 > $OUT/seed$i.out 2> $OUT/seed$i.err
  echo "rc=$? seed$i $(tail -n 1 $OUT/seed$i.out)"
done
