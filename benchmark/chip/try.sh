#!/bin/bash
# A look at one cell on the chip: N untraced runs, then one traced run.
#   bash benchmark/chip/try.sh <cell> <seconds> <first seed> <untraced runs> <out dir>
W=${1:-tpch-sf1-1chip.scan-agg}; S=${2:-25}; SEED=${3:-3000000001}; N=${4:-2}; OUT=chiprun_out/${5:-try}
mkdir -p $OUT
env | grep -E "^JAX|^XLA|^TPU_" | sort
run() { # seed trace tag
  python3 benchmark/run.py --workload $W --seed $1 --seconds $S --trace $2 --keep $OUT/$3 > $OUT/$3.out 2> $OUT/$3.err
  echo "rc=$? $3"; tail -n 1 $OUT/$3.out; grep -E "^\[bench|^compared|^correct" $OUT/$3.err | tail -n 12
}
for i in $(seq 1 $N); do run $((SEED + i)) 0 run$i; done
run $((SEED + N + 1)) 1 traced
du -sh .jax_cache 2>/dev/null; ls .jax_cache 2>/dev/null | wc -l
