#!/bin/bash
# Everything the contract asks of one cell, in one call: the two sets, two
# more traced runs on seeds of their own, and the control's readings at the
# cell's own size on three seeds.
#   bash benchmark/chip/prove.sh <cell> <first seed> <out dir> [runs per set] [control: 1|0]
# (the control is plain numpy at the configuration's scale: a second cell of
# the same data takes the first's readings and passes 0)
W=$1; SEED=$2; OUT=$3; N=${4:-6}; CONTROL=${5:-1}
bash benchmark/chip/sets.sh $W $SEED $OUT $N
S=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
for i in 1 2; do
  python3 benchmark/run.py --workload $W --seed $((SEED + 100 + i)) --seconds $S --trace 1 --keep chiprun_out/$OUT/traced$i > chiprun_out/$OUT/traced$i.out 2> chiprun_out/$OUT/traced$i.err
  echo "rc=$? traced$i $(tail -n 1 chiprun_out/$OUT/traced$i.out)"
done
[ "$CONTROL" = 1 ] || exit 0
python3 benchmark/control.py --workload $W --seeds $((SEED + 201)) $((SEED + 202)) $((SEED + 203))
python3 benchmark/control.py --workload $W --seeds $((SEED + 201)) --precision float32
