#!/usr/bin/env bash
# Tier-1 verify: the exact command the ROADMAP pins (CPU-pinned jax, slow
# tests excluded, collection errors tolerated so one broken module can't
# hide the rest).  Prints DOTS_PASSED= the count of passing tests and
# exits with pytest's status.
#
# Usage: dev/tier1.sh [--bench-smoke] [--chaos-smoke] [extra pytest args...]
#   --bench-smoke  additionally run the shuffle write/fetch micro-benches
#                  on tiny inputs after the tests — a compile/regression
#                  smoke for the benchmark harnesses themselves, NOT a
#                  measurement and NOT part of default tier-1.
#   --chaos-smoke  additionally run the bounded chaos soaks (pytest
#                  -m chaos): executors are drained/killed at random
#                  during small queries, and the scheduler itself is
#                  SIGKILLed mid-burst and restarted (admission-WAL
#                  replay + orphan-fleet adoption) — everything must
#                  still complete with correct results.  Seeded via
#                  BALLISTA_CHAOS_SEED.
set -o pipefail
cd "$(dirname "$0")/.."
BENCH_SMOKE=0
CHAOS_SMOKE=0
while :; do
  case "$1" in
    --bench-smoke) BENCH_SMOKE=1; shift ;;
    --chaos-smoke) CHAOS_SMOKE=1; shift ;;
    *) break ;;
  esac
done
# proto drift gate: a NEW_FIELDS edit without regeneration (or a
# generated field missing from ballista.proto) fails fast, before tests
timeout -k 10 60 env JAX_PLATFORMS=cpu python dev/regen_proto.py --check || exit 1
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
  "$@" 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
if [ "$BENCH_SMOKE" = "1" ]; then
  echo "--- bench smoke (tiny inputs; compile check, not a measurement) ---"
  timeout -k 10 120 env JAX_PLATFORMS=cpu python - <<'EOF'
import json
from benchmarks.shuffle_fetch import run_fetch_bench
from benchmarks.shuffle_write import run_write_bench

print(json.dumps({"bench_smoke": "shuffle_fetch",
                  **run_fetch_bench(n_locations=4, mb_per_location=0.5,
                                    batch_rows=4096, concurrency=2)}))
print(json.dumps({"bench_smoke": "shuffle_write",
                  **run_write_bench(n_batches=4, rows_per_batch=8192,
                                    n_out=4, compression="zstd", iters=1)}))
EOF
  smoke_rc=$?
  [ $rc -eq 0 ] && rc=$smoke_rc
  timeout -k 10 120 env JAX_PLATFORMS=cpu python - <<'EOF'
import json
from benchmarks.shuffle_locality import run_locality_smoke

# locality A/B on tiny inputs: all three transports bit-identical, the
# local leg zero-copy, the batched leg fewer round trips
print(json.dumps({"bench_smoke": "shuffle_locality",
                  **run_locality_smoke()}))
EOF
  smoke_rc=$?
  [ $rc -eq 0 ] && rc=$smoke_rc
  timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import json
from benchmarks.aqe_starjoin import run_aqe_smoke

# AQE A/B on tiny inputs: asserts bit-identical results static-vs-
# adaptive and that the tiny-partition aggregate actually coalesced
print(json.dumps({"bench_smoke": "aqe", **run_aqe_smoke()}))
EOF
  smoke_rc=$?
  [ $rc -eq 0 ] && rc=$smoke_rc
  timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import json
from benchmarks.keyed_path import run_keyed_smoke

# keyed device-path A/B on tiny inputs: all legs bit-identical, the
# fused leg device-encodes with zero host group encode
print(json.dumps({"bench_smoke": "keyed_path", **run_keyed_smoke()}))
EOF
  smoke_rc=$?
  [ $rc -eq 0 ] && rc=$smoke_rc
  timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import json
from benchmarks.concurrent_clients import run_admission_smoke

# admission smoke: saturate 2 slots with 6 jobs from two weighted pools
# over the real wire — fair-share release order, zero failures, and
# job_queued/job_admitted journal events asserted inside
print(json.dumps({"bench_smoke": "admission", **run_admission_smoke()}))
EOF
  smoke_rc=$?
  [ $rc -eq 0 ] && rc=$smoke_rc
  timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import json
from benchmarks.pipelined_stage import run_pipelining_smoke

# pipelined-execution smoke: tiny 2-executor job with one manufactured
# slow map task — the pipelined leg's first reduce dispatch must precede
# the last map commit and results must be bit-identical to the barrier
# leg (asserted inside)
print(json.dumps({"bench_smoke": "pipelined", **run_pipelining_smoke()}))
EOF
  smoke_rc=$?
  [ $rc -eq 0 ] && rc=$smoke_rc
  timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import json
from benchmarks.obs_doctor import run_doctor_smoke

# query-doctor smoke: tiny standalone job with a manufactured straggler
# — the critical_path endpoint's category sum must land within
# tolerance of wall-clock and the doctor must fire skewed_stage with
# evidence naming the real stage/partition (asserted inside)
print(json.dumps({"bench_smoke": "doctor", **run_doctor_smoke()}))
EOF
  smoke_rc=$?
  [ $rc -eq 0 ] && rc=$smoke_rc
  timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import json
from benchmarks.elastic_burst import run_autoscaler_smoke

# autoscaler smoke: tiny burst against a 1-executor elastic cluster —
# one scale-out, one drain-based scale-in after the idle cooldown, zero
# failed tasks, autoscale_decision/executor_launched/executor_retired
# journal events present (asserted inside)
print(json.dumps({"bench_smoke": "autoscaler", **run_autoscaler_smoke()}))
EOF
  smoke_rc=$?
  [ $rc -eq 0 ] && rc=$smoke_rc
  timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import json
from benchmarks.plan_cache import run_plan_cache_smoke

# plan-cache smoke: repeat submission of an identical query must serve
# from the fingerprint cache with zero dispatched tasks and identical
# rows; re-registering different data must invalidate; the knob-off leg
# must never touch the cache (asserted inside)
print(json.dumps({"bench_smoke": "plan_cache", **run_plan_cache_smoke()}))
EOF
  smoke_rc=$?
  [ $rc -eq 0 ] && rc=$smoke_rc
  timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import json
from benchmarks.whole_stage_fusion import run_fusion_smoke

# whole-stage fusion smoke: tiny q3-shaped + scan-heavy stages — the
# fused leg must plan ONE segment covering >1 operator and execute it
# as ONE dispatch per task (zero host round-trips between fused ops),
# bit-identical to the knob-off per-batch leg (asserted inside)
print(json.dumps({"bench_smoke": "whole_stage_fusion",
                  **run_fusion_smoke()}))
EOF
  smoke_rc=$?
  [ $rc -eq 0 ] && rc=$smoke_rc
fi
if [ "$CHAOS_SMOKE" = "1" ]; then
  echo "--- chaos smoke (bounded kill/drain + scheduler-kill soaks) ---"
  timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m chaos \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly
  chaos_rc=$?
  [ $rc -eq 0 ] && rc=$chaos_rc
fi
exit $rc
