#!/bin/bash
# Two sets of runs of one cell (the same seeds in both), then one traced run:
# the spreads the bounds in BENCHMARK.json come from.
#   chiprun --chips <n> --timeout 1500 -- bash benchmarks/sweep/measure_spread.sh <cell> <seconds> ["<seeds>"]
# Runs in the checkout this script lies in; results go to its chiprun_out/m_<cell>/.
cd "$(dirname "$0")/../.." || exit 9
W=$1; S=$2; SEEDS=${3:-"101 202 303 2147483999 3000000005 4000000006"}
OUT=chiprun_out/m_$W
mkdir -p "$OUT"
bad=0
for set in A B; do
 for seed in $SEEDS; do
  python3 benchmarks/run.py --workload "$W" --seed "$seed" --seconds "$S" --trace 0 > "$OUT/out_${set}_$seed.txt" 2> "$OUT/err_${set}_$seed.txt"
  rc=$?; [ $rc -ne 0 ] && bad=1
  echo "$W $set $seed rc=$rc $(tail -n 1 "$OUT/out_${set}_$seed.txt" | python3 -c "
import sys, json
l = json.loads(sys.stdin.read())
print(l['correct'], l['attempted'], l['failed'], {k: v['value'] for k, v in l['metrics'].items()}, 'leave_s', round(l['detail']['leave_s'], 1))" 2>&1 | tail -n 1)"
 done
done
python3 benchmarks/run.py --workload "$W" --seed 707 --seconds "$S" --trace 1 > "$OUT/out_T_707.txt" 2> "$OUT/err_T_707.txt"
rc=$?; [ $rc -ne 0 ] && bad=1
echo "$W traced rc=$rc"; tail -c 5000 "$OUT/out_T_707.txt"
python3 benchmarks/sweep/spread.py "$OUT"
exit $bad
