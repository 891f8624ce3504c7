#!/bin/bash
# The committed files alone: in a directory that holds what `git archive` gives
# and nothing else, with a compile cache of its own that starts empty, run each
# named cell cold (it compiles), warm with another seed, and traced.
#   git add -A && mkdir -p .bench_checkout && git archive $(git write-tree) | tar -x -C .bench_checkout
#   chiprun --chips <n> --timeout 1800 -- bash benchmarks/sweep/fresh_checkout_proof.sh .bench_checkout <cell> [<cell>...]
# PROOF_RUNS="<seed> <trace>;..." replaces the three runs (four chips: one cold traced run).
# Exits 0 only if every run exited 0 with "correct": true on the TPU and no
# process of the benchmark is left. Lines go to chiprun_out/proof/ of the
# checkout the script was started from.
START=$(pwd); D=$1; shift
O=$START/chiprun_out/proof; mkdir -p "$O"
cd "$D" || exit 9
unset JAX_COMPILATION_CACHE_DIR   # the driver's machine need not set it
test -e .git && { echo "$D is a git repository"; exit 9; }
rm -rf .jax_cache
bad=0
for W in "$@"; do
 IFS=';' read -ra specs <<< "${PROOF_RUNS:-4100000001 0;12 0;13 1}"
 for spec in "${specs[@]}"; do
  set -- $spec
  python3 benchmarks/run.py --workload "$W" --seed "$1" --seconds 50 --trace "$2" > "$O/out_${W}_$1_$2.txt" 2> "$O/err_${W}_$1_$2.txt"
  rc=$?
  tail -n 1 "$O/out_${W}_$1_$2.txt" | python3 -c "
import sys, json
l = json.loads(sys.stdin.read()); d = l['detail']
print('$W seed=$1 trace=$2 rc=$rc', l['correct'], l['attempted'], l['failed'], {k: v['value'] for k, v in l['metrics'].items()}, l['device'], 'leave_s', round(d['leave_s'], 1), 'wall_s', round(d['wall_s'], 1))
print('   breakdown', json.dumps(l.get('breakdown'))[:1500])
sys.exit(0 if l['correct'] and l['device']['platform'] == 'tpu' else 1)" || bad=1
  [ $rc -ne 0 ] && { bad=1; tail -n 20 "$O/err_${W}_$1_$2.txt"; }
 done
done
left=$(pgrep -f "benchmarks/run.py|ray_tpu" | wc -l)
echo "processes left: $left; cache: $(du -sh .jax_cache 2>/dev/null | cut -f1)"
[ "$left" -ne 0 ] && bad=1
exit $bad
