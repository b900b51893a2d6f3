# The two sets of 6 runs that a bound is set from, and the traced runs, of one
# cell, each run a process of its own (run through the chip tool, from the
# root of a checkout):  bash benchmark/tests/sets.sh <cell> <traced runs>
# One line a run in chiprun_out/sets_<cell>.jsonl; benchmark/tests/summ.py reads it.
cell=$1; ntrace=${2:-3}
secs=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
out=${OUT:-$PWD/chiprun_out}; mkdir -p $out
one() {  # one <set> <seed> <trace>
  t0=$SECONDS
  python3 benchmark/run.py --workload $cell --seed $2 --seconds $secs --trace $3 > $out/_o 2> $out/_e; rc=$?
  echo "{\"cell\": \"$cell\", \"set\": $1, \"trace\": $3, \"seed\": $2, \"rc\": $rc, \"wall_s\": $((SECONDS-t0)), \"cwd\": \"$PWD\", \"line\": $(tail -n 1 $out/_o | grep '^{' || echo null), \"err\": $(grep -E '^(warm-up|set-up|window|reference|diagnostic|train_step|hist_kernel|check)' $out/_e | python3 -c 'import json,sys; print(json.dumps(sys.stdin.read()))')}" >> $out/sets_$cell.jsonl
  [ $rc -ne 0 ] && tail -n 30 $out/_e
}
for set in 1 2; do for seed in 2147483659 3000000019 1234567891 2718281829 4000000007 987654321; do
  one $set $seed 0
done; done
i=0; for seed in 1111111111 2222222222 3333333333; do i=$((i+1)); [ $i -gt $ntrace ] && break
  one null $seed 1
done
rm -f $out/_o $out/_e
python3 benchmark/tests/summ.py $out/sets_$cell.jsonl
