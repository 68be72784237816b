#!/bin/bash
# The sets of runs a bound is set from: for one cell, <sets> sets of
# runs with the same seeds in each, all in one call, then (trace = 1)
# one traced run of the first seed.  Result lines go to
# <out>/set<n>.jsonl and <out>/traced.jsonl, the findings lines beside
# them, and chipbench/spread.py reads the sets.  Each run's arrivals,
# records, cell, compile ledger and engine log are kept under
# <out>/<set>_<seed>/, which chipbench/phase.py reads.
#
#   bash chipbench/measure.sh <cell> <seconds> <out dir> "<sets>" <trace> ["<seeds>"]
cell=$1; secs=$2; out=$3; sets=$4; trace=$5
seeds=${6:-"2147484001 1234567 42 2147483999 987654321 31337"}
mkdir -p "$out"
one_run() {  # <seed> <trace> <name of the .jsonl>
  local t0=$(date +%s)
  python3 chipbench/run.py --workload "$cell" --seed "$1" --seconds "$secs" \
    --trace "$2" > "$out/run.out" 2> "$out/run.err"
  local rc=$?
  echo "$3 seed $1 rc $rc wall $(( $(date +%s) - t0 ))s" | tee -a "$out/log.txt"
  grep '^\[chipbench' "$out/run.err" | grep -v warmed >> "$out/log.txt"
  if [ $rc -ne 0 ]; then tail -40 "$out/run.err"; return; fi
  tail -n 1 "$out/run.out" >> "$out/$3.jsonl"
  head -n -1 "$out/run.out" | tail -n 1 >> "$out/findings_$3.jsonl"
  mkdir -p "$out/$3_$1"
  cp ".chipbench/runs/$cell"/{arrivals,records,cell,compiles}.json "$out/$3_$1/"
  gzip -c ".chipbench/runs/$cell/engine.log" > "$out/$3_$1/engine.log.gz"
}
for set in $sets; do
  for seed in $seeds; do one_run $seed 0 "set$set"; done
done
if [ "$trace" = "1" ]; then
  one_run ${seeds%% *} 1 traced
  cp ".chipbench/runs/$cell/trace_summary.json" "$out/" 2>/dev/null
fi
if [ -n "$sets" ]; then
  python3 chipbench/spread.py "$out"/set*.jsonl | tee "$out/spread.txt"
  python3 chipbench/phase.py "$out"/set*_*/ > "$out/phase.txt"
  grep -v '^{' "$out/phase.txt"
fi
