#!/bin/sh
# One run of a cell for every seed given, in order, on the machine this is
# started on (under chiprun: `chiprun -- sh chipbench/sets.sh ...`):
#   sh chipbench/sets.sh <label> <seconds> <trace 0|1> <cell> <seed>...
# A run's result line goes to chiprun_out/<label>/<cell>_<seed>.out and its
# log to .err; one summary line a run is printed.  Two sets of six are two
# labels with the same six seeds.
label=$1; seconds=$2; trace=$3; cell=$4; shift 4
mkdir -p "chiprun_out/$label"
for seed in "$@"; do
  to="chiprun_out/$label/${cell}_$seed"
  python3 -m chipbench.run --workload "$cell" --seed "$seed" \
    --seconds "$seconds" --trace "$trace" > "$to.out" 2> "$to.err"
  echo "$label $cell $seed rc=$? $(tail -n 1 "$to.out" | cut -c1-900)"
  grep -o '"reference_worst": {[^}]*}\|"failures": \[[^]]*\]\|"setup_phases_s": {[^}]*}\|"window_s": [^,]*' "$to.err" | tr '\n' ' '
  echo
done
