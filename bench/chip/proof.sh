# A cell's proof on the chip, in one call:
#
#     bash bench/chip/proof.sh <workload> <run_seconds> <seed base>
#
# Run from the root of a checkout on a machine with the cell's chips. In
# order: one short run that compiles in a cold checkout and stops the call
# unless it is correct; two sets of 6 runs of <run_seconds> with the same
# seeds in both (base+11..16), the sets that the bounds are set from; four
# short runs of the program and the float32 control on three seeds, in one
# process (bench/control.py); three traced runs (base+51..53). Result
# lines go to standard output, each run's standard error to chiprun_out/.
# bench/chip/spread.py reads the sets' spreads from the output.
set -u
wl=$1 secs=$2 base=$3
mkdir -p chiprun_out
nproc
out=$(bash bench/chip/runs.sh "$wl" 5 0 $((base + 1)))
echo "$out"
tail -n 3 "chiprun_out/${wl}_$((base + 1))_t0.err"
echo "$out" | grep -q '"correct": true' || exit 1
sets=$(seq $((base + 11)) $((base + 16)))
echo "== set 1"
bash bench/chip/runs.sh "$wl" "$secs" 0 $sets
echo "== set 2"
bash bench/chip/runs.sh "$wl" "$secs" 0 $sets
echo "== control"
python3 bench/control.py --workload "$wl" --seconds 8 \
  --program-seeds "$(seq -s, $((base + 21)) $((base + 24)))" \
  --control-seeds "$(seq -s, $((base + 41)) $((base + 43)))" \
  2>"chiprun_out/${wl}_control.err"
tail -n 3 "chiprun_out/${wl}_control.err"
echo "== traced"
bash bench/chip/runs.sh "$wl" "$secs" 1 $(seq $((base + 51)) $((base + 53)))
