# Runs of one cell in a row, each its own process, as the benchmark's
# check makes them:
#
#     bash bench/chip/runs.sh <workload> <seconds> <trace 0|1> <seed>...
#
# Run from the root of a checkout on a machine with the cell's chips. Each
# run's result line goes to standard output; its standard error to
# chiprun_out/<workload>_<seed>_t<trace>.err.
set -u
wl=$1 secs=$2 tr=$3
shift 3
mkdir -p chiprun_out
for seed in "$@"; do
  python3 bench/run.py --workload "$wl" --seed "$seed" --seconds "$secs" \
    --trace "$tr" 2>"chiprun_out/${wl}_${seed}_t${tr}.err" | tail -n 1
done
