#!/bin/sh
# The two sets of six runs of each cell named, the same seeds in both sets,
# every run a new process, and one traced run: sh sets.sh <seconds> <cell>...
# (CHECKOUT=<dir> runs them from another checkout, e.g. an unpacked git archive)
seconds=$1; shift
seeds=2147483659,2147491651,2147502143,2147516423,2147523599,2147535061
for cell in "$@"; do
  python3 benchmarks/tools/series.py --dir "${CHECKOUT:-.}" --workload "$cell" --seconds "$seconds" --seeds $seeds --tag "$cell-setA"
  python3 benchmarks/tools/series.py --dir "${CHECKOUT:-.}" --workload "$cell" --seconds "$seconds" --seeds $seeds --tag "$cell-setB"
  python3 benchmarks/tools/series.py --dir "${CHECKOUT:-.}" --workload "$cell" --seconds "$seconds" --seeds 2147549183 --trace-last --tag "$cell-traced"
done
