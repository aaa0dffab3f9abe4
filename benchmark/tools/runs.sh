#!/bin/bash
# Several runs of one cell in one chip call, each line checked.
#   benchmark/tools/runs.sh <workload> <seconds> <trace> <control> <seed>...
# Results (last lines) are appended to chiprun_out/runs/<workload>.$TAG.jsonl
# (TAG from the environment, "runs" when unset), each run's standard error
# to chiprun_out/runs/<workload>.<seed>.t<trace>.err
w=$1; s=$2; t=$3; c=$4; shift 4
mkdir -p chiprun_out/runs
for seed in "$@"; do
  python3 -m benchmark.run --workload "$w" --seed "$seed" --seconds "$s" --trace "$t" --control "$c" \
    2> "chiprun_out/runs/$w.$seed.t$t.err" | tee "chiprun_out/runs/$w.$seed.t$t.out" \
    | python3 -m benchmark.tools.check_lastline --workload "$w" --trace "$t"
  echo "rc=${PIPESTATUS[0]} seed=$seed trace=$t"
  grep -E "^(control|compared|benchmark:)" "chiprun_out/runs/$w.$seed.t$t.err" | cut -c1-900
  grep -E '^\{"workload"' "chiprun_out/runs/$w.$seed.t$t.err" | cut -c1-1600
  tail -n 1 "chiprun_out/runs/$w.$seed.t$t.out" >> "chiprun_out/runs/$w.${TAG:-runs}.jsonl"
done
