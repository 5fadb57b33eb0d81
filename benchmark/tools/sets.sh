#!/bin/bash
# Two sets of runs of one cell with the same seeds in both, then traced
# runs; every result line goes to chiprun_out/sets/<cell>.jsonl.
#   bash benchmark/tools/sets.sh <cell> <seconds> <runs-per-set> <traced>
cell=$1; seconds=$2; n=${3:-6}; traced=${4:-3}
out=chiprun_out/sets; mkdir -p $out
seeds=(2147483659 1300000077 2900000111 170000039 2200000181 990000007 2500000033 40000003)
for set in 1 2; do
  for i in $(seq 0 $((n - 1))); do
    s=${seeds[$i]}
    python3 benchmark/run.py --workload $cell --seed $s --seconds $seconds --trace 0 \
      > $out/$cell.last.out 2> $out/$cell.last.err
    rc=$?
    echo "{\"set\": $set, \"seed\": $s, \"rc\": $rc, \"line\": $(tail -n 1 $out/$cell.last.out)}" >> $out/$cell.jsonl
    grep "^\[benchmark\] run" $out/$cell.last.out >> $out/$cell.log
    [ $rc -ne 0 ] && tail -n 5 $out/$cell.last.err >> $out/$cell.log
  done
done
for i in $(seq 0 $((traced - 1))); do
  s=$((3100000000 + 7001 * i))
  python3 benchmark/run.py --workload $cell --seed $s --seconds $seconds --trace 1 \
    > $out/$cell.last.out 2> $out/$cell.last.err
  rc=$?
  echo "{\"set\": 0, \"seed\": $s, \"rc\": $rc, \"line\": $(tail -n 1 $out/$cell.last.out)}" >> $out/$cell.jsonl
  grep "^\[benchmark\] run" $out/$cell.last.out >> $out/$cell.log
  [ $rc -ne 0 ] && tail -n 5 $out/$cell.last.err >> $out/$cell.log
done
python3 benchmark/tools/spread.py $out/$cell.jsonl
