#!/bin/sh
# PR 36, call 7 (the final tree): (1) chip_smoke's serve phase with its new check; (2) traced runs with 16 s of
# trace beside three watchers of their own clocks (pr36_beats.py: a sleeping process, a spinning process, a
# sleeping thread of the run): does a stall freeze them too?; (3) the committed files alone
# (chip_scratch/archive = git archive $(git write-tree)): one --trace 1 run of each one-chip cell.
tag=pr36f; mkdir -p chiprun_out/$tag
python3 chip_scratch/pr36_smoke_serve.py > chiprun_out/$tag/smoke_serve.out 2> chiprun_out/$tag/smoke_serve.err
echo "smoke serve rc=$?"; grep -a "^\[serve\]\|serve phase ok" chiprun_out/$tag/smoke_serve.out | cut -c1-400
st() { # cell seed
  o=chiprun_out/$tag/$1-$2
  python3 chip_scratch/pr36_stalls.py --workload $1 --seed $2 --trace-seconds 16 --beats 600 --out $o > $o.out 2> $o.err
  echo "== $1 seed $2 rc=$? $(grep -ac '^STALL' $o.out) slow steps"; grep -a "^BEATS" $o.out | cut -c1-600
}
st serve-glm47f-offline-decode 2147500031
st train-345m-1chip 2147500032
st serve-longcat-offline-decode 2147500033
st serve-glm47f-offline-decode 2147500034
st serve-lfm2-offline-decode 2147500035
st train-345m-1chip 2147500036
date
A=chip_scratch/archive
sh chip_scratch/pr36_run.sh $tag archive $A serve-345m-offline-decode 2147500041 1 archive $A serve-glm47f-offline-decode 2147500042 1 \
  archive $A serve-longcat-offline-decode 2147500043 1 archive $A serve-lfm2-offline-decode 2147500044 1 archive $A train-345m-1chip 2147500045 1
du -sh chiprun_out/$tag
