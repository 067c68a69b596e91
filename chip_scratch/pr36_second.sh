#!/bin/sh
# PR 36, call 2: what the host's /proc holds, then traced runs with 12 s of trace, one row a slow step
tag=pr36b; mkdir -p chiprun_out/$tag
python3 chip_scratch/pr36_probe.py 2>&1 | grep -v cpu_aot
st() { # cell seed extra
  o=chiprun_out/$tag/$1-$2
  python3 chip_scratch/pr36_stalls.py --workload $1 --seed $2 --trace-seconds 12 --out $o $3 $4 > $o.out 2> $o.err
  echo "== $1 seed $2 rc=$?"; grep -a "the two clocks\|carry none" $o.out; grep -a "^SUMMARY\|^STALL" $o.out | cut -c1-1500
}
st serve-glm47f-offline-decode 2147500011 --roundtrip 2
st serve-glm47f-offline-decode 2147500012
st train-345m-1chip 2147500013 --roundtrip 1
st serve-glm47f-offline-decode 2147500014
st train-345m-1chip 2147500015
st serve-lfm2-offline-decode 2147500016
st serve-longcat-offline-decode 2147500017
du -sh chiprun_out/$tag; date
