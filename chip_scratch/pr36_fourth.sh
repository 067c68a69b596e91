#!/bin/sh
# PR 36, call 4: traced runs with 16 s of trace, one row a slow step and, for a long wake gap, every host-plane event in it
tag=pr36d; mkdir -p chiprun_out/$tag
st() { # cell seed
  o=chiprun_out/$tag/$1-$2
  python3 chip_scratch/pr36_stalls.py --workload $1 --seed $2 --trace-seconds 16 --out $o > $o.out 2> $o.err
  echo "== $1 seed $2 rc=$? $(grep -ac '^STALL' $o.out) slow steps"
}
st serve-glm47f-offline-decode 2147500021
st serve-longcat-offline-decode 2147500022
st serve-lfm2-offline-decode 2147500023
st train-345m-1chip 2147500024
st serve-glm47f-offline-decode 2147500025
st serve-lfm2-offline-decode 2147500026
st serve-longcat-offline-decode 2147500027
st train-345m-1chip 2147500028
st serve-glm47f-offline-decode 2147500029
du -sh chiprun_out/$tag; date
