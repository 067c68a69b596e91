#!/usr/bin/env python3
"""PR 36: a process that never touches JAX or the chip and only watches its
own clock, beside a cell's run. `sleep`: time.sleep(0.5 ms) in a loop (a timer
wake-up each turn); `spin`: perf_counter in a loop (on a CPU all the time).
Every turn that took over 5 ms is a row (start, end) on CLOCK_MONOTONIC, which
perf_counter reads in every process of the host: a stall of the engine's
thread that these rows share froze the whole sandbox, one they do not share
is the process's own (the runtime, the chip).

    python3 chip_scratch/pr36_beats.py sleep|spin <seconds> <out.json>
"""
import json
import signal
import sys
import time

mode, seconds, out = sys.argv[1], float(sys.argv[2]), sys.argv[3]
rows, turns = [], 0
stop = []
signal.signal(signal.SIGTERM, lambda *a: stop.append(1))   # the run is over
t0 = a = time.perf_counter()
while a - t0 < seconds and not stop:
    if mode == "sleep":
        time.sleep(0.0005)
    b = time.perf_counter()
    turns += 1
    if b - a > 0.005:
        rows.append((a, b))
    a = b
json.dump({"mode": mode, "from": t0, "to": a, "turns": turns, "late": rows},
          open(out, "w"))
