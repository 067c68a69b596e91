#!/usr/bin/env python3
"""PR 36: one traced run of a cell with a longer traced window and the trace
kept; prints the result line, then one JSON row a slow step of the window
(tpubench.readers.waits.stalls), each with the milliseconds of CPython's own
collections that overlap it.

    python3 chip_scratch/pr36_stalls.py --workload <cell> --seed <n> \
        --trace-seconds 12 --out chiprun_out/<tag>/<name>
"""
import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import gc            # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ap = argparse.ArgumentParser()
ap.add_argument("--workload", required=True)
ap.add_argument("--seed", type=int, required=True)
ap.add_argument("--trace-seconds", type=float, default=12.0)
ap.add_argument("--out", required=True)
ap.add_argument("--roundtrip", type=int, default=0,
                help="print every host-plane event of this many traced "
                     "steps from the middle of the trace")
ap.add_argument("--beats", type=float, default=0.0,
                help="seconds for which two other processes "
                     "(pr36_beats.py: one sleeping, one spinning) and a "
                     "thread of this one watch their own clocks")
args = ap.parse_args()

beats, late_thread = [], []
if args.beats:
    import subprocess
    import threading

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for mode in ("sleep", "spin"):
        beats.append((mode, args.out + f".beats-{mode}.json", subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "chip_scratch",
                                          "pr36_beats.py"),
             mode, str(args.beats), args.out + f".beats-{mode}.json"])))

    def _beat():
        a = time.perf_counter()
        while True:
            time.sleep(0.0005)
            b = time.perf_counter()
            if b - a > 0.005:
                late_thread.append((a, b))
            a = b

    threading.Thread(target=_beat, daemon=True).start()

from tpubench import core                      # noqa: E402
from tpubench.readers import program, waits    # noqa: E402

collections, _open = [], {}


def _gc(phase, info):
    if phase == "start":
        _open[info["generation"]] = time.perf_counter()
    else:
        collections.append((_open.pop(info["generation"]),
                            time.perf_counter(), info["generation"]))


gc.callbacks.append(_gc)
cell = core.Cell(args.workload)
cell.traffic["trace_seconds"] = args.trace_seconds
run = core.Run(cell, args.seed, 20.0, 1, args.out, T_START)
run.claim_devices()
core.kind(cell.traffic).run(run)
line = core.result_line(run)
print(json.dumps(line), flush=True)

specs = {m["name"]: spec for m, spec in cell.metrics("per_layer")}
(spec,) = [s for n, s in specs.items()
           if n.startswith("slow_step_device_ms.")]
a = spec["args"]
t0 = run.window[0]
rows = waits.stalls(run, a["root"], a["wait"], a["enqueue"], a["module"])
got = waits._traced(run)
(trip,) = [s for n, s in specs.items()
           if n.startswith("idle_roundtrip_share.")]
skew = got and waits.clock_skew(
    got[0], got[1], a["enqueue"], a["wait"][0], a["module"],
    trip["args"].get("after"))
steps = program.steps(run, a["root"])
names = sorted({k["name"][len(program.PREFIX):]
                for _, kids in steps for k in kids})
summary = {
    "workload": args.workload, "seed": args.seed,
    "steps": len(steps), "slow_steps": len(rows or ()),
    "traced_window_s": got[0].window_seconds() if got else None,
    "skew": skew,
    "span_ms_p50": {n: program.step_ms_p50(run, a["root"], include=[n])
                    for n in names},
    "gc": [{"at_s": s - t0, "ms": 1e3 * (e - s), "gen": g}
           for s, e, g in collections if t0 <= e <= run.window[1]]}
print("SUMMARY " + json.dumps(summary), flush=True)
if args.roundtrip and got:
    tr, driver, runtime = got
    progs, _ = waits._programs(tr, a["module"])
    opens = [s for n, s, _, _ in driver
             if n == program.PREFIX + a["enqueue"]]
    k = len(opens) // 2
    lo, hi = opens[k] - 0.0005, opens[k + args.roundtrip] - 0.0005
    evs = [("host", n[len(program.PREFIX):], s, e) for n, s, e, _ in driver]
    evs += [(line, n, s, e) for line, n, s, e in runtime]
    evs += [("DEVICE", "program", s, e) for s, e in progs]
    ops = tr.busy(min(tr.devices))
    evs += [("DEVICE", "busy", s, e) for s, e in ops]
    for line, n, s, e in sorted(evs, key=lambda x: x[2]):
        if lo <= s < hi:
            print(f"RT {1e3 * (s - opens[k]):9.3f} ms +{1e3 * (e - s):8.3f}"
                  f"  {line[:28]:28s} {n[:90]}", flush=True)
late = {"thread": list(late_thread)}
for mode, path, proc in beats:
    proc.terminate()
    proc.wait(timeout=30)
    got_ = json.load(open(path))
    late[mode] = got_["late"]
    print("BEATS " + json.dumps({
        "mode": mode, "turns": got_["turns"],
        "late_in_window_ms": [[round(a_ - t0, 3), round(1e3 * (b_ - a_), 1)]
                              for a_, b_ in got_["late"]
                              if t0 <= b_ and a_ <= run.window[1]]}),
        flush=True)
print("BEATS " + json.dumps({
    "mode": "thread", "late_in_window_ms": [
        [round(a_ - t0, 3), round(1e3 * (b_ - a_), 1)]
        for a_, b_ in late_thread
        if t0 <= b_ and a_ <= run.window[1] and b_ - a_ > 0.02]}),
    flush=True)
for r in rows or ():
    a_s, b_s = t0 + r["at_s"], t0 + r["at_s"] + r["step_ms"] / 1e3
    for who, ivs in late.items():
        # the longest late turn of that watcher that overlaps the step
        r["late_" + who + "_ms"] = 1e3 * max(
            (b_ - a_ for a_, b_ in ivs if a_ < b_s and b_ > a_s),
            default=0.0)
    r["gc_ms"] = 1e3 * sum(max(0.0, min(e, b_s) - max(s, a_s))
                           for s, e, _ in collections)
    print("STALL " + json.dumps(r), flush=True)
    if got and r.get("wake_gap_ms", 0) > 20:
        # every host-plane event from the stalled program's end to the
        # close of its wait, on the trace's clock, from that end
        tr, driver, runtime = got
        progs, _ = waits._programs(tr, a["module"])
        roots = [(s_, st) for n, s_, _, st in driver
                 if n == program.PREFIX + a["root"]]
        lo = next(s_ for s_, st in roots if st == r["step"])
        hi = min((s_ for s_, _ in roots if s_ > lo), default=lo + 1.0)
        end = max(e for _, e in progs if lo <= e < hi)
        evs = [("host", n[len(program.PREFIX):], s_, e)
               for n, s_, e, _ in driver]
        evs += list(runtime)
        shown = [x for x in sorted(evs, key=lambda x: x[2])
                 if end - 0.0005 <= x[2] < hi + 0.0005]
        for line, n, s_, e in shown[:120]:
            print(f"GAP {1e3 * (s_ - end):9.3f} ms +{1e3 * (e - s_):8.3f}"
                  f"  {line[:28]:28s} {n[:90]}", flush=True)
run.drop_trace()        # read above; chiprun_out/ brings back 64 MiB
