#!/usr/bin/env python3
"""PR 36: what the chip's host keeps of the three accountings a wait span
reads, and what a reading costs there."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in ("/proc/thread-self/schedstat", "/proc/pressure/cpu",
          "/proc/self/cgroup", "/sys/fs/cgroup/cpu.max",
          "/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu.pressure",
          "/sys/fs/cgroup/cpu/cpu.cfs_quota_us", "/proc/loadavg"):
    try:
        print(p, "->", open(p).read().strip().replace("\n", " | ")[:400])
    except OSError as e:
        print(p, "->", type(e).__name__, e)
for p in ("/proc/version", "/proc/thread-self/status", "/proc/self/status",
          "/proc/self/stat", "/proc/self/sched", "/proc/stat"):
    try:
        print(p, "->", open(p).read().strip().replace("\n", " | ")[:700])
    except OSError as e:
        print(p, "->", type(e).__name__, e)
print("ls /proc/thread-self:", sorted(os.listdir("/proc/thread-self"))
      if os.path.isdir("/proc/thread-self") else None)
print("ls /proc/self/task/<tid>:", sorted(os.listdir(
    "/proc/self/task/" + os.listdir("/proc/self/task")[0])))
import resource  # noqa: E402
print("rusage thread", resource.getrusage(resource.RUSAGE_THREAD))
print("cpus", os.cpu_count(), "affinity", len(os.sched_getaffinity(0)))
os.environ["JAX_PLATFORMS"] = "cpu"
from paddle_tpu.monitor import flight  # noqa: E402

print("cgroup dirs", flight._cgroup_dirs())
print("reading", flight._host_reading(), "counts", flight._host_counts)
for name, make in (("span", flight.span), ("wait_span", flight.wait_span)):
    n, t = 20000, time.perf_counter()
    for _ in range(n):
        with make("io/x"):
            pass
    print(name, "us each", 1e6 * (time.perf_counter() - t) / n)
n, t = 20000, time.perf_counter()
for _ in range(n):
    flight._host_reading()
print("reading us each", 1e6 * (time.perf_counter() - t) / n)
