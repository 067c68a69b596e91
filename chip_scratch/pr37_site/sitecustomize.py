"""PR 37: at exit, print the program's own memory gauges and the flash
path counters of a plain tpubench run (PYTHONPATH=chip_scratch/pr37_site)."""
import atexit
import sys


def _dump():
    mon = sys.modules.get("paddle_tpu.core.monitor")
    if mon is None:
        return
    snap = mon.registry.snapshot()
    for name in sorted(snap):
        if name.startswith(("mem/program/", "kernels/flash/")):
            print(f"[pr37] {name} = {snap[name]}", file=sys.stderr, flush=True)


atexit.register(_dump)
