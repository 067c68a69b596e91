#!/usr/bin/env python3
"""PR 36: chip_smoke's serve phase alone, at the published width (its new
check of the wait spans and of the two clocks; the whole smoke is 6 min)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402
from paddle_tpu.jit import persistent_cache  # noqa: E402

persistent_cache.arm_native()
chip_smoke.phase_serve(chip_smoke.WIDTH, **chip_smoke.SERVE)
print("serve phase ok", flush=True)
