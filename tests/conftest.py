"""Test environment: force an 8-virtual-device CPU platform so
distributed/sharding tests run without TPU hardware and math checks are
exact f32 (SURVEY.md §7 / driver contract).

The suite always runs on the CPU platform: JAX_PLATFORMS is forced
here, before jax is imported, so it holds on a machine with a chip
too. The chip is exercised by `python chip_smoke.py`, not by pytest."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()


import re

import pytest


@pytest.fixture
def hlo_sans_locations():
    """Compiled-HLO text with source-location metadata stripped: the
    FileNames/FunctionNames/FileLocations/StackFrames tables ahead of
    the first computation and the per-op `stack_frame_id=` tags. Two
    lowerings of one program from different call sites then compare
    equal, and a real difference in the program still shows."""
    def strip(text):
        text = re.sub(r"^FileNames\n.*?(?=^(?:%|ENTRY ))", "", text,
                      flags=re.M | re.S)
        return re.sub(r"\s*stack_frame_id=\d+", "", text)

    return strip
