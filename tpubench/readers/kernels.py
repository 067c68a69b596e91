"""A reader over the custom calls of a trace BY NAME: a program that launches
kernels of several kinds (a paged attention and a grouped matmul in one
decode step) shows them all in one breakdown group,
`custom-call:tpu_custom_call`, and `device.roofline` cannot tell them apart.
The compiler names a custom call's instruction after the function it was
traced from (`%attend.12 = ... custom-call(...)` for a kernel launched from a
function `attend`), and `xplane.parse_op` keeps that base name. A trace whose
events carry no such name gives the reader nothing to read."""
from __future__ import annotations

import fnmatch

from . import device


def roofline_by_name(run, group, names, least_fn):
    """`device.roofline` over the events of breakdown group `group` whose
    instruction base name matches one of the glob patterns `names`."""
    tr = run.reduced_trace() if run.on_tpu else None
    if tr is None:
        return None
    evs = [(a, b) for (base, _, g), a, b in tr._ops(min(tr.devices))
           if g == group and any(fnmatch.fnmatchcase(base, n) for n in names)]
    if not evs:
        return None
    need = device._model_fn(least_fn)(run, len(evs))
    if need is None:
        return None
    p = run.peaks()
    least = max(need[0] / p["bf16_flops_per_s"],
                need[1] / p["hbm_bytes_per_s"])
    return 100.0 * least / sum(b - a for a, b in evs)
