"""The reduction from a profiler trace (.xplane.pb) to numbers. Reads the file
with jax.profiler.ProfileData and nothing else.

What a v5e trace holds (seen on the chip, PR 23): one plane `/device:TPU:<n>`
per chip, with the lines `XLA Modules` (one event per executed program, named
`jit_<fn>(<hash>)`), `XLA Ops` (one event per HLO instruction the TensorCore
ran, named by the instruction's whole text) and `Async XLA Ops` (transfers in
flight). Host threads are lines of the plane `/host:CPU`; the benchmark's own
spans are the events there whose names start with `tpubench/`. All times are
nanoseconds from the start of the profile, host and device on one clock to
within about a millisecond.

Busy time is the union of the `XLA Ops` intervals, clipped to the traced
window: the span `tpubench/trace_window` where the run recorded one, else
first to last device event.
"""
from __future__ import annotations

import re

WINDOW_SPAN = "tpubench/trace_window"
SPAN_PREFIX = "tpubench/"
NO_SPAN = "_no_benchmark_span_"
_CONTAINERS = {"while", "conditional", "call"}
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all", "collective-broadcast")
_SUFFIX = re.compile(r"(\.(\d+|remat\d*|clone))+$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def parse_op(text):
    """(instruction base name, opcode, group) of an `XLA Ops` event name such
    as `%fusion.12 = bf16[..] fusion(..), kind=kOutput, calls=..`. The group
    is what the breakdown sums by: a nameless fusion by its kind, a custom
    call by its target, anything else by its base name."""
    name, _, rest = text.partition(" = ")
    base = _SUFFIX.sub("", name.lstrip("%")) or name
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else base
    group = base
    if opcode == "custom-call":
        t = re.search(r'custom_call_target="([^"]+)"', rest)
        group = "custom-call:" + (t.group(1) if t else "?")
    elif opcode == "fusion" and base == "fusion":
        k = re.search(r"kind=(k\w+)", rest)
        group = "fusion:" + (k.group(1) if k else "?")
    return base, opcode, group


def is_collective(opcode):
    return opcode.startswith(_COLLECTIVES)


def union(intervals):
    """Sorted, merged copy of [(start, end), ...]."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def measure(intervals):
    return sum(b - a for a, b in intervals)


def subtract(a, b):
    """Parts of the merged intervals `a` not covered by the merged `b`."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


class Trace:
    """The events of one trace, as plain tuples in seconds."""

    def __init__(self, planes):
        # planes: {plane name: {line name: [(name, start_s, end_s), ...]}}
        self.devices = {}          # chip -> {"ops": [...], "modules": [...]}
        self.spans = []            # (name, start_s, end_s), benchmark spans
        for pname, lines in planes.items():
            m = _DEVICE.match(pname)
            if m:
                self.devices[int(m.group(1))] = {
                    "ops": lines.get("XLA Ops", []),
                    "modules": lines.get("XLA Modules", [])}
            elif pname == "/host:CPU":
                for evs in lines.values():
                    self.spans += [e for e in evs
                                   if e[0].startswith(SPAN_PREFIX)]
        self.spans.sort(key=lambda e: e[1])
        self.window = self._window()
        self._parsed = {}          # chip -> the window's parsed ops

    @classmethod
    def from_file(cls, path):
        from jax.profiler import ProfileData

        planes = {}
        for plane in ProfileData.from_file(path).planes:
            if not (_DEVICE.match(plane.name) or plane.name == "/host:CPU"):
                continue
            lines = planes.setdefault(plane.name, {})
            for line in plane.lines:
                if plane.name == "/host:CPU":
                    evs = [(e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events
                           if e.name.startswith(SPAN_PREFIX)]
                elif line.name in ("XLA Ops", "XLA Modules"):
                    evs = [(e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events]
                else:
                    continue
                lines.setdefault(line.name, []).extend(evs)
        return cls(planes)

    def _window(self):
        for name, a, b in self.spans:
            if name == WINDOW_SPAN:
                return (a, b)
        evs = [e for d in self.devices.values() for e in d["ops"]]
        if not evs:
            return None
        return (min(e[1] for e in evs), max(e[2] for e in evs))

    # -- device ---------------------------------------------------------
    def _ops(self, chip):
        """(parsed, start, end) of the chip's ops inside the window, without
        the container instructions whose bodies are traced themselves."""
        if chip not in self._parsed:
            lo, hi = self.window
            out = []
            for text, a, b in self.devices[chip]["ops"]:
                if b <= lo or a >= hi:
                    continue
                parsed = parse_op(text)
                if parsed[1] not in _CONTAINERS:
                    out.append((parsed, max(a, lo), min(b, hi)))
            self._parsed[chip] = out
        return self._parsed[chip]

    def busy(self, chip):
        return union((a, b) for _, a, b in self._ops(chip))

    def busy_seconds(self):
        """Seconds with an operation running, averaged over the chips."""
        if not self.devices or self.window is None:
            return None
        return sum(measure(self.busy(c)) for c in self.devices) \
            / len(self.devices)

    def window_seconds(self):
        return None if self.window is None else self.window[1] - self.window[0]

    def op_seconds(self, chip=None):
        """{group: seconds}, averaged over the chips (or of one chip)."""
        chips = list(self.devices) if chip is None else [chip]
        total = {}
        for c in chips:
            for (_, _, group), a, b in self._ops(c):
                total[group] = total.get(group, 0.0) + (b - a) / len(chips)
        return total

    def group_events(self, group, chip=None):
        """[(start, end)] of the ops of one breakdown group."""
        chips = list(self.devices) if chip is None else [chip]
        return [(a, b) for c in chips for (p, a, b) in self._ops(c)
                if p[2] == group]

    def module_events(self, prefix, chip=None, most_frequent=False):
        """[(start, end)] of executed programs whose name starts with
        `prefix`, wholly inside the window; with `most_frequent`, of the one
        program (name and hash) among them that ran most often."""
        lo, hi = self.window
        chips = list(self.devices) if chip is None else [chip]
        evs = [(name, a, b) for c in chips
               for name, a, b in self.devices[c]["modules"]
               if name.startswith(prefix) and a >= lo and b <= hi]
        if most_frequent and evs:
            names = [e[0] for e in evs]
            top = max(sorted(set(names)), key=names.count)
            evs = [e for e in evs if e[0] == top]
        return [(a, b) for _, a, b in evs]

    def exposed_collective_seconds(self):
        """Collective time during which no other operation runs on that
        chip, averaged over the chips; None if the trace has no collective."""
        vals = []
        for c in self.devices:
            ops = self._ops(c)
            coll = union((a, b) for p, a, b in ops if is_collective(p[1]))
            if not coll:
                continue
            comp = union((a, b) for p, a, b in ops
                         if not is_collective(p[1]))
            vals.append(measure(subtract(coll, comp)))
        return sum(vals) / len(vals) if vals else None

    # -- host -----------------------------------------------------------
    def span_at(self, t):
        """The innermost benchmark span open at time t."""
        best = None
        for name, a, b in self.spans:
            if a > t:
                break
            if b >= t and name != WINDOW_SPAN:
                best = name
        return best or NO_SPAN

    def idle_gaps(self, chip=None):
        """{span: seconds}: the chip's idle gaps inside the window, each
        charged to the benchmark span the host was in at its middle."""
        if self.window is None or not self.devices:
            return {}
        chip = min(self.devices) if chip is None else chip
        out = {}
        for a, b in subtract([self.window], self.busy(chip)):
            name = self.span_at((a + b) / 2)
            out[name] = out.get(name, 0.0) + (b - a)
        return out

    def breakdown(self, top=10):
        def rank(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(self.op_seconds()),
                "idle_gaps": rank(self.idle_gaps())}
