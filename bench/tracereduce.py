"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData`` alone.  A device is a plane named
``/device:TPU:<n>``.  Its ``XLA Ops`` line holds one event per operation
run on the device, and its ``XLA Modules`` line one event per execution
of a compiled program, named after the program.  The host plane
``/host:CPU`` holds the harness's own ``TraceAnnotation`` spans, whose
names start with ``SPAN_PREFIX``.

- busy time of a device: the union of its operation intervals inside the
  traced window (where a device shows no ``XLA Ops`` line, its module
  intervals stand in);
- device time per program: the summed durations of the module events
  whose name contains the program's name;
- idle gaps: the stretches of the window in which a device runs nothing,
  each named after the innermost harness span around its middle.
"""
from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
# control flow, and the regions of computation it calls, whose events span
# the operations they run
_CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]* |^region\.\d+$")


def latest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals) -> list:
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo: float, hi: float) -> list:
    """The stretches of ``[lo, hi]`` that ``busy`` (disjoint, sorted)
    leaves uncovered."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


class Trace:
    """The parts of one trace that the benchmark reads."""

    def __init__(self, profile):
        self.devices = {}        # id -> {"ops": [...], "modules": [...]}
        self.spans = []          # (start_ns, end_ns, name), host side
        for plane in profile.planes:
            m = _DEVICE.match(plane.name)
            if m:
                dev = {"ops": [], "modules": []}
                for line in plane.lines:
                    key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                        line.name)
                    if key is None:
                        continue
                    dev[key].extend((e.start_ns, e.end_ns, e.name)
                                    for e in line.events)
                self.devices[int(m.group(1))] = dev
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    self.spans.extend(
                        (e.start_ns, e.end_ns, e.name) for e in line.events
                        if e.name.startswith(SPAN_PREFIX))
        self.spans.sort()

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls(ProfileData.from_file(str(path)))

    @classmethod
    def from_dir(cls, trace_dir: str) -> "Trace":
        return cls.from_file(latest_xplane(trace_dir))

    def window(self) -> tuple:
        """The traced window: from the first harness span's start to the
        last one's end, in ns."""
        if not self.spans:
            raise ValueError("the trace holds no harness span")
        return self.spans[0][0], max(e for _, e, _ in self.spans)

    def device_ids(self, used=None) -> list:
        ids = sorted(self.devices)
        return [i for i in ids if used is None or i in used]

    def busy_intervals(self, dev: int) -> list:
        d = self.devices[dev]
        events = d["ops"] or d["modules"]
        lo, hi = self.window()
        return clip(union((s, e) for s, e, _ in events), lo, hi)

    def busy_s(self, dev: int) -> float:
        return covered(self.busy_intervals(dev)) * 1e-9

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) * 1e-9

    def module_s(self, dev: int, name: str) -> float:
        """Device seconds of the programs whose module name contains
        ``name``, inside the window."""
        lo, hi = self.window()
        return sum(min(e, hi) - max(s, lo)
                   for s, e, n in self.devices[dev]["modules"]
                   if name in n and e > lo and s < hi) * 1e-9

    def module_count(self, dev: int, name: str) -> int:
        lo, hi = self.window()
        return sum(1 for s, e, n in self.devices[dev]["modules"]
                   if name in n and e > lo and s < hi)

    def top_ops(self, devs, k: int = 10) -> list:
        """The ``k`` operations that took most device time, averaged over
        ``devs``; loops and conditionals, whose events span the operations
        inside them, are left out."""
        lo, hi = self.window()
        tot: dict = {}
        for d in devs:
            dev = self.devices[d]
            for s, e, n in (dev["ops"] or dev["modules"]):
                if e > lo and s < hi and not _CONTAINER.match(n):
                    tot[n] = tot.get(n, 0.0) + (min(e, hi) - max(s, lo))
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t * 1e-9 / len(devs)] for n, t in ranked]

    def span_at(self, t: float) -> str:
        """The innermost harness span that covers time ``t``."""
        best = None
        for s, e, n in self.spans:
            if s > t:
                break
            if e >= t and (best is None or s >= best[0]):
                best = (s, n)
        return best[1][len(SPAN_PREFIX):] if best else "outside_spans"

    def idle_gaps(self, dev: int, k: int = 10) -> list:
        """Device ``dev``'s idle time in the window, summed by the harness
        span around each gap, largest first."""
        lo, hi = self.window()
        tot: dict = {}
        for s, e in gaps(self.busy_intervals(dev), lo, hi):
            name = self.span_at((s + e) / 2)
            tot[name] = tot.get(name, 0.0) + (e - s)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t * 1e-9] for n, t in ranked]
