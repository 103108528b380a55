"""The program's own host spans in a benchmark trace.

The program marks its layers with ``jax.profiler.TraceAnnotation`` spans
named ``apcvfl.<layer>`` (``src/repro/core/spans.py``).  They lie on
the ``/host:CPU`` plane of the same ``.xplane.pb`` as the device's ``XLA
Ops`` and ``XLA Modules`` lines, on the same clock.  This module reads them beside ``tracereduce.Trace``,
which it leaves as it is: the window is still bounded by the harness's
spans, and busy time is still the union of the device's operations.

- the window is cut at every span's start and end, and each piece is
  named after the innermost program span over it (its full name), else
  the innermost harness span (``Trace.span_at``: no prefix, or
  ``outside_spans``);
- self time of a span name: the pieces named after it;
- idle by span: each idle gap of a device cut into those pieces, each part
  counted under its piece's name;
- device time of a program inside a span name: its module events clipped
  to the intervals of the spans of that name.

A trace whose program has no spans gives no program readings: each reader
then returns None.
"""
from __future__ import annotations

from tracereduce import clip, covered, gaps, latest_xplane, union

PROGRAM_PREFIX = "apcvfl."
ENGINE = "run_fit_k"          # the lane engine's program


def matches(name: str, names) -> bool:
    """``name`` is one of ``names`` or nested under one by its dots
    (``apcvfl.lanes`` takes ``apcvfl.lanes.prep``)."""
    return any(name == n or name.startswith(n + ".") for n in names)


class ProgramSpans:
    """The program spans of one trace's window, and what they contain."""

    def __init__(self, trace, profile):
        self.trace = trace
        lo, hi = trace.window()
        self.spans = []          # (start_ns, end_ns, name)
        for plane in profile.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                self.spans.extend(
                    (max(e.start_ns, lo), min(e.end_ns, hi), e.name)
                    for e in line.events
                    if e.name.startswith(PROGRAM_PREFIX)
                    and e.end_ns > lo and e.start_ns < hi)
        # at one start the outer span first, so the last one over a time
        # is the innermost
        self.spans.sort(key=lambda sp: (sp[0], -sp[1]))
        cuts = sorted({lo, hi} | {t for s, e, _ in self.spans
                                  for t in (s, e)}
                      | {t for s, e, _ in trace.spans for t in (s, e)
                         if lo < t < hi})
        self.pieces = [(a, b, self.innermost((a + b) / 2)
                        or trace.span_at((a + b) / 2))
                       for a, b in zip(cuts, cuts[1:])]

    @classmethod
    def from_dir(cls, trace, trace_dir: str) -> "ProgramSpans":
        from jax.profiler import ProfileData
        return cls(trace, ProfileData.from_file(latest_xplane(trace_dir)))

    def innermost(self, t: float):
        """The name of the innermost program span over time ``t``, or
        None."""
        best = None
        for s, e, n in self.spans:
            if s > t:
                break
            if e > t:
                best = n
        return best

    def names(self) -> set:
        return {n for _, _, n in self.spans}

    def self_s(self, name: str) -> float:
        """Seconds in which ``name`` is the innermost span."""
        return sum(b - a for a, b, n in self.pieces if n == name) * 1e-9

    def idle_s(self, dev: int) -> dict:
        """Device ``dev``'s idle seconds in the window by the name of the
        piece they fall in."""
        lo, hi = self.trace.window()
        tot: dict = {}
        k = 0
        for s, e in gaps(self.trace.busy_intervals(dev), lo, hi):
            while self.pieces[k][1] <= s:
                k += 1
            j = k
            while j < len(self.pieces) and self.pieces[j][0] < e:
                a, b, name = self.pieces[j]
                a, b = max(a, s), min(b, e)
                if b > a:
                    tot[name] = tot.get(name, 0.0) + (b - a)
                j += 1
        return {n: t * 1e-9 for n, t in tot.items()}

    def module_in_s(self, dev: int, program: str, name: str) -> float:
        """Device seconds of the programs whose module name contains
        ``program``, inside the spans named ``name``."""
        inside = union((s, e) for s, e, n in self.spans if n == name)
        return sum(covered(clip(inside, s, e))
                   for s, e, n in self.trace.devices[dev]["modules"]
                   if program in n) * 1e-9


def of(ctx: dict):
    """The program spans of a traced run, read once a run and kept in
    ``ctx``; None where the run holds no trace."""
    if "program_spans" not in ctx:
        ps = None
        if ctx.get("trace") is not None:
            from run import TRACE_DIR
            ps = ProgramSpans.from_dir(ctx["trace"], str(TRACE_DIR))
        ctx["program_spans"] = ps
    return ctx["program_spans"]


def idlest(ctx: dict) -> int:
    """The cell's chip with the least busy time in the window."""
    tr = ctx["trace"]
    return min(ctx["devices"], key=tr.busy_s)


def idle_ms_per_fit(ctx: dict, names, *, dev=None):
    """Milliseconds per fit in which chip ``dev`` (the cell's first chip
    by default) idles inside the spans ``names`` (``matches``), each
    idle stretch counted in its innermost program span alone."""
    ps = of(ctx)
    if ps is None or not any(matches(n, names) for n in ps.names()):
        return None
    idle = ps.idle_s(ctx["devices"][0] if dev is None else dev)
    return (sum(t for n, t in idle.items() if matches(n, names))
            / ctx["window"]["fits"] * 1e3)


def module_ms_per_fit(ctx: dict, program: str, name: str):
    """Device milliseconds per fit of ``program`` inside the spans
    ``name``, on the cell's first chip."""
    ps = of(ctx)
    dev = ctx["devices"][0]
    if (ps is None or name not in ps.names()
            or not ctx["trace"].module_count(dev, program)):
        return None
    return ps.module_in_s(dev, program, name) / ctx["window"]["fits"] * 1e3

