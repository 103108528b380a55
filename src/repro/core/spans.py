"""Host spans at the program's layer boundaries, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation`` named ``apcvfl.<name>``, so
it lands in the same trace as the device's programs.  With the profiler
off a span costs about a microsecond, so spans mark layers, never steps,
epochs or rows, and never sit inside jitted code.
"""
from __future__ import annotations

import jax

PREFIX = "apcvfl."


def span(name: str) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name)
