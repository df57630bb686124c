"""The program's phases (``repro.serving.telemetry.phase``) in a reduced
profiler trace (``trace["host"]``, see ``trace.py``), per decode step.

A step runs from one ``decode/dispatch`` phase to the next. The readers
take the whole steps of the window: from the first dispatch that starts
inside it to the last, each phase counted at its whole duration when it
starts in that span. A step cut by either edge of the window counts in
neither the sum nor the number of steps, so it cannot skew a mean over a
few steps: the profiler starts while a step is in flight, and that
step's readback would otherwise land in the window without its
dispatch. A trace of a program without phases has no steps, and every
reader here then returns None.
"""
from __future__ import annotations

STEP = "decode/dispatch"


def phase_ns(trace: dict, names, span=None) -> tuple[int, float]:
    """(count, ns) of the host events named exactly one of ``names``
    that start inside ``span`` (default: the window)."""
    names = frozenset(names)
    lo, hi = span or trace["window"]
    hits = [d for n, s, d in trace["host"] if n in names and lo <= s < hi]
    return len(hits), float(sum(hits))


def ms_per_step(trace: dict, names) -> float | None:
    """Milliseconds of the phases ``names`` per whole decode step in the
    window, or None with fewer than two dispatches in it."""
    w0, w1 = trace["window"]
    starts = sorted(s for n, s, _ in trace["host"]
                    if n == STEP and w0 <= s < w1)
    if len(starts) < 2:
        return None
    _, ns = phase_ns(trace, names, (starts[0], starts[-1]))
    return ns / 1e6 / (len(starts) - 1)
