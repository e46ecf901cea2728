"""Median self time of the scheduler's ``step`` span over the traced
window, in ms: its duration less the ``compile`` and ``retire`` spans
under it, which leaves the host's own work at a boundary."""

from harness import program_spans
from harness.readings import percentile


def read(ctx):
    laid = program_spans.of(ctx)
    if laid is None:
        return None
    mid = percentile(program_spans.step_self_ns(laid), 50)
    return None if mid is None else mid / 1e6
