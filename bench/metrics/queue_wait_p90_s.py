"""90th percentile of the scheduler's ``queued`` span over the requests
submitted in the traced window, in seconds: from ``submit`` to the
first staging span the KV pool granted. A request never staged inside
the window reads the time to its end."""

from harness import program_spans
from harness.readings import percentile


def read(ctx):
    laid = program_spans.of(ctx)
    if laid is None:
        return None
    p90 = percentile(program_spans.queue_waits_ns(laid), 90)
    return None if p90 is None else p90 / 1e9
