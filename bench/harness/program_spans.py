"""The scheduler's own spans, laid on the device trace's clock, and the
idle device time each covers.

The paged server keeps its spans in memory while the profiler collects
(``srv.tracer``: ``repro.launch.tracing``), stamped on the host's
realtime clock. They are laid on the trace's clock by the median gap
between the midpoints of the harness's ``step`` spans (``bench.step``
in the trace) and of the program's ``step`` spans, which the harness's
enclose; the gap's median and quartile range go on an earlier line.
Busy device time is rebuilt from the trace's operations (chip 0) with
``trace.union``, and each idle stretch of the window (``trace.holes``)
is credited to the innermost program span that covers it, or to none:
the client's.

A program without the tracer, or a run without the trace, gives
nothing to read: ``of`` returns None and so does every reader.
"""

from __future__ import annotations

import bisect
import dataclasses
import statistics
from collections import defaultdict

from harness import trace

STEP = "step"
COMPILE = "compile"
QUEUED = "queued"
RETIRE = "retire"


@dataclasses.dataclass
class Laid:
    """Program spans on the trace's clock, as tuples ``(name, start,
    end, parent, rid)`` in the tracer's order (``end`` None while open,
    ``parent`` an index into ``spans``), and the window's idle time by
    the innermost nested span covering it (None: outside every one)."""

    spans: list
    window: tuple[int, int]
    idle_by: dict

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def idle_share(self, names) -> float:
        """% of the window idle under the innermost spans ``names``."""
        return 100.0 * sum(self.idle_by.get(n, 0) for n in names) \
            / self.window_ns

    def count(self, name: str) -> int:
        return sum(sp[0] == name for sp in self.spans)


def of(ctx) -> Laid | None:
    """The run's program spans, laid once per context."""
    memo = vars(ctx)
    if "_program_spans" not in memo:
        memo["_program_spans"] = _lay(ctx)
    return memo["_program_spans"]


def _lay(ctx) -> Laid | None:
    tracer = getattr(getattr(ctx.client, "srv", None), "tracer", None)
    if ctx.trace is None or tracer is None:
        return None
    spans = [tuple(sp) for sp in tracer.spans]
    program = [(s, e) for name, s, e, _, rid in spans
               if name == STEP and rid is None and e is not None]
    harness = sorted((s, e) for name, s, e in ctx.trace.spans
                     if name == STEP)
    got = clock_offset(program, harness)
    if got is None:
        ctx.notes.append(f"program spans {len(spans)}: no step to pair "
                         f"({len(program)} program, {len(harness)} harness)")
        return None
    offset, spread, paired = got
    ctx.notes.append(f"program span clock offset_ns median {offset} "
                     f"quartile_range {spread} steps paired {paired} of "
                     f"{len(program)} program, {len(harness)} harness; "
                     f"spans {len(spans)} dropped {tracer.dropped}")
    spans = [(name, s + offset, None if e is None else e + offset, parent,
              rid)
             for name, s, e, parent, rid in spans]
    lo, hi = ctx.trace.window
    busy = trace.union(trace.clip([(op.start, op.end) for op in ctx.trace.ops],
                                  lo, hi))
    nested = [(name, s, e) for name, s, e, _, rid in spans
              if rid is None and e is not None]
    return Laid(spans=spans, window=(lo, hi),
                idle_by=idle_by_span(list(trace.holes(busy, lo, hi)),
                                     innermost(nested)))


def clock_offset(program, harness):
    """(median, quartile range, pairs) of the harness's step midpoint
    less the program's, in ns, the median rounded to a whole ns. Each
    program step is paired with the harness step its midpoint falls in,
    once moved by the gap between the first of each; None when no step
    pairs."""
    if not program or not harness:
        return None
    program, harness = sorted(program), sorted(harness)
    guess = harness[0][0] - program[0][0]
    starts = [s for s, _ in harness]
    gaps = []       # less ``guess``: the clocks' origins lie ~2**60 ns apart
    for s, e in program:
        mid = (s + e) // 2 + guess
        j = bisect.bisect_right(starts, mid) - 1
        if j >= 0 and harness[j][1] >= mid:
            gaps.append((harness[j][0] + harness[j][1] - s - e) / 2 - guess)
    if not gaps:
        return None
    if len(gaps) < 2:
        return guess + round(gaps[0]), 0.0, 1
    q1, q2, q3 = statistics.quantiles(gaps, n=4)
    return guess + round(q2), q3 - q1, len(gaps)


def innermost(spans) -> list[tuple[int, int, str]]:
    """Properly nested spans ``(name, start, end)`` as the disjoint
    stretches ``(start, end, name)`` of the innermost span open in
    each, in time order; time outside every span is left out."""
    out = []
    stack: list[tuple[str, int]] = []
    t = None
    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            if end > t:
                out.append((t, end, top))
                t = end
        if stack and s > t:
            out.append((t, s, stack[-1][0]))
        stack.append((name, e))
        t = s
    while stack:
        top, end = stack.pop()
        if end > t:
            out.append((t, end, top))
            t = end
    return out


def idle_by_span(idle, stretches) -> dict:
    """Nanoseconds of the sorted, disjoint ``idle`` intervals under each
    name of ``stretches`` (from ``innermost``); None: under none."""
    out: dict = defaultdict(float)
    j = 0
    for s, e in idle:
        left = e - s
        while j < len(stretches) and stretches[j][1] <= s:
            j += 1
        k = j
        while k < len(stretches) and stretches[k][0] < e:
            a, b, name = stretches[k]
            o = min(e, b) - max(s, a)
            if o > 0:
                out[name] += o
                left -= o
            k += 1
        out[None] += left
    return dict(out)


def step_self_ns(laid: Laid) -> list[int]:
    """Per ``step`` that opened inside the window: its duration less
    the ``compile`` and ``retire`` spans under it."""
    lo, hi = laid.window
    root: dict[int, int] = {}
    self_ns: dict[int, int] = {}
    for i, (name, s, e, parent, rid) in enumerate(laid.spans):
        if rid is not None or e is None:
            continue
        if name == STEP and parent is None:
            if lo <= s < hi:
                root[i] = i
                self_ns[i] = e - s
        elif parent in root:
            root[i] = root[parent]
            if name in (COMPILE, RETIRE):
                self_ns[root[i]] -= e - s
    return list(self_ns.values())


def queue_waits_ns(laid: Laid) -> list[int]:
    """Per request submitted inside the window: from submit to its
    first staging span, or to the window's end if that came later."""
    lo, hi = laid.window
    return [(hi if e is None else min(e, hi)) - s
            for name, s, e, _, rid in laid.spans
            if name == QUEUED and rid is not None and lo <= s < hi]
