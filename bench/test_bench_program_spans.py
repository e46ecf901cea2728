"""The scheduler's own spans laid on the trace's clock: the offset found
from the step spans, idle device time credited to the innermost span,
and the metrics that read them. Planes are built by hand, as in
``test_bench_trace.py``; the program's clock runs ``SHIFT`` ns ahead of
the trace's."""

from types import SimpleNamespace as NS

import pytest

from harness import manifest, program_spans, trace

SHIFT = 123_456_789
METRICS = ("compile_idle_share", "sched_idle_share", "step_self_ms",
           "queue_wait_p90_s")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def planes():
    busy = [(0, 1200), (1450, 3000), (3800, 3850), (4100, 5500),
            (5800, 6160), (6250, 6400), (8800, 10000)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        ev("fusion", s, e - s) for s, e in busy])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 10000), ev("bench.step", 1000, 3000),
        ev("bench.step", 6000, 3000), ev("serve.step", 1100, 2800)])])
    return [host, dev]


def program():
    """(name, start, end, parent, rid) on the trace's clock: two steps,
    the first compiling inside staging, and three requests' queues."""
    return [
        ("queued", -100, 300, None, 9),      # submitted before the window
        ("queued", 500, 1200, None, 0),
        ("step", 1100, 3900, None, None),
        ("stage", 1150, 1500, 2, None),
        ("compile", 1200, 1450, 3, None),
        ("staged", 1200, 1700, None, 0),
        ("grow", 1500, 1600, 2, None),
        ("dispatch", 1600, 1700, 2, None),
        ("retire", 3000, 3800, 2, None),
        ("queued", 5000, None, None, 1),     # never staged
        ("step", 6100, 8900, None, None),
        ("stage", 6150, 6300, 10, None),
        ("dispatch", 6160, 6250, 11, None),
        ("dispatch", 6300, 6400, 10, None),
        ("retire", 6400, 8800, 10, None),
    ]


def ctx_of(spans=None, tracer=True, traced=True):
    spans = program() if spans is None else spans
    moved = [(n, s + SHIFT, None if e is None else e + SHIFT, p, r)
             for n, s, e, p, r in spans]
    srv = NS(tracer=NS(spans=moved, dropped=0)) if tracer else NS()
    stats0 = {"compile_calls": 3, "retire_waits": 1}
    stats1 = {"compile_calls": 4, "retire_waits": 2}
    return NS(trace=trace.reduce(planes(), (0, 10000), 1) if traced else None,
              client=NS(srv=srv), notes=[],
              delta=lambda k: stats1[k] - stats0[k])


def test_idle_time_goes_to_the_innermost_span():
    laid = program_spans.of(ctx_of())
    # idle: [1200,1450) compile; [3000,3800) retire; [3850,3900) the
    # first step itself, [3900,4100) and [5500,5800) outside any step;
    # [6160,6250) a dispatch inside staging; [6400,8800) retire
    assert laid.idle_by == {"compile": 250, "retire": 3200, "step": 50,
                            "dispatch": 90, None: 500}


def test_innermost_leaves_time_outside_every_span_out():
    spans = [("step", 0, 100), ("stage", 10, 40), ("compile", 20, 30),
             ("step", 200, 300)]
    assert program_spans.innermost(spans) == [
        (0, 10, "step"), (10, 20, "stage"), (20, 30, "compile"),
        (30, 40, "stage"), (40, 100, "step"), (200, 300, "step")]


def test_the_offset_is_recovered_from_shifted_step_spans():
    ctx = ctx_of()
    laid = program_spans.of(ctx)
    assert [sp[:3] for sp in laid.spans] == [sp[:3] for sp in program()]
    assert f"offset_ns median {-SHIFT} quartile_range 0.0" in ctx.notes[0]
    # the harness's spans enclose the program's unevenly: the median of
    # the midpoint gaps, and their quartile range, come back
    harness = [(1000 * j, 1000 * j + 800) for j in range(10)]
    prog = [(s + 10 + SHIFT, e - 2 * j + SHIFT)
            for j, (s, e) in enumerate(harness)]
    # gaps -SHIFT - 5 + j, for j = 0..9: median -SHIFT - 0.5, to a ns
    assert program_spans.clock_offset(prog, harness) == (-SHIFT, 5.5, 10)


def test_compile_scheduler_and_client_shares_sum_to_the_idle_share():
    ctx = ctx_of()
    got = {m: manifest.reader(f"{m}.decode")(ctx) for m in METRICS}
    assert got["compile_idle_share"] == pytest.approx(2.5)
    assert got["sched_idle_share"] == pytest.approx(33.4)
    client = program_spans.of(ctx).idle_share([None])
    assert client == pytest.approx(5.0)
    idle = 100.0 * (1 - ctx.trace.busy_ns / ctx.trace.window_ns)
    assert (got["compile_idle_share"] + got["sched_idle_share"] + client
            == pytest.approx(idle))
    assert any("client 5.0" in n for n in ctx.notes)
    assert any("retire_waits 1 steps 2" in n for n in ctx.notes)


def test_step_self_time_and_queue_wait():
    ctx = ctx_of()
    # step 1: 2800 less compile 250 and retire 800; step 2: 2800 less 2400
    assert sorted(program_spans.step_self_ns(program_spans.of(ctx))) == [
        400, 1750]
    assert manifest.reader("step_self_ms.sessions")(ctx) == pytest.approx(
        1075 / 1e6)
    # rid 0 waited 700 ns, rid 1 to the window's end (5000); rid 9 came
    # before the window
    assert manifest.reader("queue_wait_p90_s.decode")(ctx) == pytest.approx(
        (700 + 0.9 * 4300) / 1e9)


@pytest.mark.parametrize("kind", ["no tracer", "untraced", "no steps"])
def test_nothing_to_read_without_the_program_spans(kind):
    ctx = {"no tracer": lambda: ctx_of(tracer=False),
           "untraced": lambda: ctx_of(traced=False),
           "no steps": lambda: ctx_of(spans=[])}[kind]()
    for m in METRICS:
        assert manifest.reader(f"{m}.decode")(ctx) is None
