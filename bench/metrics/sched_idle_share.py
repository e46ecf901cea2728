"""Share (%) of the traced window in which the device was idle while
the host was inside the scheduler's ``step`` span but not in a
``compile`` span under it: staging, admission, span growth, dispatch
and the retirement copy. Earlier lines give the idle share outside
every ``step`` (the client's), the three shares beside the trace's
``idle_share``, and the retirement copies that waited per step."""

from harness import program_spans


def read(ctx):
    laid = program_spans.of(ctx)
    if laid is None:
        return None
    compile_ = laid.idle_share([program_spans.COMPILE])
    client = laid.idle_share([None])
    sched = laid.idle_share([n for n in laid.idle_by
                             if n not in (None, program_spans.COMPILE)])
    red = ctx.trace
    ctx.notes.append(f"idle shares compile {compile_} scheduler {sched} "
                     f"client {client} sum {compile_ + sched + client} "
                     f"idle_share {100.0 * (1 - red.busy_ns / red.window_ns)}")
    steps = laid.count(program_spans.STEP)
    ctx.notes.append(f"retire_waits {ctx.delta('retire_waits')} steps {steps}"
                     f" per step {ctx.delta('retire_waits') / max(steps, 1)}")
    return sched
