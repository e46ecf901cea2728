"""Share (%) of the traced window in which the device was idle while
the host was inside the scheduler's ``compile`` span: the first call of
a program the executable cache (``_compiled``) had just built, which
traces, lowers and compiles it or loads it from the persistent cache."""

from harness import program_spans


def read(ctx):
    laid = program_spans.of(ctx)
    if laid is None:
        return None
    ctx.notes.append(f"compile spans {laid.count(program_spans.COMPILE)} "
                     f"compile_calls in the window "
                     f"{ctx.delta('compile_calls')}")
    return laid.idle_share([program_spans.COMPILE])
