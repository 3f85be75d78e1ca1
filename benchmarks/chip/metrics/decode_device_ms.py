"""Device time of the engine's decode-step program per decode step, over
the traced window (profiler trace: the program executions launched inside the
benchmark's host spans around each call, divided by the spans)."""
import profile_reduce as PR


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    t = PR.summed(PR.module_roles(tr)["decode"], tr.span)
    n = PR.span_count(tr, "decode")
    if n == 0 or t <= 0:
        return None
    return 1e3 * t / n
