"""Device time of the trainer's step program per optimizer step, over the
traced window (profiler trace: program executions named after the jitted
`train_step`; steps counted by the benchmark's host spans)."""
import profile_reduce as PR


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    t = PR.summed(PR.module_roles(tr)["train_step"], tr.span)
    n = PR.span_count(tr, "train_step")
    if n == 0 or t <= 0:
        return None
    return 1e3 * t / n
