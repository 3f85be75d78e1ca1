"""Device time of the trainer's step program per optimizer step, over the
traced window (profiler trace: program executions named after the jitted
`train_step`; steps counted by the benchmark's host spans). Each chip's
plane is reduced on its own and the planes that ran the step are
averaged: on a mesh every chip runs its share of each step."""
import profile_reduce as PR


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    per = PR.role_seconds_by_plane(tr, "train_step")
    n = PR.span_count(tr, "train_step")
    if n == 0 or not per:
        return None
    return 1e3 * (sum(per) / len(per)) / n
