"""Mean weight lag, in versions, of the tokens trained in the window: the
delta of the pipeline's lag histogram (`lag_stats()`, the program's
counter). Set by the simulated schedule and the cell's shape (slots x
rollout length / tokens per step), not by measured speed: it guards
on-policyness rather than timing."""


def read(ctx):
    hist = ctx.window["lag_hist"]
    n = sum(hist.values())
    if n <= 0:
        return None
    return sum(k * v for k, v in hist.items()) / n
