"""Share of the traced window in which no operation ran on the device,
averaged over the chips used (profiler trace)."""
import numpy as np

import profile_reduce as PR


def read(ctx):
    tr = ctx.trace
    used = [k for k in tr.devices if tr.ops[k]] if tr is not None else []
    span = tr.span[1] - tr.span[0] if tr is not None else 0.0
    if not used or span <= 0:
        return None
    return 100.0 * float(np.mean(
        [1.0 - PR.busy_seconds(tr.ops[k], tr.span) / span for k in used]))
