"""Share of its roofline reached by the fused head + cross-entropy kernels
(`_fwd_kernel`, `_bwd_dh_kernel`, `_bwd_dw_kernel` of
kernels/fused_logprob.py: the named custom calls in the train-step
program, whose only Pallas kernels they are; the trace names them after
JAX's transformations, `jvp__` and `transpose_jvp___`) over the window: 6 N D V FLOPs per optimizer
step at the pack's N rows (forward logits, backward dh and dW; the
backward's recomputed logits are not counted) over the kernels' summed
device time. Bound by compute."""
import flops as FL
import profile_reduce as PR


def read(ctx):
    tr, w = ctx.trace, ctx.window
    if tr is None or not ctx.peak:
        return None
    ops = PR.kernels(tr, "train_step")
    t = PR.summed(ops, tr.span)
    n_rows = w["rows"] * ctx.workload["pipeline"]["pack_seq"]
    if t <= 0 or n_rows <= 0:
        return None
    fl, by = FL.fused_loss_cost(ctx.config, n_rows)
    least = max(fl / ctx.peak["bf16_flops"], by / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / t
