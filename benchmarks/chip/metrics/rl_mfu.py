"""Model FLOPs of the whole RL step over the window, as a share of the
chips' bf16 peak: trainer forward and backward on the trained rollouts,
engine forward on every sampled and prefilled token. Recomputation is not
counted. Counts from the window's host-side counters, peak from peaks.py."""
import flops as FL


def read(ctx):
    w, c = ctx.window, ctx.config
    if not ctx.peak or w["window_s"] <= 0:
        return None
    total = (FL.train_flops(c, w["seq_tokens"], w["ctx_sum"])
             + FL.forward_flops(c, w["decode_tokens"], w["decode_ctx"])
             + FL.forward_flops(c, w["prefill_tokens"], w["prefill_tokens"]))
    if total <= 0:
        return None
    return 100.0 * total / (w["window_s"] * ctx.chips
                            * ctx.peak["bf16_flops"])
