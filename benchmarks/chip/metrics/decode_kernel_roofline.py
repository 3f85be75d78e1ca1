"""Share of its roofline reached by the decode-attention kernel
(`_decode_kernel` of kernels/decode_attention.py, the `flash_decode`
custom call inside the decode-step program) over the window: the least
time the work could take, max(FLOPs / peak, bytes / HBM bandwidth), over
the kernel's summed device time. The work is each step's active slots at
their actual cache lengths: their K/V, q and o. It is bound by memory
bandwidth (about 4 FLOPs per byte
with 4 query heads per K/V head)."""
import flops as FL
import profile_reduce as PR

NEEDLES = ("flash_decode",)


def read(ctx):
    tr, w = ctx.trace, ctx.window
    if tr is None or not ctx.peak:
        return None
    ops = PR.matching(PR.kernels(tr, "decode"), NEEDLES)
    t = PR.summed(ops, tr.span)
    if t <= 0 or w["decode_ctx"] <= 0:
        return None
    fl, by = FL.decode_kernel_cost(ctx.config, w["decode_ctx"],
                                   w["decode_tokens"])
    least = max(fl / ctx.peak["bf16_flops"], by / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / t
