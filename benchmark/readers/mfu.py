"""End-to-end utilisation: tokens/s/chip x FLOPs a token requires / the chip's peak.
Recomputed operations do not count. Not a kernel's roofline share."""

from harness import opcount, peaks


def read(result, summary, ctx):
    rate = result.observed.get("tokens_per_s_chip")
    if rate is None:
        return None
    flops = opcount.train_flops_per_token(ctx.arch, ctx.traffic["sequence_length"])
    return 100.0 * rate * flops / peaks.peak(ctx.devices[0].device_kind, "bf16_flops")
