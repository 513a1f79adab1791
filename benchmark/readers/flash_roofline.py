"""The flash attention kernels' share of their roofline.

Operations: causal-halved QK^T and PV forward plus the five backward matmuls
(``opcount.flash_train_flops_per_seq``), for the sequences one chip processed
in the window. At T 1024 and head size 64 the kernels do about 350 FLOPs per
byte moved, above the v5e's 240: the compute bound applies, so the least time
is operations over peak FLOP/s. Divided by the kernels' device time."""

from harness import opcount, peaks


def read(result, summary, ctx, match):
    t = sum(v for n, v in summary["op_s"].items() if any(m in n for m in match))
    seqs = result.observed.get("sequences_per_chip_in_window")
    if t == 0.0 or not seqs:
        return None
    flops = opcount.flash_train_flops_per_seq(ctx.arch, ctx.traffic["sequence_length"]) * seqs
    return 100.0 * flops / peaks.peak(ctx.devices[0].device_kind, "bf16_flops") / t
