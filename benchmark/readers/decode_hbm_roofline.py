"""A decode step's share of its HBM roofline.

Bytes a step must read: every weight once plus the K and V of every resident
token once (``opcount.decode_step_min_bytes``; resident tokens are the closed
loop's constant). A decode step is memory-bound at these batch sizes, so the
least time is bytes over peak bytes/s; divided by the decode program's mean
device time per step."""

from harness import opcount, peaks
from readers import module_time


def read(result, summary, ctx, match):
    ds = module_time.runs(summary, match)
    obs = result.observed
    if not ds or not obs.get("resident_tokens"):
        return None
    b = opcount.decode_step_min_bytes(ctx.arch, obs["resident_tokens"], obs["rows"])
    t_min = b / peaks.peak(ctx.devices[0].device_kind, "hbm_bytes_per_s")
    return 100.0 * t_min / (sum(ds) / len(ds))
