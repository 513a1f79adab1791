"""A state-space layer's decode step against its roofline, for a family whose
recurrent layers keep a state a row (``harness/families/<family>.py``:
``ssm_step_bytes``). ``readers/kda_roofline.py`` asks a family for ``kda_*`` by
name; this is its counterpart for ``ssm_*``.

``ssm.step`` of the decode program moves every row's state once in and once out
and the token's x, B, C, delta and y; the least time is those bytes over peak
bytes/s, the time the scope's device time over the runs of the program traced
under ``match``. None where the family has no such count, the trace no such
program or no op in the scope, as the parent's program gives."""

from harness import families, peaks, program_trace, reduce_trace
from readers import module_time, part_roofline


def read(result, summary, ctx, match, scopes):
    fam = families.of(ctx.arch)
    runs = module_time.runs(summary, match)
    if not runs or not hasattr(fam, "ssm_step_bytes"):
        return None
    trace = program_trace.load(reduce_trace.find_xplane(ctx.trace_dir))
    seconds = part_roofline.decode_scope_seconds(trace, match, scopes)
    if seconds == 0.0:
        return None
    least = len(runs) * fam.ssm_step_bytes(ctx.arch, result.observed["rows"])
    return 100.0 * least / peaks.peak(ctx.devices[0].device_kind, "hbm_bytes_per_s") / seconds
