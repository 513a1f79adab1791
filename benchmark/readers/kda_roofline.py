"""The KDA recurrence's share of its roofline, decode step and prefill, for a
family whose linear-attention layers keep a state a row
(``harness/families/<family>.py``: ``kda_step_bytes``, ``kda_chunk_ops_bytes``).

``part="step"``: ``kda.step`` of the decode program moves every row's state
once in and once out and the token's q, k, v, log-decay and beta; the least
time is those bytes over peak bytes/s, the time the scope's device time a run
of the program traced under ``match``.
``part="chunk"``: ``kda.chunk`` of the prefill program traced under ``match``;
the least time is the larger of its operations over the peak operation rate
and its bytes over peak bytes/s, for the prompt tokens the engine prefilled in
the window (``observed["prefill_tokens"]``); the time is the scope's device
time in the window.
None where the family has no such count, the trace no such program or no op
in the scope, as the parent's program gives."""

from harness import families, peaks, program_trace, reduce_trace
from readers import module_time, part_roofline


def read(result, summary, ctx, part, match, scopes):
    fam, obs = families.of(ctx.arch), result.observed
    runs = module_time.runs(summary, match)
    if not runs or not hasattr(fam, "kda_step_bytes"):
        return None
    trace = program_trace.load(reduce_trace.find_xplane(ctx.trace_dir))
    seconds = part_roofline.decode_scope_seconds(trace, match, scopes)
    if seconds == 0.0:
        return None
    kind = ctx.devices[0].device_kind
    if part == "step":
        least = len(runs) * fam.kda_step_bytes(ctx.arch, obs["rows"]) / peaks.peak(kind, "hbm_bytes_per_s")
    elif part == "chunk":
        if not obs.get("prefill_tokens"):
            return None
        ops, moved = fam.kda_chunk_ops_bytes(ctx.arch, obs["prefill_tokens"])
        least = max(ops / peaks.peak(kind, "bf16_flops"), moved / peaks.peak(kind, "hbm_bytes_per_s"))
    else:
        raise ValueError(f"kda_roofline has no part {part!r}")
    return 100.0 * least / seconds
