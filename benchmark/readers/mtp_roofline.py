"""The module's half of a self-drafting round (``mtp.draft``) as a share of
its HBM roofline: the bytes that half cannot avoid reading
(``families/<family>.py::mtp_step_bytes``: the module's block with the experts
its own tokens touched, the projection, the head, its layer of the resident
latents) over peak bytes/s, against the device time a round spends in the
round program's ops under ``scopes``.

The experts touched in the module's block are the engine's own count of that
layer, ``mtp_touched`` on the ``serving.commit`` spans. None where the family
has no such count, the trace no such program or the spans no such counter, as
a model without a module and the parent's program give."""

from harness import families, peaks, program_trace, reduce_trace
from readers import commit_counter, module_time, part_roofline


def read(result, summary, ctx, match, scopes):
    fam, obs = families.of(ctx.arch), result.observed
    runs = module_time.runs(summary, match)
    ms = commit_counter.metas(ctx, "mtp_touched")
    if not runs or not ms or not obs.get("resident_tokens") or not hasattr(fam, "mtp_step_bytes"):
        return None
    touched = sum(float(m["mtp_touched"]) for m in ms) / sum(float(m["moe_experts"]) for m in ms)
    need = fam.mtp_step_bytes(ctx.arch, obs["resident_tokens"], obs["rows"], touched)
    trace = program_trace.load(reduce_trace.find_xplane(ctx.trace_dir))
    seconds = part_roofline.decode_scope_seconds(trace, match, scopes) / len(runs)
    if seconds == 0.0:
        return None
    return 100.0 * need / peaks.peak(ctx.devices[0].device_kind, "hbm_bytes_per_s") / seconds
