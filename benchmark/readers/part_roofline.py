"""A part of the decode step's share of its HBM roofline, for a family that
counts its own bytes (``harness/families/<family>.py``: a latent cache and
experts of which a step touches some are not what ``opcount`` counts).

``part``: ``moe`` (the experts touched, the shared expert and the router:
``moe_step_bytes``), ``latent_attn`` (every resident token's latent in every
layer and the absorbed projection: ``latent_step_bytes``) or ``all``
(``decode_step_min_bytes``). The least time is bytes over peak bytes/s; the
time is the decode program's mean device time a run for ``all``, and for a
part the device time, a run, of the decode program's ops whose path holds one
of ``scopes``. The decode program's ops are those that share a ``program_id``
with an op traced under ``match``: XLA's TPU expansion of ``ragged_dot`` names
its custom calls itself and drops the path they were traced under, so those
are found by name and told from the prefill's by the program they run in.
The experts touched come from the engine's counter (``readers/moe_counter``).
None where the family has no such count, the trace no decode program, no
counter or no op in the scopes, as the parent's program gives."""

from harness import families, peaks, program_trace, reduce_trace
from readers import module_time, moe_counter


def decode_scope_seconds(tr, match, scopes):
    """Device seconds inside the window of the ops in ``scopes`` of the program
    traced under ``match``, each nanosecond counted for the innermost op running."""
    if tr.window is None:
        return 0.0
    t0, t1 = tr.window
    want, total = set(scopes), 0.0
    for ops in tr.ops.values():
        programs = {o.program_id for o in ops if match[len("jit_"):] in o.path}
        inside = [(max(o.start, t0), min(o.start + o.dur, t1), o) for o in ops
                  if o.program_id in programs and o.start + o.dur > t0 and o.start < t1]
        total += sum(self_ns for op, self_ns, _ in program_trace.self_times(inside)
                     if want.intersection(program_trace.words(op.path)))
    return total / max(len(tr.ops), 1) / 1e9


def read(result, summary, ctx, part, match, scopes=()):
    fam, obs = families.of(ctx.arch), result.observed
    runs = module_time.runs(summary, match)
    touched = moe_counter.touched_share(ctx)
    if not runs or touched is None or not obs.get("resident_tokens") or not hasattr(fam, "moe_step_bytes"):
        return None
    if part == "all":
        need = fam.decode_step_min_bytes(ctx.arch, obs["resident_tokens"], obs["rows"], touched)
        seconds = sum(runs) / len(runs)
    else:
        need = (fam.moe_step_bytes(ctx.arch, touched) if part == "moe"
                else fam.latent_step_bytes(ctx.arch, obs["resident_tokens"]))
        trace = program_trace.load(reduce_trace.find_xplane(ctx.trace_dir))
        seconds = decode_scope_seconds(trace, match, scopes) / len(runs)
    if seconds == 0.0:
        return None
    return 100.0 * need / peaks.peak(ctx.devices[0].device_kind, "hbm_bytes_per_s") / seconds
