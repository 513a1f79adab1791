"""The paged attention kernel's share of its HBM roofline, one kind of layer
at a time, in a stack that keeps two cache lifetimes.

``kind``: ``"full"`` (bytes: K and V of every resident token, the rows' whole
lengths, in each full layer) or ``"window"`` (K and V of what lies inside the
window, in each window layer): ``harness/families/<family>.py::attn_step_bytes``
on the closed loop's constants (a row's length is uniform over [prompt,
prompt + output)). The least time is bytes over peak bytes/s; the time is the
device time a run of the decode program's ops under both ``attn.<kind>`` and
one of ``scopes`` (``attn.core``: the kernel). The decode program's ops are
those that share a ``program_id`` with an op traced under ``match``.
None where the family counts no such bytes, or the trace has no decode program
or no op under the outer scope, as the parent's program gives."""

from harness import families, peaks, program_trace, reduce_trace
from readers import module_time


def kind_seconds(tr, match, outer, scopes):
    """Device seconds inside the window of the decode program's ops under
    ``outer`` and one of ``scopes``, each nanosecond counted for the innermost op."""
    if tr.window is None:
        return 0.0
    t0, t1 = tr.window
    want, total = set(scopes), 0.0
    for ops in tr.ops.values():
        programs = {o.program_id for o in ops if match[len("jit_"):] in o.path}
        inside = [(max(o.start, t0), min(o.start + o.dur, t1), o) for o in ops
                  if o.program_id in programs and o.start + o.dur > t0 and o.start < t1]
        for op, self_ns, _ in program_trace.self_times(inside):
            w = program_trace.words(op.path)
            if outer in w and want.intersection(w):
                total += self_ns
    return total / max(len(tr.ops), 1) / 1e9


def read(result, summary, ctx, kind, match, scopes=("attn.core",)):
    fam, obs, tr = families.of(ctx.arch), result.observed, ctx.traffic
    runs = module_time.runs(summary, match)
    if not runs or not hasattr(fam, "attn_step_bytes") or not obs.get("resident_tokens"):
        return None
    tokens = (fam.window_tokens(ctx.arch, obs["rows"], tr["prompt_tokens"], tr["output_tokens"])
              if kind == "window" else obs["resident_tokens"])
    need = fam.attn_step_bytes(ctx.arch, kind, tokens)
    trace = program_trace.load(reduce_trace.find_xplane(ctx.trace_dir))
    seconds = kind_seconds(trace, match, f"attn.{kind}", scopes) / len(runs)
    if seconds == 0.0:
        return None
    return 100.0 * need / peaks.peak(ctx.devices[0].device_kind, "hbm_bytes_per_s") / seconds
