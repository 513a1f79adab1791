"""Counters the engine writes on its ``serving.commit`` spans, over the decode
windows committed inside the traced window, for a model that holds a share of
its experts and keeps a state slot a row (``generation/serving.py``):
``moe_routed`` ((token, choice) pairs routed, wherever the expert lives),
``moe_routed_here`` (pairs that met an expert held here), ``moe_busiest``,
``moe_experts`` (experts held), ``state_slots`` (slots owned at the commit).

``stat="routed_here_share"``: pairs that met an expert held over pairs routed,
percent (a quarter of the experts under even routing: 25).
``stat="load_max_over_mean"``: the busiest held expert's pairs over the mean
held expert's (``readers/moe_counter`` divides by all pairs routed, which is
the same number only where every expert is held).
``stat="state_slots_peak"``: the most slots owned at a commit over the slots
there are (the batch rows, ``observed["rows"]``), percent.
None when the trace has no such span or the spans carry no such counter, as a
dense model's and the parent's do."""

from harness import program_trace


def metas(ctx, key):
    red = program_trace.for_run(ctx)
    found = [u.span.meta for u in red.uses if u.span.name == "serving.commit"] if red is not None else []
    return [m for m in found if key in m]


def read(result, summary, ctx, stat):
    if stat == "state_slots_peak":
        ms = metas(ctx, "state_slots")
        rows = result.observed.get("rows")
        return 100.0 * max(float(m["state_slots"]) for m in ms) / rows if ms and rows else None
    ms = metas(ctx, "moe_routed_here")
    here = sum(float(m["moe_routed_here"]) for m in ms)
    if not ms or not here:
        return None
    if stat == "routed_here_share":
        return 100.0 * here / sum(float(m["moe_routed"]) for m in ms)
    if stat == "load_max_over_mean":
        return sum(float(m["moe_busiest"]) for m in ms) * float(ms[0]["moe_experts"]) / here
    raise ValueError(f"commit_counter has no stat {stat!r}")
