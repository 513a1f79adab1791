"""The engine's routing counters of a dropless expert model, over the decode
windows committed inside the traced window. The engine adds each window's
counters into ``stats["moe_*"]`` and writes the window's own totals on its
``serving.commit`` span: ``moe_steps``, ``moe_layers``, ``moe_experts``,
``moe_touched`` (experts that got a token, summed over expert layers and
steps), ``moe_routed`` ((token, choice) pairs) and ``moe_busiest`` (the
busiest expert's pairs, summed over expert layers).

``stat="touched_share"``: experts touched a step over experts held, percent.
``stat="load_max_over_mean"``: the busiest expert's pairs over the mean
expert's, layer by layer and window by window (1 = even).
None when the trace has no such span or the spans carry no counters, as a
dense model's and the parent's do."""

from harness import program_trace


def totals(ctx):
    """Sums of the counters over the window's commits, or None."""
    red = program_trace.for_run(ctx)
    metas = [u.span.meta for u in red.uses if u.span.name == "serving.commit"] if red is not None else []
    metas = [m for m in metas if "moe_steps" in m]
    if not metas:
        return None
    keys = ("moe_steps", "moe_touched", "moe_routed", "moe_busiest")
    out = {k: sum(float(m[k]) for m in metas) for k in keys}
    out["slots"] = sum(float(m["moe_steps"]) * float(m["moe_layers"]) * float(m["moe_experts"]) for m in metas)
    out["experts"] = float(metas[0]["moe_experts"])
    return out


def touched_share(ctx):
    """Experts touched a step over experts held, as a fraction, or None."""
    t = totals(ctx)
    return t["moe_touched"] / t["slots"] if t and t["slots"] else None


def read(result, summary, ctx, stat):
    t = totals(ctx)
    if t is None:
        return None
    if stat == "touched_share":
        return 100.0 * t["moe_touched"] / t["slots"]
    if stat == "load_max_over_mean":
        return t["moe_busiest"] * t["experts"] / t["moe_routed"] if t["moe_routed"] else None
    raise ValueError(f"moe_counter has no stat {stat!r}")
