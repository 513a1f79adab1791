"""The sum of one counter that the program writes on a host span, over the
spans of that name inside the traced window (``serving.release_pages`` carries
``released``: window-layer pages given back behind the window that tick).
None when the trace has no such span or the spans carry no such counter, as
the parent's do."""

from harness import program_trace


def read(result, summary, ctx, span, key):
    red = program_trace.for_run(ctx)
    metas = [u.span.meta for u in red.uses if u.span.name == span] if red is not None else []
    metas = [m for m in metas if key in m]
    if not metas:
        return None
    return sum(float(m[key]) for m in metas)
