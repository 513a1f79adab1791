"""One statistic of the program's host spans called ``span`` that lie inside
the window (``serving.*`` and ``loop.*``, on the profiler's clock).

``stat``: ``mean_ms`` and ``p95_ms`` (nearest rank) of the spans' durations;
``self_mean_ms``, the mean of their self times (duration minus what their
children on the same thread cover); ``share``, their total over the window in
percent. ``minus``: span names whose time inside each span is taken off its
duration first (``serving.tick`` minus ``serving.host_blocked`` is the host's
own work in a tick). None when the trace has no such span."""

from harness import loadgen, program_trace


def read(result, summary, ctx, span, stat="mean_ms", minus=()):
    red = program_trace.for_run(ctx)
    uses = [u for u in red.uses if u.span.name == span] if red is not None else []
    if not uses:
        return None
    if stat == "self_mean_ms":
        return sum(u.self_ns for u in uses) / len(uses) / 1e6
    ms = [(u.span.dur - sum(u.below.get(m, 0.0) for m in minus)) / 1e6 for u in uses]
    if stat == "mean_ms":
        return sum(ms) / len(ms)
    if stat == "p95_ms":
        return loadgen.percentile(ms, 0.95)
    if stat == "share":
        return 100.0 * sum(ms) / 1e3 / red.window_s
    raise ValueError(f"span_stat has no stat {stat!r}")
