"""Device idle time that fell inside host spans called ``span``, over the window."""


def read(result, summary, ctx, span):
    return 100.0 * summary["idle_by_span_s"].get(span, 0.0) / summary["window_s"]
