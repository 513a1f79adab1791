"""One small reader per kind of per-layer metric: ``read(result, summary, ctx, **args)``.
A reader that finds nothing to read returns None and the metric is left out."""
