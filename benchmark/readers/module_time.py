"""Device time of the program runs whose name contains ``match``:
``stat="mean_ms"`` per run, or ``stat="share"`` of the window in percent."""


def runs(summary, match):
    return [d for n, ds in summary["module_runs_s"].items() if match in n for d in ds]


def read(result, summary, ctx, match, stat="mean_ms"):
    ds = runs(summary, match)
    if not ds:
        return None
    if stat == "mean_ms":
        return 1e3 * sum(ds) / len(ds)
    return 100.0 * sum(ds) / summary["window_s"]
