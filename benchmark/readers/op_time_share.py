"""Device time of the ops whose name contains any of ``match``, over the
window (``over="window"``) or over the device's busy time (``over="busy"``).
Only the synchronous ``XLA Ops`` line is read: for a collective that is the
time the chip spends in it, not the time it is in flight beside compute."""


def read(result, summary, ctx, match, over="window"):
    total = sum(v for n, v in summary["op_s"].items() if any(m in n for m in match))
    if total == 0.0:
        return None
    return 100.0 * total / summary["window_s" if over == "window" else "busy_s"]
