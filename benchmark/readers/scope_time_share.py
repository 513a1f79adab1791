"""Device time of the ops inside the program's named scopes, in percent.

``scopes``: the ops whose scope path (``tf_op`` in the trace's event
metadata) holds any of these ``jax.named_scope`` names, forward, backward
(``transpose(jvp(<scope>))``) and recompute alike; empty means every op.
``path_has``: keep only ops whose path holds this substring
(``rematted_computation`` is the recompute under remat). ``over``: the window,
or the device's busy time. ``scopes: "all"`` stands for every scope the
program has (``program_trace.SCOPES``): with ``over="busy"`` that is the share
of the device's work that carries a scope at all. None when no op of the
trace matches, as a program without the scopes gives."""

from harness import program_trace


def read(result, summary, ctx, scopes=(), path_has="", over="window"):
    red = program_trace.for_run(ctx)
    if red is None:
        return None
    if scopes == "all":
        scopes = program_trace.SCOPES
    total = program_trace.scope_seconds(red.by_path, scopes, path_has)
    if total == 0.0:
        return None
    return 100.0 * total / (red.window_s if over == "window" else red.busy_s)
