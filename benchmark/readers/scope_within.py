"""Device time of the ops that lie under BOTH an outer scope and one of some
inner scopes, in percent: ``scope_share`` with a second condition, for a
program that runs the same inner scopes (``attn.core``, ``attn.kv_write``, ...)
under more than one outer one (``attn.window``, ``attn.full``: a stack of
window and full attention layers).

``outer``: the ``jax.named_scope`` every counted op's path must hold.
``scopes``: it must also hold one of these (empty: the outer scope alone).
``over``: the window, or the device's busy time. None when no op of the trace
matches, as a program without the outer scope (the parent's) gives."""

from harness import program_trace


def seconds(by_path, outer, scopes=()):
    want = set(scopes)
    return sum(
        s for (path, _), s in by_path.items()
        if outer in (w := program_trace.words(path)) and (not want or want.intersection(w))
    )


def read(result, summary, ctx, outer, scopes=(), over="window"):
    red = program_trace.for_run(ctx)
    if red is None:
        return None
    total = seconds(red.by_path, outer, scopes)
    if total == 0.0:
        return None
    return 100.0 * total / (red.window_s if over == "window" else red.busy_s)
