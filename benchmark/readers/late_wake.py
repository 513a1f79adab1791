"""What the program's late-wake witness (``observability/witness.py``) saw in
the traced window, laid against the device's idle gaps.

The witness leaves one empty ``loop.late_wake`` span a late wake, one period
after it ended (an annotation cannot be opened in the past): the interval in
which this process could not run is
``[start - ended_ms_ago - late_ms, start - ended_ms_ago]`` on the span's own
clock. It also leaves one ``loop.witness_beat`` a second, which tells a
window in which nothing was late (0) from a program that has no witness
(None, as the parent's).

``stat``:

- ``late_ms``: the late intervals' time inside the window, ms;
- ``idle_late_share``: the first chip's idle time inside a late interval, over
  the window, %: the device waited for a host that did not run;
- ``idle_blocked_share``: the first chip's idle time while the engine thread's
  innermost span was ``serving.host_blocked`` and no late interval covered
  it, over the window, %: host and device each waiting for the other with
  every sleeper on time, which leaves the runtime or the transfer.

The gaps are taken as ``program_trace.idle_by_span`` takes them, device times
moved onto the host's clock by the offset ``program_trace.for_run`` estimated.
The run's xplane is parsed once for this reader and kept on ``ctx``.
"""

from harness import program_trace, reduce_trace

BEAT, LATE, BLOCKED = "loop.witness_beat", "loop.late_wake", "serving.host_blocked"


def trace_for(ctx):
    if not hasattr(ctx, "_late_wake_trace"):
        tr = program_trace.load(reduce_trace.find_xplane(ctx.trace_dir)) if ctx.trace_dir else None
        ctx._late_wake_trace = tr
        if tr is not None and tr.window is not None:
            report(tr, device_gaps(tr, ctx) or [], ctx.log)
    return ctx._late_wake_trace


def device_gaps(tr, ctx):
    """The first chip's idle intervals inside the window, on the host's clock
    (kept on ``ctx``); None without a device plane."""
    if not hasattr(ctx, "_late_wake_gaps"):
        plane = min(tr.ops, default=None)
        gaps = None
        if plane is not None:
            t0, t1 = tr.window
            red = program_trace.for_run(ctx)
            offset = red.offset["estimate_ns"] if red is not None and red.offset else 0.0
            ops = reduce_trace.clip([(o.name, o.start + offset, o.dur) for o in tr.ops[plane]], t0, t1)
            gaps = reduce_trace.gaps(ops, t0, t1)
        ctx._late_wake_gaps = gaps
    return ctx._late_wake_gaps


def late_wakes(tr):
    """(start ns, end ns, span) of every late wake in the trace: the span is
    left after the fact and says in its meta how long ago the interval ended."""
    for spans in tr.threads.values():
        for s in spans:
            if s.name == LATE:
                end = s.start - 1e6 * float(s.meta.get("ended_ms_ago", 0.0))
                yield end - 1e6 * float(s.meta.get("late_ms", 0.0)), end, s


def report(tr, gaps, log):
    """Every late wake of the trace into the run's log: where in the window,
    how long, what the sleeper outside and the collector cover of it, the
    engine thread's innermost spans meanwhile, and the device's idle gaps
    that touch it, each against the interval's two ends."""
    thread = program_trace.tick_thread(tr)
    segs = program_trace.innermost_segments(tr.threads[thread]) if thread else []
    for start, end, s in late_wakes(tr):
        outside_ms = float(s.meta.get("outside_ms", 0.0))
        verdict = ("unknown" if not s.meta.get("outside", 1)
                   else "machine" if 2e6 * outside_ms >= end - start else "process")
        during: dict = {}
        for a, b, name in segs:
            if min(b, end) > max(a, start):
                during[name] = during.get(name, 0.0) + (min(b, end) - max(a, start)) / 1e6
        idle = [f"{(b - a) / 1e6:.1f} ms from {(a - start) / 1e6:+.1f} ms of its start to {(b - end) / 1e6:+.1f} ms of its end"
                for a, b in gaps if min(b, end) > max(a, start) and b - a >= 1e6]
        log(f"late wake {(start - tr.window[0]) / 1e6:.1f} ms into the window: {(end - start) / 1e6:.1f} ms, "
            f"{verdict} (sleeper outside late {outside_ms:.1f} ms, collector {float(s.meta.get('gc_ms', 0.0)):.1f} ms); "
            "engine thread in " + (" ".join(f"{n}={ms:.1f}ms" for n, ms in sorted(during.items(), key=lambda kv: -kv[1])) or "no span")
            + "; device idle " + ("; ".join(idle) or "for no millisecond of it"))


def late_intervals(tr):
    """Sorted disjoint (start, end) ns inside the window in which the process could not run."""
    events = [(LATE, start, end - start) for start, end, _ in late_wakes(tr)]
    return reduce_trace.merged(reduce_trace.clip(events, *tr.window))


def shared(a, b):
    """The intervals that two sorted disjoint lists of (start, end) share."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if min(hi, b[k][1]) > max(lo, b[k][0]):
                out.append((max(lo, b[k][0]), min(hi, b[k][1])))
            k += 1
    return out


def total(intervals):
    return sum(hi - lo for lo, hi in intervals)


def read(result, summary, ctx, stat):
    tr = trace_for(ctx)
    if tr is None or tr.window is None:
        return None
    t0, t1 = tr.window
    if not any(s.name == BEAT and t0 <= s.start <= t1 for spans in tr.threads.values() for s in spans):
        return None
    late = late_intervals(tr)
    if stat == "late_ms":
        return total(late) / 1e6
    gaps = device_gaps(tr, ctx)
    if gaps is None:
        return None
    if stat == "idle_late_share":
        return 100.0 * total(shared(gaps, late)) / (t1 - t0)
    if stat == "idle_blocked_share":
        thread = program_trace.tick_thread(tr)
        if thread is None:
            return None
        blocked = [(a, b) for a, b, name in program_trace.innermost_segments(tr.threads[thread]) if name == BLOCKED]
        waiting = shared(gaps, blocked)
        return 100.0 * (total(waiting) - total(shared(waiting, late))) / (t1 - t0)
    raise ValueError(f"late_wake has no stat {stat!r}")
