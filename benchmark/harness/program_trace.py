"""The program's own scopes and spans, read from the run's ``.xplane.pb``.

``reduce_trace`` reads the trace through ``jax.profiler.ProfileData``, which
shows an event's name and time and nothing else. The file holds more, on each
device plane's *event metadata*: ``tf_op`` (JAX's ``op_name``, the path a
``jax.named_scope`` lands in: ``jit(step_fn)/transpose(jvp(loss.ce))/...``),
``hlo_category`` and ``program_id``. This module reads the file as a protobuf
with a small wire-format reader (five message types; importing the generated
classes through TensorFlow costs 14 s and 1 GB where the chip runs) and gives:

- seconds per scope: every nanosecond the device is busy goes to one op (the
  innermost event running, so a ``while`` keeps only what its body's ops do
  not cover) and every op to the innermost scope of ``SCOPES`` in its path, or
  to ``(unscoped)``; the rows sum to the busy time, averaged over chips;
- the program's host spans (``serving.*``, ``loop.*``) per thread, with the
  self time of each (its duration minus what its children on that thread
  cover);
- the device's idle gaps attributed to the innermost program span of the
  thread that carries ``serving.tick``;
- an estimate of the offset between the device's clock and the host's, from
  causality: a ``serving.host_blocked`` span cannot end before the decode
  program whose result it reads has ended, and that program cannot start
  before the ``serving.dispatch_window`` span that launched it began.

A trace of a program without these scopes or spans gives empty tables and
``None`` from the readers: nothing here raises for their absence.
"""

from __future__ import annotations

import re
import struct
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from harness import reduce_trace

# The named scopes of the compiled programs (PERF.md, "spans, scopes and counters").
SCOPES = (
    "embed", "blk.norm", "attn.qkv", "attn.rope", "attn.kv_write", "attn.paged_gather",
    "attn.core", "attn.out", "mlp", "final_norm", "lm_head", "loss.ce", "sample",
    "optimizer", "grad_clip", "microbatch",
)
UNSCOPED = "(unscoped)"
SPAN_PREFIXES = ("serving.", "loop.")
WINDOW = "bench.window"
DECODE_MODULE = "jit_paged_decode_step"

# -- the protobuf wire format, as far as xplane.proto needs it ----------------------


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message; length-delimited
    values come back as views into ``buf``."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i : i + size]
            i += size
        elif wire == 1:
            value = buf[i : i + 8]
            i += 8
        elif wire == 5:
            value = buf[i : i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} is not in xplane.proto")
        yield tag >> 3, wire, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf: memoryview) -> Tuple[int, Any]:
    """XStat -> (metadata id, value); a ``ref_value`` comes back as ("ref", id)."""
    key, value = 0, None
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num in (5, 6):
            value = bytes(v).decode("utf-8", "replace")
        elif num == 7:
            value = ("ref", v)
    return key, value


def _map_entry(buf: memoryview) -> Tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


@dataclass
class _Plane:
    name: str = ""
    lines: List[memoryview] = field(default_factory=list)
    event_meta: Dict[int, memoryview] = field(default_factory=dict)
    stat_names: Dict[int, str] = field(default_factory=dict)


def _planes(data: memoryview) -> Iterator[_Plane]:
    for num, _, plane_buf in _fields(data):
        if num != 1:  # XSpace.planes
            continue
        plane = _Plane()
        for f, _, v in _fields(plane_buf):
            if f == 2:
                plane.name = bytes(v).decode()
            elif f == 3:
                plane.lines.append(v)
            elif f == 4:
                key, value = _map_entry(v)
                plane.event_meta[key] = value
            elif f == 5:
                key, value = _map_entry(v)
                plane.stat_names[key] = next(
                    (bytes(x).decode() for n, _, x in _fields(value) if n == 2), "")
        yield plane


def _event_meta(buf: memoryview, stat_names: Dict[int, str], want: Sequence[str]) -> Dict[str, Any]:
    """XEventMetadata -> {"name": ..., <wanted stat>: ...}."""
    out: Dict[str, Any] = {"name": ""}
    for f, _, v in _fields(buf):
        if f == 2:
            out["name"] = bytes(v).decode("utf-8", "replace")
        elif f == 5:
            key, value = _stat(v)
            name = stat_names.get(key)
            if name in want:
                out[name] = stat_names.get(value[1], "") if isinstance(value, tuple) else value
    return out


def _line(buf: memoryview) -> Tuple[str, int, List[memoryview]]:
    name, timestamp_ns, events = "", 0, []
    for f, _, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            timestamp_ns = v
        elif f == 4:
            events.append(v)
    return name, timestamp_ns, events


def _event(buf: memoryview) -> Tuple[int, int, int, List[memoryview]]:
    """XEvent -> (metadata id, offset ps, duration ps, stats)."""
    meta_id = offset_ps = duration_ps = 0
    stats = []
    for f, _, v in _fields(buf):
        if f == 1:
            meta_id = v
        elif f == 2:
            offset_ps = v
        elif f == 3:
            duration_ps = v
        elif f == 4:
            stats.append(v)
    return meta_id, offset_ps, duration_ps, stats


# -- what the file holds --------------------------------------------------------------


@dataclass
class Op:
    name: str  # reduce_trace.short_name of the HLO line
    start: float  # ns, as ProfileData gives it
    dur: float
    path: str  # tf_op: the scope path, "" when the op has none
    category: str = ""
    program_id: int = 0


@dataclass
class Span:
    name: str
    start: float
    dur: float
    meta: Dict[str, Any] = field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class ProgramTrace:
    ops: Dict[str, List[Op]] = field(default_factory=dict)  # device plane -> XLA Ops
    modules: Dict[str, List[Span]] = field(default_factory=dict)  # device plane -> XLA Modules
    threads: Dict[str, List[Span]] = field(default_factory=dict)  # host line -> program spans
    window: Optional[Tuple[float, float]] = None


def load(path: str) -> ProgramTrace:
    with open(path, "rb") as f:
        data = memoryview(f.read())
    tr = ProgramTrace()
    for plane in _planes(data):
        if plane.name.startswith("/device:TPU:"):
            metas: Dict[int, Dict[str, Any]] = {}
            for line_buf in plane.lines:
                line_name, ts, events = _line(line_buf)
                if line_name not in ("XLA Ops", "XLA Modules"):
                    continue
                for ev in events:
                    meta_id, offset_ps, duration_ps, _ = _event(ev)
                    meta = metas.get(meta_id)
                    if meta is None:
                        meta = metas[meta_id] = _event_meta(
                            plane.event_meta.get(meta_id, memoryview(b"")), plane.stat_names,
                            ("tf_op", "hlo_category", "program_id"))
                        meta["short"] = reduce_trace.short_name(meta["name"])
                    start, dur = ts + offset_ps / 1e3, duration_ps / 1e3
                    if line_name == "XLA Ops":
                        tr.ops.setdefault(plane.name, []).append(Op(
                            meta["short"], start, dur, str(meta.get("tf_op", "")).rstrip(":"),
                            str(meta.get("hlo_category", "")), int(meta.get("program_id", 0) or 0)))
                    else:
                        tr.modules.setdefault(plane.name, []).append(Span(meta["name"], start, dur))
        elif plane.name.startswith("/host:"):
            names = {k: _event_meta(v, {}, ())["name"].split("#")[0] for k, v in plane.event_meta.items()}
            wanted = {k for k, n in names.items() if n.startswith(SPAN_PREFIXES) or n == WINDOW}
            if not wanted:
                continue
            for line_buf in plane.lines:
                line_name, ts, events = _line(line_buf)
                spans = []
                for ev in events:
                    meta_id, offset_ps, duration_ps, stats = _event(ev)
                    if meta_id not in wanted:
                        continue
                    start, dur = ts + offset_ps / 1e3, duration_ps / 1e3
                    if names[meta_id] == WINDOW:
                        tr.window = tr.window or (start, start + dur)
                        continue
                    meta = {}
                    for s in stats:
                        key, value = _stat(s)
                        meta[plane.stat_names.get(key, str(key))] = value
                    spans.append(Span(names[meta_id], start, dur, meta))
                if spans:
                    key = line_name if line_name not in tr.threads else f"{line_name}#{len(tr.threads)}"
                    tr.threads[key] = sorted(spans, key=lambda s: (s.start, -s.dur))
    return tr


# -- self time: what an interval's children do not cover ------------------------------


def self_times(items: Sequence[Tuple[float, float, Any]]) -> List[Tuple[Any, float, int]]:
    """(payload, self ns, index of the parent in the result or -1) for
    intervals ``(start, end, payload)`` that nest. An interval that starts
    inside another is its child and is cut at the parent's end; the self
    times sum to the union of the intervals."""
    order = sorted(items, key=lambda it: (it[0], -(it[1] - it[0])))
    out: List[List[Any]] = []
    stack: List[Tuple[float, int]] = []  # (end, index in out)
    for start, end, payload in order:
        while stack and stack[-1][0] <= start:
            stack.pop()
        parent = stack[-1][1] if stack else -1
        if parent >= 0:
            end = min(end, stack[-1][0])
            out[parent][1] -= end - start
        out.append([payload, end - start, parent])
        stack.append((end, len(out) - 1))
    return [(p, max(0.0, s), parent) for p, s, parent in out]


# -- the device by scope --------------------------------------------------------------


def words(path: str) -> List[str]:
    """``jit(f)/transpose(jvp(loss.ce))/while`` -> jit f transpose jvp loss.ce while."""
    return [w for w in re.split(r"[/();]", path) if w]


def innermost_scope(path: str) -> str:
    for word in reversed(words(path)):
        if word in SCOPES:
            return word
    return UNSCOPED


def device_by_path(tr: ProgramTrace) -> Tuple[Dict[Tuple[str, str], float], int]:
    """{(scope path, op name): busy seconds inside the window}, averaged over chips."""
    if tr.window is None:
        return {}, 0
    t0, t1 = tr.window
    total: Dict[Tuple[str, str], float] = {}
    for ops in tr.ops.values():
        inside = [(max(o.start, t0), min(o.start + o.dur, t1), o) for o in ops
                  if o.start + o.dur > t0 and o.start < t1]
        for op, self_ns, _ in self_times(inside):
            key = (op.path, op.name)
            total[key] = total.get(key, 0.0) + self_ns
    n = len(tr.ops)
    return {k: v / n / 1e9 for k, v in total.items()}, n


def scope_table(by_path: Dict[Tuple[str, str], float]) -> Dict[str, float]:
    rows: Dict[str, float] = {}
    for (path, _), s in by_path.items():
        scope = innermost_scope(path)
        rows[scope] = rows.get(scope, 0.0) + s
    return rows


def unscoped_ops(by_path: Dict[Tuple[str, str], float], top: int = 8) -> List[Tuple[str, float]]:
    ops: Dict[str, float] = {}
    for (path, name), s in by_path.items():
        if innermost_scope(path) == UNSCOPED:
            ops[name] = ops.get(name, 0.0) + s
    return sorted(ops.items(), key=lambda kv: -kv[1])[:top]


def scope_seconds(by_path: Dict[Tuple[str, str], float], scopes: Sequence[str] = (),
                  path_has: str = "") -> float:
    """Busy seconds of the ops whose path holds one of ``scopes`` (any, when
    empty) and the substring ``path_has``; an op counts once."""
    want = set(scopes)
    return sum(
        s for (path, _), s in by_path.items()
        if path_has in path and (not want or want.intersection(words(path)))
    )


# -- the host by span -----------------------------------------------------------------


def tick_thread(tr: ProgramTrace) -> Optional[str]:
    """The host thread that drives the engine: the one with most ``serving.tick`` spans."""
    counts = {name: sum(s.name == "serving.tick" for s in spans) for name, spans in tr.threads.items()}
    best = max(counts, key=counts.get, default=None)
    return best if best is not None and counts[best] else None


@dataclass
class SpanUse:
    """One span inside the window, with its self time and the seconds its
    descendants of each name cover."""
    span: Span
    self_ns: float
    below: Dict[str, float] = field(default_factory=dict)


def spans_in_window(tr: ProgramTrace) -> List[SpanUse]:
    """Every program span that lies wholly inside the window, on any thread."""
    if tr.window is None:
        return []
    t0, t1 = tr.window
    out = []
    for spans in tr.threads.values():
        timed = self_times([(s.start, s.end, s) for s in spans])
        uses = [SpanUse(s, self_ns) for s, self_ns, _ in timed]
        for use, (_, _, parent) in zip(uses, timed):
            while parent >= 0:
                below = uses[parent].below
                below[use.span.name] = below.get(use.span.name, 0.0) + use.span.dur
                parent = timed[parent][2]
        out += [u for u in uses if u.span.start >= t0 and u.span.end <= t1]
    return out


def span_table(uses: Sequence[SpanUse]) -> Dict[str, Dict[str, float]]:
    rows: Dict[str, Dict[str, float]] = {}
    for u in uses:
        row = rows.setdefault(u.span.name, {"count": 0, "total_s": 0.0, "self_s": 0.0, "max_ms": 0.0})
        row["count"] += 1
        row["total_s"] += u.span.dur / 1e9
        row["self_s"] += u.self_ns / 1e9
        row["max_ms"] = max(row["max_ms"], u.span.dur / 1e6)
    return rows


# -- the two clocks, and the device's gaps by program span ----------------------------


def clock_offset(tr: ProgramTrace, module: str = DECODE_MODULE) -> Optional[Dict[str, float]]:
    """Nanoseconds to add to device times to put them on the host's clock, as
    an interval. Window ``w`` is launched by the ``serving.dispatch_window``
    span with ``window=w`` and read by the ``serving.reap_window`` span with
    the same number; the decode programs (``module`` in their name) run in
    that order, so the k-th in the trace is window ``w0 + k`` for one unknown
    ``w0``. For the right
    ``w0`` every pair bounds the offset:

        dispatch.begin - program.start  <=  offset  <=  host_blocked.end - program.end

    A wrong ``w0`` moves both bounds by whole steps, so of the ``w0`` whose
    bounds do not cross the one nearest zero is taken (the profiler aligns
    the clocks to about a millisecond). The bound that many pairs come close
    to is the tight one: a host that waits for the device ends its wait right
    after the program does; a device that waits for the host starts right
    after the dispatch."""
    thread = tick_thread(tr)
    plane = min(tr.modules, default=None)
    if thread is None or plane is None:
        return None
    programs = [m for m in tr.modules[plane] if module in m.name]
    dispatch, blocked = {}, {}
    reaping: Optional[int] = None
    for s in tr.threads[thread]:
        w = s.meta.get("window")
        if s.name == "serving.dispatch_window" and w is not None:
            dispatch[int(w)] = s.start
        elif s.name == "serving.reap_window" and w is not None:
            reaping = int(w)
        elif s.name == "serving.host_blocked" and reaping is not None:
            blocked[reaping], reaping = s.end, None
    if not programs or not dispatch or not blocked:
        return None
    slack = 0.2e6  # ns: how near a bound a pair must come to count as support for it
    best = None
    for w0 in range(min(dispatch) - len(programs), max(dispatch) + 1):
        lows = [dispatch[w0 + k] - p.start for k, p in enumerate(programs) if w0 + k in dispatch]
        highs = [blocked[w0 + k] - p.end for k, p in enumerate(programs) if w0 + k in blocked]
        if len(lows) < 2 or len(highs) < 2 or max(lows) > min(highs):
            continue
        low, high = max(lows), min(highs)
        nearest = 0.0 if low <= 0.0 <= high else min(abs(low), abs(high))
        if best is None or nearest < best[0]:
            n_low = sum(v >= low - slack for v in lows)
            n_high = sum(v <= high + slack for v in highs)
            if high - low <= 2 * slack or n_low == n_high:
                estimate = (low + high) / 2
            else:
                estimate = high if n_high > n_low else low
            best = (nearest, {"low_ns": low, "high_ns": high, "estimate_ns": estimate,
                              "pairs": min(len(lows), len(highs)),
                              "support_low": n_low, "support_high": n_high})
    return best[1] if best else None


def innermost_segments(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """The time one thread's nested spans cover, cut into disjoint sorted
    (start, end, name of the innermost span running)."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []  # (end, name), outermost first
    cur = 0.0

    def own(until: float) -> None:
        nonlocal cur
        if until > cur:
            segs.append((cur, until, stack[-1][1]))
        cur = max(cur, until)

    for s in sorted(spans, key=lambda s: (s.start, -s.dur)):
        while stack and stack[-1][0] <= s.start:
            own(stack[-1][0])
            stack.pop()
        if stack:
            own(s.start)
        cur = s.start
        stack.append((min(s.end, stack[-1][0]) if stack else s.end, s.name))
    while stack:
        own(stack[-1][0])
        stack.pop()
    return segs


def idle_by_span(tr: ProgramTrace, offset_ns: float = 0.0) -> Dict[str, float]:
    """Seconds the first chip idles inside the window, by the innermost
    program span of the engine's thread at that time (device times moved by
    ``offset_ns`` onto the host's clock)."""
    thread = tick_thread(tr)
    plane = min(tr.ops, default=None)
    if thread is None or plane is None or tr.window is None:
        return {}
    t0, t1 = tr.window
    ops = reduce_trace.clip([(o.name, o.start + offset_ns, o.dur) for o in tr.ops[plane]], t0, t1)
    segs = innermost_segments(tr.threads[thread])
    out: Dict[str, float] = {}
    i = 0
    for a, b in reduce_trace.gaps(ops, t0, t1):
        while i < len(segs) and segs[i][1] <= a:
            i += 1
        j, cur = i, a
        while j < len(segs) and segs[j][0] < b:
            sa, sb, name = segs[j]
            if sa > cur:
                out["unattributed"] = out.get("unattributed", 0.0) + (sa - cur) / 1e9
            lo, hi = max(sa, cur), min(sb, b)
            if hi > lo:
                out[name] = out.get(name, 0.0) + (hi - lo) / 1e9
            cur = max(cur, hi)
            j += 1
        if b > cur:
            out["unattributed"] = out.get("unattributed", 0.0) + (b - cur) / 1e9
    return out


# -- one parse per run ----------------------------------------------------------------


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    by_path: Dict[Tuple[str, str], float]
    uses: List[SpanUse]
    offset: Optional[Dict[str, float]]
    idle_by_span_s: Dict[str, float]


def reduce(tr: ProgramTrace) -> Reduced:
    by_path, n = device_by_path(tr)
    offset = clock_offset(tr)
    window_s = (tr.window[1] - tr.window[0]) / 1e9 if tr.window else 0.0
    return Reduced(
        window_s=window_s, busy_s=sum(by_path.values()), n_devices=n, by_path=by_path,
        uses=spans_in_window(tr), offset=offset,
        idle_by_span_s=idle_by_span(tr, offset["estimate_ns"] if offset else 0.0),
    )


def report(red: Reduced, log) -> None:
    """The scope table, the span table and idle by program span, into the run's log."""
    rows = scope_table(red.by_path)
    if red.busy_s:
        log(f"device busy {red.busy_s:.4f} s of a {red.window_s:.4f} s window on {red.n_devices} chip(s), by scope:")
        for scope, s in sorted(rows.items(), key=lambda kv: -kv[1]):
            remat = scope_seconds(red.by_path, (scope,) if scope != UNSCOPED else (), "rematted_computation")
            extra = f"  recompute {remat:.4f}" if remat and scope != UNSCOPED else ""
            log(f"  scope {scope:18s} {s:9.4f} s {100 * s / red.busy_s:6.2f}%{extra}")
        for name, s in unscoped_ops(red.by_path):
            log(f"    unscoped op {name[:90]:90s} {s:8.4f} s")
    table = span_table(red.uses)
    if table:
        log("program spans inside the window (count, total s, self s, mean ms, longest ms):")
        for name, r in sorted(table.items(), key=lambda kv: -kv[1]["self_s"]):
            log(f"  span {name:26s} {r['count']:6d} {r['total_s']:9.4f} {r['self_s']:9.4f} "
                f"{1e3 * r['total_s'] / r['count']:9.3f} {r['max_ms']:9.3f}")
    if red.offset:
        o = red.offset
        log(f"device clock + offset = host clock: offset in [{o['low_ns'] / 1e6:.3f}, {o['high_ns'] / 1e6:.3f}] ms "
            f"from {o['pairs']} windows ({o['support_low']} near the low bound, {o['support_high']} near "
            f"the high); {o['estimate_ns'] / 1e6:.3f} ms used")
    if red.idle_by_span_s:
        log("device idle inside the window, by the engine thread's innermost program span (s): "
            + " ".join(f"{n}={s:.5f}" for n, s in sorted(red.idle_by_span_s.items(), key=lambda kv: -kv[1])))


def for_run(ctx) -> Optional[Reduced]:
    """The run's trace, parsed and logged once and kept on ``ctx``."""
    if not hasattr(ctx, "_program_trace"):
        red = None
        if ctx.trace_dir is not None:
            red = reduce(load(reduce_trace.find_xplane(ctx.trace_dir)))
            report(red, ctx.log)
        ctx._program_trace = red
    return ctx._program_trace
