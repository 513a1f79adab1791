"""xplane -> busy union, per-op device time, idle gaps attributed to host spans.

Reads the profiler's ``.xplane.pb`` with ``jax.profiler.ProfileData`` alone.
Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per executed HLO op and ``XLA Modules`` one per program run. Host spans are
the ``bench.*`` ``TraceAnnotation`` events on the host plane; all times are
nanoseconds on one clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns
CONTAINERS = ("while", "conditional", "call.", "call ")

_OPERAND = re.compile(r"%((?:params|pools)__[A-Za-z0-9_]+?)(?:\.\d+)?[,)]")


_FUSED_COLLECTIVE = re.compile(r"calls=%((?:all-|reduce-scatter|collective-)[a-z-]*)")


def short_name(hlo: str) -> str:
    """An op event is named by its whole HLO line. Keep the instruction's own
    name, its opcode and the first parameter or pool array it reads:
    ``fusion.1786.remat fusion <-params__blocks____mlp____w1__``; a custom
    call keeps its target, a fusion that calls a collective the collective's name."""
    head, _, rest = hlo.partition(" = ")
    name = head.lstrip("%")
    if not rest:
        return name
    m = re.search(r"\s([a-z][a-z0-9-]*)\(", " " + rest.split("), ")[-1] if rest.startswith("(") else " " + rest)
    kind = m.group(1) if m else ""
    out = f"{name} {kind}".strip()
    t = re.search(r'custom_call_target="([^"]+)"', rest) or _FUSED_COLLECTIVE.search(rest)
    if t:
        out += f" {t.group(1)}"
    o = _OPERAND.search(rest)
    if o:
        out += f" <-{o.group(1)}"
    return out


@dataclass
class Trace:
    device_ops: Dict[str, List[Event]] = field(default_factory=dict)  # plane -> ops
    device_modules: Dict[str, List[Event]] = field(default_factory=dict)
    host_spans: List[Event] = field(default_factory=list)  # bench.* annotations


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, span_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.device_ops[plane.name] = [
                        (short_name(e.name), float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    ]
                elif line.name == "XLA Modules":
                    tr.device_modules[plane.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        tr.host_spans.append((e.name, float(e.start_ns), float(e.duration_ns)))
    tr.host_spans.sort(key=lambda e: e[1])
    return tr


def span(tr: Trace, name: str) -> Optional[Tuple[float, float]]:
    """(start, end) of the first host span called ``name``."""
    for n, s, d in tr.host_spans:
        if n == name:
            return s, s + d
    return None


def clip(events: Sequence[Event], t0: float, t1: float) -> List[Event]:
    out = []
    for n, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((n, a, b - a))
    return out


def merged(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Union of the events' intervals, as sorted disjoint (start, end)."""
    out: List[Tuple[float, float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            if s + d > out[-1][1]:
                out[-1] = (out[-1][0], s + d)
        else:
            out.append((s, s + d))
    return out


def busy_ns(events: Sequence[Event]) -> float:
    return sum(b - a for a, b in merged(events))


def per_op_ns(events: Sequence[Event]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for n, _, d in events:
        out[n] = out.get(n, 0.0) + d
    return out


def gaps(events: Sequence[Event], t0: float, t1: float) -> List[Tuple[float, float]]:
    """Idle intervals of the device inside [t0, t1]."""
    out = []
    cur = t0
    for a, b in merged(events):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        out.append((cur, t1))
    return out


def attribute(gap_list: Sequence[Tuple[float, float]], host_spans: Sequence[Event],
              skip: Sequence[str] = ("bench.window",)) -> List[Tuple[str, float, float]]:
    """Split each gap over the innermost host span covering each part of it.

    Returns (span name or "unattributed", gap start, nanoseconds). A span that
    starts later is taken as nested inside an earlier one that still runs.
    """
    spans = [e for e in host_spans if e[0] not in skip]
    out = []
    for ga, gb in gap_list:
        cuts = {ga, gb}
        for _, s, d in spans:
            for t in (s, s + d):
                if ga < t < gb:
                    cuts.add(t)
        pts = sorted(cuts)
        for a, b in zip(pts, pts[1:]):
            mid = (a + b) / 2
            owner = "unattributed"
            best = -1.0
            for n, s, d in spans:
                if s <= mid < s + d and s > best:
                    owner, best = n, s
            out.append((owner, a, b - a))
    return out


def summarize(tr: Trace, window: str = "bench.window", top: int = 10) -> Dict[str, object]:
    """Busy seconds averaged over chips, window seconds, top ops, gaps by host span."""
    w = span(tr, window)
    if w is None:
        raise ValueError(f"trace has no {window} span")
    t0, t1 = w
    busy, ops_total, gap_parts = [], {}, []
    modules: Dict[str, List[float]] = {}
    for plane, ops in sorted(tr.device_ops.items()):
        inside = clip(ops, t0, t1)
        busy.append(busy_ns(inside))
        # a while/conditional/call event spans the ops inside it, which have events
        # of their own: it counts for the busy union, not as an op
        leaves = [e for e in inside if not e[0].startswith(CONTAINERS)]
        for n, v in per_op_ns(leaves).items():
            ops_total[n] = ops_total.get(n, 0.0) + v
        if plane == sorted(tr.device_ops)[0]:
            gap_parts = attribute(gaps(inside, t0, t1), tr.host_spans)
            for n, _, d in clip(tr.device_modules.get(plane, []), t0, t1):
                modules.setdefault(n, []).append(d / 1e9)
    if not busy:
        raise ValueError("trace has no /device:TPU plane with an 'XLA Ops' line")
    n_dev = len(busy)
    by_span: Dict[str, float] = {}
    for n, _, d in gap_parts:
        by_span[n] = by_span.get(n, 0.0) + d
    longest = sorted(gap_parts, key=lambda g: -g[2])[: max(0, top - len(by_span))]
    idle = [[f"sum:{n}", v / 1e9] for n, v in sorted(by_span.items(), key=lambda kv: -kv[1])]
    idle += [[f"longest:{n}", d / 1e9] for n, _, d in longest]
    return {
        "busy_s": sum(busy) / n_dev / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "n_devices": n_dev,
        "op_s": {n: v / n_dev / 1e9 for n, v in ops_total.items()},
        "device_ops": [[n, v / n_dev / 1e9] for n, v in
                       sorted(ops_total.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": idle[:top],
        "idle_by_span_s": {n: v / 1e9 for n, v in by_span.items()},
        # program runs on the first chip inside the window: name -> seconds of each run
        "module_runs_s": modules,
    }
