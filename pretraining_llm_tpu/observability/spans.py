"""Host-side nested spans, on the profiler's clock and (opt-in) in memory.

``span(name, **meta)`` is the one way the program marks host time. It always
enters a ``jax.profiler.TraceAnnotation``: when a profiler trace is running
the span lands on the host plane of the same ``.xplane.pb`` that holds the
device ops, on the same clock, so a device gap can be laid against what the
host was doing; when none is running it costs a fraction of a microsecond.

Recording in memory is opt-in. A ``SpanRecorder`` keeps ``perf_counter``
pairs and exports Chrome trace-event JSON (opens in Perfetto); the module
default exists only once somebody asked for it — ``get_recorder()`` (the
request ``Tracer``, ``scripts/serve.py``, the remote replica) or
``set_recorder()`` (the trainer's hub, tests). Until then ``span()`` appends
nothing, takes no lock and builds no dict. Memory is bounded: past
``max_events`` new spans are counted as dropped instead of recorded.

Spans nest per-thread: each records its thread id and stack depth, and the
"X" (complete) Chrome events reconstruct the nesting from time containment.
"""

from __future__ import annotations

import json
import os
import statistics
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

# jax.profiler.TraceAnnotation, imported at the first span: this package
# stays importable where jax is not (scripts/obs_report.py).
_annotation: Any = None

# The serving engine's tick and the engine loop's turn keep an always-on
# account of their own duration. One is slow, and logged, when it lasts longer
# than both of these: seconds, and a multiple of the median of the last
# SLOW_HISTORY of its kind.
SLOW_S = 0.25
SLOW_FACTOR = 8.0
SLOW_HISTORY = 64


def slow_factor(seconds: float, history: Sequence[float]) -> Optional[float]:
    """``seconds`` over the median of ``history`` when that makes it slow
    by the rule above, else None."""
    if seconds <= SLOW_S or len(history) < 8:
        return None
    factor = seconds / max(statistics.median(history), 1e-9)
    return factor if factor > SLOW_FACTOR else None


def format_split(split: Dict[str, float]) -> str:
    """``phase=12.3ms ...``, largest first, for the slow-tick log line."""
    return " ".join(
        f"{k}={v * 1e3:.1f}ms"
        for k, v in sorted(split.items(), key=lambda kv: -kv[1]) if v > 0
    )


class Span:
    """One open span: a profiler annotation, plus an entry in ``recorder``
    when there is one. ``set(**meta)`` adds to both what is only known
    inside the body (a count, the seconds spent blocked)."""

    __slots__ = ("_ann", "_rec", "_name", "_meta", "_t0", "_depth")

    def __init__(self, name: str, recorder: Optional["SpanRecorder"], meta: Dict[str, Any]) -> None:
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        self._ann = _annotation(name, **meta)
        self._rec = recorder
        if recorder is not None:
            self._name = name
            self._meta = meta

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        rec = self._rec
        if rec is not None:
            self._depth = getattr(rec._local, "depth", 0)
            rec._local.depth = self._depth + 1
            self._t0 = time.perf_counter()
        return self

    def set(self, **meta: Any) -> None:
        self._ann.set_metadata(**meta)
        if self._rec is not None:
            self._meta.update(meta)

    def __exit__(self, *exc: Any) -> None:
        rec = self._rec
        if rec is not None:
            dur = time.perf_counter() - self._t0
            rec._local.depth = self._depth
            rec._append(
                (self._name, self._t0, dur, threading.get_ident(), self._depth, self._meta)
            )
        self._ann.__exit__(*exc)


class SpanRecorder:
    def __init__(self, max_events: int = 200_000) -> None:
        self.max_events = max_events
        # (name, t_start perf_counter s, dur s, thread id, depth, meta)
        self._events: List[Tuple[str, float, float, int, int, Dict[str, Any]]] = []
        self._dropped = 0
        self._lock = threading.Lock()
        self._local = threading.local()
        # Anchor for converting perf_counter timestamps to epoch us at
        # export: one wall/perf pair read together at construction.
        self._wall0 = time.time()
        self._perf0 = time.perf_counter()

    def span(self, name: str, **meta: Any) -> Span:
        """Record a span; ``meta`` (plus anything the body adds through
        ``Span.set``) lands in the Chrome trace event's ``args``, so
        per-span counters — e.g. the serving scheduler's host-blocked
        seconds per decode window — are inspectable in Perfetto. Values
        must be JSON-serializable."""
        return Span(name, self, meta)

    def _append(self, event: Tuple[str, float, float, int, int, Dict[str, Any]]) -> None:
        with self._lock:
            if len(self._events) < self.max_events:
                self._events.append(event)
            else:
                self._dropped += 1

    def record(
        self,
        name: str,
        t0: float,
        dur: float,
        *,
        meta: Optional[Dict[str, Any]] = None,
        track: Optional[str] = None,
    ) -> None:
        """Record a COMPLETED span with explicit timestamps (perf_counter
        seconds). The request tracer needs this because its spans start
        and end on different threads — a context manager cannot bracket
        them. ``track`` places the span on a named virtual track in the
        Chrome trace export (per-request waterfalls) instead of the
        calling thread's row."""
        m = dict(meta) if meta else {}
        if track is not None:
            m["_track"] = track
        self._append((name, t0, dur, threading.get_ident(), 0, m))

    def drain(self) -> Tuple[List[Tuple[str, float, float, int, int, Dict[str, Any]]], int]:
        """Pop every recorded span plus the drop count accumulated since
        the last drain. This is the worker-side export path: the bounded
        ``_events`` list doubles as the span-export buffer, spans ship
        exactly once, and resetting the drop counter makes the returned
        count an increment the parent can feed a monotonic counter."""
        with self._lock:
            events, self._events = self._events, []
            dropped, self._dropped = self._dropped, 0
        return events, dropped

    # -- aggregate views ----------------------------------------------

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-name count + total/max seconds (host accounting). ``max_s``
        singles out the straggler occurrence — for the serving reap span
        that is the window where the host actually blocked on the device."""
        with self._lock:
            events = list(self._events)
        out: Dict[str, Dict[str, float]] = {}
        for name, _t0, dur, _tid, _depth, _meta in events:
            agg = out.setdefault(name, {"count": 0, "total_s": 0.0, "max_s": 0.0})
            agg["count"] += 1
            agg["total_s"] += dur
            agg["max_s"] = max(agg["max_s"], dur)
        return out

    @property
    def dropped(self) -> int:
        return self._dropped

    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON object ("X" complete events, us units).

        Spans recorded with a ``track`` (the request tracer's waterfalls)
        render on synthetic tids with a thread_name metadata event each,
        so Perfetto shows one named row per request next to the real host
        threads. A nonzero dropped count is surfaced as an explicit
        instant event IN the trace — a saturated recorder must not look
        like a complete one."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        pid = os.getpid()
        trace = []
        track_tids: Dict[str, int] = {}
        t_last = 0.0
        for name, t0, dur, tid, depth, meta in events:
            track = meta.get("_track")
            if track is not None:
                vt = track_tids.get(track)
                if vt is None:
                    # Virtual tids far above any real thread id's low bits
                    # collide with nothing Perfetto groups by.
                    vt = track_tids[track] = (1 << 22) + len(track_tids)
                    trace.append({
                        "name": "thread_name", "ph": "M", "pid": pid,
                        "tid": vt, "args": {"name": track},
                    })
                tid = vt
                meta = {k: v for k, v in meta.items() if k != "_track"}
            ts = (self._wall0 + (t0 - self._perf0)) * 1e6
            t_last = max(t_last, ts + dur * 1e6)
            trace.append({
                "name": name,
                "ph": "X",
                "ts": ts,
                "dur": dur * 1e6,
                "pid": pid,
                "tid": tid,
                "args": {"depth": depth, **meta},
            })
        if dropped:
            trace.append({
                "name": "spans_dropped", "ph": "i", "s": "p", "pid": pid,
                "tid": 0, "ts": t_last, "args": {"dropped": dropped},
            })
        return {
            "traceEvents": trace,
            "displayTimeUnit": "ms",
            "otherData": {"dropped_spans": dropped},
        }

    def export(self, path: str) -> str:
        """Write the Chrome trace JSON atomically; returns the path."""
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        os.replace(tmp, path)
        return path

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0


class PhaseClock:
    """Always-on account of one thread's time by phase: one ``perf_counter``
    read per boundary moves the clock from the phase that ends to the one
    that begins; ``acc`` keeps the running seconds of each. ``phase`` is
    None while the clock stands (outside the scheduler's calls)."""

    __slots__ = ("acc", "phase", "t")

    def __init__(self, acc: Dict[str, float]) -> None:
        self.acc = acc
        self.phase: Optional[str] = None
        self.t = 0.0

    def switch(self, phase: Optional[str]) -> Tuple[Optional[str], float]:
        """Returns (the phase that ended, the time of the switch)."""
        now = time.perf_counter()
        prev = self.phase
        if prev is not None:
            self.acc[prev] += now - self.t
        self.phase, self.t = phase, now
        return prev, now

    def span(self, phase: str, name: Optional[str] = None, **meta: Any) -> "PhaseSpan":
        """A context whose body is ``phase``; with ``name`` also a ``span``."""
        return PhaseSpan(self, phase, span(name, **meta) if name else None)


class PhaseSpan:
    """One phase of a ``PhaseClock``, and the span of that name if it has
    one; ``t0``/``t1`` are the clock's own reads at its two boundaries."""

    __slots__ = ("_clock", "_phase", "_span", "_prev", "t0", "t1")

    def __init__(self, clock: PhaseClock, phase: str, span: Optional[Span]) -> None:
        self._clock, self._phase, self._span = clock, phase, span

    def __enter__(self) -> "PhaseSpan":
        if self._span is not None:
            self._span.__enter__()
        self._prev, self.t0 = self._clock.switch(self._phase)
        return self

    def set(self, **meta: Any) -> None:
        if self._span is not None:
            self._span.set(**meta)

    def __exit__(self, *exc: Any) -> None:
        _, self.t1 = self._clock.switch(self._prev)
        if self._span is not None:
            self._span.__exit__(*exc)


# Module-level default recorder, None until somebody asks for one: layers
# without a hub reference (the checkpoint module, the serving engine) record
# into it when it exists; the trainer's hub installs its own so their spans
# land in the same export.
_default: Optional[SpanRecorder] = None


def get_recorder() -> SpanRecorder:
    """The module default, created on first request: asking for it is what
    turns in-memory recording on."""
    global _default
    if _default is None:
        _default = SpanRecorder()
    return _default


def set_recorder(recorder: Optional[SpanRecorder]) -> None:
    """Install ``recorder`` as the module default (the hub adopts its own so
    checkpoint-layer spans land in the exported trace); ``None`` turns
    in-memory recording off again."""
    global _default
    _default = recorder


def span(name: str, **meta: Any) -> Span:
    """A span on the profiler's clock, recorded in the module default
    recorder too if one exists. Never creates the recorder."""
    return Span(name, _default, meta)
