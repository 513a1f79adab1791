"""A late-wake witness: could this process run, and if not, who held it?

The phase account (``spans.PhaseClock``) says in which call a slow tick stood;
it cannot say whether the thread was running. Two sleepers can. One is a
daemon thread of this process that sleeps ``PERIOD_S`` and reads how late it
woke: late means it could not get the interpreter, because the machine or the
cgroup stood still or because another thread held the GIL that long (a
runtime call, the collector). The other is a child process that does the same
and shares the machine and nothing else with this one. A wake of the inside
sleeper later than ``LATE_S`` is a *late wake* with the interval
``[due, woke]`` on ``time.monotonic``; one period later (the child's line has
had time to arrive) it gets its verdict:

- ``machine``: the outside sleeper was late over at least half of the interval;
- ``process``: it was on time, so this interpreter was held (the span's
  ``gc_ms`` and the slow line's ``collector`` say how much of it a collection covers);
- ``unknown``: there is no outside sleeper (it did not start, or it died).

``ensure()`` starts the one witness of a process; it is always on, like the
phase account: ``counters`` are plain numbers (``summary`` and ``record``
print them), the history three rings of ``RING``. A caller that found an
interval slow (the serving engine's slow tick, the engine loop's slow turn)
hands ``when_settled`` the interval and what to do with its cause: the answer
is not known when the interval ends (the sleeper may not have woken yet, and
the verdict comes a period after it has), and the caller's thread does not
wait for it, so the witness thread writes the caller's line a period or two
later. On the profiler's clock the witness thread leaves one empty span
``loop.late_wake`` a verdict (an annotation cannot be opened in the past: the
interval is ``[start - ended_ms_ago - late_ms, start - ended_ms_ago]`` on the
span's own clock) and one empty ``loop.witness_beat`` a second, which tells
"nothing was late" from "this program has no witness". Neither enters the
in-memory span recorder: a beat a second would fill it, and its exports are
the requests' waterfalls.
"""

from __future__ import annotations

import atexit
import collections
import gc
import os
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Deque, Dict, List, NamedTuple, Optional, Tuple

from pretraining_llm_tpu.observability.spans import Span

PERIOD_S = 0.010
# Four of the interpreter's 5 ms switch intervals, under the smallest freeze on record (40 ms).
LATE_S = 0.020
BEAT_S = 1.0
RING = 64
# A sleeper that is late at every wake never settles: a caller's line then goes out with what is known.
GIVE_UP_S = 1.0
# A collection shorter than this cannot make a wake late, and hundreds of them a
# second would push the one that did out of its ring before the verdict.
GC_KEEP_S = 0.001
VERDICTS = ("machine", "process", "unknown")

# The outside sleeper. It sleeps in ``select`` on its stdin, so the parent's end
# of file (its exit, however it comes) wakes and ends it; should somebody else
# keep that pipe open, it ends when it finds itself adopted.
_CHILD = """
import os, select, sys, time
period, late, parent = %d, %d, os.getppid()
woke = time.monotonic_ns()
while os.getppid() == parent:
    due = woke + period
    if select.select([sys.stdin], [], [], max(0, due - time.monotonic_ns()) / 1e9)[0]:
        break
    woke = time.monotonic_ns()
    if woke - due > late:
        sys.stdout.write("%%d %%d\\n" %% (due, woke))
        sys.stdout.flush()
""" % (int(PERIOD_S * 1e9), int(LATE_S * 1e9))


class Outside:
    """The child process and the pipe its late wakes arrive on."""

    def __init__(self) -> None:
        self.proc: Optional[subprocess.Popen] = None
        self._buf = b""
        self._lock = threading.Lock()  # the witness thread drains, the exiting main thread closes
        try:
            self.proc = subprocess.Popen(
                [sys.executable, "-S", "-E", "-c", _CHILD],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            )
            os.set_blocking(self.proc.stdout.fileno(), False)
        except (OSError, ValueError):
            self.close()

    @property
    def alive(self) -> bool:
        return self.proc is not None

    def drain(self) -> List[Tuple[float, float]]:
        """The intervals (seconds) that have arrived since the last call; never blocks."""
        with self._lock:
            if self.proc is None:
                return []
            try:
                data = os.read(self.proc.stdout.fileno(), 1 << 16)
            except BlockingIOError:
                return []
            except OSError:
                data = b""
            if data:
                *lines, self._buf = (self._buf + data).split(b"\n")
                return [(int(a) / 1e9, int(b) / 1e9) for a, b in (line.split() for line in lines)]
        self.close()  # end of file: the child is gone
        return []

    def close(self) -> None:
        with self._lock:
            proc, self.proc = self.proc, None
        if proc is None:
            return
        for pipe in (proc.stdin, proc.stdout):
            try:
                pipe.close()
            except OSError:
                pass
        try:
            proc.wait(timeout=1.0)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


class LateWake(NamedTuple):
    due: float
    woke: float
    verdict: str


def _covered(a: float, b: float, intervals: Any) -> float:
    """Seconds of [a, b] that the intervals' union covers."""
    total, cur = 0.0, a
    for lo, hi in sorted(i[:2] for i in intervals):
        lo, hi = max(lo, cur), min(hi, b)
        if hi > lo:
            total += hi - lo
            cur = hi
    return total


def new_counters() -> Dict[str, float]:
    out: Dict[str, float] = {"late_wakes": 0, "late_s": 0.0, "longest_late_s": 0.0}
    for v in VERDICTS:
        out[v] = 0
        out[v + "_s"] = 0.0
    return out


class Witness:
    """The inside sleeper's state; ``step`` is one sleep and one wake. The
    clock, the sleep and the outside sleeper can be handed in (tests)."""

    def __init__(
        self,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
        outside: Any = None,
        counters: Optional[Dict[str, float]] = None,
    ) -> None:
        self.clock, self.sleep, self.outside = clock, sleep, outside
        self.counters = new_counters() if counters is None else counters
        self.late: Deque[LateWake] = collections.deque(maxlen=RING)
        self.outside_late: Deque[Tuple[float, float]] = collections.deque(maxlen=RING)
        self.collections: Deque[Tuple[float, float]] = collections.deque(maxlen=RING)
        self.pending: Optional[Tuple[float, float]] = None  # a late wake that waits for its verdict
        self.asked: Deque[Tuple[float, float, Callable[[str], None]]] = collections.deque()  # `when_settled`
        self.settled = self._woke = self._beat = clock()  # every late wake that ended before `settled` is judged
        self._gc_start = 0.0

    # -- the collector ----------------------------------------------------------------

    def on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._gc_start = self.clock()
        else:
            stop = self.clock()
            if stop - self._gc_start >= GC_KEEP_S:
                self.collections.append((self._gc_start, stop))

    def collected(self, a: float, b: float) -> float:
        """Seconds of [a, b] inside a collection; the ring is copied first,
        because a collection can end in any thread while it is read."""
        return _covered(a, b, tuple(self.collections))

    # -- the sleeper ------------------------------------------------------------------

    def step(self) -> None:
        due = self._woke + PERIOD_S
        self.sleep(max(0.0, due - self.clock()))
        woke = self._woke = self.clock()
        c = self.counters
        if self.outside is not None:
            self.outside_late.extend(self.outside.drain())
        if self.pending is not None:
            self._judge(*self.pending)
            self.pending = None
        if woke - due > LATE_S:
            self.pending = (due, woke)
            late = woke - due
            c["late_wakes"] += 1
            c["late_s"] += late
            c["longest_late_s"] = max(c["longest_late_s"], late)
        else:
            self.settled = woke
        while self.asked and (self.settled >= self.asked[0][1] or woke - self.asked[0][1] > GIVE_UP_S):
            t0, t1, emit = self.asked[0]
            emit(self.explain(t0, t1))
            self.asked.popleft()  # after the line is out: an empty `asked` means nothing is still to be said
        if woke - self._beat >= BEAT_S:
            self._beat = woke
            self.beat()

    def beat(self) -> None:
        with Span("loop.witness_beat", None, {}):
            pass

    def _has_outside(self) -> int:
        return int(self.outside is not None and self.outside.alive)

    def _judge(self, due: float, woke: float) -> None:
        outside_s = _covered(due, woke, self.outside_late)
        gc_s = self.collected(due, woke)
        has_outside = self._has_outside()
        if not has_outside:
            verdict = "unknown"
        else:
            verdict = "machine" if 2.0 * outside_s >= woke - due else "process"
        self.late.append(LateWake(due, woke, verdict))
        self.counters[verdict] += 1
        self.counters[verdict + "_s"] += woke - due
        with Span("loop.late_wake", None, {
            "late_ms": 1e3 * (woke - due), "ended_ms_ago": 1e3 * (self.clock() - woke),
            "outside_ms": 1e3 * outside_s, "gc_ms": 1e3 * gc_s, "outside": has_outside,
        }):
            pass

    # -- what a caller's slow line asks (`when_settled`) --------------------------------

    def overlap(self, t0: float, t1: float) -> Tuple[float, Optional[str]]:
        """(late seconds inside [t0, t1], the verdict of the longest late wake
        there: None without one, ``pending`` for one not yet judged)."""
        wakes = {(w.due, w.woke): w.verdict for w in self.late}  # asked on the sleeper's own thread (`step`)
        if self.pending is not None:
            wakes[self.pending] = "pending"
        inside = [(min(b, t1) - max(a, t0), v) for (a, b), v in wakes.items() if min(b, t1) > max(a, t0)]
        if not inside:
            return 0.0, None
        return _covered(t0, t1, wakes), max(inside)[1]

    def explain(self, t0: float, t1: float) -> str:
        """The end of a slow line: who held [t0, t1], in the words PERF.md reads."""
        late_s, verdict = self.overlap(t0, t1)
        if verdict is None:
            return "every sleeper was on time: the device or the transfer"
        why = {
            "machine": "machine: the sleeper outside was late too",
            "process": "process: the sleeper outside was on time; collector %.1f ms"
            % (1e3 * self.collected(t0, t1)),
            "unknown": "unknown: no sleeper outside",
            "pending": "pending: the sleepers have not answered",
        }[verdict]
        return "the process could not run for %.1f of it (%s)" % (1e3 * late_s, why)


# -- the one witness of a process -----------------------------------------------------

counters: Dict[str, float] = new_counters()
_witness: Optional[Witness] = None
_lock = threading.Lock()


def _run(w: Witness) -> None:
    w._woke = w.settled = w.clock()  # the first wake is due from here, not from where `w` was made
    while True:
        w.step()


def ensure() -> Witness:
    """Start the process's witness (thread, child, collector callback) once."""
    global _witness
    with _lock:
        if _witness is None:
            w = Witness(outside=Outside(), counters=counters)
            w.beat()  # here, not in the sleeper: the first span imports jax.profiler
            gc.callbacks.append(w.on_gc)
            atexit.register(w.outside.close)
            threading.Thread(target=_run, args=(w,), name="late-wake-witness", daemon=True).start()
            _witness = w
    return _witness


def when_settled(t0: float, t1: float, emit: Callable[[str], None]) -> None:
    """Hand ``emit`` the end of a slow line for [t0, t1] (``Witness.explain``)
    once every late wake that ended before ``t1`` has its verdict: from the
    witness thread, a period or two from now. The caller does not wait."""
    if _witness is None:
        emit("no witness ran")
    else:
        _witness.asked.append((t0, t1, emit))


def summary() -> str:
    """The counters, for a closing log line."""
    c = counters
    by_verdict = ", ".join(
        "%s %d: %.1f ms" % (v, c[v], 1e3 * c[v + "_s"]) for v in VERDICTS if c[v] or v != "unknown"
    )
    return "late wakes %d, %.1f ms, longest %.1f (%s)" % (
        c["late_wakes"], 1e3 * c["late_s"], 1e3 * c["longest_late_s"], by_verdict,
    )


def record() -> Dict[str, float]:
    """The same counters as numbers, for the trainer's log record."""
    c = counters
    return {
        "late_wakes": c["late_wakes"], "late_ms": 1e3 * c["late_s"],
        "late_longest_ms": 1e3 * c["longest_late_s"],
        "late_machine": c["machine"], "late_machine_ms": 1e3 * c["machine_s"],
        "late_process": c["process"], "late_process_ms": 1e3 * c["process_s"],
    }
