"""The late-wake witness (observability/witness.py): the inside sleeper under an
injected clock and sleep, the verdicts from hand-made outside intervals and
collections, the real child process under SIGSTOP, and the two callers that
ask it why an interval was slow (the serving engine's slow tick, the engine
loop's slow turn)."""

import dataclasses
import logging
import os
import re
import signal
import sys
import threading
import time

import jax
import numpy as np
import pytest

from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.frontend.engine_loop import EngineLoop
from pretraining_llm_tpu.generation import serving
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.observability import witness
from pretraining_llm_tpu.observability.witness import LATE_S, PERIOD_S, RING, Witness

MS = 1e-3


class Clock:
    """A clock that moves only when somebody sleeps: by what was asked for,
    plus what the next entry of ``extra`` says the sleeper overslept."""

    def __init__(self):
        self.now = 100.0
        self.extra = []

    def __call__(self):
        return self.now

    def sleep(self, seconds):
        self.now += seconds + (self.extra.pop(0) if self.extra else 0.0)


class FakeOutside:
    def __init__(self):
        self.alive = True
        self.lines = []

    def drain(self):
        lines, self.lines = self.lines, []
        return lines


def make(outside=None):
    clock = Clock()
    return Witness(clock=clock, sleep=clock.sleep, outside=outside), clock


@pytest.fixture
def made(monkeypatch):
    """(name, recorder, meta) of every span the witness leaves, instead of the annotation."""
    out = []

    class Recording:
        def __init__(self, name, recorder, meta):
            out.append((name, recorder, meta))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(witness, "Span", Recording)
    return out


def test_a_punctual_run_counts_wakes_and_no_late_wake():
    w, clock = make()
    for _ in range(50):
        w.step()
    assert clock.now == pytest.approx(100.0 + 50 * PERIOD_S)
    assert w.counters["late_wakes"] == 0
    assert w.counters["late_s"] == 0.0 and not w.late and w.pending is None
    assert w.settled == clock.now
    assert w.overlap(100.0, clock.now) == (0.0, None)
    assert w.explain(100.0, clock.now) == "every sleeper was on time: the device or the transfer"


def test_a_wake_just_under_the_threshold_is_not_late():
    w, clock = make()
    clock.extra = [LATE_S - MS]
    w.step()
    assert w.counters["late_wakes"] == 0 and w.pending is None


def test_a_late_wake_has_its_interval_its_counters_and_its_overlap():
    w, clock = make(FakeOutside())
    w.step()
    clock.extra = [0.089]
    w.step()  # due at 100.02, woke at 100.109
    due, woke = 100.0 + 2 * PERIOD_S, 100.0 + 2 * PERIOD_S + 0.089
    assert w.pending == pytest.approx((due, woke))
    assert w.counters["late_wakes"] == 1 and w.counters["late_s"] == pytest.approx(0.089)
    assert w.settled == pytest.approx(100.0 + PERIOD_S)  # not past the wake that waits for its verdict
    assert w.overlap(due - 1, woke + 1) == (pytest.approx(0.089), "pending")
    w.step()  # one period later: the verdict
    (late,) = w.late
    assert (late.due, late.woke) == pytest.approx((due, woke)) and w.pending is None
    assert w.settled == clock.now
    assert w.counters["longest_late_s"] == pytest.approx(0.089)
    assert w.overlap(due - 1, woke + 1) == (pytest.approx(0.089), "process")
    assert w.overlap(due + 0.030, due + 0.050) == (pytest.approx(0.020), "process")  # clipped to the asker's interval
    assert w.overlap(woke + MS, woke + 1) == (0.0, None)


@pytest.mark.parametrize("outside_late,alive,verdict", [
    ([(0.001, 0.088)], True, "machine"),  # the sleeper outside stood still over the same interval
    ([(0.0, 0.030), (0.040, 0.060)], True, "machine"),  # in two pieces, 50 of 89 ms
    ([(0.050, 0.080)], True, "process"),  # 30 of 89 ms: under half
    ([], True, "process"),  # it was on time
    ([(-0.5, -0.3)], True, "process"),  # late at another time
    ([], False, "unknown"),  # there is none
], ids=["machine", "machine-in-pieces", "under-half", "on-time", "another-time", "no-outside"])
def test_verdict_from_hand_made_outside_intervals(outside_late, alive, verdict, made):
    outside = FakeOutside()
    w, clock = make(outside)
    w.step()
    clock.extra = [0.089]
    w.step()
    due = 100.0 + 2 * PERIOD_S
    outside.lines = [(due + a, due + b) for a, b in outside_late]
    outside.alive = alive
    w.step()
    (late,) = w.late
    assert late.verdict == verdict
    ((_, _, meta),) = made
    assert meta["outside_ms"] == pytest.approx(1e3 * sum(min(b, 0.089) - max(a, 0.0) for a, b in outside_late if b > 0))
    assert meta["outside"] == int(alive)
    assert w.counters[verdict] == 1 and w.counters[verdict + "_s"] == pytest.approx(0.089)
    assert sum(w.counters[v] for v in witness.VERDICTS) == 1
    text = w.explain(due, due + 0.089)
    assert text.startswith("the process could not run for 89.0 of it (%s: " % verdict)


def test_witness_without_an_outside_sleeper_says_unknown():
    w, clock = make(None)
    clock.extra = [0.050]
    w.step()
    w.step()
    assert [l.verdict for l in w.late] == ["unknown"]
    assert w.explain(100.0, 101.0) == "the process could not run for 50.0 of it (unknown: no sleeper outside)"


def test_gc_ms_from_a_hand_made_collection(made):
    w, clock = make(FakeOutside())
    w.step()
    clock.extra = [0.100]
    # a collection of 60 ms inside the late interval, as the callback sees it; and one too short to keep
    start = 100.0 + 2 * PERIOD_S + 0.010
    for t0, t1 in ((start, start + 0.060), (start + 0.070, start + 0.0705)):
        clock.now = t0
        w.on_gc("start", {})
        clock.now = t1
        w.on_gc("stop", {})
    clock.now = 100.0 + PERIOD_S
    w.step()
    w.step()
    (late,) = w.late
    assert list(w.collections) == [pytest.approx((start, start + 0.060))]
    assert made[0][2]["gc_ms"] == pytest.approx(60.0) and late.verdict == "process"
    assert w.explain(late.due, late.woke).endswith("(process: the sleeper outside was on time; collector 60.0 ms)")


def test_the_rings_stay_at_their_size():
    outside = FakeOutside()
    w, clock = make(outside)
    for i in range(3 * RING):
        clock.extra = [0.030]
        outside.lines = [(clock.now, clock.now + 0.001)]
        w.on_gc("start", {})
        clock.now += 0.002
        w.on_gc("stop", {})
        w.step()
    assert len(w.late) == len(w.outside_late) == len(w.collections) == RING
    assert w.counters["late_wakes"] == 3 * RING  # the counters keep what the rings forget
    assert w.counters["late_s"] == pytest.approx(3 * RING * 0.030, rel=1e-6)


def test_late_wake_and_beat_spans_carry_their_meta(made):
    outside = FakeOutside()
    w, clock = make(outside)
    w.step()
    clock.extra = [0.089]
    w.step()
    outside.lines = [(clock.now - 0.080, clock.now)]
    assert not made  # a span has no past: nothing until the verdict
    w.step()
    ((name, recorder, meta),) = made
    assert name == "loop.late_wake" and recorder is None  # never into the in-memory recorder
    assert set(meta) == {"late_ms", "ended_ms_ago", "outside_ms", "gc_ms", "outside"}  # what the reader takes
    assert meta["late_ms"] == pytest.approx(89.0) and meta["ended_ms_ago"] == pytest.approx(10.0)
    assert meta["outside_ms"] == pytest.approx(80.0) and meta["gc_ms"] == 0.0 and meta["outside"] == 1
    assert w.late[0].verdict == "machine"
    del made[:]
    while not made:  # once a second of the witness's clock
        w.step()
    assert clock.now == pytest.approx(100.0 + witness.BEAT_S, abs=PERIOD_S)
    ((name, recorder, meta),) = made
    assert name == "loop.witness_beat" and recorder is None and meta == {}  # read for its presence alone


# -- a caller's slow line: written once the cause is known, never waited for ----------


def test_a_line_asked_for_before_the_sleeper_woke_waits_for_the_verdict():
    outside = FakeOutside()
    w, clock = make(outside)
    w.step()
    said = []
    # The caller's interval ends inside a freeze that the sleeper has not woken from yet.
    t1 = clock.now + PERIOD_S + 0.085
    w.asked.append((t1 - 0.150, t1, said.append))
    clock.extra = [0.089]
    w.step()  # the late wake: pending
    assert not said
    outside.lines = [(clock.now - 0.085, clock.now)]
    w.step()  # on time: the verdict, and every late wake that ended before t1 is judged
    assert said == ["the process could not run for %.1f of it (machine: the sleeper outside was late too)" % 85.0]
    assert not w.asked
    w.step()
    assert len(said) == 1


def test_a_line_for_an_interval_without_a_late_wake_says_so_at_the_next_wake():
    w, clock = make()
    w.step()
    said = []
    w.asked.append((clock.now - 0.4, clock.now, said.append))
    w.asked.append((clock.now - 0.3, clock.now + MS, said.append))
    w.step()
    assert said == ["every sleeper was on time: the device or the transfer"] * 2


def test_a_sleeper_that_never_settles_gives_up_and_says_what_it_knows():
    w, clock = make(FakeOutside())
    w.step()
    said = []
    w.asked.append((clock.now - 0.1, clock.now + 0.050, said.append))
    n = 0
    while not said:
        clock.extra = [0.030]  # late at every wake
        w.step()
        n += 1
    assert n == pytest.approx(witness.GIVE_UP_S / (PERIOD_S + 0.030), abs=3)
    assert re.fullmatch(r"the process could not run for [0-9.]+ of it \(process: [^)]*\)", said[0])


def test_without_a_witness_the_line_goes_out_at_once(monkeypatch):
    monkeypatch.setattr(witness, "_witness", None)
    said = []
    witness.when_settled(1.0, 2.0, said.append)
    assert said == ["no witness ran"]


# -- the process's one witness, and the real child ------------------------------------


def test_ensure_twice_starts_one_thread_and_one_child():
    w = witness.ensure()
    assert witness.ensure() is w
    assert sum(t.name == "late-wake-witness" for t in threading.enumerate()) == 1
    assert w.counters is witness.counters and w.outside.alive
    said = []
    witness.when_settled(time.monotonic() - 0.001, time.monotonic(), said.append)
    deadline = time.monotonic() + 5.0
    while not said and time.monotonic() < deadline:
        time.sleep(PERIOD_S)
    assert len(said) == 1  # the sleeper runs, and answers
    assert re.fullmatch(r"late wakes \d+, [0-9.]+ ms, longest [0-9.]+ \(machine \d+: [0-9.]+ ms, process \d+: [0-9.]+ ms\)",
                        witness.summary())
    assert set(witness.record()) == {"late_wakes", "late_ms", "late_longest_ms", "late_machine", "late_machine_ms",
                                     "late_process", "late_process_ms"}


def test_the_closing_counters_say_seconds_by_verdict(monkeypatch):
    c = witness.new_counters()
    monkeypatch.setattr(witness, "counters", c)
    w, clock = make(FakeOutside())
    w.counters = c
    for late, alive in ((0.089, True), (0.040, True), (0.050, False)):
        clock.extra = [late]
        w.step()
        w.outside.alive = alive
        w.step()
    assert witness.summary() == "late wakes 3, 179.0 ms, longest 89.0 (machine 0: 0.0 ms, process 2: 129.0 ms, unknown 1: 50.0 ms)"
    assert witness.record()["late_process_ms"] == pytest.approx(129.0) and witness.record()["late_machine"] == 0


def test_the_real_child_reports_a_stop_and_ends_with_its_stdin():
    out = witness.Outside()
    assert out.alive
    pid = out.proc.pid
    time.sleep(0.3)  # let it start and sleep a few periods
    out.drain()  # whatever its start-up cost it
    t_stop = time.monotonic()
    os.kill(pid, signal.SIGSTOP)
    time.sleep(0.060)
    t_cont = time.monotonic()
    os.kill(pid, signal.SIGCONT)
    deadline = time.monotonic() + 5.0
    got = []
    while not got and time.monotonic() < deadline:
        time.sleep(PERIOD_S)
        got = [(due, woke) for due, woke in out.drain() if woke >= t_cont]  # a busy machine may add its own, earlier
    due, woke = got[0]
    assert 0.040 <= woke - due <= 0.5
    assert t_stop - 2 * PERIOD_S <= due <= t_cont and woke - t_cont < 0.5  # CLOCK_MONOTONIC, the clock this process reads too
    t0 = time.monotonic()
    out.close()
    assert time.monotonic() - t0 < 1.0 and not out.alive
    with pytest.raises(ProcessLookupError):
        os.kill(pid, 0)
    assert out.drain() == []


def test_a_child_that_dies_leaves_a_witness_that_says_so():
    out = witness.Outside()
    clock = Clock()
    w = Witness(clock=clock, sleep=clock.sleep, outside=out)
    out.proc.kill()
    out.proc.wait()
    w.step()  # end of file on the pipe
    assert not out.alive
    clock.extra = [0.050]
    w.step()
    w.step()
    assert [l.verdict for l in w.late] == ["unknown"]


# -- the two callers ------------------------------------------------------------------

TINY = get_preset("tiny")
CFG = dataclasses.replace(TINY.model, compute_dtype="float32", n_layers=1)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda key: transformer.init_params(CFG, key))(jax.random.key(0))  # one compile, not one an op


def _prompts(n, lengths=(5, 9, 14, 7, 11, 3)):
    rng = np.random.default_rng(42)
    return [rng.integers(0, CFG.vocab_size, size=lengths[i % len(lengths)]).tolist() for i in range(n)]


def _engine(params):
    return ServingEngine(params, CFG, max_batch=2, n_blocks=32, block_size=8, temperature=0.0,
                         steps_per_sched=1, pipeline_depth=2)


def _lines(caplog, what, timeout_s=5.0):
    """The logged lines that hold ``what``. The witness thread writes a slow
    line a period or two after the caller found it slow: wait for the first,
    then as long again as a second would take."""
    deadline = time.monotonic() + timeout_s
    found = lambda: [r.getMessage() for r in caplog.records if what in r.getMessage()]
    while not found() and time.monotonic() < deadline:
        time.sleep(PERIOD_S)
    time.sleep(5 * PERIOD_S)
    return found()


ON_TIME_OR_BRIEFLY_HELD = re.compile(
    r"; (every sleeper was on time: the device or the transfer"
    r"|the process could not run for [0-9.]+ of it \((machine|process|unknown): [^)]*\))$")


class _StalledReadback:
    """``numpy`` for the serving module, whose next ``asarray`` stands still for
    ``delay`` seconds: asleep (the interpreter free, as in a wait for the device),
    or ``holding`` the interpreter in one call (as a runtime call that keeps the GIL)."""

    def __init__(self):
        self.delay, self.holding = 0.0, False

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, *args, **kw):
        delay, self.delay = self.delay, 0.0
        if delay and self.holding:
            end = time.monotonic() + delay
            while time.monotonic() < end:
                sum(range(200_000))  # a few milliseconds a call; the test makes the switch interval long
        else:
            time.sleep(delay)
        return np.asarray(*args, **kw)


@pytest.mark.parametrize("holding", [False, True], ids=["asleep", "holding-the-interpreter"])
def test_a_stalled_readbacks_slow_tick_line_ends_with_a_verdict(params, monkeypatch, caplog, holding):
    old = sys.getswitchinterval()
    stalled = _StalledReadback()
    stalled.holding = holding
    monkeypatch.setattr(serving, "np", stalled)
    eng = _engine(params)
    assert sum(t.name == "late-wake-witness" for t in threading.enumerate()) == 1  # the engine ensured it
    for p in _prompts(2):
        eng.submit(p, 40)
    with caplog.at_level(logging.WARNING, logger="pretraining_llm_tpu.serving"):
        for _ in range(20):
            eng.pipeline_tick()
        assert not caplog.records
        stalled.delay = 0.4
        eng.stats["longest_tick"]["seconds"] = 0.0  # a fresh account: the first tick compiled
        if holding:
            sys.setswitchinterval(10.0)  # the stalled call keeps the interpreter to itself
        try:
            t0 = time.monotonic()
            eng.pipeline_tick()  # returns with the line still to come: it does not wait for the sleepers
            tick_s = time.monotonic() - t0
        finally:
            sys.setswitchinterval(old)
        assert eng.stats["slow_ticks"] == 1 and tick_s < eng.stats["longest_tick"]["seconds"] + 0.005
        (message,) = _lines(caplog, "slow tick")
    assert message.startswith("slow tick 21: ") and eng.stats["longest_tick"]["tick"] == 21
    if holding:
        # The hold ends with the tick, before the sleeper has woken from it: the line still names it.
        found = re.search(r"; the process could not run for ([0-9.]+) of it "
                          r"\(process: the sleeper outside was on time; collector [0-9.]+ ms\)$", message)
        assert found and 300.0 <= float(found.group(1)) <= 1e3 * eng.stats["longest_tick"]["seconds"]
    else:  # the interpreter was free: on time, unless this machine held the test itself for 20 ms
        assert ON_TIME_OR_BRIEFLY_HELD.search(message)


def test_the_closing_line_carries_the_counters_and_no_late_wake_is_logged_alone(params, caplog):
    eng = _engine(params)
    for p in _prompts(2):
        eng.submit(p, 6)
    with caplog.at_level(logging.DEBUG):
        sum(range(3_000_000))  # some tens of milliseconds in one call: a late wake, most likely
        eng.run(pipeline=True)
        time.sleep(5 * PERIOD_S)  # past the verdict of whatever was late
    (line,) = [r.getMessage() for r in caplog.records if "engine empty" in r.getMessage()]
    assert re.search(r"; late wakes \d+, [0-9.]+ ms, longest [0-9.]+ \(machine \d+: [0-9.]+ ms, process \d+: [0-9.]+ ms", line)
    assert line.endswith(")")
    assert not [r for r in caplog.records if "late wake" in r.getMessage() and "engine empty" not in r.getMessage()]


def test_a_slow_turn_of_the_engine_loop_ends_with_a_verdict(params, monkeypatch, caplog):
    eng = _engine(params)
    loop = EngineLoop(eng, idle_wait_s=0.002)
    stall = {"s": 0.0}
    drain = loop._drain_inbox

    def slow_inbox():
        s, stall["s"] = stall["s"], 0.0
        time.sleep(s)
        drain()

    monkeypatch.setattr(loop, "_drain_inbox", slow_inbox)
    with caplog.at_level(logging.WARNING, logger="pretraining_llm_tpu.frontend.engine_loop"):
        with loop:
            time.sleep(0.1)  # some tens of idle turns: a history to be slow against
            stall["s"] = 0.4
            deadline = time.monotonic() + 10.0
            while not loop.counters["slow_turns"] and time.monotonic() < deadline:
                time.sleep(0.01)
            (message,) = _lines(caplog, "slow turn")
    assert ON_TIME_OR_BRIEFLY_HELD.search(message)
