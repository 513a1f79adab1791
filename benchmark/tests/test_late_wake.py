"""``readers/late_wake.py`` on a hand-built ``ProgramTrace`` (times in ms below,
nanoseconds in the trace), and the cells that report its six entries.

The window runs from 1,000 to 5,000 ms. The first chip is busy all through
it but for two gaps: 1,500-1,590 (90 ms), which a late interval of the
process covers from 1,495 to 1,585 (85 of the gap's 90 ms), and 3,000-3,040
(40 ms), which falls inside a ``serving.host_blocked`` span of the engine
thread (2,990-3,030: 30 of the gap's 40 ms) of which a second late interval
covers the last 5 ms (3,025-3,050)."""

import types

import pytest

from harness import program_trace as pt, registry
from readers import late_wake

MS = 1e6


def span(name, start_ms, end_ms, **meta):
    return pt.Span(name, start_ms * MS, (end_ms - start_ms) * MS, meta)


def late_span(start_ms, end_ms, judged_ms, **meta):
    """The empty span the witness leaves ``judged_ms`` after a late interval ended."""
    return span("loop.late_wake", end_ms + judged_ms, end_ms + judged_ms + 0.02,
                late_ms=float(end_ms - start_ms), ended_ms_ago=float(judged_ms), **meta)


def beat(at_ms):
    return span("loop.witness_beat", at_ms, at_ms + 0.02)


def trace(witness_spans, offset_ms=0.0):
    ops = [pt.Op("fusion.1", (a - offset_ms) * MS, (b - a) * MS, "jit(paged_decode_step)/mlp")
           for a, b in ((900, 1500), (1590, 3000), (3040, 5200))]
    engine = [span("serving.tick", 2980, 3045), span("serving.reap_window", 2985, 3044, window=7),
              span("serving.host_blocked", 2990, 3030), span("serving.commit", 3030, 3044),
              span("serving.tick", 1400, 1600)]
    tr = pt.ProgramTrace(ops={"/device:TPU:0": ops, "/device:TPU:1": []},
                         threads={"python": sorted(engine, key=lambda s: (s.start, -s.dur))},
                         window=(1000 * MS, 5000 * MS))
    if witness_spans:
        tr.threads["python#1"] = list(witness_spans)
    return tr


def ctx_for(tr, offset_ms=0.0):
    offset = {"estimate_ns": offset_ms * MS} if offset_ms else None
    return types.SimpleNamespace(_late_wake_trace=tr, _program_trace=types.SimpleNamespace(offset=offset))


def read(tr, stat, offset_ms=0.0):
    return late_wake.read(None, None, ctx_for(tr, offset_ms), stat)


LATE = [late_span(1495, 1585, 10.3, outside_ms=88.0, gc_ms=0.0, outside=1),
        late_span(3025, 3050, 12.0, outside_ms=0.0, gc_ms=21.0, outside=1)]


def test_the_three_stats_by_hand():
    tr = trace([beat(1200), beat(2200)] + LATE)
    assert late_wake.late_intervals(tr) == [pytest.approx((1495 * MS, 1585 * MS)), pytest.approx((3025 * MS, 3050 * MS))]
    assert read(tr, "late_ms") == pytest.approx(90 + 25)
    # the first gap's 1,500-1,585 and the second's 3,025-3,040, of a 4,000 ms window
    assert read(tr, "idle_late_share") == pytest.approx(100 * (85 + 15) / 4000)
    # the second gap under host_blocked is 3,000-3,030; its last 5 ms are a late interval's
    assert read(tr, "idle_blocked_share") == pytest.approx(100 * (30 - 5) / 4000)


def test_a_late_interval_is_clipped_to_the_window():
    tr = trace([beat(1200), late_span(950, 1040, 10.0), late_span(4990, 5100, 10.0)])
    assert read(tr, "late_ms") == pytest.approx(40 + 10)
    assert read(tr, "idle_late_share") == 0.0


def test_device_times_are_moved_by_the_runs_offset():
    """The device's clock stands 2 ms behind the host's: the ops are recorded 2 ms early."""
    tr = trace([beat(1200)] + LATE, offset_ms=2.0)
    assert read(tr, "idle_late_share", offset_ms=2.0) == pytest.approx(100 * (85 + 15) / 4000)
    assert read(tr, "idle_late_share") == pytest.approx(100 * (87 + 13) / 4000)  # unmoved: gaps at 1,498-1,588, 2,998-3,038


def test_none_without_a_beat_and_zero_with_a_beat_and_no_late_wake():
    for stat in ("late_ms", "idle_late_share", "idle_blocked_share"):
        assert read(trace([]), stat) is None  # the parent's program: no witness
        assert read(trace(LATE), stat) is None  # a late wake alone is no proof that the witness ran all through
        assert read(trace([beat(900)]), stat) is None  # a beat before the window opened
    quiet = trace([beat(1200), beat(2200), beat(3200)])
    assert read(quiet, "late_ms") == 0.0 and read(quiet, "idle_late_share") == 0.0
    assert read(quiet, "idle_blocked_share") == pytest.approx(100 * 30 / 4000)


def test_no_trace_no_window_no_engine_thread():
    assert late_wake.read(None, None, types.SimpleNamespace(_late_wake_trace=None), "late_ms") is None
    tr = trace([beat(1200)])
    tr.window = None
    assert read(tr, "late_ms") is None
    train = trace([beat(1200)] + LATE)  # a training cell: no serving.tick anywhere
    del train.threads["python"]
    assert read(train, "late_ms") == pytest.approx(115) and read(train, "idle_blocked_share") is None
    with pytest.raises(ValueError):
        read(trace([beat(1200)]), "median")


def test_report_names_each_late_wake_its_verdict_the_engines_spans_and_the_devices_gaps():
    lines = []
    tr = trace([beat(1200)] + LATE + [late_span(4000, 4050, 10.0, outside=0)])
    late_wake.report(tr, late_wake.device_gaps(tr, ctx_for(tr)), lines.append)
    assert len(lines) == 3
    assert lines[0].startswith("late wake 495.0 ms into the window: 90.0 ms, machine (sleeper outside late 88.0 ms")
    assert "; engine thread in serving.tick=90.0ms; " in lines[0]
    assert lines[0].endswith("device idle 90.0 ms from +5.0 ms of its start to +5.0 ms of its end")
    assert ", process (sleeper outside late 0.0 ms, collector 21.0 ms)" in lines[1]
    # and 5 ms of it in no span
    assert "; engine thread in serving.commit=14.0ms serving.host_blocked=5.0ms serving.tick=1.0ms; " in lines[1]
    assert lines[1].endswith("device idle 40.0 ms from -25.0 ms of its start to -10.0 ms of its end")
    assert ", unknown (" in lines[2] and lines[2].endswith("engine thread in no span; device idle for no millisecond of it")


DECODE = ["serve_mistral_decode", "serve_xing_decode_7k", "serve_ling_decode_4k", "serve_joyai_mtp_decode_2k",
          "serve_trinity_decode_1k_8k", "serve_granite_decode_1k_4k", "serve_olmo_hybrid_decode_512_2k"]
ENTRIES = {
    "host_late_ms.decode": ("host process", "late_ms", "output_tokens_per_s", DECODE),
    "host_late_ms.chat": ("host process", "late_ms", "tpot_p50_ms", ["serve_mistral_chat_open"]),
    "host_late_ms.train": ("host process", "late_ms", "train_tokens_per_s_chip", ["train_gpt2large_1chip", "train_gpt2xl_fsdp4"]),
    "device_idle_late_share.decode": ("device", "idle_late_share", "output_tokens_per_s", DECODE),
    "device_idle_late_share.chat": ("device", "idle_late_share", "tpot_p50_ms", ["serve_mistral_chat_open"]),
    "device_idle_blocked_share.decode": ("device", "idle_blocked_share", "output_tokens_per_s", DECODE),
}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_manifest_gives_each_cell_the_entries_the_table_names(name):
    layer, stat, moves, cells = ENTRIES[name]
    (entry,) = [m for m in registry.manifest()["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "ms" if stat == "late_ms" else "%", "better": "lower",
                     "source": "program_span", "layer": layer, "moves": moves, "workloads": cells}
    assert registry.layer_metric_spec(name) == {"reader": "late_wake", "args": {"stat": stat}}
    assert registry.reader("late_wake") is late_wake.read
    for cell in (w["name"] for w in registry.manifest()["workloads"]):
        reported = [m["name"] for m in registry.metrics_for(cell, trace=True)]
        assert (name in reported) == (cell in cells), cell
