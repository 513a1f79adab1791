"""``program_trace`` and its two readers on a small xplane recorded on a TPU v5e
in PR 25 (``chipjobs``-style script: three runs of one jitted program whose two
matmul stages sit in the scopes ``mlp`` and ``attn.core`` and whose last matmul
sits in none, each run under ``serving.tick`` > ``serving.dispatch_window``
(meta ``steps``, ``window``) and ``serving.reap_window`` > ``serving.host_blocked``
on one thread, a ``loop.idle_wait`` on another, all under ``bench.window``), on
PR 24's small xplane, whose program has neither scopes nor spans, and on
hand-made intervals. Expected numbers are worked out by hand from the events'
times (microseconds below)."""

import os
import types

import pytest

from harness import program_trace as pt
from readers import scope_time_share, span_stat

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "small_program_v5e.xplane.pb")
NO_PROGRAM = os.path.join(DATA, "small_v5e.xplane.pb")
US = 1e-6


@pytest.fixture(scope="module")
def tr():
    return pt.load(SMALL)


@pytest.fixture(scope="module")
def red(tr):
    return pt.reduce(tr)


def ctx_for(reduced):
    """What a reader sees: the run's context with its trace already reduced."""
    return types.SimpleNamespace(_program_trace=reduced)


def test_what_the_file_holds(tr):
    assert tr.window == pytest.approx((44856399.0, 58822189.0))
    (ops,) = tr.ops.values()
    assert len(ops) == 18  # three runs of copy-start, copy-done and four fusions
    assert {o.path for o in ops} == {
        "", "jit(prog)/mlp/dot_general", "jit(prog)/attn.core/dot_general",
        "jit(prog)/attn.core/reduce_sum", "jit(prog)/dot_general"}
    assert {o.category for o in ops if "mlp" in o.path} == {"convolution fusion"}
    assert len({o.program_id for o in ops if o.path}) == 1
    (modules,) = tr.modules.values()
    assert [m.name.split("(")[0] for m in modules] == ["jit_prog"] * 3
    # two host threads of the same name are kept apart
    assert sorted(len(s) for s in tr.threads.values()) == [1, 12]
    dispatch = [s for s in tr.threads[pt.tick_thread(tr)] if s.name == "serving.dispatch_window"]
    assert [s.meta for s in dispatch] == [{"steps": 1, "window": w} for w in range(3)]
    reap = [s for s in tr.threads[pt.tick_thread(tr)] if s.name == "serving.reap_window"]
    assert all(s.meta["host_blocked_s"] == 0.001 for s in reap)


def test_scope_table_sums_to_busy(red):
    # the first run ended before the window span opened; inside it, two runs of
    # copy-start 0.014 + copy-done 0.003|0.004, mlp 11.575|11.576, attn.core
    # 11.543|11.544 + 0.894, and the unscoped last matmul 13.275
    rows = pt.scope_table(red.by_path)
    assert rows["mlp"] == pytest.approx(23.151 * US, abs=0.005 * US)
    assert rows["attn.core"] == pytest.approx(24.875 * US, abs=0.005 * US)
    assert rows[pt.UNSCOPED] == pytest.approx(26.585 * US, abs=0.005 * US)
    assert sum(rows.values()) == pytest.approx(red.busy_s, rel=1e-12)
    assert red.busy_s == pytest.approx(74.611 * US, abs=0.01 * US)
    assert red.window_s == pytest.approx(13965.79 * US, rel=1e-6)
    assert red.n_devices == 1
    assert pt.unscoped_ops(red.by_path)[0][0] == "fusion.2 fusion"


def test_scope_words():
    path = "jit(step_fn)/transpose(jvp(loss.ce))/while/body/closed_call/lm_head/sd,dv->sv/dot_general"
    assert pt.words(path)[:5] == ["jit", "step_fn", "transpose", "jvp", "loss.ce"]
    assert pt.innermost_scope(path) == "lm_head"
    assert pt.innermost_scope("jit(f)/mlp_hidden/add") == pt.UNSCOPED  # a word, not a substring
    by_path = {(path, "a"): 2.0, ("jit(f)/checkpoint/rematted_computation/mlp/dot_general", "b"): 3.0,
               ("jit(f)/mlp/dot_general", "c"): 5.0, ("", "d"): 7.0}
    assert pt.scope_seconds(by_path, ("lm_head", "loss.ce")) == 2.0  # in both, counted once
    assert pt.scope_seconds(by_path, ("mlp",)) == 8.0
    assert pt.scope_seconds(by_path, (), "rematted_computation") == 3.0
    assert pt.scope_seconds(by_path, pt.SCOPES) == 10.0


def test_self_times_by_hand():
    items = [(0.0, 100.0, "outer"), (10.0, 30.0, "a"), (15.0, 20.0, "a.1"), (40.0, 120.0, "b"), (200.0, 210.0, "c")]
    got = {p: (s, parent) for p, s, parent in pt.self_times(items)}
    # b starts inside outer and is cut at outer's end
    assert got == {"outer": (100.0 - 20.0 - 60.0, -1), "a": (15.0, 0), "a.1": (5.0, 1), "b": (60.0, 0), "c": (10.0, -1)}
    assert sum(s for s, _ in got.values()) == 110.0  # the union of the intervals
    segs = pt.innermost_segments([pt.Span("t", 0.0, 100.0), pt.Span("x", 10.0, 20.0), pt.Span("y", 50.0, 10.0)])
    assert segs == [(0.0, 10.0, "t"), (10.0, 30.0, "x"), (30.0, 50.0, "t"), (50.0, 60.0, "y"), (60.0, 100.0, "t")]


def test_span_table_and_self_time(red):
    table = pt.span_table(red.uses)
    assert {n: r["count"] for n, r in table.items()} == {
        "serving.tick": 3, "serving.dispatch_window": 3, "serving.reap_window": 3,
        "serving.host_blocked": 3, "loop.idle_wait": 1}
    # ticks of 2381.81, 2190.03 and 2171.67; inside them dispatch 294.15 320.73 242.23
    # and reap 495.54 527.77 540.66, which hold host_blocked 480.67 510.42 527.21
    assert table["serving.tick"]["total_s"] == pytest.approx(6743.51 * US, abs=0.02 * US)
    assert table["serving.tick"]["self_s"] == pytest.approx((1592.12 + 1341.53 + 1388.78) * US, abs=0.05 * US)
    assert table["serving.reap_window"]["self_s"] == pytest.approx((14.87 + 17.35 + 13.45) * US, abs=0.05 * US)
    assert table["serving.host_blocked"]["self_s"] == table["serving.host_blocked"]["total_s"]


def test_span_stat_reader(red):
    ctx = ctx_for(red)
    read = lambda **kw: span_stat.read(None, None, ctx, **kw)
    assert read(span="serving.tick") == pytest.approx(6743.51 / 3 / 1e3, abs=1e-5)
    assert read(span="serving.tick", stat="self_mean_ms") == pytest.approx(4322.43 / 3 / 1e3, abs=1e-4)
    assert read(span="serving.tick", minus=["serving.host_blocked"]) == pytest.approx(5225.21 / 3 / 1e3, abs=1e-5)
    assert read(span="serving.dispatch_window", stat="p95_ms") == pytest.approx(0.32073, abs=1e-5)
    assert read(span="serving.host_blocked", stat="share") == pytest.approx(100 * 1518.30 / 13965.79, abs=1e-3)
    assert read(span="serving.admit", stat="p95_ms") is None  # no such span in this trace
    with pytest.raises(ValueError):
        read(span="serving.tick", stat="median_ms")


def test_scope_time_share_reader(red):
    ctx = ctx_for(red)
    read = lambda **kw: scope_time_share.read(None, None, ctx, **kw)
    assert read(scopes=["mlp"]) == pytest.approx(100 * 23.151 / 13965.79, rel=1e-3)
    assert read(scopes=["mlp", "attn.core"], over="busy") == pytest.approx(100 * 48.026 / 74.611, rel=1e-3)
    assert read(scopes="all", over="busy") == pytest.approx(100 * 48.026 / 74.611, rel=1e-3)
    assert read(scopes=["optimizer"]) is None
    assert read(path_has="rematted_computation") is None


def test_clock_offset_and_idle_by_span(tr, red):
    assert red.offset is None  # no program of the engine's decode step in this trace
    off = pt.clock_offset(tr, module="jit_prog")
    # window w: dispatch begins 45460.219 50076.249 54462.229, its program runs
    # 44469.786 49075.407 53409.881 for 37.31, host_blocked ends 46243.979 50916.389 55241.179
    assert off["low_ns"] == pytest.approx(1052.348e3, abs=5)
    assert off["high_ns"] == pytest.approx(1736.879e3, abs=5)
    assert off["pairs"] == 3 and off["low_ns"] <= off["estimate_ns"] <= off["high_ns"]
    # on the device's own clock the first run lies before the window and before its dispatch
    raw = pt.idle_by_span(tr)
    assert sum(raw.values()) == pytest.approx(13965.79 * US - red.busy_s, rel=1e-9)
    # moved onto the host's clock all three runs fall into their host_blocked spans
    moved = pt.idle_by_span(tr, off["estimate_ns"])
    assert sum(moved.values()) == pytest.approx((13965.79 - 3 * 37.31) * US, abs=0.1 * US)
    assert moved["serving.host_blocked"] == pytest.approx((1518.30 - 3 * 37.31) * US, abs=0.1 * US)
    assert moved["serving.dispatch_window"] == pytest.approx(857.11 * US, abs=0.05 * US)
    assert moved["serving.tick"] == pytest.approx(4322.43 * US, abs=0.1 * US)


def test_a_program_without_scopes_or_spans_reads_none():
    red = pt.reduce(pt.load(NO_PROGRAM))
    assert red.busy_s == pytest.approx(31.2e-6, abs=0.05e-6)  # as reduce_trace reads it
    assert set(pt.scope_table(red.by_path)) == {pt.UNSCOPED}
    assert red.uses == [] and red.offset is None and red.idle_by_span_s == {}
    ctx = ctx_for(red)
    assert scope_time_share.read(None, None, ctx, scopes=["mlp"]) is None
    assert scope_time_share.read(None, None, ctx, scopes="all", over="busy") is None
    assert span_stat.read(None, None, ctx, span="serving.tick") is None
    pt.report(red, lambda line: None)  # logs what it has, raises for nothing


def test_a_run_without_a_trace_reads_none():
    ctx = types.SimpleNamespace(trace_dir=None)
    assert pt.for_run(ctx) is None
    assert span_stat.read(None, None, ctx, span="serving.tick") is None
    assert scope_time_share.read(None, None, ctx, scopes=["mlp"]) is None


def test_new_metric_files_use_the_new_readers():
    from harness import registry

    new = {m["name"] for m in registry.manifest()["per_layer"]
           if registry.layer_metric_spec(m["name"])["reader"] in ("scope_time_share", "span_stat")}
    assert new == {
        "paged_attn_time_share.decode", "paged_attn_time_share.chat", "mlp_time_share.decode",
        "optimizer_time_share.train", "remat_recompute_time_share.train", "ce_head_time_share.train",
        "scope_coverage.train", "scope_coverage.decode", "scope_coverage.chat",
        "tick_host_ms.decode", "tick_host_nowait_ms.chat", "admit_ms_p95.chat", "loop_overhead_ms_per_turn.chat"}
    listed = registry.list_all()
    assert {"scope_time_share", "span_stat"} <= set(listed["readers"])
    assert new <= set(listed["layer_metrics"])
