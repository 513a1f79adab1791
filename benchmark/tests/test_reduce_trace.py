"""``reduce_trace`` on a small xplane recorded on a TPU v5e in PR 24
(``chipjobs``-style script: three runs of a 1024x1024 bf16 matmul-and-sum and one
tanh, under bench.* spans), and on hand-made intervals."""

import os

import pytest

from harness import reduce_trace as rt

SMALL = os.path.join(os.path.dirname(__file__), "data", "small_v5e.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return rt.summarize(rt.load(SMALL))


def test_busy_and_window(summary):
    # window span 12005.729 us; inside it two matmul fusions of 11.84 us and one tanh of
    # 7.49 us (the first matmul ran before the window span opened), plus copies of a few ns
    assert summary["n_devices"] == 1
    assert summary["window_s"] == pytest.approx(12005.729e-6, rel=1e-6)
    assert summary["busy_s"] == pytest.approx(31.2e-6, abs=0.05e-6)
    assert 100 * (1 - summary["busy_s"] / summary["window_s"]) == pytest.approx(99.74, abs=0.01)


def test_per_op_and_modules(summary):
    ops = summary["op_s"]
    assert ops["convolution_reduce_fusion fusion"] == pytest.approx(2 * 11.84e-6, rel=2e-3)
    assert ops["tanh_multiply_fusion fusion"] == pytest.approx(7.49e-6, rel=2e-3)
    assert summary["device_ops"][0][0] == "convolution_reduce_fusion fusion"
    runs = summary["module_runs_s"]
    assert sorted(len(v) for v in runs.values()) == [1, 2]


def test_gap_attribution(summary):
    by = summary["idle_by_span_s"]
    # every idle nanosecond goes to exactly one owner
    assert sum(by.values()) == pytest.approx(summary["window_s"] - summary["busy_s"], rel=1e-9)
    # the three bench.next_batch spans last 2192.2 + 2188.5 + 2367.3 us and hold all 31.2 us of work
    assert by["bench.next_batch"] == pytest.approx(6748.0e-6 - 31.2e-6, abs=0.2e-6)
    assert by["bench.sync"] == pytest.approx((704.1 + 761.4 + 638.4) * 1e-6, abs=0.2e-6)
    assert max(by, key=by.get) == "bench.next_batch"
    assert summary["idle_gaps"][0][0] == "sum:bench.next_batch"


def test_intervals_by_hand():
    ev = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 30.0, 5.0)]
    assert rt.merged(ev) == [(0.0, 15.0), (30.0, 35.0)]
    assert rt.busy_ns(ev) == 20.0
    assert rt.per_op_ns(ev + [("a", 40.0, 1.0)])["a"] == 11.0
    assert rt.gaps(ev, 0.0, 50.0) == [(15.0, 30.0), (35.0, 50.0)]
    assert rt.clip(ev, 8.0, 32.0) == [("a", 8.0, 2.0), ("b", 8.0, 7.0), ("c", 30.0, 2.0)]
    spans = [("bench.outer", 10.0, 30.0), ("bench.inner", 20.0, 5.0)]
    parts = rt.attribute([(15.0, 30.0), (35.0, 50.0)], spans)
    owners = {}
    for n, _, d in parts:
        owners[n] = owners.get(n, 0.0) + d
    assert owners == {"bench.outer": 10.0 + 5.0, "bench.inner": 5.0, "unattributed": 10.0}


def test_short_names():
    w1 = ("%fusion.1786.remat = (bf16[1,4096]{1,0}, bf16[1,2]{1,0}) fusion(bf16[18,4096,2,14336]{3,2,1,0} "
          "%params__blocks____mlp____w1__.1), kind=kLoop, calls=%fused_computation.1603.clone")
    assert rt.short_name(w1) == "fusion.1786.remat <-params__blocks____mlp____w1__"
    assert rt.short_name("%copy.255 = bf16[1,4]{1,0} copy(bf16[1,4]{0,1} %x.8)") == "copy.255 copy"
    assert rt.short_name("jit_step(123)") == "jit_step(123)"
    fused = ("%fusion.361 = f32[6400,448]{0,1} fusion(f32[6400,1600]{0,1} %get-tuple-element.2510), "
             "kind=kCustom, calls=%all-reduce-scatter.1.clone.clone")
    assert rt.short_name(fused) == "fusion.361 fusion all-reduce-scatter"
    assert rt.short_name("%all-reduce.47 = f32[2048,50304]{1,0} all-reduce(f32[2048,50304]{1,0} %x.1), "
                         "channel_id=10") == "all-reduce.47 all-reduce"
