"""Deep-pipelined serving scheduler: depth-N in-flight window queue.

The correctness bar (CPU-enforced): greedy tokens are BIT-IDENTICAL to
the synchronous scheduler (`run(pipeline=False)`) at EVERY pipeline
depth, through admission churn, early finishes, stop tokens, and the
preemption/replay reconciliation path. The pipelining is pure host
scheduling — a depth that changed a single emitted token would be a
speculation-reconciliation bug, not a perf trade-off.
"""

import dataclasses

import jax
import numpy as np
import pytest

from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.generation.generate import generate
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer

import jax.numpy as jnp

CFG = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
DRAFT_CFG = dataclasses.replace(CFG, n_layers=1, d_model=16, n_heads=2)

DEPTHS = [1, 2, 3]


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


@pytest.fixture(scope="module")
def draft_params():
    return transformer.init_params(DRAFT_CFG, jax.random.key(99))


def _prompts(n, lengths=(5, 9, 14, 7, 11, 3, 16, 6)):
    rng = np.random.default_rng(42)
    out = []
    for i in range(n):
        p = int(lengths[i % len(lengths)])
        out.append(rng.integers(0, CFG.vocab_size, size=p).tolist())
    return out


def _reference_greedy(params, cfg, prompt, n_new):
    toks = generate(
        params, cfg, jnp.asarray([prompt], jnp.int32), n_new,
        jax.random.key(7), temperature=0.0,
    )
    return np.asarray(toks)[0].tolist()


def _run_pair(params, prompts, n_new, *, depth, **kw):
    """Run the SAME workload through the synchronous scheduler and the
    pipelined one at ``depth``; returns (sync_out, piped_out, piped_eng).
    Two engines: run() mutates allocator/pool state."""
    sync = ServingEngine(params, CFG, temperature=0.0, **kw)
    s_rids = [sync.submit(p, n_new) for p in prompts]
    s_out = sync.run(pipeline=False)
    piped = ServingEngine(
        params, CFG, temperature=0.0, pipeline_depth=depth, **kw
    )
    p_rids = [piped.submit(p, n_new) for p in prompts]
    p_out = piped.run(pipeline=True)
    assert s_rids == p_rids  # same submission order -> same rids
    return s_out, p_out, piped


# -- bit-identity at every depth ------------------------------------------


@pytest.mark.parametrize("depth", DEPTHS)
def test_depth_identity_admission_churn(params, depth):
    """More requests than rows: rows free and re-admit continuously, so
    windows carry surplus tokens for finished rows and admission merges
    land mid-queue — tokens must not move by one bit at any depth."""
    prompts = _prompts(6)
    n_new = 9  # not a multiple of the window: mid-window finishes
    s_out, p_out, eng = _run_pair(
        params, prompts, n_new, depth=depth,
        max_batch=2, n_blocks=24, block_size=8, steps_per_sched=4,
    )
    assert p_out == s_out
    for rid, p in zip(sorted(p_out), prompts):
        assert p_out[rid] == _reference_greedy(params, CFG, p, n_new)
    assert eng.stats["windows_reaped"] == eng.stats["windows"]


@pytest.mark.parametrize("depth", DEPTHS)
def test_depth_identity_early_finish_stop_token(params, depth):
    """A stop token landing mid-window finishes rows early while deeper
    queues keep dispatching surplus windows for them — the surplus must
    be discarded at reap, never emitted."""
    prompts = _prompts(3)
    n_new = 12
    refs = [_reference_greedy(params, CFG, p, n_new) for p in prompts]
    stop = refs[0][4]  # a token greedy WILL emit for prompt 0
    s_out, p_out, _ = _run_pair(
        params, prompts, n_new, depth=depth,
        max_batch=2, n_blocks=32, block_size=8, steps_per_sched=4,
        stop_token=stop,
    )
    assert p_out == s_out
    for rid, ref in zip(sorted(p_out), refs):
        want = ref[: ref.index(stop)] if stop in ref else ref
        assert p_out[rid] == want


@pytest.mark.parametrize("depth", DEPTHS)
def test_depth_identity_preemption_replay(params, depth):
    """Tiny pool forcing preemption: the queue must FLUSH before any
    eviction decision (committed prompt+generated bookkeeping), then
    replay from committed state — recompute-on-resume resumes from the
    exact prefix at every depth."""
    prompts = [_prompts(1, lengths=(12,))[0], _prompts(1, lengths=(10,))[0]]
    n_new = 24
    s_out, p_out, eng = _run_pair(
        params, prompts, n_new, depth=depth,
        max_batch=2, n_blocks=8, block_size=8, steps_per_sched=4,
    )
    assert p_out == s_out
    assert eng.stats["preemptions"] >= 1
    for rid, p in zip(sorted(p_out), prompts):
        assert p_out[rid] == _reference_greedy(params, CFG, p, n_new)


def test_replay_path_flushes_inflight_queue(params):
    """The reconciliation path itself: with a deep queue and a pool too
    small for the in-flight horizon, a dry allocator must drain the
    queue (stats['flushes']), invalidate the speculative chain, and the
    next dispatch must restart from committed host state — outputs still
    exact. This is the test that fails if _flush_inflight or the
    empty-queue replay branch of _dispatch_window regresses."""
    prompts = [_prompts(1, lengths=(12,))[0], _prompts(1, lengths=(10,))[0]]
    n_new = 24
    s_out, p_out, eng = _run_pair(
        params, prompts, n_new, depth=3,
        max_batch=2, n_blocks=8, block_size=8, steps_per_sched=4,
    )
    assert p_out == s_out
    assert eng.stats["flushes"] >= 1, eng.stats
    # Every dispatched window is accounted for despite the flushes.
    assert eng.stats["windows_reaped"] == eng.stats["windows"]


@pytest.mark.parametrize("depth", [2, 3])
def test_depth_identity_max_new_one(params, depth):
    """max_new=1 finishes on the deferred admission token alone — the
    row must free and recycle without ever joining a decode window."""
    prompts = _prompts(3)
    s_out, p_out, _ = _run_pair(
        params, prompts, 1, depth=depth,
        max_batch=1, n_blocks=16, block_size=8, steps_per_sched=4,
    )
    assert p_out == s_out
    for rid, p in zip(sorted(p_out), prompts):
        assert p_out[rid] == _reference_greedy(params, CFG, p, 1)


# -- speculative rounds join the queue ------------------------------------


@pytest.mark.parametrize("depth", [2, 3])
def test_spec_rounds_join_queue_identity(params, draft_params, depth):
    """Speculative serving at depth > 1: round k+1 chains seed+frontier
    on device (spec_next_inputs) while round k is unreaped. Greedy
    output must equal the synchronous spec scheduler AND the dense-cache
    target-only reference, with an untrained low-hit-rate draft."""
    prompts = _prompts(4)
    n_new = 10
    s_out, p_out, eng = _run_pair(
        params, prompts, n_new, depth=depth,
        max_batch=2, n_blocks=32, block_size=8,
        draft_params=draft_params, draft_cfg=DRAFT_CFG, spec_k=3,
    )
    assert p_out == s_out
    assert eng.stats["spec_rounds"] > 0
    for rid, p in zip(sorted(p_out), prompts):
        assert p_out[rid] == _reference_greedy(params, CFG, p, n_new)


def test_spec_pipelined_self_draft_acceptance_accounting(params):
    """Self-draft at depth 2: acceptance must still be total, and the
    reap-time telemetry must count only SURVIVING rows' rounds — surplus
    rounds for finished rows skew neither proposed nor accepted."""
    p = _prompts(1)[0]
    n_new = 9
    eng = ServingEngine(
        params, CFG, max_batch=1, n_blocks=32, block_size=8,
        temperature=0.0, draft_params=params, draft_cfg=CFG, spec_k=2,
        pipeline_depth=2,
    )
    rid = eng.submit(p, n_new)
    out = eng.run(pipeline=True)
    assert out[rid] == _reference_greedy(params, CFG, p, n_new)
    st = eng.stats
    assert st["spec_accepted"] == st["spec_proposed"], st


# -- cross-window admission batching --------------------------------------


def test_admit_batch_defers_then_batches(params):
    """admit_batch=3 with one row initially free: the gate must DEFER
    dribble admissions until three can land in one batched prefill, and
    the deferral must not change a single emitted token."""
    prompts = _prompts(6)
    n_new = 8
    kw = dict(max_batch=4, n_blocks=48, block_size=8, steps_per_sched=4)
    s_out, p_out, eng = _run_pair(
        params, prompts, n_new, depth=2, admit_batch=3, **kw
    )
    assert p_out == s_out
    assert eng.stats.get("admit_batches", 0) + eng.stats.get(
        "admit_deferrals", 0) >= 1, eng.stats
    for rid, p in zip(sorted(p_out), prompts):
        assert p_out[rid] == _reference_greedy(params, CFG, p, n_new)


def test_admit_batch_idle_engine_never_deadlocks(params):
    """An idle engine (no active rows) must admit whatever fits even if
    fewer than admit_batch requests are waiting — the gate only defers
    while the device has other work."""
    prompts = _prompts(2)
    n_new = 6
    eng = ServingEngine(
        params, CFG, max_batch=4, n_blocks=32, block_size=8,
        temperature=0.0, pipeline_depth=2, admit_batch=8,
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run(pipeline=True)
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, CFG, p, n_new)


# -- host-blocked telemetry -----------------------------------------------


def test_host_blocked_counter_monotonic(params, monkeypatch):
    """Per-reap telemetry invariants: windows_reaped increments by
    exactly one per reap and host_blocked_s is monotonically
    non-decreasing (a reap that SUBTRACTED blocked time would corrupt
    the blocked time per window)."""
    seen = []
    orig = ServingEngine._reap_window

    def spy(self, w):
        orig(self, w)
        seen.append(
            (self.stats["windows_reaped"], self.stats["host_blocked_s"])
        )

    monkeypatch.setattr(ServingEngine, "_reap_window", spy)
    prompts = _prompts(4)
    eng = ServingEngine(
        params, CFG, max_batch=2, n_blocks=32, block_size=8,
        temperature=0.0, steps_per_sched=4, pipeline_depth=2,
    )
    for p in prompts:
        eng.submit(p, 8)
    eng.run(pipeline=True)
    assert len(seen) >= 2
    assert [n for n, _ in seen] == list(range(1, len(seen) + 1))
    blocked = [b for _, b in seen]
    assert all(b2 >= b1 >= 0.0 for b1, b2 in zip(blocked, blocked[1:]))
    assert eng.stats["host_blocked_s"] == blocked[-1]


def test_reap_window_records_spans(params):
    """Each dispatch/reap lands a span with the per-window host-blocked
    seconds in its meta — the counters the Chrome trace exposes."""
    from pretraining_llm_tpu.observability import spans

    rec = spans.SpanRecorder()
    spans.set_recorder(rec)
    try:
        eng = ServingEngine(
            params, CFG, max_batch=2, n_blocks=32, block_size=8,
            temperature=0.0, steps_per_sched=4, pipeline_depth=2,
        )
        for p in _prompts(2):
            eng.submit(p, 6)
        eng.run(pipeline=True)
        summary = rec.summary()
        assert summary["serving.dispatch_window"]["count"] == eng.stats["windows"]
        assert summary["serving.reap_window"]["count"] == eng.stats["windows_reaped"]
        trace = rec.to_chrome_trace()["traceEvents"]
        reaps = [e for e in trace if e["name"] == "serving.reap_window"]
        assert reaps and all(
            "host_blocked_s" in e["args"] and e["args"]["host_blocked_s"] >= 0
            for e in reaps
        )
    finally:
        spans.set_recorder(None)


# -- engine knob validation ------------------------------------------------


def test_pipeline_knob_validation(params):
    with pytest.raises(ValueError, match="pipeline_depth"):
        ServingEngine(params, CFG, pipeline_depth=0)
    with pytest.raises(ValueError, match="admit_batch"):
        ServingEngine(params, CFG, admit_batch=-1)


def test_depth_one_is_double_buffered_scheduler(params):
    """depth=1 must reproduce the classic double-buffered scheduler:
    never more than one unreaped window beyond the reap threshold, and
    outputs identical to sync (the degenerate case of the depth
    contract)."""
    prompts = _prompts(4)
    n_new = 8
    s_out, p_out, eng = _run_pair(
        params, prompts, n_new, depth=1,
        max_batch=2, n_blocks=32, block_size=8, steps_per_sched=4,
    )
    assert p_out == s_out
    assert eng.pipeline_depth == 1
    assert eng.stats["windows_reaped"] == eng.stats["windows"]
