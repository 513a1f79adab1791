"""The program's own instrumentation: host spans (observability/spans.py) in
the serving engine and the engine loop, the always-on phase account of the
scheduler tick, and the ``jax.named_scope`` names in the compiled programs.

The names are a contract: the benchmark's readers (benchmark/harness/
program_trace.py) find spans and scopes by them, PERF.md lists them.
"""

import contextlib
import dataclasses
import logging
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.frontend.engine_loop import EngineLoop
from pretraining_llm_tpu.generation import paged, serving
from pretraining_llm_tpu.generation.serving import PHASES, ServingEngine
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.observability import spans, witness
from pretraining_llm_tpu.training import train_step as ts

TINY = get_preset("tiny")
# RoPE and grouped KV heads, so every scope of the attention block is on the path.
CFG = dataclasses.replace(
    TINY.model, compute_dtype="float32", pos_embed="rope", n_kv_heads=2, remat="full"
)
TRAIN_CFG = dataclasses.replace(
    TINY, model=CFG, train=dataclasses.replace(TINY.train, batch_size=4, microbatches=2)
)


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


def _prompts(n, lengths=(5, 9, 14, 7, 11, 3)):
    rng = np.random.default_rng(42)
    return [rng.integers(0, CFG.vocab_size, size=lengths[i % len(lengths)]).tolist() for i in range(n)]


def _engine(params, **kw):
    kw = {"max_batch": 2, "n_blocks": 32, "block_size": 8, "temperature": 0.0,
          "steps_per_sched": 4, "pipeline_depth": 2, **kw}
    return ServingEngine(params, CFG, **kw)


# -- (a) spans: off by default, names and nesting when a recorder exists ------------


def test_no_recorder_means_nothing_is_recorded(params, monkeypatch):
    monkeypatch.setattr(spans, "_default", None)
    eng = _engine(params)
    for p in _prompts(3):
        eng.submit(p, 6)
    out = eng.run(pipeline=True)
    assert len(out) == 3
    # module-level span() never made a recorder, so there is nowhere a span could be
    assert spans._default is None
    rec = spans.get_recorder()  # asking for it is what turns recording on
    assert spans._default is rec and rec.summary() == {}


@pytest.fixture()
def recorded_run(params, monkeypatch):
    rec = spans.SpanRecorder()
    monkeypatch.setattr(spans, "_default", rec)
    eng = _engine(params)
    for p in _prompts(3):
        eng.submit(p, 6)
    eng.run(pipeline=True)
    events, dropped = rec.drain()
    assert dropped == 0
    return eng, events


def test_span_names_and_counts(recorded_run):
    eng, events = recorded_run
    count = {}
    for name, *_ in events:
        count[name] = count.get(name, 0) + 1
    assert count["serving.tick"] == eng.stats["ticks"]
    assert count["serving.dispatch_window"] == eng.stats["windows"]
    assert count["serving.reap_window"] == eng.stats["windows_reaped"]
    assert count["serving.host_blocked"] == count["serving.commit"] == eng.stats["windows_reaped"]
    # three requests over two rows: the third waits, so admission runs in more than one turn
    assert count["serving.prefill_dispatch"] >= 2
    assert count["serving.admit"] >= count["serving.prefill_dispatch"]
    assert count["serving.ensure_pages"] == count["serving.dispatch_window"]
    admitted = [m for name, *_, m in events if name == "serving.admit"]
    assert sum(m["rows"] for m in admitted) == eng.stats["admissions"] == 3
    reaps = [m for name, *_, m in events if name == "serving.reap_window"]
    assert all(m["host_blocked_s"] >= 0 for m in reaps)


@pytest.mark.parametrize("child,parent", [
    ("serving.admit", "serving.tick"),
    ("serving.prefill_dispatch", "serving.admit"),
    ("serving.ensure_pages", "serving.tick"),
    ("serving.dispatch_window", "serving.tick"),
    ("serving.reap_window", "serving.tick"),
    ("serving.host_blocked", "serving.reap_window"),
    ("serving.commit", "serving.reap_window"),
])
def test_span_nesting(recorded_run, child, parent):
    _, events = recorded_run
    parents = [(t0, t0 + dur, depth) for name, t0, dur, _, depth, _ in events if name == parent]
    children = [(t0, t0 + dur, depth) for name, t0, dur, _, depth, _ in events if name == child]
    assert children
    for a, b, depth in children:
        assert any(pa <= a and b <= pb and pdepth < depth for pa, pb, pdepth in parents), (child, a, b)


def test_engine_loop_spans(params, monkeypatch):
    rec = spans.SpanRecorder()
    monkeypatch.setattr(spans, "_default", rec)
    eng = _engine(params)
    with EngineLoop(eng, idle_wait_s=0.005) as loop:
        status, tokens, _ = loop.submit(_prompts(1)[0], 6).result(timeout=60)
        time.sleep(0.05)  # a few idle turns
    assert status == "done" and len(tokens) == 6
    by_thread = {}
    for name, t0, dur, tid, depth, _ in rec.drain()[0]:
        by_thread.setdefault(tid, []).append((name, t0, t0 + dur, depth))
    (loop_events,) = [ev for ev in by_thread.values() if any(e[0] == "loop.turn" for e in ev)]
    names = {e[0] for e in loop_events}
    assert {"loop.turn", "loop.inbox", "loop.deadlines", "loop.idle_wait", "serving.tick"} <= names
    turns = [e for e in loop_events if e[0] == "loop.turn"]
    for name, a, b, depth in loop_events:
        if name in ("loop.inbox", "loop.deadlines", "loop.idle_wait", "serving.tick"):
            assert any(ta <= a and b <= tb and tdepth < depth for _, ta, tb, tdepth in turns), name
    assert loop.counters["slow_turns"] == 0


WITNESS_SPANS = {
    "loop.late_wake": {"late_ms", "ended_ms_ago", "outside_ms", "gc_ms", "outside"},
    "loop.witness_beat": set(),  # read for its presence alone
}


@pytest.mark.parametrize("name", sorted(WITNESS_SPANS))
def test_witness_span_names_and_meta(name, monkeypatch):
    """The late-wake witness's two spans (observability/witness.py), as
    ``benchmark/readers/late_wake.py`` finds them: profiler annotations with
    numbers in their meta, and nothing in the in-memory recorder."""
    rec = spans.SpanRecorder()
    monkeypatch.setattr(spans, "_default", rec)
    made = []
    real = spans.Span

    def recording(span_name, recorder, meta):
        made.append((span_name, recorder, dict(meta)))
        return real(span_name, recorder, meta)

    monkeypatch.setattr(witness, "Span", recording)
    now = [50.0]
    w = witness.Witness(clock=lambda: now[0], sleep=lambda s: now.__setitem__(0, now[0] + s))
    w.step()
    now[0] += 0.1  # the next wake comes 100 ms late
    for _ in range(int(witness.BEAT_S / witness.PERIOD_S)):
        w.step()
    (meta,) = [m for n, r, m in made if n == name]
    assert set(meta) == WITNESS_SPANS[name]
    assert all(isinstance(v, (int, float)) for v in meta.values())  # what `program_trace.load` brings back
    assert all(r is None for _, r, _ in made) and rec.summary() == {}
    assert meta.get("outside", 0) == 0  # this one has no child


# -- (b) the always-on phase account --------------------------------------------------


def test_phase_account_sums_to_the_ticks(recorded_run):
    eng, events = recorded_run
    phase_s = eng.stats["phase_s"]
    assert set(phase_s) == set(PHASES)
    ticks_s = sum(dur for name, _, dur, *_ in events if name == "serving.tick")
    assert sum(phase_s.values()) == pytest.approx(ticks_s, rel=0.01)
    assert phase_s["host_blocked"] == pytest.approx(eng.stats["host_blocked_s"], rel=1e-9)
    for phase in ("admit", "prefill_dispatch", "ensure_pages", "dispatch", "host_blocked", "commit", "other"):
        assert phase_s[phase] > 0, phase
    longest = eng.stats["longest_tick"]
    assert 1 <= longest["tick"] <= eng.stats["ticks"]
    assert sum(longest["phase_s"].values()) == pytest.approx(longest["seconds"], rel=1e-6)
    assert eng.stats["slow_ticks"] == 0


def _tick_pages(eng):
    """(live, tabled) one tick added: a tick dispatches at most one window."""
    before = (eng.stats["attn_pages_live"], eng.stats["attn_pages_tabled"])
    eng.pipeline_tick()
    return (eng.stats["attn_pages_live"] - before[0], eng.stats["attn_pages_tabled"] - before[1])


@pytest.mark.parametrize("window", [0, 12], ids=["no-window", "sliding-window"])
def test_attention_pages_live_and_tabled_grow_with_each_window(params, window, caplog):
    """``attn_pages_live`` counts, for every step of a decode window and every
    decoding row, the pages that hold a slot the step can see; ``attn_pages_tabled``
    the pages its block tables name, ``max_batch x max_blocks`` a step."""
    cfg = dataclasses.replace(CFG, sliding_window=window)
    eng = ServingEngine(params, cfg, max_batch=3, n_blocks=32, block_size=8, max_seq=64,
                        steps_per_sched=2, pipeline_depth=2)
    assert (eng.stats["attn_pages_live"], eng.stats["attn_pages_tabled"]) == (0, 0)
    assert _tick_pages(eng) == (0, 0)  # every row idle: no window, nothing read
    prompts = _prompts(2, lengths=(21, 9))
    for p in prompts:
        eng.submit(p, 20)
    lengths = np.asarray([len(p) for p in prompts])  # a row's first decode step writes slot len(prompt)

    def expected(seq):
        steps = seq[:, None] + np.arange(2)[None, :]
        first = np.maximum(steps - window + 1, 0) // 8 if window else 0
        return int(np.sum(steps // 8 - first + 1))

    tabled = 2 * 3 * eng.max_blocks  # two steps, three rows' tables, one of them idle
    assert _tick_pages(eng) == (expected(lengths), tabled)
    assert _tick_pages(eng) == (expected(lengths + 2), tabled)
    assert 0 < eng.stats["attn_pages_live"] <= eng.stats["attn_pages_tabled"]
    with caplog.at_level(logging.INFO, logger="pretraining_llm_tpu.serving"):
        while eng.pipeline_tick():
            pass
    st = eng.stats
    assert st["attn_pages_live"] <= st["attn_pages_tabled"] == st["steps"] * 3 * eng.max_blocks
    lines = [r.getMessage() for r in caplog.records if "engine empty" in r.getMessage()]
    assert len(lines) == 1 and f"{st['attn_pages_live']} live pages of {st['attn_pages_tabled']} tabled" in lines[0]


class _SlowReadback:
    """``numpy`` for the serving module, whose next ``asarray`` takes ``delay`` seconds."""

    def __init__(self):
        self.delay = 0.0

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, *args, **kw):
        delay, self.delay = self.delay, 0.0
        time.sleep(delay)
        return np.asarray(*args, **kw)


def test_stalled_readback_logs_one_slow_tick_naming_the_phase(params, monkeypatch, caplog):
    slow_np = _SlowReadback()
    monkeypatch.setattr(serving, "np", slow_np)
    eng = _engine(params, steps_per_sched=1)
    for p in _prompts(2):
        eng.submit(p, 40)
    with caplog.at_level(logging.WARNING, logger="pretraining_llm_tpu.serving"):
        for _ in range(20):
            eng.pipeline_tick()
        assert eng.stats["slow_ticks"] == 0 and not caplog.records
        # nobody waits and both rows decode: the tick's first host read is the window's readback
        slow_np.delay = 0.4
        eng.pipeline_tick()
        for _ in range(5):
            eng.pipeline_tick()
        # the witness thread writes the line, a period or two after the tick, once it knows the cause
        deadline = time.monotonic() + 5.0
        while not caplog.records and time.monotonic() < deadline:
            time.sleep(witness.PERIOD_S)
        time.sleep(5 * witness.PERIOD_S)  # as long again as a second line would take
    assert eng.stats["slow_ticks"] == 1
    (record,) = [r for r in caplog.records if "slow tick" in r.getMessage()]
    message = record.getMessage()
    assert "slow tick 21:" in message
    # the split lists phases largest first
    assert message.split(": ", 2)[2].startswith("host_blocked=")
    longest = eng.stats["longest_tick"]
    assert longest["tick"] == 21 and longest["phase_s"]["host_blocked"] >= 0.4
    # the line ends with the witness's verdict: the readback slept, so the interpreter was free
    # and the sleepers were on time, unless this machine held the test itself for 20 ms
    assert re.search(r"; (every sleeper was on time: the device or the transfer"
                     r"|the process could not run for [0-9.]+ of it \((machine|process|unknown): [^)]*\))$", message)
    assert max(longest["phase_s"], key=longest["phase_s"].get) == "host_blocked"


# -- (c) scope names in the lowered programs ------------------------------------------

MODEL_SCOPES = ("embed", "blk.norm", "attn.qkv", "attn.rope", "attn.kv_write", "attn.core",
                "attn.out", "mlp", "final_norm", "lm_head")
PROGRAM_SCOPES = {
    "decode": MODEL_SCOPES + ("attn.paged_gather", "sample"),
    "prefill": MODEL_SCOPES + ("sample",),
    "train": tuple(s for s in MODEL_SCOPES if s != "attn.kv_write")
    + ("loss.ce", "optimizer", "grad_clip", "microbatch"),
}


def _decode_args(params, rows=2):
    pools = transformer.make_paged_kv_pool(CFG, 16, 8)
    tables = jnp.asarray(np.arange(1, 1 + rows * 4).reshape(rows, 4), jnp.int32)
    return (params, pools, jnp.asarray([3, 5][:rows], jnp.int32), tables,
            jnp.asarray([4, 9][:rows], jnp.int32), jax.random.key(1))


def _prefill_args(params):
    pools = transformer.make_paged_kv_pool(CFG, 16, 8)
    rng = np.random.default_rng(7)
    prompts = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 16)), jnp.int32)
    return (params, pools, prompts, jnp.asarray([16, 11], jnp.int32),
            jnp.asarray([[1, 2], [3, 4]], jnp.int32), jax.random.key(2))


def _train_args():
    state = ts.init_train_state(TRAIN_CFG, jax.random.key(3))
    rng = np.random.default_rng(11)
    b, t = TRAIN_CFG.train.batch_size, CFG.context_length
    x = jnp.asarray(rng.integers(0, CFG.vocab_size, (b, t)), jnp.int32)
    return state, (x, jnp.roll(x, -1, axis=1))


def _run_programs(params):
    """The three programs on fixed inputs: every output leaf, as numpy."""
    toks, pools = paged.paged_decode_steps(*_decode_args(params), CFG, n_steps=3)
    first, pools2 = paged._prefill_scatter_sample(*_prefill_args(params), CFG, 16, 2)
    state, metrics = ts.build_train_step(TRAIN_CFG)(*_train_args())
    return [np.asarray(leaf) for leaf in jax.tree.leaves((toks, pools, first, pools2, state, metrics))]


@pytest.fixture(scope="module")
def scope_tokens(params):
    """Per program, every word of every op's name path in the lowered text."""
    lowered = {
        "decode": paged.paged_decode_steps.lower(*_decode_args(params), CFG, n_steps=3),
        "prefill": paged._prefill_scatter_sample.lower(*_prefill_args(params), CFG, 16, 2),
        "train": ts.lower_train_step(TRAIN_CFG),
    }
    out = {}
    for program, low in lowered.items():
        paths = set(re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True)))
        out[program] = {"paths": paths, "words": {w for p in paths for w in re.split(r"[/()]", p)}}
    return out


@pytest.mark.parametrize("program,scope", [(p, s) for p, ss in PROGRAM_SCOPES.items() for s in ss])
def test_scope_is_in_the_lowered_program(scope_tokens, program, scope):
    assert scope in scope_tokens[program]["words"]


# Latent attention, dropless experts and residual streams bring their own scopes, nested so
# that the tables above still add up: moe.* inside mlp, the low-rank projections inside
# attn.qkv, mla.absorb beside attn.core, hc.* at block level.
MECHANISM_CFG = transformer.ModelConfig(
    vocab_size=64, context_length=64, d_model=32, n_heads=2, n_layers=2, d_head=24, mlp_ratio=2.0,
    activation="swiglu", norm="rmsnorm", pos_embed="rope", tie_embeddings=False, mlp_bias=False,
    compute_dtype="float32", n_experts=4, experts_per_token=2, moe_routing="dropless",
    moe_score="sigmoid", moe_score_bias=True, n_shared_experts=1, d_expert=16, n_dense_layers=1,
    kv_lora_rank=16, q_lora_rank=12, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_scaling="yarn", rope_factor=4.0, rope_original_context=16, rope_mscale_all_dim=1.0, hc_mult=2,
)
MOE_SCOPES = ("moe.router", "moe.dispatch", "moe.experts", "moe.shared", "moe.combine")
MECHANISM_SCOPES = {
    "decode": MOE_SCOPES + ("mla.absorb", "hc.coef", "hc.mix", "attn.kv_write", "attn.paged_gather",
                            "attn.core", "attn.qkv", "attn.rope", "attn.out", "mlp"),
    "prefill": MOE_SCOPES + ("hc.coef", "hc.mix", "attn.kv_write", "attn.core", "attn.qkv", "mlp"),
}


@pytest.fixture(scope="module")
def mechanism_paths():
    p = transformer.init_params(MECHANISM_CFG, jax.random.key(0))
    pools = lambda: transformer.make_paged_kv_pool(MECHANISM_CFG, 16, 8)
    tables = jnp.asarray(np.arange(1, 9).reshape(2, 4), jnp.int32)
    lowered = {
        "decode": paged.paged_decode_steps.lower(
            p, pools(), jnp.asarray([3, 5], jnp.int32), tables, jnp.asarray([4, 9], jnp.int32),
            jax.random.key(1), MECHANISM_CFG, n_steps=2),
        "prefill": paged._prefill_scatter_sample.lower(
            p, pools(), jnp.zeros((2, 16), jnp.int32), jnp.asarray([16, 11], jnp.int32),
            jnp.asarray([[1, 2], [3, 4]], jnp.int32), jax.random.key(2), MECHANISM_CFG, 16, 2),
    }
    return {k: set(re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True))) for k, low in lowered.items()}


@pytest.mark.parametrize("program,scope", [(p, s) for p, ss in MECHANISM_SCOPES.items() for s in ss])
def test_mechanism_scope_is_in_the_lowered_program(mechanism_paths, program, scope):
    paths = [p for p in mechanism_paths[program] if scope in re.split(r"[/()]", p)]
    assert paths
    if scope.startswith("moe."):  # nested inside mlp, so mlp_time_share still holds the expert layer
        assert all("mlp" in re.split(r"[/()]", p) for p in paths)


def test_the_expert_kernel_sits_under_moe_experts(monkeypatch):
    """Where ``moe.experts_form`` picks the kernel (a TPU, bfloat16 experts of whole lane tiles, a
    decode step's rows) the call's trace path holds ``mlp`` and ``moe.experts``, which is what
    ``moe_time_share.*`` and ``moe_experts_hbm_roofline.*`` sum; no grouped matmul is left."""
    import functools

    from pretraining_llm_tpu.models import moe

    cfg = dataclasses.replace(
        MECHANISM_CFG, d_model=128, d_expert=128, compute_dtype="bfloat16", param_dtype="bfloat16",
        n_experts=8, hc_mult=1,
    )
    monkeypatch.setattr(moe, "experts_form", functools.partial(moe.experts_form, backend="tpu"))
    jax.clear_caches()
    try:
        p = jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.key(0))
        pools = jax.eval_shape(lambda: transformer.make_paged_kv_pool(cfg, 16, 8))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        text = paged.paged_decode_steps.lower(
            p, pools, i32(2), i32(2, 4), i32(2), jax.eval_shape(lambda: jax.random.key(1)), cfg, n_steps=2
        ).as_text(debug_info=True)
    finally:
        jax.clear_caches()
    paths = set(re.findall(r'loc\("([^"]+)"', text))
    kernel = [path for path in paths if "jit(_visits_call)" in path]
    assert kernel and all({"mlp", "moe.experts"} <= set(re.split(r"[/()]", path)) for path in kernel)
    assert not [path for path in paths if "ragged_dot" in path]


def test_routing_counters_ride_the_window_and_reach_the_stats_and_the_commit_span(monkeypatch):
    p = transformer.init_params(MECHANISM_CFG, jax.random.key(0))
    rec = spans.SpanRecorder()
    monkeypatch.setattr(spans, "_default", rec)
    eng = ServingEngine(p, MECHANISM_CFG, max_batch=2, n_blocks=16, block_size=8)
    eng.submit([1, 2, 3, 4, 5], 6)
    eng.run()
    events, _ = rec.drain()
    st = eng.stats
    assert set(st) >= {"moe_expert_tokens", "moe_experts_touched", "moe_steps"}
    assert st["moe_expert_tokens"].shape == (1, 4) and st["moe_experts_touched"].shape == (1,)
    # every row of the batch routes, the idle one too: 2 rows x 2 choices a step
    assert st["moe_expert_tokens"].sum() == st["moe_steps"] * 2 * 2
    commits = [meta for name, *_, meta in events if name == "serving.commit"]
    keys = {"moe_steps", "moe_layers", "moe_experts", "moe_touched", "moe_routed", "moe_busiest"}
    assert commits and all(keys <= set(meta) for meta in commits)
    assert sum(meta["moe_routed"] for meta in commits) == st["moe_expert_tokens"].sum()


def test_a_dense_model_has_no_routing_counters(params):
    eng = _engine(params)
    eng.submit(_prompts(1)[0], 4)
    eng.run()
    assert not [k for k in eng.stats if k.startswith("moe_")]


# A model that drafts for itself (models/mtp.py): the module's scopes, with the block's own
# attn.*, mla.absorb and moe.* nested inside mtp.block so that a reader can split module from
# stack, and the round's accept/reject under spec.accept.
MTP_CFG = dataclasses.replace(MECHANISM_CFG, hc_mult=1, rope_scaling="none", mtp_depth=1)
MTP_SCOPES = {
    "round": ("spec.verify", "mtp.draft", "mtp.embed_proj", "mtp.block", "mtp.head", "spec.accept", "mla.absorb", "attn.paged_gather",
              "attn.kv_write", "attn.core", "moe.experts", "lm_head"),
    "prefill": ("mtp.embed_proj", "mtp.block", "mtp.head", "attn.kv_write", "attn.core", "moe.experts", "sample"),
}


@pytest.fixture(scope="module")
def mtp_paths():
    p = transformer.init_params(MTP_CFG, jax.random.key(0))
    pools = lambda: transformer.make_paged_kv_pool(MTP_CFG, 16, 8)
    tables = jnp.asarray(np.arange(1, 9).reshape(2, 4), jnp.int32)
    lowered = {
        "round": paged.paged_mtp_round.lower(
            p, pools(), jnp.asarray([3, 5], jnp.int32), jnp.asarray([7, 9], jnp.int32), tables,
            jnp.asarray([4, 9], jnp.int32), jax.random.key(1), MTP_CFG),
        "prefill": paged._prefill_scatter_sample.lower(
            p, pools(), jnp.zeros((2, 16), jnp.int32), jnp.asarray([16, 11], jnp.int32),
            jnp.asarray([[1, 2], [3, 4]], jnp.int32), jax.random.key(2), MTP_CFG, 16, 2, with_draft=True),
    }
    return {k: set(re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True))) for k, low in lowered.items()}


@pytest.mark.parametrize("program,scope", [(p, s) for p, ss in MTP_SCOPES.items() for s in ss])
def test_mtp_scope_is_in_the_lowered_program(mtp_paths, program, scope):
    words = [re.split(r"[/()]", p) for p in mtp_paths[program]]
    paths = [w for w in words if scope in w]
    assert paths
    if scope.startswith(("attn.", "mla.", "moe.")):
        # the stack's and the module's: inside mtp.block and outside it
        assert [w for w in paths if "mtp.block" in w] and [w for w in paths if "mtp.block" not in w]
    if scope == "spec.accept":
        assert not [w for w in paths if "mtp.block" in w or "spec.verify" in w or "mtp.draft" in w]
    if scope.startswith("mtp.") and scope != "mtp.draft" and program == "round":
        # the round's two halves: the module's scopes lie in mtp.draft, never in the verify
        assert all("mtp.draft" in w and "spec.verify" not in w for w in paths)


def test_a_self_drafting_round_says_so_on_its_spans(monkeypatch):
    p = transformer.init_params(MTP_CFG, jax.random.key(0))
    rec = spans.SpanRecorder()
    monkeypatch.setattr(spans, "_default", rec)
    eng = ServingEngine(p, MTP_CFG, max_batch=2, n_blocks=16, block_size=8, spec_k=1)
    eng.submit([1, 2, 3, 4, 5], 6)
    eng.run()
    events, _ = rec.drain()
    dispatches = [meta for name, *_, meta in events if name == "serving.dispatch_window"]
    assert dispatches and all(m["kind"] == "spec" and m["steps"] == 2 for m in dispatches)
    commits = [meta for name, *_, meta in events if name == "serving.commit"]
    keys = {"spec_proposed", "spec_accepted", "spec_emitted", "moe_steps", "moe_layers", "moe_routed_here"}
    assert commits and all(keys <= set(meta) for meta in commits)
    st = eng.stats
    assert sum(m["spec_proposed"] for m in commits) == st["spec_proposed"] > 0
    assert sum(m["spec_accepted"] for m in commits) == st["spec_accepted"]
    assert sum(m["spec_emitted"] for m in commits) == st["tokens"] - 1  # the first token is the prefill's
    assert all(m["moe_layers"] == 2 for m in commits)  # the stack's expert layer and the module's block
    # the module's block's experts touched, by themselves: at most all held, at most the round's total
    assert all(0 < m["mtp_touched"] <= min(m["moe_experts"], m["moe_touched"]) for m in commits)
    assert eng.pool_info()["draft"] == "mtp"
    # every round's dispatch is one serving.spec_round span inside its serving.dispatch_window
    rounds = [(t0, t0 + dur, depth, meta) for name, t0, dur, _, depth, meta in events if name == "serving.spec_round"]
    windows = [(t0, t0 + dur, depth) for name, t0, dur, _, depth, _ in events if name == "serving.dispatch_window"]
    assert len(rounds) == len(windows) == st["spec_rounds"]
    assert all(m == {"k": 1, "draft": "mtp", "attention": "gather"} for *_, m in rounds)  # no TPU here
    for a, b, depth, _ in rounds:
        assert any(wa <= a and b <= wb and wdepth < depth for wa, wb, wdepth in windows)


def test_a_self_drafting_engine_records_nothing_and_syncs_nowhere_new(monkeypatch):
    """No recorder: the round's span and counters leave nothing behind. And a
    pipelined round reads the device where a decode window does (the reap's
    ``np.asarray`` of the emitted tokens, counts, drafts and routing counters),
    never at dispatch: with every ``np.asarray`` of a device array in the
    engine's module counted, ensuring pages and dispatching two rounds makes none."""
    import types

    from pretraining_llm_tpu.generation import serving

    monkeypatch.setattr(spans, "_default", None)
    p = transformer.init_params(MTP_CFG, jax.random.key(0))
    eng = ServingEngine(p, MTP_CFG, max_batch=2, n_blocks=16, block_size=8, spec_k=1)
    eng.submit([1, 2, 3, 4, 5], 12)
    eng.pipeline_tick()  # admit, prefill, the first round
    reads = []

    def counted(a, *args, **kw):
        if isinstance(a, jax.Array):
            reads.append(a.shape)
        return np.asarray(a, *args, **kw)

    numpy_seen = types.SimpleNamespace(**{k: getattr(np, k) for k in dir(np) if not k.startswith("__")})
    numpy_seen.asarray = counted
    monkeypatch.setattr(serving, "np", numpy_seen)
    for _ in range(2):
        eng._ensure_write_pages(horizon=2 * (len(eng._inflight) + 1))
        eng._dispatch_spec_round()
    assert len(eng._inflight) == 3 and not reads
    eng._reap_window(eng._inflight.popleft())
    assert (2, 2) in reads and (2,) in reads  # the reap is where the host waits: emit, n_emit and the drafts
    monkeypatch.setattr(serving, "np", np)
    while eng.pipeline_tick():
        pass
    assert [len(toks) for toks in eng.finished.values()] == [12]
    assert spans._default is None and eng.stats["spec_rounds"] >= 3


def test_backward_and_recompute_carry_the_scopes(scope_tokens):
    paths = scope_tokens["train"]["paths"]
    assert any("transpose(jvp(loss.ce))" in p for p in paths)
    assert any("rematted_computation/mlp" in p for p in paths)
    assert any("rematted_computation/attn.core" in p for p in paths)


def test_causal_tile_kernels_are_named_under_attn_core_and_say_so_once_a_shape(monkeypatch, caplog):
    """At a context of 1,024 the flash forward (run and recomputed) and the fused backward take
    the tiled form: their pallas_calls carry names of their own inside attn.core, and the
    module says which form a shape took, and where the kernels find the heads (these heads of
    8 are folded first), in one INFO line however often it is traced."""
    from pretraining_llm_tpu.ops import flash_attention, pallas_flash

    monkeypatch.setattr(flash_attention, "_pallas_available", lambda: True)  # interpreted off the TPU
    cfg = dataclasses.replace(
        TRAIN_CFG, model=dataclasses.replace(CFG, attention_impl="flash", context_length=1024)
    )
    pallas_flash._log_form.cache_clear()
    with caplog.at_level(logging.INFO, logger="pretraining_llm_tpu.ops.pallas_flash"):
        low = ts.lower_train_step(cfg)
        ts.lower_train_step(cfg)
    paths = set(re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True)))
    for call in ("attn.core/flash_fwd_tiles/pallas_call", "attn.core/flash_bwd_tiles/pallas_call",
                 "rematted_computation/attn.core/flash_fwd_tiles/pallas_call"):
        assert any(p.endswith(call) for p in paths), call
    assert any(p.startswith("attn.core/flash_fwd_tiles") for p in paths)  # the forward outside the remat
    n = 1024 // pallas_flash.CAUSAL_TILE
    b, h, d = cfg.train.batch_size // cfg.train.microbatches, CFG.n_heads, CFG.d_model // CFG.n_heads
    lines = [r.getMessage() for r in caplog.records if r.name == "pretraining_llm_tpu.ops.pallas_flash"]
    assert lines == [
        f"flash attention (B*H, T, D) = ({b * h}, 1024, {d}): causal tiles of "
        f"{pallas_flash.CAUSAL_TILE}, {n * (n + 1) // 2} of {n * n} sub-tiles computed; heads first"
    ]


# -- (d) the scopes are metadata: they change no output bit ---------------------------


def test_outputs_are_bit_identical_without_the_scopes(params, monkeypatch):
    with_scopes = _run_programs(params)

    def no_scope(name):
        return contextlib.nullcontext()

    monkeypatch.setattr(jax, "named_scope", no_scope)
    jax.clear_caches()
    try:
        low = paged.paged_decode_steps.lower(*_decode_args(params), CFG, n_steps=3)
        assert "attn.core" not in low.as_text(debug_info=True)  # the scopes are really gone
        without = _run_programs(params)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert len(with_scopes) == len(without)
    for a, b in zip(with_scopes, without):
        assert a.dtype == b.dtype and np.array_equal(a, b)


# A stack of window and full attention layers (two cache lifetimes): an outer scope a kind
# around the attention sublayer, so that the device trace tells a window layer's kernel from
# a full layer's; the gate and the head norms under names of their own; the page manager's
# release as a host span that counts what it gave back.
LIFETIMES_CFG = dataclasses.replace(get_preset("trinity-toy").model, compute_dtype="float32")
LIFETIMES_SCOPES = {
    "decode": ("attn.window", "attn.full", "attn.qk_norm", "attn.gate", "attn.kv_write", "attn.core", "attn.rope",
               "moe.experts"),
    "prefill": ("attn.window", "attn.full", "attn.qk_norm", "attn.gate", "attn.kv_write", "attn.core", "sample"),
}


@pytest.fixture(scope="module")
def lifetimes_paths():
    p = transformer.init_params(LIFETIMES_CFG, jax.random.key(0))
    pools = lambda: transformer.make_paged_kv_pool(LIFETIMES_CFG, 16, 8, window_blocks=9)
    tables = jnp.asarray(np.arange(1, 9).reshape(2, 4), jnp.int32)
    lowered = {
        "decode": paged.paged_decode_steps.lower(
            p, pools(), jnp.asarray([3, 5], jnp.int32), tables, jnp.asarray([4, 9], jnp.int32),
            jax.random.key(1), LIFETIMES_CFG, n_steps=2, window_tables=tables),
        "prefill": paged._prefill_scatter_sample.lower(
            p, pools(), jnp.zeros((2, 16), jnp.int32), jnp.asarray([16, 11], jnp.int32),
            jnp.asarray([[1, 2], [3, 4]], jnp.int32), jax.random.key(2), LIFETIMES_CFG, 16, 2,
            window_ids=jnp.asarray([[1, 2], [3, 4]], jnp.int32)),
    }
    return {k: set(re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True))) for k, low in lowered.items()}


@pytest.mark.parametrize("program,scope", [(p, s) for p, ss in LIFETIMES_SCOPES.items() for s in ss])
def test_lifetimes_scope_is_in_the_lowered_program(lifetimes_paths, program, scope):
    words = [re.split(r"[/()]", p) for p in lifetimes_paths[program]]
    paths = [w for w in words if scope in w]
    assert paths
    if scope in ("attn.qk_norm", "attn.gate", "attn.kv_write", "attn.core"):
        # under each kind's outer scope, never under both, never outside one (the staged
        # pages' scatter after a prefill is the page manager's, outside every layer)
        inside = [w for w in paths if "attn.window" in w or "attn.full" in w]
        assert [w for w in inside if "attn.window" in w] and [w for w in inside if "attn.full" in w]
        assert not [w for w in inside if "attn.window" in w and "attn.full" in w]
        assert inside == paths or (program, scope) == ("prefill", "attn.kv_write")
    if scope == "attn.rope":  # the full layer carries no position encoding
        assert all("attn.window" in w for w in paths)


def test_a_one_kind_stack_has_no_outer_attention_scope(params):
    pools = transformer.make_paged_kv_pool(CFG, 16, 8)
    tables = jnp.asarray(np.arange(1, 9).reshape(2, 4), jnp.int32)
    text = paged.paged_decode_steps.lower(
        params, pools, jnp.asarray([3, 5], jnp.int32), tables, jnp.asarray([4, 9], jnp.int32),
        jax.random.key(1), CFG, n_steps=2).as_text(debug_info=True)
    assert "attn.core" in text and "attn.window" not in text and "attn.full" not in text


def test_two_lifetimes_say_so_on_spans_counters_and_pool_info(monkeypatch):
    p = transformer.init_params(LIFETIMES_CFG, jax.random.key(0))
    rec = spans.SpanRecorder()
    monkeypatch.setattr(spans, "_default", rec)
    eng = ServingEngine(p, LIFETIMES_CFG, max_batch=2, n_blocks=24, block_size=8, max_seq=96)
    eng.submit(list(range(1, 20)), 40)
    eng.run()
    events, _ = rec.drain()
    releases = [(t0, t0 + dur, depth, meta) for name, t0, dur, _, depth, meta in events if name == "serving.release_pages"]
    ticks = [(t0, t0 + dur, depth) for name, t0, dur, _, depth, _ in events if name == "serving.tick"]
    st = eng.stats
    assert len(releases) == st["ticks"] and all(set(m) == {"released"} for *_, m in releases)
    assert sum(m["released"] for *_, m in releases) == st["window_pages_released"] == (19 + 40 - 16) // 8
    for a, b, depth, _ in releases:  # inside the tick, a child of its span
        assert any(ta <= a and b <= tb and tdepth < depth for ta, tb, tdepth in ticks)
    assert st["window_blocks_peak"] == 16 // 8 + 1 and st["kv_blocks_peak"] == (19 + 40) // 8 + 1
    assert st["kv_blocks_in_use"] == st["window_blocks_in_use"] == 0
    # a full layer reads the whole row, a window layer what its table names
    assert st["attn_pages_live"] > st["window_attn_pages_live"] > 0
    assert st["window_attn_pages_live"] <= st["window_attn_pages_tabled"] < st["attn_pages_tabled"]
    info = eng.pool_info()
    per_block = 8 * 2 * LIFETIMES_CFG.kv_heads * LIFETIMES_CFG.head_dim * 4
    assert (info["n_blocks"], info["window_n_blocks"]) == (24, 2 * (16 // 8 + 2) + 1)
    assert (info["full_layers"], info["window_layers"], info["sliding_window"]) == (1, 4, 16)
    assert info["full_pool_bytes"] == 24 * per_block and info["window_pool_bytes"] == 4 * 9 * per_block
    assert info["pool_bytes"] == info["full_pool_bytes"] + info["window_pool_bytes"]
    assert info["bytes_per_block"] == per_block


# A state-space hybrid (Mamba-2 layers around one position-free attention layer): the mixer's
# parts under `ssm.*` inside `blk.*` as `kda.*` are, the attention layer under the scopes
# per-head attention has, the slots on the commit span and in pool_info by kind of layer.
SSM_CFG = dataclasses.replace(get_preset("granite-toy").model, compute_dtype="float32")
SSM_SCOPES = {
    "decode": ("ssm.proj", "ssm.conv", "ssm.step", "ssm.norm", "ssm.out", "attn.qkv", "attn.kv_write", "attn.core",
               "attn.out", "moe.router", "moe.experts", "moe.shared", "blk.norm", "final_norm", "lm_head"),
    "prefill": ("ssm.proj", "ssm.conv", "ssm.chunk", "ssm.norm", "ssm.out", "attn.qkv", "attn.kv_write", "attn.core",
                "moe.experts", "sample"),
}


@pytest.fixture(scope="module")
def ssm_paths():
    p = transformer.init_params(SSM_CFG, jax.random.key(0))
    pools = lambda: transformer.make_paged_kv_pool(SSM_CFG, 16, 8, state_slots=2)
    tables = jnp.asarray(np.arange(1, 9).reshape(2, 4), jnp.int32)
    lowered = {
        "decode": paged.paged_decode_steps.lower(
            p, pools(), jnp.asarray([3, 5], jnp.int32), tables, jnp.asarray([4, 9], jnp.int32),
            jax.random.key(1), SSM_CFG, n_steps=2),
        "prefill": paged._prefill_scatter_sample.lower(
            p, pools(), jnp.zeros((2, 16), jnp.int32), jnp.asarray([16, 11], jnp.int32),
            jnp.asarray([[1, 2], [3, 4]], jnp.int32), jax.random.key(2), SSM_CFG, 16, 2,
            slots=jnp.asarray([0, 1], jnp.int32)),
    }
    return {k: set(re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True))) for k, low in lowered.items()}


@pytest.mark.parametrize("program,scope", [(p, s) for p, ss in SSM_SCOPES.items() for s in ss])
def test_ssm_scope_is_in_the_lowered_program(ssm_paths, program, scope):
    words = [re.split(r"[/()]", p) for p in ssm_paths[program]]
    assert [w for w in words if scope in w]
    # the one form a program runs: the recurrence in the decode step, the chunked form in a prefill
    other = {"decode": "ssm.chunk", "prefill": "ssm.step"}[program]
    assert not [w for w in words if other in w]
    assert not [w for w in words if "attn.rope" in w or "kda.step" in w]  # no position of any kind


def test_state_slots_say_so_on_the_commit_span_and_in_pool_info(monkeypatch):
    p = transformer.init_params(SSM_CFG, jax.random.key(0))
    rec = spans.SpanRecorder()
    monkeypatch.setattr(spans, "_default", rec)
    eng = ServingEngine(p, SSM_CFG, max_batch=2, n_blocks=24, block_size=8, max_seq=64)
    eng.submit(list(range(1, 20)), 12)
    eng.submit(list(range(3, 9)), 12)
    eng.run()
    events, _ = rec.drain()
    commits = [meta for name, *_, meta in events if name == "serving.commit"]
    assert commits and max(m["state_slots"] for m in commits) == 2 == eng.stats["state_slots_peak"]
    assert all({"moe_steps", "moe_routed", "moe_routed_here", "moe_experts"} <= set(m) for m in commits)
    assert commits[0]["moe_experts"] == 4 and commits[0]["moe_layers"] == 5
    info = eng.pool_info()
    assert (info["state_mixer"], info["state_layers"], info["page_layers"]) == ("mamba", 4, 1)
    slot = 4 * (4 * 16 * 16) * 4 + 4 * 3 * (4 * 16 + 2 * 16) * 4  # float32 toy: a state and a 3-row tail a layer
    assert info["bytes_per_slot"] == slot and info["state_bytes"] == 3 * slot
    assert info["pool_bytes"] == 24 * 8 * 2 * SSM_CFG.kv_heads * SSM_CFG.head_dim * 4  # one layer's pages
    assert info["decode_state"] == "jnp" and info["decode_experts"] == "grouped"


# A Gated DeltaNet hybrid under output-side norms: the mixer's parts under `gdn.*`, the attention
# layers under the scopes per-head attention has inside `attn.full` (what tells them from the
# recurrent layers in a trace), `blk.norm` on each sublayer's output, the forms in pool_info.
GDN_CFG = dataclasses.replace(get_preset("olmo-hybrid-toy").model, compute_dtype="float32")
GDN_SCOPES = {
    "decode": ("gdn.proj", "gdn.conv", "gdn.gate", "gdn.step", "gdn.out", "attn.full", "attn.qkv", "attn.qk_norm",
               "attn.kv_write", "attn.core", "attn.out", "mlp", "blk.norm", "final_norm", "lm_head"),
    "prefill": ("gdn.proj", "gdn.conv", "gdn.gate", "gdn.chunk", "gdn.out", "attn.full", "attn.qkv", "attn.kv_write",
                "attn.core", "mlp", "blk.norm", "sample"),
}


@pytest.fixture(scope="module")
def gdn_paths():
    p = transformer.init_params(GDN_CFG, jax.random.key(0))
    pools = lambda: transformer.make_paged_kv_pool(GDN_CFG, 16, 8, state_slots=2)
    tables = jnp.asarray(np.arange(1, 9).reshape(2, 4), jnp.int32)
    lowered = {
        "decode": paged.paged_decode_steps.lower(
            p, pools(), jnp.asarray([3, 5], jnp.int32), tables, jnp.asarray([4, 9], jnp.int32),
            jax.random.key(1), GDN_CFG, n_steps=2),
        "prefill": paged._prefill_scatter_sample.lower(
            p, pools(), jnp.zeros((2, 16), jnp.int32), jnp.asarray([16, 11], jnp.int32),
            jnp.asarray([[1, 2], [3, 4]], jnp.int32), jax.random.key(2), GDN_CFG, 16, 2,
            slots=jnp.asarray([0, 1], jnp.int32)),
    }
    return {k: set(re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True))) for k, low in lowered.items()}


@pytest.mark.parametrize("program,scope", [(p, s) for p, ss in GDN_SCOPES.items() for s in ss])
def test_gdn_scope_is_in_the_lowered_program(gdn_paths, program, scope):
    words = [re.split(r"[/()]", p) for p in gdn_paths[program]]
    assert [w for w in words if scope in w]
    # the one form a program runs: the recurrence in the decode step, the chunked form in a prefill
    other = {"decode": "gdn.chunk", "prefill": "gdn.step"}[program]
    assert not [w for w in words if other in w]
    assert not [w for w in words if "attn.rope" in w or "kda.step" in w or "ssm.step" in w]
    # the attention layers' parts stand inside attn.full, the recurrent layers' parts outside it
    assert [w for w in words if "attn.core" in w and "attn.full" in w]
    assert not [w for w in words if "attn.full" in w and [x for x in w if x.startswith("gdn.")]]


def test_gdn_slots_and_forms_say_so_on_the_commit_span_and_in_pool_info(monkeypatch):
    p = transformer.init_params(GDN_CFG, jax.random.key(0))
    rec = spans.SpanRecorder()
    monkeypatch.setattr(spans, "_default", rec)
    eng = ServingEngine(p, GDN_CFG, max_batch=2, n_blocks=24, block_size=8, max_seq=64)
    eng.submit(list(range(1, 20)), 12)
    eng.submit(list(range(3, 9)), 12)
    eng.run()
    events, _ = rec.drain()
    commits = [meta for name, *_, meta in events if name == "serving.commit"]
    assert commits and max(m["state_slots"] for m in commits) == 2 == eng.stats["state_slots_peak"]
    info = eng.pool_info()
    assert (info["state_mixer"], info["state_layers"], info["page_layers"]) == ("gdn", 6, 2)
    assert info["decode_state"] == "jnp" and info["decode_attention"] == "gather" and "decode_experts" not in info
    assert info["pool_kv_heads"] == GDN_CFG.kv_heads == 3  # three heads of 16: stored as they are
    wide = dataclasses.replace(GDN_CFG, n_heads=30, n_kv_heads=30, d_head=128)
    pools = jax.eval_shape(lambda: transformer.make_paged_kv_pool(wide, 4, 8, state_slots=2))
    assert pools["layers"][3]["k_pool"].shape == (4, 8, 32, 128)  # thirty heads of 128: stored as 32


# A table of single sublayers (Nemotron-H): every layer one sublayer under one norm, and each kind of
# layer keeps its own scopes: the mixer's parts under `ssm.*`, the attention's under `attn.full`, the
# expert FFN's under `mlp` / `moe.*`, the layer's one norm under `blk.norm`.
NEMO_CFG = dataclasses.replace(get_preset("nemotron-h-toy").model, compute_dtype="float32")
NEMO_SCOPES = {
    "decode": ("ssm.proj", "ssm.conv", "ssm.step", "ssm.norm", "ssm.out", "attn.full", "attn.qkv", "attn.kv_write",
               "attn.core", "attn.out", "mlp", "moe.router", "moe.dispatch", "moe.experts", "moe.shared",
               "moe.combine", "blk.norm", "final_norm", "lm_head"),
    "prefill": ("ssm.proj", "ssm.conv", "ssm.chunk", "ssm.norm", "ssm.out", "attn.full", "attn.kv_write",
                "attn.core", "moe.experts", "moe.shared", "blk.norm", "sample"),
}


@pytest.fixture(scope="module")
def nemo_paths():
    p = transformer.init_params(NEMO_CFG, jax.random.key(0))
    pools = lambda: transformer.make_paged_kv_pool(NEMO_CFG, 16, 8, state_slots=2)
    tables = jnp.asarray(np.arange(1, 9).reshape(2, 4), jnp.int32)
    lowered = {
        "decode": paged.paged_decode_steps.lower(
            p, pools(), jnp.asarray([3, 5], jnp.int32), tables, jnp.asarray([4, 9], jnp.int32),
            jax.random.key(1), NEMO_CFG, n_steps=2),
        "prefill": paged._prefill_scatter_sample.lower(
            p, pools(), jnp.zeros((2, 16), jnp.int32), jnp.asarray([16, 11], jnp.int32),
            jnp.asarray([[1, 2], [3, 4]], jnp.int32), jax.random.key(2), NEMO_CFG, 16, 2,
            slots=jnp.asarray([0, 1], jnp.int32)),
    }
    return {k: set(re.findall(r'loc\("([^"]+)"', low.as_text(debug_info=True))) for k, low in lowered.items()}


@pytest.mark.parametrize("program,scope", [(p, s) for p, ss in NEMO_SCOPES.items() for s in ss])
def test_single_sublayer_scope_is_in_the_lowered_program(nemo_paths, program, scope):
    words = [re.split(r"[/()]", p) for p in nemo_paths[program]]
    assert [w for w in words if scope in w]
    other = {"decode": "ssm.chunk", "prefill": "ssm.step"}[program]
    assert not [w for w in words if other in w]
    assert not [w for w in words if "attn.rope" in w]  # no position of any kind
    # one sublayer a layer: nothing of the experts stands inside a mixer's scope, nor the other way round
    assert not [w for w in words if "attn.full" in w and [x for x in w if x.startswith(("ssm.", "moe."))]]
    assert not [w for w in words if "mlp" in w and [x for x in w if x.startswith(("ssm.", "attn."))]]

