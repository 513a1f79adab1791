"""Paged KV cache + continuous-batching serving engine.

Ground truth everywhere is the proven dense-cache path: greedy paged
serving must emit EXACTLY the tokens `generation.generate` (batch-1,
temperature 0) emits for the same prompt, regardless of admission order,
block fragmentation, preemption, int8 pools, or sliding windows.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.config import ModelConfig, get_preset
from pretraining_llm_tpu.generation import paged
from pretraining_llm_tpu.generation.generate import generate
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer

CFG = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


def _prompts(n, lengths=(5, 9, 14, 7, 11, 3, 16, 6)):
    rng = np.random.default_rng(42)
    out = []
    for i in range(n):
        p = int(lengths[i % len(lengths)])
        out.append(rng.integers(0, CFG.vocab_size, size=p).tolist())
    return out


def _reference_greedy(params, cfg, prompt, n_new):
    """Batch-1 dense-cache greedy generation (the proven path)."""
    toks = generate(
        params, cfg, jnp.asarray([prompt], jnp.int32), n_new,
        jax.random.key(7), temperature=0.0,
    )
    return np.asarray(toks)[0].tolist()


# -- allocator ------------------------------------------------------------


def test_allocator_invariants():
    a = paged.BlockAllocator(8)
    assert a.available == 7  # block 0 reserved
    got = a.alloc(3)
    assert got is not None and len(set(got)) == 3 and 0 not in got
    assert a.alloc(5) is None  # only 4 left: all-or-nothing
    assert a.available == 4
    a.free(got[:2])
    assert a.available == 6
    with pytest.raises(ValueError):
        a.free([got[0]])  # double free
    with pytest.raises(ValueError):
        paged.BlockAllocator(1)


def test_required_blocks():
    assert paged.required_blocks(1, 8) == 1
    assert paged.required_blocks(8, 8) == 1
    assert paged.required_blocks(9, 8) == 2


# -- forward-path contracts ----------------------------------------------


def test_forward_paged_validation(params):
    pools = transformer.make_paged_kv_pool(CFG, 4, 8, dtype="float32")
    tok = jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(ValueError, match="paged=PagedInfo"):
        transformer.forward(params, tok, CFG, kv_cache=pools)
    info = transformer.PagedInfo(
        jnp.zeros((2, 8), jnp.int32), jnp.zeros((2,), jnp.int32)
    )
    dense = transformer.make_kv_cache(CFG, 2, 16, dtype="float32")
    with pytest.raises(ValueError, match="pool-layout"):
        transformer.forward(params, tok, CFG, kv_cache=dense, paged=info)


def test_pool_shape_and_reserved_block():
    # One pool a layer (carry-aliasable); the block axis of a leaf is 0.
    pools = transformer.make_paged_kv_pool(CFG, 6, 8)
    assert set(pools) == {"layers"} and len(pools["layers"]) == CFG.n_layers
    assert pools["layers"][0]["k_pool"].shape == (
        6, 8, CFG.kv_heads, CFG.head_dim
    )
    assert paged.pool_block_size(pools, CFG) == 8
    # Prefill stages through the STACKED dense cache; a per-layer one is
    # refused rather than scattering nothing.
    staged = transformer.make_kv_cache(CFG, 1, 16, stacked=True)
    assert staged["k"].shape == (CFG.n_layers, 1, 16, CFG.kv_heads, CFG.head_dim)
    ids = jnp.asarray([1, 2], jnp.int32)
    out = paged._scatter_staged_pages(pools, staged, ids, 2)
    assert out["layers"][0]["k_pool"].shape == pools["layers"][0]["k_pool"].shape
    with pytest.raises(ValueError, match="stacked=True"):
        paged._scatter_staged_pages(pools, transformer.make_kv_cache(CFG, 1, 16), ids, 2)
    with pytest.raises(ValueError, match="multiple of 8"):
        transformer.make_paged_kv_pool(CFG, 6, 12)
    with pytest.raises(ValueError, match="n_blocks"):
        transformer.make_paged_kv_pool(CFG, 1, 8)


# -- engine == dense-cache greedy ----------------------------------------


def test_engine_matches_generate(params):
    prompts = _prompts(3)
    n_new = 10
    eng = ServingEngine(
        params, CFG, max_batch=3, n_blocks=32, block_size=8, temperature=0.0
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run()
    assert eng.stats["preemptions"] == 0
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, CFG, p, n_new), (
            f"request {rid} diverged from the dense-cache greedy path"
        )


def test_engine_more_requests_than_rows_fragmented(params):
    """6 requests through 2 rows: admission order + freed-block reuse give
    non-contiguous, reused block tables; outputs must be unaffected."""
    prompts = _prompts(6)
    n_new = 8
    eng = ServingEngine(
        params, CFG, max_batch=2, n_blocks=24, block_size=8, temperature=0.0
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run()
    assert sorted(out) == sorted(rids)
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, CFG, p, n_new)


def test_engine_preemption_recovers_exactly(params):
    """A pool too small for both rows' full lengths forces preemption;
    recompute-on-resume greedy output must equal uninterrupted greedy."""
    prompts = [_prompts(1, lengths=(12,))[0], _prompts(1, lengths=(10,))[0]]
    n_new = 24
    # Each request needs ceil((12+24)/8)=5 blocks eventually; 7 usable
    # blocks cannot hold 5+5, so growth must preempt the younger row.
    eng = ServingEngine(
        params, CFG, max_batch=2, n_blocks=8, block_size=8, temperature=0.0
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run()
    assert eng.stats["preemptions"] >= 1
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, CFG, p, n_new)


def test_engine_stop_token(params):
    p = _prompts(1)[0]
    n_new = 12
    ref = _reference_greedy(params, CFG, p, n_new)
    stop = ref[4]  # force an early stop on a token greedy WILL emit
    eng = ServingEngine(
        params, CFG, max_batch=1, n_blocks=16, block_size=8,
        temperature=0.0, stop_token=stop,
    )
    rid = eng.submit(p, n_new)
    out = eng.run()
    want = ref[: ref.index(stop)]
    assert out[rid] == want


def test_engine_int8_pool_matches_dense_int8(params):
    cfg8 = dataclasses.replace(CFG, kv_cache_dtype="int8")
    prompts = _prompts(2)
    n_new = 8
    eng = ServingEngine(
        params, cfg8, max_batch=2, n_blocks=24, block_size=8, temperature=0.0
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run()
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, cfg8, p, n_new), (
            "paged int8 decode diverged from dense int8 decode"
        )


def test_engine_sliding_window(params):
    cfgw = dataclasses.replace(CFG, sliding_window=16)
    p = _prompts(1, lengths=(20,))[0]
    n_new = 10
    eng = ServingEngine(
        params, cfgw, max_batch=1, n_blocks=16, block_size=8, temperature=0.0
    )
    rid = eng.submit(p, n_new)
    out = eng.run()
    assert out[rid] == _reference_greedy(params, cfgw, p, n_new)


def test_engine_rejects_oversized(params):
    eng = ServingEngine(params, CFG, max_batch=1, n_blocks=4, block_size=8)
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(list(range(40)), CFG.context_length)
    with pytest.raises(ValueError, match="pool only has"):
        eng.submit(list(range(20)), 10)  # 30 tokens needs 4 blocks; 3 usable
    with pytest.raises(ValueError, match="empty"):
        eng.submit([], 4)


@pytest.mark.parametrize("window", [3, 8])
def test_engine_multistep_matches_generate(params, window):
    """steps_per_sched>1 runs K decode steps per device dispatch; greedy
    output must be unchanged, including rows finishing mid-window (their
    surplus tokens are discarded) and stop tokens."""
    prompts = _prompts(3)
    n_new = 10  # not a multiple of either window: mid-window finishes
    eng = ServingEngine(
        params, CFG, max_batch=3, n_blocks=32, block_size=8,
        temperature=0.0, steps_per_sched=window,
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run()
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, CFG, p, n_new)


def test_engine_multistep_capacity_overshoot(params):
    """A row whose max_new ends exactly at pool/table capacity inside a
    multi-step window: the in-program scratch redirect must keep live
    blocks intact (other rows' outputs unchanged)."""
    # capacity = max_seq = 48 with block_size 24 on ctx-64 tiny.
    eng = ServingEngine(
        params, CFG, max_batch=2, n_blocks=8, block_size=24,
        temperature=0.0, steps_per_sched=8,
    )
    # 41+7 = 48 == capacity AND max_new(7) < window(8): the row's final
    # window step runs at seq == capacity, firing the in_range=False
    # scratch redirect (41+8 with an 8-aligned window would stop at
    # seq == capacity-1 and never exercise the guard).
    p_long = _prompts(1, lengths=(41,))[0]
    p_short = _prompts(1, lengths=(7,))[0]
    r1 = eng.submit(p_long, 7)
    r2 = eng.submit(p_short, 30)
    out = eng.run()
    assert out[r1] == _reference_greedy(params, CFG, p_long, 7)
    assert out[r2] == _reference_greedy(params, CFG, p_short, 30)


def test_engine_multistep_preemption(params):
    prompts = [_prompts(1, lengths=(12,))[0], _prompts(1, lengths=(10,))[0]]
    n_new = 24
    eng = ServingEngine(
        params, CFG, max_batch=2, n_blocks=8, block_size=8,
        temperature=0.0, steps_per_sched=4,
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run()
    assert eng.stats["preemptions"] >= 1
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, CFG, p, n_new)


def test_engine_block_size_not_dividing_context(params):
    """block_size that doesn't divide context_length: max_seq clamps to
    the aligned floor, so a near-context prompt is rejected at submit()
    instead of crashing prefill mid-serving (prefill pads to whole
    blocks, which would overflow the position tables)."""
    # tiny ctx=64; block_size=24 -> aligned max_seq=48
    eng = ServingEngine(params, CFG, max_batch=1, n_blocks=8, block_size=24)
    assert eng.max_seq == 48
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(list(range(45)), 10)  # fits ctx=64 but not aligned 48
    p = _prompts(1, lengths=(14,))[0]
    rid = eng.submit(p, 8)
    out = eng.run()
    assert out[rid] == _reference_greedy(params, CFG, p, 8)


def test_engine_sharded_matches_single_device(params, mesh8):
    """Paged serving over a dp x fsdp x tp mesh (params TP/FSDP-sharded,
    pool kv_heads sharded over 'tensor') == unsharded serving."""
    from pretraining_llm_tpu.generation.generate import shard_params_for_inference

    prompts = _prompts(2)
    n_new = 8
    sharded = shard_params_for_inference(params, mesh8)
    eng = ServingEngine(
        sharded, CFG, max_batch=2, n_blocks=24, block_size=8,
        temperature=0.0, steps_per_sched=4, mesh=mesh8,
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run()
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, CFG, p, n_new)


def test_multitoken_paged_forward_matches_stepwise(params):
    """The multi-token paged forward (speculative verify) must produce,
    position by position, the same logits as T sequential single-token
    paged steps from the same pool state — and leave the pools in the
    same state."""
    rng = np.random.default_rng(3)
    prompts = _prompts(2)
    toks = [rng.integers(0, CFG.vocab_size, size=4).tolist() for _ in range(2)]
    bs = 8

    def build():
        pools = transformer.make_paged_kv_pool(CFG, 16, bs, dtype="float32")
        alloc = paged.BlockAllocator(16)
        tables = np.zeros((2, 4), np.int32)
        seq = np.zeros((2,), np.int32)
        for i, p in enumerate(prompts):
            need = paged.required_blocks(len(p) + 5, bs)
            ids = alloc.alloc(need)
            _, pools = paged.prefill_into_pool(
                params, CFG, pools, p, ids[: paged.required_blocks(len(p), bs)]
            )
            tables[i, : len(ids)] = ids
            seq[i] = len(p)
        return pools, tables, seq

    # A: one T=4 multi-token paged forward
    pools_a, tables, seq = build()
    tok_arr = jnp.asarray(np.stack([np.asarray(t) for t in toks]), jnp.int32)
    info = transformer.PagedInfo(jnp.asarray(tables), jnp.asarray(seq))
    logits_a, pools_a = transformer.forward(
        params, tok_arr, CFG, kv_cache=pools_a, paged=info
    )
    # B: 4 sequential single-token steps
    pools_b, tables_b, seq_b = build()
    logits_b = []
    for j in range(4):
        info_j = transformer.PagedInfo(
            jnp.asarray(tables_b), jnp.asarray(seq_b + j)
        )
        lj, pools_b = transformer.forward(
            params, tok_arr[:, j : j + 1], CFG, kv_cache=pools_b, paged=info_j
        )
        logits_b.append(np.asarray(lj[:, 0]))
    np.testing.assert_allclose(
        np.asarray(logits_a), np.stack(logits_b, axis=1), atol=2e-4
    )
    for leaf_a, leaf_b in zip(
        jax.tree.leaves(pools_a), jax.tree.leaves(pools_b)
    ):
        np.testing.assert_allclose(
            np.asarray(leaf_a), np.asarray(leaf_b), atol=1e-5
        )


def test_batched_prefill_matches_sequential(params):
    """One fused prefill program for N prompts == N sequential prefills:
    same pool bytes on every real block, same greedy first tokens."""
    prompts = _prompts(3)
    pools_a = transformer.make_paged_kv_pool(CFG, 16, 8, dtype="float32")
    pools_b = jax.tree.map(jnp.copy, pools_a)
    alloc = paged.BlockAllocator(16)
    ids = [alloc.alloc(paged.required_blocks(len(p), 8)) for p in prompts]
    lasts = []
    for p, b in zip(prompts, ids):
        last, pools_a = paged.prefill_into_pool(params, CFG, pools_a, p, b)
        lasts.append(int(np.argmax(np.asarray(last))))
    toks, pools_b = paged.prefill_into_pool_batched(
        params, CFG, pools_b, prompts, ids, jax.random.key(3),
        temperature=0.0,
    )
    assert np.asarray(toks).tolist() == lasts

    def k_block(pools, blk):
        return np.stack([np.asarray(l["k_pool"][blk]) for l in pools["layers"]])

    for blk in sorted(set(b for row in ids for b in row)):
        np.testing.assert_allclose(
            k_block(pools_a, blk), k_block(pools_b, blk), atol=1e-6
        )


def test_batched_prefill_validation(params):
    pools = transformer.make_paged_kv_pool(CFG, 8, 8, dtype="float32")
    with pytest.raises(ValueError, match="no prompts"):
        paged.prefill_into_pool_batched(
            params, CFG, pools, [], [], jax.random.key(0)
        )
    with pytest.raises(ValueError, match="exactly"):
        paged.prefill_into_pool_batched(
            params, CFG, pools, [[1, 2, 3]], [[1, 2]], jax.random.key(0)
        )


@pytest.mark.parametrize("pipeline", [False, True])
def test_engine_pipeline_modes_match_generate(params, pipeline):
    """run(pipeline=...) must emit identical greedy outputs in both the
    synchronous and the double-buffered scheduler, through a gauntlet of
    more-requests-than-rows, mid-window finishes, and stop tokens."""
    prompts = _prompts(6)
    n_new = 9  # not a multiple of the window: mid-window finishes
    eng = ServingEngine(
        params, CFG, max_batch=2, n_blocks=24, block_size=8,
        temperature=0.0, steps_per_sched=4,
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run(pipeline=pipeline)
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, CFG, p, n_new)


@pytest.mark.parametrize("pipeline", [False, True])
def test_engine_pipeline_preemption_match(params, pipeline):
    """Tiny pool forcing preemption: the pipelined scheduler must flush
    its in-flight window before evicting, so recompute-on-resume resumes
    from the exact generated prefix in both modes."""
    prompts = [_prompts(1, lengths=(12,))[0], _prompts(1, lengths=(10,))[0]]
    n_new = 24
    eng = ServingEngine(
        params, CFG, max_batch=2, n_blocks=8, block_size=8,
        temperature=0.0, steps_per_sched=4,
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run(pipeline=pipeline)
    assert eng.stats["preemptions"] >= 1
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, CFG, p, n_new)


@pytest.mark.parametrize("pipeline", [False, True])
def test_engine_window_budget_clamp(params, pipeline):
    """A 32-step scheduling window with max_new=5 must CLAMP its decode
    windows to the rows' remaining-token budget (pow2-bucketed) instead
    of burning 32 lockstep steps per dispatch — outputs unchanged."""
    prompts = _prompts(2)
    n_new = 5
    eng = ServingEngine(
        params, CFG, max_batch=2, n_blocks=32, block_size=8,
        temperature=0.0, steps_per_sched=32,
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run(pipeline=pipeline)
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, CFG, p, n_new)
    # 5 tokens/request: 1 from prefill + <= 8 window steps (pow2 bucket of
    # the 4 remaining), NOT 32+ — the clamp is the assertion.
    assert eng.stats["steps"] <= 16, eng.stats


def test_engine_pipelined_max_new_one(params):
    """max_new=1 requests finish on their deferred admission token alone;
    the row must free and be reusable without a dispatched window."""
    prompts = _prompts(3)
    eng = ServingEngine(
        params, CFG, max_batch=1, n_blocks=16, block_size=8,
        temperature=0.0, steps_per_sched=4,
    )
    rids = [eng.submit(p, 1) for p in prompts]
    out = eng.run(pipeline=True)
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, CFG, p, 1)


# Heads of 128: a page is a copy of its own, so the decode step takes the
# in-place kernel (ops/pallas_paged.py, interpreted here) wherever
# ``paged_attention_form`` sees a TPU (conftest's ``paged_kernel_forced``).
WIDE_CFG = dataclasses.replace(CFG, d_head=128)


@pytest.fixture(scope="module")
def wide_params():
    return transformer.init_params(WIDE_CFG, jax.random.key(0))


def test_decode_kernel_engine_matches_generate(wide_params, paged_kernel_forced):
    """The decode step through the Pallas block-table kernel (interpret
    mode on CPU) must emit the same greedy tokens as the dense-cache
    ground truth — through fragmentation, mid-window finishes, and block
    reuse."""
    prompts = _prompts(4)
    n_new = 8
    eng = ServingEngine(
        wide_params, WIDE_CFG, max_batch=2, n_blocks=24, block_size=8,
        temperature=0.0, steps_per_sched=4,
    )
    assert eng.decode_attention == "kernel"
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run()
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(wide_params, WIDE_CFG, p, n_new)


def test_decode_kernel_gqa_and_window(request):
    """Kernel path with GQA heads + sliding window == gather path, token
    for token."""
    cfg = dataclasses.replace(WIDE_CFG, n_heads=4, n_kv_heads=2, sliding_window=16)
    params_g = transformer.init_params(cfg, jax.random.key(1))
    p = _prompts(1, lengths=(20,))[0]
    n_new = 10
    out = {}
    for form in ("gather", "kernel"):
        if form == "kernel":
            request.getfixturevalue("paged_kernel_forced")
        eng = ServingEngine(
            params_g, cfg, max_batch=1, n_blocks=16, block_size=8,
            temperature=0.0,
        )
        assert eng.decode_attention == form
        rid = eng.submit(p, n_new)
        out[form] = eng.run()[rid]
    assert out["kernel"] == out["gather"]


# -- the gather form against the reference of paged attention -------------------------------


@dataclasses.dataclass(frozen=True)
class Gathered:
    """One call of ``_attention_core`` over a per-head page pool: ``t``
    queries a row, of which ``q_lens`` are real ("ragged": one to ``t`` a
    row, "mixed": decode rows beside chunk rows, "uniform": all ``t``, or
    the counts), at ``seq`` committed tokens a row (None: random, with room
    for the row's queries) over fragmented tables."""

    g: int = 2
    t: int = 6
    window: int = 0
    b: int = 3
    h: int = 8
    bs: int = 8
    max_blocks: int = 5
    q_lens: object = "ragged"
    seq: object = None
    pool: str = "float32"  # the pages' dtype; "int8": codes under float32 scales, "int8-bf16": bfloat16 ones


_EDGES = {
    # the last live slot one below, on and one above the first slot of the second page
    f"page-16-last-slot-{edge}-window-{window}-{pool}-{kind}": Gathered(
        b=2, t=4, bs=16, max_blocks=3, window=window, pool=pool,
        q_lens=(n, n), seq=(edge - n + 1,) * 2,
    )
    for edge in (15, 16, 17) for window in (0, 12) for pool in ("float32", "int8")
    for kind, n in (("decode", 1), ("chunk", 4))
}
GATHERED = {
    **{f"ragged-g{g}-window-{w}": Gathered(g=g, window=w) for g, w in ((8, 0), (2, 0), (4, 12), (1, 0))},
    **{f"decode-beside-chunk-rows-g{g}": Gathered(g=g, b=4, t=8, max_blocks=6, q_lens="mixed") for g in (4, 2)},
    "pad-queries-and-a-row-of-none": Gathered(h=4, t=4, max_blocks=2, q_lens=(2, 4, 0), seq=(0, 8, 3)),
    "bf16": Gathered(b=2, t=5, h=4, max_blocks=3, q_lens="mixed", pool="bfloat16"),
    **{
        f"{pool}-g{g}-window-{w}": Gathered(g=g, window=w, pool=pool)
        for pool in ("int8", "int8-bf16") for g, w in ((8, 0), (2, 0), (4, 12), (1, 0))
    },
    "uniform-batch": Gathered(g=4, b=2, t=4, q_lens="uniform"),
    **{
        f"several-queries-a-row-g{g}-t{t}-window-{w}": Gathered(g=g, b=2, t=t, window=w, q_lens="uniform")
        for g, t, w in ((4, 5, 0), (2, 3, 0), (4, 4, 12))
    },
    **_EDGES,
}


def _quantize_pool(x):
    """The engine's page convention (``transformer._kv_quantize``): int8
    codes under a per-(slot, head) amax scale over the channel dim."""
    scale = np.maximum(np.abs(x).max(axis=-1, keepdims=True), 1e-8)
    return np.round(x / scale * 127.0).astype(np.int8), scale.astype(np.float32)


@pytest.mark.parametrize("name", GATHERED)
def test_gather_form_matches_the_reference(name):
    """What the model computes for several queries a row, per-row query
    counts and int8 pages is ``ops.pallas_paged.gather_attention`` over the
    pool it hands back, and that pool holds every real token's K/V at the
    slot its row's table names."""
    from pretraining_llm_tpu.models import layers
    from pretraining_llm_tpu.ops.pallas_paged import gather_attention

    case = GATHERED[name]
    b, t, h, g, bs, d, d_model = case.b, case.t, case.h, case.g, case.bs, 16, 16 * case.h
    quantized = case.pool.startswith("int8")
    cdt = "float32" if quantized else case.pool
    cfg = ModelConfig(
        vocab_size=64, context_length=64, d_model=d_model, n_heads=h, n_kv_heads=g, d_head=d, n_layers=1,
        pos_embed="none", use_output_proj=False, compute_dtype=cdt, kv_cache_dtype="int8" if quantized else "compute",
    )
    rng = np.random.default_rng(list(GATHERED).index(name))
    normal = lambda *shape, std=1.0: (std * rng.normal(size=shape)).astype(np.float32)

    # fragmented tables, a count of real queries a row, and room for them behind ``seq``
    n_blocks = 1 + b * case.max_blocks
    free = rng.permutation(np.arange(1, n_blocks)).tolist()
    tables = np.zeros((b, case.max_blocks), np.int32)
    if isinstance(case.q_lens, tuple):
        q_lens = np.asarray(case.q_lens, np.int32)
    elif case.q_lens == "mixed":
        q_lens = np.asarray([1 if i % 2 == 0 else rng.integers(2, t + 1) for i in range(b)], np.int32)
    else:
        q_lens = np.full((b,), t, np.int32) if case.q_lens == "uniform" else rng.integers(1, t + 1, b).astype(np.int32)
    seq = np.zeros((b,), np.int32)
    for i in range(b):
        least = 1 if case.seq is None else (case.seq[i] + max(int(q_lens[i]), 1) - 1) // bs + 1
        own = int(rng.integers(least, case.max_blocks + 1))
        tables[i, :own] = [free.pop() for _ in range(own)]
        seq[i] = rng.integers(0, own * bs - t + 1) if case.seq is None else case.seq[i]

    blk = jax.tree.map(lambda a: a[0], transformer.init_params(cfg, jax.random.key(0))["blocks"])
    blk["attn"] = {name: jnp.asarray(normal(*w.shape, std=2 / d_model ** 0.5)) for name, w in blk["attn"].items()}
    kv = {}
    for name in ("k_pool", "v_pool"):
        pages = normal(n_blocks, bs, g, d, std=2.0 if name == "k_pool" else 1.0)
        if quantized:
            pages, scale = _quantize_pool(pages)
            kv[name.replace("_pool", "_scale_pool")] = jnp.asarray(
                scale, jnp.bfloat16 if case.pool == "int8-bf16" else jnp.float32
            )
        kv[name] = jnp.asarray(pages, jnp.int8 if quantized else cdt)
    x = jnp.asarray(normal(b, t, d_model), cdt)
    info = transformer.PagedInfo(jnp.asarray(tables), jnp.asarray(seq), q_lens=jnp.asarray(q_lens))

    out, new_kv = jax.jit(
        lambda x, kv, info: transformer._attention_core(
            blk, x, cfg, None, jnp.arange(t), kv, None, False, None, None, info, False, case.window
        )
    )(x, kv, info)

    # the layer's own projections, its three lines
    hidden = layers.apply_norm(cfg.norm, blk["ln1"], x, cfg.norm_eps).astype(cdt)
    project = lambda spec, w: jnp.einsum(spec, hidden, w.astype(cdt), preferred_element_type=jnp.float32).astype(cdt)
    if "wqkv" in blk["attn"]:
        q, k, _ = project("btd,dchn->cbthn", blk["attn"]["wqkv"])
    else:
        q, k = project("btd,dhn->bthn", blk["attn"]["wq"]), project("btd,dcgn->cbtgn", blk["attn"]["wkv"])[0]

    scales = {"k_scale": new_kv["k_scale_pool"], "v_scale": new_kv["v_scale_pool"]} if quantized else {}
    want = gather_attention(q, new_kv["k_pool"], new_kv["v_pool"], info.block_tables, info.seq_lens, info.q_lens,
                            window=case.window, **scales)
    real = (np.arange(t)[None, :] < q_lens[:, None])[:, :, None, None]
    got = np.where(real, np.asarray(out, np.float32).reshape(b, t, h, d), 0.0)
    # bfloat16 results differ by an ulp of the output: 2 ** -8 of its size
    atol, rtol = (3e-2, 1e-2) if case.pool == "bfloat16" else (2e-5, 1e-7)
    np.testing.assert_allclose(got, np.asarray(want, np.float32), atol=atol, rtol=rtol)
    assert np.all(np.isfinite(np.asarray(out, np.float32)))

    # every real token's key lies where its row's table says
    k_pages = np.asarray(new_kv["k_pool"], np.float32)
    if quantized:
        k_pages = k_pages * np.asarray(new_kv["k_scale_pool"], np.float32) / 127.0
    for i in range(b):
        for j in range(int(q_lens[i])):
            slot = int(seq[i]) + j
            page = k_pages[tables[i, slot // bs], slot % bs]
            err = np.abs(page - np.asarray(k[i, j], np.float32)).max()
            assert err <= (0.05 if quantized else 1e-6) * max(1.0, np.abs(page).max()), (i, j, err)


DRAFT_CFG = dataclasses.replace(CFG, n_layers=1, d_model=16, n_heads=2)


@pytest.fixture(scope="module")
def draft_params():
    return transformer.init_params(DRAFT_CFG, jax.random.key(99))


def test_spec_serving_matches_generate(params, draft_params):
    """Speculative serving greedy output == dense-cache target-only greedy
    for ANY draft (here an untrained 1-layer model with a low hit rate):
    acceptance always verifies against the target argmax."""
    prompts = _prompts(4)
    n_new = 10
    eng = ServingEngine(
        params, CFG, max_batch=2, n_blocks=32, block_size=8,
        temperature=0.0, draft_params=draft_params, draft_cfg=DRAFT_CFG,
        spec_k=3,
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run()
    assert eng.stats["spec_rounds"] > 0
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, CFG, p, n_new)


def test_spec_serving_self_draft_accepts_everything(params):
    """Target-as-draft: fp32 greedy acceptance must be ~total, so each
    round emits k+1 tokens (the degenerate upper bound pins the
    accept/emit plumbing)."""
    p = _prompts(1)[0]
    n_new = 9
    eng = ServingEngine(
        params, CFG, max_batch=1, n_blocks=32, block_size=8,
        temperature=0.0, draft_params=params, draft_cfg=CFG, spec_k=2,
    )
    rid = eng.submit(p, n_new)
    out = eng.run()
    assert out[rid] == _reference_greedy(params, CFG, p, n_new)
    st = eng.stats
    assert st["spec_accepted"] == st["spec_proposed"], st


def test_spec_serving_preemption_and_stop(params, draft_params):
    """Spec serving through a pool small enough to force preemption, plus
    a stop token that lands mid-round: recompute-on-resume and surplus
    discard must both hold."""
    prompts = [_prompts(1, lengths=(12,))[0], _prompts(1, lengths=(10,))[0]]
    n_new = 16
    ref0 = _reference_greedy(params, CFG, prompts[0], n_new)
    stop = ref0[5]
    eng = ServingEngine(
        params, CFG, max_batch=2, n_blocks=8, block_size=8,
        temperature=0.0, stop_token=stop, draft_params=draft_params,
        draft_cfg=DRAFT_CFG, spec_k=3,
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run()
    for rid, p in zip(rids, prompts):
        ref = _reference_greedy(params, CFG, p, n_new)
        want = ref[: ref.index(stop)] if stop in ref else ref
        assert out[rid] == want, f"request {rid}"


def test_spec_serving_kernel_path_matches_generate(params, paged_kernel_forced):
    """Speculative serving where the form says kernel: the draft's steps
    (one query a row, heads of 128) run the in-place kernel, the verify's
    k + 1 queries a row the gather form — greedy output must still equal
    dense-cache target-only decoding."""
    draft_k = dataclasses.replace(DRAFT_CFG, d_head=128)
    assert transformer.paged_attention_form(draft_k, 1, False) == "kernel"
    assert transformer.paged_attention_form(CFG, 4, False) == "gather"
    prompts = _prompts(2)
    n_new = 8
    eng = ServingEngine(
        params, CFG, max_batch=2, n_blocks=32, block_size=8,
        temperature=0.0, draft_params=transformer.init_params(draft_k, jax.random.key(99)),
        draft_cfg=draft_k, spec_k=3,
    )
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run()
    assert eng.stats["spec_rounds"] > 0
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, CFG, p, n_new)


def test_spec_serving_validation(params, draft_params):
    with pytest.raises(ValueError, match="all three"):
        ServingEngine(params, CFG, spec_k=2)
    with pytest.raises(ValueError, match="all three"):
        ServingEngine(params, CFG, draft_params=draft_params,
                      draft_cfg=DRAFT_CFG)
    bad = dataclasses.replace(DRAFT_CFG, vocab_size=128)
    with pytest.raises(ValueError, match="vocab"):
        ServingEngine(params, CFG, draft_params=draft_params,
                      draft_cfg=bad, spec_k=2)
    with pytest.raises(ValueError, match="temperature-only"):
        ServingEngine(params, CFG, draft_params=draft_params,
                      draft_cfg=DRAFT_CFG, spec_k=2, top_k=5)


def test_engine_interleaved_submission(params):
    """Requests submitted WHILE others are decoding (the continuous part
    of continuous batching): mid-flight admission must not perturb
    already-running rows."""
    prompts = _prompts(4)
    n_new = 10
    eng = ServingEngine(
        params, CFG, max_batch=2, n_blocks=32, block_size=8, temperature=0.0
    )
    rids = [eng.submit(prompts[0], n_new), eng.submit(prompts[1], n_new)]
    for _ in range(3):
        eng.step()
    rids.append(eng.submit(prompts[2], n_new))
    for _ in range(2):
        eng.step()
    rids.append(eng.submit(prompts[3], n_new))
    out = eng.run()
    for rid, p in zip(rids, prompts):
        assert out[rid] == _reference_greedy(params, CFG, p, n_new)
