"""Trinity's mechanisms at toy widths, against the plain reference
(``benchmark/references/trinity.py``: float32 at the highest matmul precision,
sharing no code with the program): window and full attention layers in one
stack (RoPE on the window layers only), gated QK-normed grouped-query
attention, four norms a layer, a scaled embedding, a leading dense layer and
sigmoid-routed dropless experts; and the two cache lifetimes the serving
engine keeps for it: a row's pages for its whole length in the full layers'
pool, the pages inside the window in the window layers' own, given back as the
row advances.

Weights are seeded with every norm scale, the gate and the selection bias
non-trivial (``harness/families/trinity.py``), so a dropped term shows.
Float32 throughout: the program differs from the reference by the order of
its sums alone.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)  # behind the repo root: `tests` must stay this directory's package

from harness import opcount, program, serving_check, trinity_check, weights  # noqa: E402
from harness.families import trinity as family  # noqa: E402

from pretraining_llm_tpu.config import ModelConfig, get_preset  # noqa: E402
from pretraining_llm_tpu.generation import paged  # noqa: E402
from pretraining_llm_tpu.generation.serving import ServingEngine  # noqa: E402
from pretraining_llm_tpu.models import transformer as tr  # noqa: E402

with open(os.path.join(BENCH, "tests", "toy", "trinity.json")) as f:
    TOY = dict(json.load(f), name="trinity-toy")
ARCH = dict(TOY, serving_dtype="float32",
            program_model={"attention_impl": "naive", "param_dtype": "float32", "compute_dtype": "float32"})
CFG = program.model_config(ARCH, 128)
WINDOW, BS = CFG.sliding_window, 8
SEEDS = (3, 2 ** 31 + 5)

# Relative error of logits, ||program - reference|| / ||reference||. The sound
# float32 program reads 5e-7, forward and paged alike; the all-full control 0.79
# and RoPE on the full layer 0.15 past the window. 2e-5 lies 40 x over the one.
LOGITS_TOL = 2e-5


@pytest.fixture(scope="module")
def params():
    return {seed: weights.serving_params(ARCH, seed) for seed in SEEDS}


def rel_err(a, b):
    return float(np.linalg.norm(np.asarray(a, np.float64) - b) / np.linalg.norm(b))


def tokens(seed, n):
    return np.random.default_rng([seed % 2 ** 31, 9]).integers(0, CFG.vocab_size, n, dtype=np.int32)


def reference(seed, toks, arch=ARCH, **control):
    return np.asarray(serving_check.reference_forward(dict(arch, **control), seed)(np.asarray(toks)))


# -- 1. the full forward pass and the loss -------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_matches_the_reference(params, seed):
    toks = tokens(seed, 70)  # four windows of 16 and more
    logits, _ = tr.forward(params[seed], toks[None], CFG)
    want = reference(seed, toks)
    assert rel_err(logits[0], want) < LOGITS_TOL
    # each mechanism's control is far off where it acts: past the window, and nowhere before it
    for control in ("all_full", "rope_on_full"):
        other = reference(seed, toks, control=control)
        assert rel_err(other[WINDOW + 8 :], want[WINDOW + 8 :]) > 0.05
    assert rel_err(reference(seed, toks, control="all_full")[:WINDOW], want[:WINDOW]) < 1e-6


@pytest.mark.parametrize("seed", SEEDS)
def test_loss_matches_the_reference(params, seed):
    toks = tokens(seed, 49)
    want = reference(seed, toks[:-1]).astype(np.float64)
    lse = np.log(np.sum(np.exp(want - want.max(-1, keepdims=True)), -1)) + want.max(-1)
    ce = float(np.mean(lse - want[np.arange(48), toks[1:]]))
    # forward and main loss only: no load-balance term, no bias update
    loss = tr.loss_fn(params[seed], toks[None, :-1], toks[None, 1:], CFG, include_aux=False)
    assert abs(float(loss) - ce) < 1e-5 * ce
    grads = jax.grad(lambda p: tr.loss_fn(p, toks[None, :-1], toks[None, 1:], CFG, include_aux=False))(params[seed])
    # every new parameter is on the path: gate, head norms, the two post-norms
    blocks = grads["blocks"]
    for leaf in (blocks["attn"]["wg"], blocks["attn"]["q_norm"]["scale"], blocks["attn"]["k_norm"]["scale"],
                 blocks["ln1_post"]["scale"], blocks["ln2_post"]["scale"], grads["dense_blocks"]["attn"]["wg"]):
        assert float(jnp.min(jnp.max(jnp.abs(leaf.reshape(leaf.shape[0], -1)), axis=1))) > 0


def test_parameter_count_is_the_tree_and_the_familys(params):
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params[SEEDS[0]]))
    m = opcount.dims(ARCH)
    # the program carries an output bias a layer that Trinity does not have (zero here)
    assert n == CFG.num_params() == opcount.num_params(ARCH) + CFG.n_layers * CFG.d_model
    assert opcount.num_params(ARCH) == m["layers"] * family.layer_params(m) + family.other_params(m)[0]
    init = tr.init_params(CFG, jax.random.key(0))
    assert jax.tree.structure(init) == jax.tree.structure(params[SEEDS[0]])
    assert [a.shape for a in jax.tree.leaves(init)] == [a.shape for a in jax.tree.leaves(params[SEEDS[0]])]
    preset = get_preset("trinity-toy").model
    assert dataclasses.replace(CFG, context_length=256, param_dtype="float32", compute_dtype="bfloat16") == preset


def test_the_published_sizes_are_the_issues():
    full = json.load(open(os.path.join(BENCH, "configs", "trinity-mini.json")))
    m = family.dims(full)
    assert family.attn_params(m) == 27_263_232 and family.dense_layer_params(m) == 65_020_160
    assert family.layer_params(m) == 839_131_520 and m["experts"] * family.expert_params(m) == 805_306_368
    total = opcount.num_params(full)
    assert round(total / 1e6, 1) == 4241.5
    assert family.kinds(full) == ("window", "window", "window", "window", "full")
    assert family.kv_bytes_per_token_layer(full) == 2048
    cfg = program.model_config(full, 9280)
    assert cfg.two_lifetimes and cfg.layer_runs == ((0, 1), (1, 4), (4, 5))
    assert cfg.num_params() == total + 5 * 2048


# -- 2. the configuration: validation, runs, one-kind stacks ----------------------------------


def test_layer_runs_split_on_the_attention_kind():
    assert CFG.layer_runs == ((0, 1), (1, 4), (4, 5)) and CFG.two_lifetimes
    assert CFG.layer_attn_kinds == ("window",) * 4 + ("full",)
    two = dataclasses.replace(CFG, attn_kinds=("window", "full", "window", "full", "full"))
    assert two.layer_runs == ((0, 1), (1, 2), (2, 3), (3, 5))
    groups = tr.layer_groups(jax.eval_shape(lambda k: tr.init_params(two, k), jax.random.key(0)), two)
    assert [(list(layers), first) for layers, _, first in groups] == [([0], 0), ([1], 0), ([2], 1), ([3, 4], 2)]


@pytest.mark.parametrize("change,match", [
    (dict(attn_kinds=("window", "full")), "each of n_layers"),
    (dict(attn_kinds=("window",) * 4 + ("global",)), "'window' or 'full'"),
    (dict(sliding_window=0), "needs sliding_window"),
    (dict(kv_cache_dtype="int8"), "unquantized cache"),
    (dict(hc_mult=2), "residual streams"),
    (dict(attn_kinds=(), rope_full_layers=False), "rope_full_layers=False needs"),
    (dict(pos_embed="learned"), "rope_full_layers=False needs"),
])
def test_config_refuses_by_name(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **change)


ONE_KIND = {
    "all-window": dataclasses.replace(CFG, attn_kinds=("window",) * 5, rope_full_layers=True),
    "all-full": dataclasses.replace(CFG, attn_kinds=("full",) * 5),
    "no-kinds": dataclasses.replace(CFG, attn_kinds=(), rope_full_layers=True),
}


@pytest.mark.parametrize("name", sorted(ONE_KIND))
def test_a_stack_of_one_kind_builds_todays_single_pool(name):
    cfg = ONE_KIND[name]
    assert not cfg.two_lifetimes and cfg.layer_runs == ((0, 1), (1, 5))
    pools = tr.make_paged_kv_pool(cfg, 16, BS)
    assert {lp["k_pool"].shape[0] for lp in pools["layers"]} == {16}
    with pytest.raises(ValueError, match="window_blocks is for"):
        tr.make_paged_kv_pool(cfg, 16, BS, window_blocks=7)
    eng = ServingEngine(tr.init_params(cfg, jax.random.key(0)), cfg, max_batch=2, n_blocks=16, block_size=BS)
    assert eng.w_alloc is None and eng.w_tables is None and not eng.two_lifetimes
    assert "window_pool_bytes" not in eng.pool_info() and "window_pages_released" not in eng.stats
    eng.submit(tokens(1, 20).tolist(), 12)
    out = eng.run()
    assert len(out[0]) == 12 and eng.alloc.available == 15


def test_two_lifetimes_get_two_pools():
    with pytest.raises(ValueError, match="window_blocks >= 2"):
        tr.make_paged_kv_pool(CFG, 16, BS)
    pools = tr.make_paged_kv_pool(CFG, 16, BS, window_blocks=7)
    assert [lp["k_pool"].shape[0] for lp in pools["layers"]] == [7, 7, 7, 7, 16]
    with pytest.raises(ValueError, match="PagedInfo.window_tables"):
        paged.paged_decode_logits(tr.init_params(CFG, jax.random.key(0)), pools, jnp.zeros((2,), jnp.int32),
                                  jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32), cfg=CFG)
    with pytest.raises(ValueError, match="window_block_ids"):
        paged.prefill_into_pool(tr.init_params(CFG, jax.random.key(0)), CFG,
                                tr.make_paged_kv_pool(CFG, 16, BS, window_blocks=7), [1, 2, 3], [1])


# -- 3. prefill and decode through both pools ---------------------------------------------------


@pytest.mark.parametrize("form", ["gather", "kernel"])
def test_prefill_then_decode_through_both_pools_matches_the_reference(params, form, request):
    """Three rows at a batch of four: one inside the window, one that crosses
    it while decoding, one prefilled past it (into its last window pages
    only); 40 teacher-forced steps, so that every row gives pages back at
    least twice and the freed pages are taken by the other rows. The decode
    step's kernel reads the same two pools where the form says so: heads of
    128 under a backend that answers "tpu" (conftest's ``paged_kernel_forced``)."""
    seed = SEEDS[0]
    arch, cfg, weights_ = ARCH, CFG, params[seed]
    if form == "kernel":
        arch = dict(ARCH, head_dim=128)
        cfg, weights_ = program.model_config(arch, 128), weights.serving_params(arch, seed)
        request.getfixturevalue("paged_kernel_forced")
    eng = ServingEngine(weights_, cfg, max_batch=4, n_blocks=40, block_size=BS, max_seq=96)
    assert eng.decode_attention == form
    sample = [(5, 40), (14, 40), (44, 40)]
    seqs = [tokens(seed + r, p + k) for r, (p, k) in enumerate(sample)]
    prog, did = trinity_check.program_logits(weights_, cfg, eng, sample, seqs)
    ref = [reference(seed, toks, arch)[p - 1 : p + k] for (p, k), toks in zip(sample, seqs)]
    assert serving_check.rel_err(prog, ref) < LOGITS_TOL
    assert float(trinity_check.row_errors(prog, ref).max()) < LOGITS_TOL
    # rows 0 and 1 give back the pages of positions < 45 + 1 - 16 and < 54 + 1 - 16, row 2
    # never held those before 44 + 1 - 16: (29 // 8) + (38 // 8) + (68 // 8 - 29 // 8) = 3 + 4 + 5
    assert did["window_pages_released"] == 12
    assert did["window_pages_held_most"] == WINDOW // BS + 1  # never the pool's + 2: one step a window here
    # the all-full control fails on every row past the window
    far = reference(seed, seqs[2], arch, control="all_full")[43:84]
    assert rel_err(prog[2], far) > 0.05


def test_a_long_prompt_is_prefilled_into_its_last_window_pages_only(params):
    seed = SEEDS[1]
    pools = tr.make_paged_kv_pool(CFG, 16, BS, window_blocks=7)
    toks = tokens(seed, 44)
    first = paged.window_first_block(44, WINDOW, BS)
    assert first == 3  # positions 0..23 lie behind the window of the query at 44
    own = [0, 0, 0, 1, 2, 3]
    last, pools = paged.prefill_into_pool(params[seed], CFG, pools, toks.tolist(), [1, 2, 3, 4, 5, 6],
                                          window_block_ids=own)
    assert rel_err(last, reference(seed, toks)[-1]) < LOGITS_TOL
    for layer in range(4):  # pages 4.. of the window pool were never written
        assert float(jnp.max(jnp.abs(pools["layers"][layer]["k_pool"][4:]))) == 0.0
        assert float(jnp.min(jnp.max(jnp.abs(pools["layers"][layer]["k_pool"][1:4]), axis=(1, 2, 3)))) > 0
    assert float(jnp.min(jnp.max(jnp.abs(pools["layers"][4]["k_pool"][1:7]), axis=(1, 2, 3)))) > 0
    # the batched admission program names the pages the same way
    first_tok, pools2 = paged.prefill_into_pool_batched(
        params[seed], CFG, tr.make_paged_kv_pool(CFG, 16, BS, window_blocks=7), [toks.tolist()],
        [[1, 2, 3, 4, 5, 6]], jax.random.key(0), rows_window_ids=[own])
    assert int(first_tok[0]) == int(jnp.argmax(last))
    for a, b in zip(pools["layers"], pools2["layers"]):
        np.testing.assert_allclose(np.asarray(a["k_pool"][1:]), np.asarray(b["k_pool"][1:]), atol=1e-4)
    with pytest.raises(ValueError, match="rows_window_ids"):
        paged.prefill_into_pool_batched(params[seed], CFG, pools2, [toks.tolist()], [[1, 2, 3, 4, 5, 6]],
                                        jax.random.key(0))


# -- 4. the engine: greedy tokens, the allocator, the refusals ----------------------------------


def greedy(p, prompt, n):
    fwd = jax.jit(lambda t: tr.forward(p, t, CFG)[0])
    seq = list(prompt)
    for _ in range(n):
        pad = jnp.zeros((1, 96), jnp.int32).at[0, : len(seq)].set(jnp.asarray(seq))
        seq.append(int(jnp.argmax(fwd(pad)[0, len(seq) - 1])))
    return seq[len(prompt):]


@pytest.mark.parametrize("sps,pipeline", [(1, True), (4, True), (1, False), (4, False)])
def test_the_engine_emits_the_models_greedy_tokens(params, sps, pipeline):
    p = params[SEEDS[0]]
    eng = ServingEngine(p, CFG, max_batch=3, n_blocks=40, block_size=BS, max_seq=96, steps_per_sched=sps)
    per_row = WINDOW // BS + 1 + 1
    assert eng.window_blocks == 3 * per_row + 1 and eng.pool_info()["window_n_blocks"] == eng.window_blocks
    prompts = [tokens(10 + i, n).tolist() for i, n in enumerate((5, 19, 11, 41))]
    rids = [eng.submit(pr, 40) for pr in prompts]
    held = []
    tick = eng.pipeline_tick if pipeline else eng.step
    while eng.has_work() or eng._inflight:
        tick()
        held.append(max((len(r.w_blocks) for r in eng.rows if r is not None), default=0))
        for r in eng.rows:  # the window table names the live pages and nothing else
            if r is not None:
                row = eng.w_tables[r.row]
                assert row[r.w_first : r.w_first + len(r.w_blocks)].tolist() == r.w_blocks
                assert not row[: r.w_first].any() and not row[r.w_first + len(r.w_blocks):].any()
    for pr, rid in zip(prompts, rids):
        assert eng.finished[rid] == greedy(p, pr, 40)
    st = eng.stats
    assert max(held) <= per_row and st["window_blocks_peak"] <= 3 * per_row
    assert st["window_pages_released"] > 2 * len(prompts)  # every request gave pages back more than twice
    assert st["kv_blocks_peak"] >= 3 * (41 // BS) and st["kv_blocks_in_use"] == st["window_blocks_in_use"] == 0
    assert eng.alloc.available == 39 and eng.w_alloc.available == eng.window_blocks - 1  # nothing leaked
    assert 0 < st["window_attn_pages_live"] <= st["window_attn_pages_tabled"]
    assert st["window_attn_pages_live"] < st["attn_pages_live"]  # a full layer reads whole rows


@pytest.mark.parametrize("lane", [dict(fused_sampling=False), dict(logprobs_k=2), dict(logprobs_k=2, steps_per_sched=2)],
                         ids=["unfused", "logprobs", "logprobs-windows"])
def test_every_decode_lane_hands_the_window_table_over(params, lane):
    p = params[SEEDS[0]]
    eng = ServingEngine(p, CFG, max_batch=2, n_blocks=30, block_size=BS, max_seq=96, **lane)
    prompt = tokens(50, 13).tolist()
    rid = eng.submit(prompt, 30)
    assert eng.run()[rid] == greedy(p, prompt, 30) and eng.stats["window_pages_released"] > 2


def test_pages_go_back_exactly_when_wholly_behind_the_window_and_are_reused(params):
    eng = ServingEngine(params[SEEDS[0]], CFG, max_batch=2, n_blocks=40, block_size=BS, max_seq=96,
                        pipeline_depth=1)
    eng.submit(tokens(1, 9).tolist(), 60)
    seen, owners = [], {}
    while eng.has_work() or eng._inflight:
        before = eng.stats["window_pages_released"]
        eng.pipeline_tick()
        req = eng.rows[0]
        if req is None:
            break
        seq = int(eng.seq_lens[0])  # the dispatched frontier: the next query's position
        # after the tick: first live page = the one that holds position seq - 1 - window + 1,
        # the lowest position the query just dispatched (at seq - 1) could see
        assert req.w_first == max(0, seq - 1 - WINDOW + 1) // BS
        assert req.w_first + len(req.w_blocks) == (seq - 1) // BS + 1
        seen.append(eng.stats["window_pages_released"] - before)
        for b in req.w_blocks:
            owners.setdefault(b, set()).add(req.w_first + req.w_blocks.index(b))
        if seq == 40:
            eng.submit(tokens(2, 30).tolist(), 8)  # another row takes pages this one gave back
    assert sum(seen) == eng.stats["window_pages_released"] and set(seen) == {0, 1}
    assert any(len(pages) > 1 for pages in owners.values())  # a block served two pages of the row in turn
    other = [r for r in eng.rows if r is not None]
    assert not other and eng.w_alloc.available == eng.window_blocks - 1


def test_preemption_frees_both_lists(params):
    # a full pool too small for two long rows: growth preempts the younger, which comes back
    p = params[SEEDS[0]]
    eng = ServingEngine(p, CFG, max_batch=2, n_blocks=14, block_size=BS, max_seq=96)
    prompts = [tokens(21, 30).tolist(), tokens(22, 28).tolist()]
    rids = [eng.submit(pr, 36) for pr in prompts]
    while eng.has_work() or eng._inflight:
        eng.pipeline_tick()
        live = sum(len(r.w_blocks) for r in eng.rows if r is not None)
        assert eng.w_alloc.available == eng.window_blocks - 1 - live
        full = sum(len(r.blocks) for r in eng.rows if r is not None)
        assert eng.alloc.available == 13 - full
    assert eng.stats["preemptions"] >= 1
    for pr, rid in zip(prompts, rids):
        assert eng.finished[rid] == greedy(p, pr, 36)


def test_the_window_pool_is_never_short_at_its_derived_size(params):
    """Every row at its most, whatever the rows' phases: prompts that end at
    every offset inside a page, windows of three steps, a full batch."""
    p = params[SEEDS[0]]
    eng = ServingEngine(p, CFG, max_batch=4, n_blocks=60, block_size=BS, max_seq=96, steps_per_sched=3)
    for i in range(12):
        eng.submit(tokens(30 + i, 17 + i).tolist(), 30 + (i % 5))
    low = eng.window_blocks
    while eng.has_work() or eng._inflight:
        eng.pipeline_tick()
        low = min(low, eng.w_alloc.available)
    assert len(eng.finished) == 12 and 0 <= low < eng.window_blocks - 1
    assert eng.stats["window_blocks_peak"] == eng.window_blocks - 1 - low


@pytest.mark.parametrize("option,name", [
    (dict(prefix_cache=True), "prefix_cache"), (dict(kv_checksum=True), "kv_checksum"),
    (dict(quantize="int8-kv"), "quantize=int8-kv"), (dict(prefill_chunk_tokens=16), "prefill_chunk_tokens"),
    (dict(spec_k=2, draft_params={}, draft_cfg=ModelConfig(vocab_size=256)), "spec_k"),
])
def test_the_engine_refuses_by_name_what_is_not_built_on_two_lifetimes(params, option, name):
    with pytest.raises(ValueError, match=f"two cache lifetimes.*served without {name}"):
        ServingEngine(params[SEEDS[0]], CFG, max_batch=2, n_blocks=16, block_size=BS, **option)


def test_generate_runs_the_same_model(params):
    """``generation/generate.py`` over the dense cache: the window mask and the
    position-free full layer by layer kind, token for token the forward's."""
    from pretraining_llm_tpu.generation.generate import generate

    p = params[SEEDS[0]]
    prompt = tokens(40, 21)
    out = generate(p, CFG, jnp.asarray(prompt)[None], 24, jax.random.key(0), temperature=0.0)
    assert np.asarray(out)[0].tolist() == greedy(p, prompt.tolist(), 24)
