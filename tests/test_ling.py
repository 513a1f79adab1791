"""Ling-3.0-flash's mechanisms at toy widths, against the plain reference
(``benchmark/references/ling.py``: float32 at the highest matmul precision,
KDA as the token-by-token recurrence, sharing no code with the program): KDA
linear attention in its chunked and recurrent forms, the state slots that live
beside the latent page pool in one cache manager, group-limited routing over a
share of the experts, the SwiGLU clamps, the head-wise gate on latent
attention and the vocabulary slice.

The toy (``benchmark/tests/toy/ling.json``) is two periods of three layers (2
KDA : 1 MLA), a leading dense layer, 8 of 16 experts held in 4 routing groups
of which 2 are kept, and non-zero clamps in its last layers. Every tolerance
has its reason and a control that fails it beside it. Weights are seeded with
every scale, bias, ``A_log``, ``dt_bias`` and router bias non-trivial
(``harness/families/ling.py``), so a dropped term shows.
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

from harness import opcount, program, serving_check as sc, weights  # noqa: E402
from harness.families import ling as family  # noqa: E402
from references import ling as ref  # noqa: E402
from references.common import int8_fake_quant  # noqa: E402

from pretraining_llm_tpu.config import get_preset  # noqa: E402
from pretraining_llm_tpu.generation import paged  # noqa: E402
from pretraining_llm_tpu.generation.generate import generate  # noqa: E402
from pretraining_llm_tpu.generation.serving import ServingEngine  # noqa: E402
from pretraining_llm_tpu.models import kda, moe, transformer as tr  # noqa: E402

with open(os.path.join(BENCH, "tests", "toy", "ling.json")) as f:
    TOY = dict(json.load(f), name="ling-toy")
# float32 throughout: the program's arithmetic then differs from the reference's
# by the order of its sums alone, and the tolerances below can be tight.
ARCH = dict(TOY, serving_dtype="float32",
            program_model={"attention_impl": "naive", "param_dtype": "float32", "compute_dtype": "float32"})
CFG = program.model_config(ARCH, 128)
SEEDS = (3, 2 ** 31 + 5)

# Relative error of logits, ||program - reference|| / ||reference||. The sound
# float32 program reads 6.9e-7 and 7.1e-7 on the forward pass here (chunked KDA
# against the reference's recurrence: the same sums in another order); the least
# of the controls below reads 1.5e-2 (the group limit dropped; a clamp dropped
# 2.7e-2 and 2.9e-2, the reference in int8 3.1e-2). 2e-5 lies 30 x over the one
# and 750 x under the other.
LOGITS_TOL = 2e-5


@pytest.fixture(scope="module")
def params():
    return {seed: weights.serving_params(ARCH, seed) for seed in SEEDS}


def reference_logits(seed, toks, arch=ARCH, quant=None):
    return np.asarray(sc.reference_forward(arch, seed, quant)(np.asarray(toks, np.int32)), np.float32)


def rel_err(a, b):
    return float(np.linalg.norm(np.asarray(a, np.float64) - b) / np.linalg.norm(b))


def tokens(seed, n):
    return np.random.default_rng([seed % 2 ** 31, 9]).integers(0, CFG.vocab_size, n, dtype=np.int32)


# -- 1. the full forward pass ---------------------------------------------------------


def test_the_toy_has_every_kind_of_layer_and_the_tree_stacks_them_by_kind(params):
    assert CFG.layer_kinds == (("kda", "dense"), ("kda", "moe"), ("attn", "moe")) + (("kda", "moe"),) * 2 + (
        ("attn", "moe"),)
    assert CFG.layer_runs == ((0, 1), (1, 2), (2, 3), (3, 5), (5, 6))
    p = params[SEEDS[0]]
    stacked = {k: jax.tree.leaves(v)[0].shape[0] for k, v in p.items() if k.endswith("blocks")}
    assert stacked == {"dense_blocks": 1, "blocks": 3, "attn_blocks": 2}
    assert "wf" in p["blocks"]["attn"] and "wkv_b" in p["attn_blocks"]["attn"]
    shapes = lambda t: jax.tree.map(lambda a: a.shape, t)
    assert shapes(jax.eval_shape(lambda k: tr.init_params(CFG, k), jax.random.key(0))) == shapes(p)


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_matches_the_reference(params, seed):
    toks = tokens(seed, 100)  # a whole chunk of 64 and a ragged one
    logits, _ = tr.forward(params[seed], toks[None], CFG)
    assert rel_err(logits[0], reference_logits(seed, toks)) < LOGITS_TOL


def test_parameter_count_is_the_tree_and_the_familys(params):
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params[SEEDS[0]]))
    assert n == CFG.num_params() == opcount.num_params(ARCH)
    m = opcount.dims(ARCH)
    assert family.kda_params(m) == CFG._kda_params() and family.mla_params(m) == CFG._attn_params()


def test_reference_in_int8_fails_too():
    seed = SEEDS[0]
    toks = tokens(seed, 48)
    assert rel_err(reference_logits(seed, toks, quant=int8_fake_quant), reference_logits(seed, toks)) > 100 * LOGITS_TOL


@pytest.mark.parametrize("dropped", ["moe_swiglu_limits", "moe_shared_swiglu_limits", "moe_n_group", "attn_output_gate"])
def test_a_dropped_term_fails_the_tolerance(params, dropped):
    """The clamps, the group limit and the head-wise gate each move the logits
    far past the tolerance: the toy exercises them."""
    seed = SEEDS[0]
    toks = tokens(seed, 48)
    off = {"moe_n_group": 1, "attn_output_gate": False}.get(dropped, ())
    cfg = dataclasses.replace(CFG, **{dropped: off}, **({"moe_topk_group": 1} if dropped == "moe_n_group" else {}))
    p = params[seed]
    if dropped == "attn_output_gate":
        attn = {k: v for k, v in p["attn_blocks"]["attn"].items() if k != "wgate"}
        p = {**p, "attn_blocks": {**p["attn_blocks"], "attn": attn}}
    logits, _ = tr.forward(p, toks[None], cfg)
    assert rel_err(logits[0], reference_logits(seed, toks)) > 100 * LOGITS_TOL


# -- 2. KDA: chunked = recurrent = the reference's recurrence -------------------------


def _kda_inputs(t, seed=0, decay=1.0, b=2, h=3, n=16):
    rng = np.random.default_rng(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    q, k = (f32(rng.normal(size=(b, t, h, n))) for _ in range(2))
    q, k = kda._l2(q) * n ** -0.5, kda._l2(k)
    v = f32(rng.normal(size=(b, t, h, n)))
    g = f32(-5.0 * decay * rng.uniform(size=(b, t, h, n)) ** 3)  # from none to e^-5 a token
    beta = f32(rng.uniform(size=(b, t, h)))
    return q, k, v, g, beta


def _recurrent(state, q, k, v, g, beta):
    outs = []
    for i in range(q.shape[1]):
        o, state = kda.recurrent_step(state, q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i])
        outs.append(o)
    return jnp.stack(outs, axis=1), state


@pytest.mark.parametrize("t", [1, 63, 64, 65, 150])
def test_chunked_kda_is_the_recurrence_from_any_state(t):
    """Lengths that are no multiple of the chunk, from a non-zero state: the
    chunked form's outputs and final state are the recurrence's (1e-6 measured:
    the same float32 sums in another order), and from a zero state the
    reference's ``delta_rule``."""
    q, k, v, g, beta = _kda_inputs(t)
    s0 = jnp.asarray(np.random.default_rng(1).normal(size=(2, 3, 16, 16)), jnp.float32)
    for state in (s0, jnp.zeros_like(s0)):
        o_c, s_c = kda.chunked(state, q, k, v, g, beta)
        o_r, s_r = _recurrent(state, q, k, v, g, beta)
        assert rel_err(o_c, np.asarray(o_r)) < 1e-5 and rel_err(s_c, np.asarray(s_r)) < 1e-5
    for row in range(2):
        want = ref.delta_rule(q[row], k[row], v[row], g[row], beta[row], 3)
        assert rel_err(o_c[row], np.asarray(want)) < 1e-5


def test_chunked_kda_holds_the_strongest_decay_without_overflow():
    """Every channel at the bound, e^-5 a token: the cumulative log-decay of a
    chunk reaches -320 and 1 / its exponential is not a float32. The chunked
    form never forms it, and still equals the recurrence."""
    q, k, v, _, beta = _kda_inputs(130, seed=2)
    g = jnp.full(q.shape, -5.0)
    s0 = jnp.ones((2, 3, 16, 16), jnp.float32)
    o_c, s_c = kda.chunked(s0, q, k, v, g, beta)
    o_r, s_r = _recurrent(s0, q, k, v, g, beta)
    assert np.isfinite(np.asarray(o_c)).all() and np.isfinite(np.asarray(s_c)).all()
    assert rel_err(o_c, np.asarray(o_r)) < 1e-5 and rel_err(s_c, np.asarray(s_r)) < 1e-5


def test_a_bucket_padded_prompt_leaves_the_state_and_the_tail_of_its_last_real_token(params):
    """The mixer over 70 real tokens padded to 128, with the true lengths: the
    state, the conv tail and the real positions' outputs are the unpadded
    run's, row by row (rows of unlike lengths in one bucket); without the
    lengths they are not."""
    p = jax.tree.map(lambda a: a[0], params[SEEDS[0]]["blocks"])["attn"]
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(2, 128, CFG.d_model)), jnp.float32)
    shapes = kda.state_shapes(CFG, 2)
    state = jnp.asarray(rng.normal(size=shapes["state"][0]), jnp.float32)
    tail = jnp.asarray(rng.normal(size=shapes["conv"][0]), jnp.float32)
    lens = jnp.asarray([70, 2], jnp.int32)  # 2: shorter than the conv's reach, the old tail shows through
    valid = jnp.arange(128)[None, :] < lens[:, None]
    y, s, c = kda.mix(p, h, CFG, state, tail, valid, lens)
    for row, n in enumerate((70, 2)):
        y1, s1, c1 = kda.mix(p, h[row : row + 1, :n], CFG, state[row : row + 1], tail[row : row + 1])
        assert rel_err(y[row, :n], np.asarray(y1[0])) < 1e-5
        assert rel_err(s[row], np.asarray(s1[0])) < 1e-5 and rel_err(c[row], np.asarray(c1[0])) < 1e-6
    _, s_blind, c_blind = kda.mix(p, h, CFG, state, tail)
    assert rel_err(s_blind[0], np.asarray(s[0])) > 1e-2 and rel_err(c_blind[0], np.asarray(c[0])) > 1e-2


# -- 3. prefill then decode through both caches ---------------------------------------


def _teacher_forced(p, seqs, prompt_lens, steps, readmit_row=None, readmit_at=None):
    """Logits after each forced token, through the engine's prefill and decode
    lanes on hand-built tables, a row's state slot its index. ``readmit_row`` is
    preempted before step ``readmit_at``: its pages are freed and what it held
    is prefilled anew into other pages and, as the engine does, its own slot."""
    bs, max_blocks, rows = 8, 16, len(seqs)
    pools = tr.make_paged_kv_pool(CFG, 64, bs, state_slots=rows)
    alloc = paged.BlockAllocator(64)
    tables = np.zeros((rows, max_blocks), np.int32)
    seq_lens = np.zeros((rows,), np.int32)
    out = [[] for _ in seqs]
    prompts, ids = [], []
    for r, (toks, n) in enumerate(zip(seqs, prompt_lens)):
        ids.append(alloc.alloc(paged.required_blocks(n + steps + 1, bs)))
        prompts.append(toks[:n].tolist())
        tables[r, : len(ids[r])] = ids[r]
        seq_lens[r] = n
    n_pre = [paged.required_blocks(n, bs) for n in prompt_lens]
    # batched prefill: rows of different lengths in one padded bucket, slots out of order
    order = list(range(rows))[::-1]
    _, pools = paged.prefill_into_pool_batched(
        p, CFG, pools, [prompts[r] for r in order], [ids[r][: n_pre[r]] for r in order],
        jax.random.key(0), slots=order)
    for j in range(steps):
        if j == readmit_at:
            r = readmit_row
            alloc.free(ids[r])
            alloc.alloc(3)  # other pages than the ones just freed
            held = seqs[r][: seq_lens[r]].tolist()
            ids[r] = alloc.alloc(paged.required_blocks(len(held) + steps + 1, bs))
            tables[r] = 0
            tables[r, : len(ids[r])] = ids[r]
            _, pools = paged.prefill_into_pool(
                p, CFG, pools, held, ids[r][: paged.required_blocks(len(held), bs)], slot=r)
        tok = np.asarray([s[n + j] for s, n in zip(seqs, prompt_lens)], np.int32)
        logits, pools = paged.paged_decode_logits(
            p, pools, jnp.asarray(tok), jnp.asarray(tables), jnp.asarray(seq_lens), cfg=CFG)
        for r in range(rows):
            out[r].append(np.asarray(logits[r], np.float32))
        seq_lens += 1
    return [np.stack(o) for o in out]


@pytest.mark.parametrize("readmit", [False, True], ids=["steady", "preempted-and-readmitted"])
def test_paged_decode_matches_the_reference(params, readmit):
    seed = SEEDS[0]
    prompt_lens, steps = (21, 9, 70), 6  # 70: more than a chunk of prompt
    seqs = [tokens(seed + r, n + steps) for r, n in enumerate(prompt_lens)]
    got = _teacher_forced(params[seed], seqs, prompt_lens, steps,
                          readmit_row=1 if readmit else None, readmit_at=3 if readmit else None)
    for toks, n, rows in zip(seqs, prompt_lens, got):
        want = reference_logits(seed, toks)[n : n + steps]  # row t scores token t + 1
        assert rel_err(rows, want) < LOGITS_TOL


def test_a_prompt_given_no_slot_takes_the_pools_cursor(params):
    """``prefill_into_pool`` without a slot (``serving_check.program_logits``):
    the n-th such prompt lands in slot n, the row a caller that builds its
    tables in prefill order decodes it at."""
    p, bs = params[SEEDS[0]], 8
    pools = tr.make_paged_kv_pool(CFG, 16, bs, state_slots=3)
    seqs = [tokens(7, 12), tokens(8, 20)]
    ids = [[1, 2, 3], [4, 5, 6, 7]]
    for toks, blocks in zip(seqs, ids):
        _, pools = paged.prefill_into_pool(p, CFG, pools, toks[:-1].tolist(),
                                           blocks[: paged.required_blocks(len(toks) - 1, bs)])
    assert int(pools["state_cursor"]) == 2
    tables = np.zeros((3, 4), np.int32)
    for r, blocks in enumerate(ids):
        tables[r, : len(blocks)] = blocks
    tok = jnp.asarray([seqs[0][-1], seqs[1][-1], 0], jnp.int32)
    lens = jnp.asarray([len(seqs[0]) - 1, len(seqs[1]) - 1, 0], jnp.int32)
    logits, pools = paged.paged_decode_logits(p, pools, tok, jnp.asarray(tables), lens, cfg=CFG)
    for r, toks in enumerate(seqs):
        assert rel_err(logits[r], reference_logits(SEEDS[0], toks)[-1]) < LOGITS_TOL
    assert int(pools["state_cursor"]) == 2  # a decode step leaves it alone


def test_a_dead_row_leaves_its_slot_alone(params):
    """A row whose table names no page (free, or mid-prefill in the engine's
    decode tables) rides the decode step and writes nothing into its slot."""
    p = params[SEEDS[0]]
    pools = tr.make_paged_kv_pool(CFG, 16, 8, state_slots=2)
    _, pools = paged.prefill_into_pool(p, CFG, pools, tokens(1, 10).tolist(), [1, 2], slot=1)
    before = jax.tree.map(np.asarray, pools["layers"][0])
    tables = jnp.zeros((2, 4), jnp.int32)  # both rows dead
    _, pools = paged.paged_decode_logits(p, pools, jnp.asarray([5, 6], jnp.int32), tables,
                                         jnp.zeros((2,), jnp.int32), cfg=CFG)
    for name, was in before.items():
        np.testing.assert_array_equal(np.asarray(pools["layers"][0][name]), was)
    assert np.abs(before["state_pool"][1]).max() > 0 and np.abs(before["state_pool"][0]).max() == 0


# -- 4. the engine: two kinds of cache in one manager ---------------------------------


@pytest.fixture(scope="module")
def greedy(params):
    p = params[SEEDS[0]]
    full = jax.jit(lambda t: tr.forward(p, t, CFG)[0])

    def run(prompt, n):
        toks = list(prompt)
        for _ in range(n):
            pad = np.zeros((1, 96), np.int32)
            pad[0, : len(toks)] = toks
            toks.append(int(jnp.argmax(full(jnp.asarray(pad))[0, len(toks) - 1])))
        return toks[len(prompt):]

    prompts = [tokens(20 + i, n).tolist() for i, n in enumerate((5, 19, 33, 12, 70, 9))]
    return prompts, [run(pr, 10) for pr in prompts]


@pytest.mark.parametrize("kw", [
    dict(n_blocks=64), dict(n_blocks=64, steps_per_sched=4, pipeline_depth=2),
    dict(n_blocks=64, prefill_chunk_tokens=16, steps_per_sched=2), dict(n_blocks=14, steps_per_sched=4),
], ids=["plain", "windows-in-flight", "chunk-lane", "preempting"])
def test_engine_output_is_the_full_forwards_greedy_continuation(params, greedy, kw):
    """Six requests through three rows: every slot is reused by a later row,
    under the pipelined scheduler while windows dispatched for its last owner
    are still in flight (their late writes precede the new owner's prefill in
    the device's order, and a reaped row's empty table stops later ones);
    the chunk lane builds a row's state chunk by chunk from its slot while
    decode windows pass it by; a pool of 13 pages preempts and recomputes."""
    prompts, want = greedy
    eng = ServingEngine(params[SEEDS[0]], CFG, max_batch=3, block_size=8, **kw)
    rids = [eng.submit(pr, 10) for pr in prompts]
    out = eng.run()
    assert [out[r] for r in rids] == want
    assert eng.stats["state_slots_peak"] == 3
    assert (eng.stats["preemptions"] > 0) == (kw["n_blocks"] == 14)
    info = eng.pool_info()
    per_slot = 4 * 4 * (4 * 16 * 16 + 3 * 3 * 4 * 16)  # 4 KDA layers, float32: a state and a 3-tap tail of q, k, v
    assert info["state_slots"] == 3 and info["bytes_per_slot"] == per_slot and info["state_bytes"] == 4 * per_slot
    assert info["pool_bytes"] == 2 * kw["n_blocks"] * 8 * CFG.latent_dim * 4  # 2 MLA layers' pages alone


def test_a_mid_prefill_row_rides_no_decode_window(params):
    eng = ServingEngine(params[SEEDS[0]], CFG, max_batch=2, n_blocks=32, block_size=8, prefill_chunk_tokens=8)
    eng.submit(tokens(1, 5).tolist(), 4)
    eng.submit(tokens(2, 30).tolist(), 4)
    eng._admit()
    eng._dispatch_prefill_chunks(defer=False)  # 8 tokens: the short row's whole prompt, 3 of the long one's
    assert [r.prefill_pos for r in eng.rows] == [None, 3]
    assert eng.tables[1].any() and not eng._decode_tables()[1].any() and eng._decode_tables()[0].any()


def test_the_engine_refuses_by_name_what_is_not_built_on_state_slots(params):
    p = params[SEEDS[0]]
    for kw, name in ((dict(prefix_cache=True), "prefix_cache"), (dict(kv_checksum=True), "kv_checksum"),
                     (dict(quantize="int8-kv"), "quantize"), (dict(quantize="int8"), "quantize"),
                     (dict(spec_k=2, draft_params=p, draft_cfg=CFG), "spec_k")):
        with pytest.raises(ValueError, match=rf"state-slot model .* is served without {name}: .*kv_transfer"):
            ServingEngine(p, CFG, max_batch=2, n_blocks=16, block_size=8, **kw)


def test_routing_counters_count_the_pairs_that_met_an_expert_held(params):
    eng = ServingEngine(params[SEEDS[0]], CFG, max_batch=2, n_blocks=32, block_size=8)
    eng.submit(tokens(1, 9).tolist(), 6)
    eng.run()
    st = eng.stats
    assert st["moe_expert_tokens"].shape == (5, 8)  # five expert layers, the 8 experts held of 16
    meta = eng._count_moe({"expert_tokens": np.ones((5, 8), np.int64), "experts_touched": np.full((5,), 8),
                           "expert_visits": np.full((5,), 9)}, 3)
    assert meta["moe_routed"] == 3 * 2 * 2 * 5 and meta["moe_routed_here"] == 40 and meta["moe_experts"] == 8
    assert meta["moe_touched"] == 40 and meta["moe_visits"] == 45
    here = st["moe_expert_tokens"].sum() / (st["moe_steps"] * 2 * 2 * 5)
    assert 0.2 < here < 0.8  # half the experts are here; random weights route about half the pairs to them


@pytest.mark.parametrize("ragged", [False, True])
def test_generate_runs_the_ragged_and_the_bucketed_path(params, greedy, ragged):
    """``generate``: a prompt bucketed past its length, and rows of unlike
    lengths in one batch, leave each KDA state as of its row's last real token."""
    prompts, want = greedy
    rows = [1, 2, 3] if ragged else [2]
    width = max(len(prompts[r]) for r in rows)
    arr = np.zeros((len(rows), width), np.int32)
    for i, r in enumerate(rows):
        arr[i, : len(prompts[r])] = prompts[r]
    lengths = jnp.asarray([len(prompts[r]) for r in rows]) if ragged else None
    out = generate(params[SEEDS[0]], CFG, jnp.asarray(arr), 10, jax.random.key(0), temperature=0.0,
                   prompt_lengths=lengths)
    assert [np.asarray(o).tolist() for o in out] == [want[r] for r in rows]


# -- 5. the router and the experts ----------------------------------------------------


def test_group_limited_selection_against_a_hand_case():
    """8 experts in 4 groups of 2, 2 groups kept, top-2. Scores: group 0 holds
    the single best expert and a poor one (sum 1.0), groups 1 and 2 two good
    ones each (sums 1.5 and 1.4): groups 1 and 2 stay, and the best expert of
    all is not chosen. Without the limit it is."""
    cfg = dataclasses.replace(CFG, n_experts=8, n_experts_held=0, experts_per_token=2, moe_n_group=4,
                              moe_topk_group=2, moe_score_bias=False, moe_norm_topk=True, moe_routed_scale=1.0)
    want = jnp.asarray([[0.9, 0.1, 0.8, 0.7, 0.75, 0.65, 0.2, 0.3]], jnp.float32)
    logit = jnp.log(want / (1 - want))  # sigmoid's inverse
    mlp = {"router": jnp.concatenate([logit, jnp.zeros((CFG.d_model - 1, 8))])}
    x = jnp.zeros((1, CFG.d_model), jnp.float32).at[0, 0].set(1.0)
    idx, gates = moe.route_dropless(mlp, x, cfg)
    assert sorted(np.asarray(idx[0]).tolist()) == [2, 4]
    np.testing.assert_allclose(np.sort(np.asarray(gates[0])), [0.75 / 1.55, 0.8 / 1.55], rtol=1e-5)
    free = dataclasses.replace(cfg, moe_n_group=1, moe_topk_group=1)
    assert sorted(np.asarray(moe.route_dropless(mlp, x, free)[0][0]).tolist()) == [0, 2]
    # the bias enters the selection of groups and experts, never the gates
    biased = dataclasses.replace(cfg, moe_score_bias=True)
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0.5, 0.5], jnp.float32)  # lifts group 3 to 1.5
    idx, gates = moe.route_dropless({**mlp, "router_bias": bias}, x, biased)
    assert sorted(np.asarray(idx[0]).tolist()) == [2, 7]
    np.testing.assert_allclose(np.sort(np.asarray(gates[0])), [0.3 / 1.1, 0.8 / 1.1], rtol=1e-5)


def _expert_layer(params, seed=SEEDS[0]):
    """(canonical weights of the last KDA layer with experts, its program block)."""
    key = weights.seed_key(seed)
    c = weights.layer(ARCH, key, 2, jnp.float32)
    return c, family.program_layer(opcount.dims(ARCH), c)


def test_the_shares_add_up_to_the_whole_layer(params):
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one layer (a routing
    group each); their parts, the shared expert counted once, sum to the
    reference's whole layer. The program holds the router's *first* experts,
    so share j is the layer with its router turned by j groups: the groups
    are the same sets, and so are the choices."""
    arch = dict(ARCH, num_experts=16)  # the whole layer's weights, made once
    m = opcount.dims(arch)
    c = family.layer(m, jax.random.fold_in(weights.seed_key(SEEDS[0]), 5), jnp.float32)
    h = jnp.asarray(np.random.default_rng(0).normal(size=(40, CFG.d_model)), jnp.float32)
    layer_index = 4  # clamps 0.05 and 0.04
    whole = ref.experts(h, c, arch, None, layer_index, held=range(16))
    shared = ref.swiglu(h, c["s_gate"], c["s_up"], c["s_down"], None, 0.04)
    cfg = dataclasses.replace(CFG, n_experts_held=4)
    dense = lambda sh, hh: tr._dense_mlp(sh, hh, cfg, jnp.float32(0.04))
    total = jnp.zeros_like(whole)
    for j in range(4):
        turn = lambda a: jnp.roll(a, -4 * j, axis=-1)
        share = {name: c[name][4 * j : 4 * j + 4] for name in ("e_gate", "e_up", "e_down")}
        mlp = family._program_ffn({**c, **share, "router": turn(c["router"]), "b_corr": turn(c["b_corr"])})
        y, counts = moe.moe_mlp_dropless({**mlp, "expert_limit": jnp.float32(0.05)}, h[None], cfg, dense)
        assert counts.shape == (4,)
        # the reference, given the same share as a list
        want = ref.experts(h, {**c, **share}, arch, None, layer_index, held=range(4 * j, 4 * j + 4))
        assert rel_err(y[0], np.asarray(want)) < 1e-5
        total = total + y[0] - shared
    assert rel_err(total + shared, np.asarray(whole)) < 1e-5


def test_the_clamp_with_a_limit_clamps_and_zero_does_not():
    g = jnp.asarray([-3.0, 0.5, 2.0, 9.0])
    u = jnp.asarray([-8.0, 0.5, 3.0, 1.0])
    np.testing.assert_allclose(moe.swiglu(g, u, jnp.float32(2.0)),
                               jax.nn.silu(jnp.asarray([-3.0, 0.5, 2.0, 2.0])) * jnp.asarray([-2.0, 0.5, 2.0, 1.0]))
    np.testing.assert_allclose(moe.swiglu(g, u, jnp.float32(0.0)), jax.nn.silu(g) * u)
    np.testing.assert_allclose(moe.swiglu(g, u), jax.nn.silu(g) * u)
    # a stack with no clamp anywhere traces none: the published zeros cost nothing
    none = dataclasses.replace(CFG, moe_swiglu_limits=(0.0,) * 6, moe_shared_swiglu_limits=())
    p = jax.eval_shape(lambda k: tr.init_params(none, k), jax.random.key(0))
    mins = lambda cfg: str(jax.make_jaxpr(lambda p, x: tr.forward(p, x, cfg)[0])(
        p, jnp.zeros((1, 8), jnp.int32))).count(" min ")
    assert mins(none) == 0 < mins(CFG)


def test_the_vocabulary_slice_is_the_whole_heads_rows_of_the_slice(params):
    """A quarter of the vocabulary: tokens of the slice through the sliced
    tables give the whole head's logits of the slice, and nothing else moves."""
    p, v = params[SEEDS[0]], CFG.vocab_size // 4
    cut = {**p, "tok_embed": {"embedding": p["tok_embed"]["embedding"][:v]},
           "lm_head": {"kernel": p["lm_head"]["kernel"][:, :v]}}
    toks = jnp.asarray(tokens(1, 40) % v)[None]
    whole, _ = tr.forward(p, toks, CFG)
    part, _ = tr.forward(cut, toks, dataclasses.replace(CFG, vocab_size=v))
    np.testing.assert_array_equal(np.asarray(part), np.asarray(whole[..., :v]))


# -- 6. the unit-test preset ----------------------------------------------------------


def test_ling_mini_preset_trains_forward_and_serves():
    cfg = get_preset("ling-mini").model
    assert cfg.n_kda_layers == 4 and cfg.layer_group_size == 3 and cfg.moe_n_group == 4
    p = tr.init_params(cfg, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(p)) == cfg.num_params()
    toks = jax.random.randint(jax.random.key(1), (2, 24), 0, cfg.vocab_size)
    loss = tr.loss_fn(p, toks, jnp.roll(toks, -1, axis=1), cfg)
    assert np.isfinite(float(loss)) and abs(float(loss) - np.log(cfg.vocab_size)) < 1.0
    eng = ServingEngine(p, cfg, max_batch=2, n_blocks=16, block_size=8)
    rid = eng.submit([1, 2, 3, 4, 5], 4)
    assert len(eng.run()[rid]) == 4
