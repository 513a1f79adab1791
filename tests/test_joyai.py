"""JoyAI-LLM-Flash's mechanisms at toy widths, against the plain reference
(``benchmark/references/joyai.py``: float32 at the highest matmul precision,
sharing no code with the program): the stack (latent attention, a leading
dense layer, sigmoid-routed dropless experts of which this chip holds half)
and the multi-token-prediction module that drafts for it; the speculative
round over the latent page pool, verify then draft; and the serving engine
with ``spec_k=1`` and no draft model, whose greedy output must be the plain
engine's token for token whatever the module proposes.

Weights are seeded with every scale and bias non-trivial
(``harness/families/joyai.py``), so a dropped term shows. Float32 throughout:
the program differs from the reference by the order of its sums alone.
"""

import dataclasses
import functools
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

from harness import mtp_check, opcount, program, weights  # noqa: E402
from harness.families import joyai as family  # noqa: E402
from references import joyai as ref  # noqa: E402

from pretraining_llm_tpu.config import get_preset  # noqa: E402
from pretraining_llm_tpu.generation import paged  # noqa: E402
from pretraining_llm_tpu.generation.serving import ServingEngine  # noqa: E402
from pretraining_llm_tpu.models import mla, moe, mtp, transformer as tr  # noqa: E402
from pretraining_llm_tpu.observability import spans  # noqa: E402

with open(os.path.join(BENCH, "tests", "toy", "joyai.json")) as f:
    TOY = dict(json.load(f), name="joyai-toy")
ARCH = dict(TOY, serving_dtype="float32",
            program_model={"attention_impl": "naive", "param_dtype": "float32", "compute_dtype": "float32"})
CFG = program.model_config(ARCH, 128)
SEEDS = (3, 2 ** 31 + 5)

# Relative error of logits, ||program - reference|| / ||reference||. The sound
# float32 program reads 1.8e-7 (stack) and 2.5e-7 (module), forward and paged
# alike; the module wired one position off reads 1.0. 2e-5 lies 100 x over the one.
LOGITS_TOL = 2e-5


@pytest.fixture(scope="module")
def params():
    return {seed: weights.serving_params(ARCH, seed) for seed in SEEDS}


def rel_err(a, b):
    return float(np.linalg.norm(np.asarray(a, np.float64) - b) / np.linalg.norm(b))


def tokens(seed, n):
    return np.random.default_rng([seed % 2 ** 31, 9]).integers(0, CFG.vocab_size, n, dtype=np.int32)


def reference(seed, toks, hidden_shift=0):
    """(the stack's logits (T, V), the module's (T - 1, V)) of the reference."""
    key = weights.seed_key(seed)
    gw = weights.globals_(ARCH, key, jnp.float32)
    toks = jnp.asarray(toks)
    h = ref.hidden(toks, lambda l: weights.layer(ARCH, key, l, jnp.float32), gw, ARCH)
    m = ref.mtp_hidden(toks, h, gw, ARCH, hidden_shift=hidden_shift)
    return np.asarray(ref.head(h, gw)), np.asarray(ref.head(m, gw))


# -- 1. the full forward pass, stack and module ----------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_matches_the_reference(params, seed):
    toks = tokens(seed, 48)
    logits, _, hid = tr.forward(params[seed], toks[None], CFG, return_hidden=True)
    m_logits, _, counts = mtp.mtp_forward(params[seed], hid["final_hidden"][:, :-1], toks[None, 1:], CFG)
    want, m_want = reference(seed, toks)
    assert rel_err(logits[0], want) < LOGITS_TOL
    assert rel_err(m_logits[0], m_want) < LOGITS_TOL
    # the module's block routes over all 16 experts and counts the 8 held
    assert counts.shape == (CFG.experts_held,) and 0 < int(counts.sum()) < 47 * CFG.experts_per_token
    # one wire off (the hidden state of the position before) is far off: what the
    # benchmark's draft_logits_rel_err is there to catch
    assert rel_err(reference(seed, toks, hidden_shift=1)[1], m_want) > 0.5


def test_parameter_count_is_the_tree_and_the_familys(params):
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params[SEEDS[0]]))
    assert n == CFG.num_params() == opcount.num_params(ARCH)
    m = opcount.dims(ARCH)
    module = family.mtp_params(m)
    assert module == family.layer_params(m) + 2 * CFG.d_model ** 2 + 3 * CFG.d_model
    assert dataclasses.replace(CFG, mtp_depth=0).num_params() == n - module
    # the training forward runs neither the module nor the experts a token did not choose
    inactive = (m["experts_held"] - m["top_k"]) * family.expert_params(m) * m["layers"]
    assert CFG.num_active_params() == n - module - inactive
    init = tr.init_params(CFG, jax.random.key(0))
    assert jax.tree.structure(init) == jax.tree.structure(params[SEEDS[0]])
    assert [a.shape for a in jax.tree.leaves(init)] == [a.shape for a in jax.tree.leaves(params[SEEDS[0]])]


def test_the_published_sizes_are_the_issues():
    full = json.load(open(os.path.join(BENCH, "configs", "joyai-llm-flash.json")))
    m = family.dims(full)
    assert round(family.attn_params(m) / 1e6, 2) == 26.35 and round(family.dense_layer_params(m) / 1e6, 2) == 70.39
    assert round(family.layer_params(m) / 1e6, 2) == 635.57 and round(family.mtp_params(m) / 1e6, 2) == 643.97
    cfg = program.model_config(dict(full, name="joyai"), 4160)
    assert cfg.num_params() == opcount.num_params(full) and round(cfg.num_params() * 2 / 1e9, 2) == 7.57
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.mtp_depth, cfg.n_cache_layers) == (5, 1, 1, 6)
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token, cfg.latent_dim) == (256, 128, 8, 576)
    assert family.latent_bytes_per_token(full) == 6 * 1152
    assert round(4161 * 64 * family.latent_bytes_per_token(full) / 1e9, 2) == 1.84
    # a round's floor: the experts 1,024 pairs touch in 5 expert blocks, 6 layers' latents, the head twice
    touched = 1 - (1 - 1 / 256) ** 1024
    assert 5.9e9 < family.moe_step_bytes(full, touched) < 6.1e9
    assert round(196608 * family.latent_bytes_per_token(full) / 1e9, 2) == 1.36
    whole = family.decode_step_min_bytes(full, 196608, 64, touched)
    assert 8.5e9 < whole < 9.2e9 and whole > family.moe_step_bytes(full, touched) + 1.36e9 + 2 * 0.529e9


# -- 2. caches: the module's block has a layer of its own -------------------------------


def test_the_module_keeps_a_cache_layer_of_its_own_behind_the_stacks(params):
    """Prefill then single steps over a dense cache, stack and module, equal
    the uncached forward; ``forward`` hands the module's layer through untouched."""
    p = params[SEEDS[0]]
    toks = tokens(7, 20)
    full, _, hid = tr.forward(p, toks[None], CFG, return_hidden=True)
    m_full, _, _ = mtp.mtp_forward(p, hid["final_hidden"][:, :-1], toks[None, 1:], CFG)
    cache = tr.make_kv_cache(CFG, 1, 32)
    assert len(cache["layers"]) == CFG.n_layers + 1
    assert tr.make_kv_cache(CFG, 1, 32, stacked=True)["latent"].shape[0] == CFG.n_layers + 1
    untouched = cache["layers"][CFG.n_layers]["latent"]
    _, cache, hid = tr.forward(p, toks[None, :12], CFG, kv_cache=cache, cache_index=jnp.int32(0), return_hidden=True)
    assert cache["layers"][CFG.n_layers]["latent"] is untouched
    got, cache, _ = mtp.mtp_forward(p, hid["final_hidden"], toks[None, 1:13], CFG, kv_cache=cache,
                                    cache_index=jnp.int32(0))
    rows = [got[0]]
    for i in range(12, 19):
        _, cache, hid = tr.forward(p, toks[None, i : i + 1], CFG, kv_cache=cache, cache_index=jnp.int32(i),
                                   return_hidden=True)
        step, cache, _ = mtp.mtp_forward(p, hid["final_hidden"], toks[None, i + 1 : i + 2], CFG, kv_cache=cache,
                                         cache_index=jnp.int32(i))
        rows.append(step[0])
    assert rel_err(jnp.concatenate(rows), np.asarray(m_full[0])) < LOGITS_TOL


def test_a_model_without_a_module_has_no_such_layer_and_says_so():
    plain = dataclasses.replace(CFG, mtp_depth=0)
    assert len(tr.make_paged_kv_pool(plain, 8, 8)["layers"]) == CFG.n_layers
    assert len(tr.make_paged_kv_pool(CFG, 8, 8)["layers"]) == CFG.n_layers + 1
    p = jax.eval_shape(lambda k: tr.init_params(plain, k), jax.random.key(0))
    assert "mtp" not in p
    with pytest.raises(ValueError, match="no multi-token-prediction module"):
        mtp.mtp_forward(p, jnp.zeros((1, 2, CFG.d_model)), jnp.zeros((1, 2), jnp.int32), plain)
    with pytest.raises(ValueError, match="one multi-token-prediction module is built"):
        dataclasses.replace(CFG, mtp_depth=2)
    with pytest.raises(ValueError, match="one more block of a homogeneous stack"):
        dataclasses.replace(get_preset("xing-mini").model, mtp_depth=1)  # four residual streams


# -- 3. prefill, then rounds through the latent pool ------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_prefill_and_rounds_through_the_pool_match_the_reference(params, seed):
    """The benchmark's own comparison (``harness/mtp_check.py``) in float32: the
    admission prefill with the module, then teacher-forced two-query rounds
    through the pool at a batch wider than the sample, stack and module."""
    sample = [(21, 6), (9, 6), (33, 4)]
    seqs = mtp_check.sample_tokens(seed, CFG.vocab_size, sample)
    pools = tr.make_paged_kv_pool(CFG, 64, 8)
    verify, draft, _ = mtp_check.program_logits(
        params[seed], CFG, pools, paged.BlockAllocator(64), 4, 16, 8, sample, seqs)
    for (p, k), toks, v, d in zip(sample, seqs, verify, draft):
        want, m_want = reference(seed, toks)
        assert v.shape == d.shape == (k, CFG.vocab_size)
        assert rel_err(v, want[p : p + k]) < LOGITS_TOL
        assert rel_err(d, m_want[p : p + k]) < LOGITS_TOL
        # the token behind the prompt is the one the prefill sampled: the stack's argmax
        assert toks[p] == int(np.argmax(want[p - 1]))


def test_a_round_is_verify_then_draft_and_leaves_both_caches_at_the_frontier(params):
    """One row by hand: a rejected draft emits the target's token alone, an
    accepted one two; the next draft is the module's argmax at the last accepted
    position; the module's pages cover what the stack's cover."""
    p, bs = params[SEEDS[0]], 8
    toks = tokens(11, 17)
    want, m_want = reference(SEEDS[0], toks)
    pools = tr.make_paged_kv_pool(CFG, 16, bs)
    ids = [5, 2, 9]
    first, pools = paged.prefill_into_pool_batched(p, CFG, pools, [toks[:16].tolist()], [ids[:2]],
                                                   jax.random.key(0), with_draft=True)
    x16 = int(np.argmax(want[15]))
    assert first.shape == (1, 2) and int(first[0, 0]) == x16
    seq = np.r_[toks[:16], x16]
    want, m_want = reference(SEEDS[0], np.r_[seq, 0])  # row 15 of the module reads token 16
    assert int(first[0, 1]) == int(np.argmax(m_want[15]))
    tables = jnp.zeros((1, 4), jnp.int32).at[0, :3].set(jnp.asarray(ids))
    x17 = int(np.argmax(reference(SEEDS[0], np.r_[seq, 0])[0][16]))
    for draft, n in ((x17, 2), ((x17 + 1) % CFG.vocab_size, 1)):
        emit, n_emit, nxt, counters, out = paged.paged_mtp_round(
            p, jax.tree.map(jnp.copy, pools), jnp.asarray([x16]), jnp.asarray([draft]), tables,
            jnp.asarray([16]), jax.random.key(1), cfg=CFG)
        full = np.r_[seq, x17, 0, 0]
        w, mw = reference(SEEDS[0], full)
        x18 = int(np.argmax(w[17]))
        assert int(n_emit[0]) == n and emit[0, :n].tolist() == [x17, x18][:n]
        # after an accept the module has read (h_16, x17) and (h_17, x18): its row 17 drafts x19
        full[18] = x18
        assert int(nxt[0]) == int(np.argmax(reference(SEEDS[0], full)[1][15 + n]))
        assert counters["expert_tokens"].shape == (CFG.n_layers - CFG.n_dense_layers + 1, CFG.experts_held)
        assert np.array_equal(counters["experts_touched"], (np.asarray(counters["expert_tokens"]) > 0).sum(-1))
        # slot 16 of the module's pages was written, by this round alone
        before = np.asarray(pools["layers"][CFG.n_layers]["latent_pool"][ids[2]])
        after = np.asarray(out["layers"][CFG.n_layers]["latent_pool"][ids[2]])
        assert not before.any() and after[0].any()


def _equations(jaxpr, outer=""):
    """(primitive, scope path, first output's shape) of every equation of a
    program, the bodies of its calls included (a kernel's own body not)."""
    for eqn in jaxpr.eqns:
        path = f"{outer}/{eqn.source_info.name_stack}"  # a call's body names its scopes from the call on
        yield eqn.primitive.name, path, eqn.outvars[0].aval.shape if eqn.outvars else ()
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(sub, path)


def _rounds_through_the_pool(p, prompts, greedy, accept):
    """A prefill with the module, then a round for every row of ``accept`` (a
    row of it says which batch rows' drafts are the target's own next token,
    taken from ``greedy``; the others get another token). Before each round
    its two forwards' logits, on a copy of the pools.
    -> (tokens emitted a row, the logits, the module's next drafts)."""
    bs, rows = 8, len(prompts)
    pools = tr.make_paged_kv_pool(CFG, 32, bs)
    ids = [list(range(1 + 8 * r, 9 + 8 * r)) for r in range(rows)]
    n_pre = [paged.required_blocks(len(pr), bs) for pr in prompts]
    first, pools = paged.prefill_into_pool_batched(
        p, CFG, pools, prompts, [i[:k] for i, k in zip(ids, n_pre)], jax.random.key(0), with_draft=True)
    tables = jnp.asarray(ids, jnp.int32)
    seq = np.asarray([len(pr) for pr in prompts], np.int32)
    out = [[int(t)] for t in first[:, 0]]
    logits, drafts = [], [np.asarray(first[:, 1])]
    for wanted in accept:
        tok = np.asarray([o[-1] for o in out], np.int32)
        nxt = np.asarray([greedy[r][len(out[r])] for r in range(rows)], np.int32) if greedy else drafts[-1]
        d = np.where(wanted, nxt, (nxt + 1) % CFG.vocab_size).astype(np.int32)
        seq_tokens = jnp.stack([jnp.asarray(tok), jnp.asarray(d)], axis=1)
        v, m, _ = paged.paged_mtp_logits(p, jax.tree.map(jnp.copy, pools), seq_tokens, seq_tokens,
                                         tables, jnp.asarray(seq), cfg=CFG)
        logits.append((np.asarray(v), np.asarray(m)))
        emit, n_emit, nxt_draft, _, pools = paged.paged_mtp_round(
            p, pools, jnp.asarray(tok), jnp.asarray(d), tables, jnp.asarray(seq), jax.random.key(1), cfg=CFG)
        for r in range(rows):
            out[r] += emit[r, : int(n_emit[r])].tolist()
        seq = seq + np.asarray(n_emit)
        drafts.append(np.asarray(nxt_draft))
    return out, logits, drafts


def test_rounds_through_the_kernel_are_the_gather_forms(params, request):
    """Both queries of a row through ``ops/pallas_latent.py`` (``decode_form``
    answering as on a TPU, the kernel interpreted): the logits of every round's
    two forwards, the tokens emitted and the module's next drafts are the gather
    form's, whether a draft was accepted or rejected the round before (after a
    rejection slot s + 1 of both caches holds garbage above the frontier: the
    next round's first query must not see it and its second overwrites it)."""
    p = params[SEEDS[0]]
    prompts = [tokens(40 + r, n).tolist() for r, n in enumerate((13, 22, 7))]
    # the target's greedy continuation: a rejected draft emits the target's own token alone
    greedy, _, _ = _rounds_through_the_pool(p, prompts, None, np.zeros((9, 3), bool))
    assert all(len(g) == 10 for g in greedy)
    # row 0 accepts, rejects, accepts, ..; row 1 the other way round; row 2 accepts twice running
    accept = np.asarray([[1, 0, 1], [0, 1, 1], [1, 0, 0], [0, 1, 1]], bool)
    round_args = (p, tr.make_paged_kv_pool(CFG, 32, 8), jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32),
                  jnp.zeros((3, 8), jnp.int32), jnp.zeros((3,), jnp.int32), jax.random.key(1))
    trace = lambda: jax.make_jaxpr(functools.partial(paged.paged_mtp_round, cfg=CFG))(*round_args)
    # ``pool[tables]``, the gathered copy: a gather whose output keeps the tables' (rows, pages) in front
    pool_gathers = lambda eqns: [path for name, path, shape in eqns if name == "gather" and shape[:2] == (3, 8)]
    gathered = list(_equations(trace().jaxpr))
    assert not any(name == "pallas_call" for name, *_ in gathered)
    copies = pool_gathers(gathered)
    assert len(copies) == 2 * (CFG.n_layers + 1)  # both fields, the module's layer too
    assert all("attn.paged_gather" in path for path in copies)
    want = _rounds_through_the_pool(p, prompts, greedy, accept)
    assert [len(o) - 1 for o in want[0]] == (1 + accept).sum(0).tolist()  # an accepted draft commits two
    assert all(o == g[: len(o)] for o, g in zip(want[0], greedy))

    request.getfixturevalue("kernel_forced")
    in_place = list(_equations(trace().jaxpr))
    kernels = [path for name, path, _ in in_place if name == "pallas_call"]
    assert len(kernels) == CFG.n_layers + 1 and all("attn.core" in path for path in kernels)
    assert not pool_gathers(in_place) and not any("attn.paged_gather" in path for _, path, _ in in_place)
    got = _rounds_through_the_pool(p, prompts, greedy, accept)
    assert got[0] == want[0]
    for (v, m), (v_want, m_want) in zip(got[1], want[1]):
        assert rel_err(v, v_want) < LOGITS_TOL and rel_err(m, m_want) < LOGITS_TOL
    assert all(np.array_equal(a, b) for a, b in zip(got[2], want[2]))


@pytest.mark.parametrize("t,backend,form", [
    (1, "tpu", "latent_kernel"), (2, "tpu", "latent_kernel"), (mla.KERNEL_QUERIES, "tpu", "latent_kernel"),
    (mla.KERNEL_QUERIES + 1, "tpu", "gather"), (512, "tpu", "gather"),
    (1, "cpu", "gather"), (2, "cpu", "gather"), (2, "gpu", "gather"), (2, None, "gather")])
def test_the_form_is_read_from_the_query_count_and_the_backend(t, backend, form):
    """A few queries a row (the decode step's one, a round's ``spec_k + 1``)
    take the in-place kernel where Mosaic compiles; a chunk's many, and every
    other backend (``None``: the one the tests run on), the gather form."""
    assert mla.decode_form(t, backend) == form


# -- 4. the engine: greedy speculative output is plain greedy output --------------------


PROMPT_LENS, BUDGETS = (9, 21, 5, 14, 30, 12), (12, 7, 16, 1, 9, 13)


def _requests():
    return [tokens(20 + i, n).tolist() for i, n in enumerate(PROMPT_LENS)]


# how the engine is driven: the pipelined scheduler two rounds deep (the default) and one, and the synchronous one
SCHEDULERS = {"depth2": dict(pipeline_depth=2), "depth1": dict(pipeline_depth=1), "synchronous": dict(pipeline=False)}


def _serve(p, pipeline=True, n_blocks=40, **kw):
    eng = ServingEngine(p, CFG, max_batch=3, n_blocks=n_blocks, block_size=8, max_seq=96, **kw)
    rids = [eng.submit(pr, n) for pr, n in zip(_requests(), BUDGETS)]
    out = eng.run(pipeline=pipeline)
    return [out[r] for r in rids], eng


@pytest.fixture(scope="module")
def plain(params):
    """What the engine emits without speculation: the target's greedy tokens."""
    out, eng = _serve(params[SEEDS[0]])
    assert "spec_rounds" not in eng.stats and "draft" not in eng.pool_info()
    assert len(eng.pools["layers"]) == CFG.n_layers  # nobody reads the module: no pages for it
    return out


@pytest.fixture
def drafts_from(params, plain, monkeypatch):
    """Patch the module's output so that its draft is looked up in a table
    (position, token that followed) -> proposal, built from the plain engine's
    output: ``oracle`` proposes the target's own next token everywhere,
    ``mix`` at even positions only. The module still runs and writes its pages;
    only the hidden state its head reads is exchanged, for the head's own
    column of the proposal (whose logit then wins)."""
    head = np.asarray(params[SEEDS[0]]["lm_head"]["kernel"], np.float64)
    assert np.array_equal(np.argmax(head.T @ head, axis=0), np.arange(CFG.vocab_size))

    def install(kind):
        table = np.zeros((128, CFG.vocab_size), np.int32)
        seen = set()
        for prompt, out in zip(_requests(), plain):
            seq = prompt + out
            for pos in range(len(prompt) - 1, len(seq) - 2):
                key = (pos, seq[pos + 1])
                assert key not in seen or table[key] == seq[pos + 2], "two requests share a (position, token)"
                seen.add(key)
                wrong = kind == "mix" and pos % 2
                table[key] = (seq[pos + 2] + 1) % CFG.vocab_size if wrong else seq[pos + 2]
        table, columns = jnp.asarray(table), jnp.asarray(head.T, jnp.float32)
        real = mtp.mtp_forward

        def looked_up(params, hidden, next_tokens, cfg, **kw):
            out, cache, aux = real(params, hidden, next_tokens, cfg, **kw)
            t = next_tokens.shape[1]
            paged_info = kw.get("paged")
            pos = (paged_info.seq_lens[:, None] if paged_info is not None else 0) + jnp.arange(t)[None, :]
            return 100.0 * columns[table[jnp.clip(pos, 0, 127), next_tokens]], cache, aux

        monkeypatch.setattr(mtp, "mtp_forward", looked_up)
        jax.clear_caches()

    yield install
    jax.clear_caches()


@pytest.mark.parametrize("scheduler", list(SCHEDULERS))
@pytest.mark.parametrize("kind", ["seeded", "oracle", "mix"])
def test_speculative_greedy_output_is_the_plain_engines(params, plain, drafts_from, kind, scheduler):
    if kind != "seeded":
        drafts_from(kind)
    got, eng = _serve(params[SEEDS[0]], spec_k=1, **SCHEDULERS[scheduler])
    assert got == plain
    st = eng.stats
    decoded = sum(BUDGETS) - len(BUDGETS)  # every request's first token comes from its prefill
    assert st["tokens"] == sum(BUDGETS) and st["spec_rounds"] > 0
    if kind == "oracle":
        # every draft is the target's token: two tokens a row a round, but for
        # a budget that ends in the middle of one
        assert st["spec_accepted"] == st["spec_proposed"]
        assert decoded <= 2 * st["spec_proposed"] <= decoded + sum(1 for b in BUDGETS if b > 1)
    elif kind == "mix":
        assert 0 < st["spec_accepted"] < st["spec_proposed"]
    else:
        assert st["spec_accepted"] < st["spec_proposed"] // 2  # a seeded module agrees by chance
    # a row-round commits its accepted draft and the target's token, but for
    # the second token of a round that a budget's end cuts off
    assert 0 <= st["spec_proposed"] + st["spec_accepted"] - decoded <= len(BUDGETS)
    info = eng.pool_info()
    assert info["draft"] == "mtp" and info["decode_attention"] == "gather" and "draft_pool_bytes" not in info
    assert info["bytes_per_token"] == (CFG.n_layers + 1) * CFG.latent_dim * 4  # float32 here, the module's layer too
    assert not eng.alloc._live and not eng.drafts.any()


def test_a_preempted_request_gets_its_draft_again_with_its_prompt(params, plain, drafts_from):
    """A pool too small for three rows at once: the youngest is preempted,
    requeued with what it generated, prefilled again (the module too), and the
    output is the plain engine's all the same; with the oracle every draft of the
    second incarnation is accepted as well."""
    drafts_from("oracle")
    got, eng = _serve(params[SEEDS[0]], n_blocks=8, spec_k=1)
    assert got == plain and eng.stats["preemptions"] > 0
    assert eng.stats["spec_accepted"] == eng.stats["spec_proposed"]
    got, eng = _serve(params[SEEDS[0]], n_blocks=8, spec_k=1, pipeline=False)
    assert got == plain and eng.stats["preemptions"] > 0


def test_the_commit_span_carries_the_rounds_counts(params, plain, drafts_from, monkeypatch):
    drafts_from("mix")
    rec = spans.SpanRecorder()
    monkeypatch.setattr(spans, "_default", rec)
    got, eng = _serve(params[SEEDS[0]], spec_k=1)
    events, _ = rec.drain()
    assert got == plain
    commits = [meta for name, *_, meta in events if name == "serving.commit"]
    assert commits and all({"spec_proposed", "spec_accepted", "spec_emitted", "moe_routed"} <= set(m) for m in commits)
    st = eng.stats
    assert sum(m["spec_proposed"] for m in commits) == st["spec_proposed"]
    assert sum(m["spec_accepted"] for m in commits) == st["spec_accepted"]
    assert sum(m["spec_emitted"] for m in commits) == st["tokens"] - len(BUDGETS)
    # a round is one step of two tokens a row, the module's block one more expert layer
    layers = CFG.n_layers - CFG.n_dense_layers + 1
    assert all(m["moe_steps"] == 1 and m["moe_layers"] == layers for m in commits)
    assert all(m["moe_routed"] == 2 * 3 * CFG.experts_per_token * layers for m in commits)
    assert 0 < sum(m["moe_routed_here"] for m in commits) < sum(m["moe_routed"] for m in commits)
    dispatches = [meta for name, *_, meta in events if name == "serving.dispatch_window"]
    assert dispatches and all(m["kind"] == "spec" and m["steps"] == 2 for m in dispatches)


def test_a_self_drafting_engine_takes_the_kernel_and_says_so(params, plain, kernel_forced, monkeypatch):
    """The engine's rounds with ``decode_form`` answering as on a TPU: the
    plain engine's tokens, ``pool_info()`` and every ``serving.spec_round``
    span name the form."""
    rec = spans.SpanRecorder()
    monkeypatch.setattr(spans, "_default", rec)
    got, eng = _serve(params[SEEDS[0]], spec_k=1)
    events, _ = rec.drain()
    assert got == plain and eng.stats["spec_rounds"] > 0
    assert eng.pool_info()["decode_attention"] == "latent_kernel"
    rounds = [meta for name, *_, meta in events if name == "serving.spec_round"]
    assert len(rounds) == eng.stats["spec_rounds"]
    assert all(m == {"k": 1, "draft": "mtp", "attention": "latent_kernel"} for m in rounds)


def test_generate_and_the_training_loss_run_the_stack_and_leave_the_module_alone(params, plain):
    """``generate.py`` decodes the stack alone over a cache that has the module's
    layer and never writes it: its greedy tokens are the engine's. ``loss_fn``
    is the main next-token loss: finite, and no gradient reaches the module."""
    from pretraining_llm_tpu.generation.generate import generate

    p = params[SEEDS[0]]
    for prompt, want in list(zip(_requests(), plain))[:2]:
        got = generate(p, CFG, jnp.asarray(prompt, jnp.int32)[None], len(want), jax.random.key(0), temperature=0.0)
        assert np.asarray(got)[0].tolist() == want
    batch = jnp.asarray(tokens(50, 2 * 33).reshape(2, 33))
    loss = lambda q: tr.loss_fn(q, batch[:, :-1], batch[:, 1:], CFG)
    value, grads = jax.value_and_grad(loss)(p)
    assert np.isfinite(float(value)) and abs(float(value) - np.log(CFG.vocab_size)) < 1.0
    assert all(not np.asarray(g).any() for g in jax.tree.leaves(grads["mtp"]))
    assert all(np.asarray(g).any() for g in jax.tree.leaves(grads["blocks"]["attn"]))
    assert float(value) == float(loss({k: v for k, v in p.items() if k != "mtp"}))


def test_sampled_rounds_emit_valid_tokens_and_count(params):
    """Temperature sampling: the module's proposal is a point mass, accepted
    with the target's probability of it; tokens stay in the vocabulary and the
    budgets are met."""
    eng = ServingEngine(params[SEEDS[0]], CFG, max_batch=2, n_blocks=24, block_size=8, max_seq=64,
                        spec_k=1, temperature=0.8, seed=3)
    rids = [eng.submit(tokens(40 + i, 7).tolist(), 9) for i in range(3)]
    out = eng.run()
    assert all(len(out[r]) == 9 and all(0 <= t < CFG.vocab_size for t in out[r]) for r in rids)
    assert eng.stats["spec_proposed"] > 0


# -- 5. the two chips' shares of an expert layer add up to the uncut layer ---------------


def test_two_shares_of_eight_add_up_to_the_uncut_reference_layer():
    """The cut holds experts 0-7 of 16 under a router that scores all 16. The
    program's layer on this chip's share plus the same layer on the other chip's
    (experts 8-15), the shared expert counted once, is the reference's layer
    that holds all 16."""
    uncut = dict(ARCH, n_routed_experts=16)
    m = family.dims(uncut)
    w = family.layer(m, jax.random.key(5), jnp.float32)
    h = jnp.asarray(np.random.default_rng(4).normal(size=(24, CFG.d_model)), jnp.float32)
    want = np.asarray(ref.experts(h, w, uncut, None))
    dense = lambda shared, x: tr._dense_mlp(shared, x, CFG)
    share = lambda sl: {k: (v[sl] if k.startswith("e_") else v) for k, v in w.items()}
    mine = family.program_layer(m, share(slice(0, 8)))["mlp"]
    first, counts = moe.moe_mlp_dropless(mine, h[None], CFG, dense)
    # the other chip: its experts at the front, the router's columns permuted alike
    perm = np.r_[8:16, 0:8]
    theirs = family.program_layer(m, dict(share(slice(8, 16)), router=w["router"][:, perm], b_corr=w["b_corr"][perm]))["mlp"]
    last, counts_b = moe.moe_mlp_dropless(theirs, h[None], CFG, dense)
    assert int(counts.sum()) + int(counts_b.sum()) == 24 * CFG.experts_per_token
    shared = dense(mine["shared"], h[None])
    np.testing.assert_allclose(np.asarray(first + last - shared)[0], want, rtol=0, atol=5e-6)
    # and the reference's own share is the program's
    np.testing.assert_allclose(np.asarray(first)[0], np.asarray(ref.experts(h, share(slice(0, 8)), ARCH, None)),
                               rtol=0, atol=5e-6)
    assert rel_err(np.asarray(first)[0], want) > 0.1  # a share alone is not the layer


# -- 6. what the engine still refuses, by name -------------------------------------------


def test_refusals_name_what_is_not_built(params):
    p = params[SEEDS[0]]
    with pytest.raises(ValueError, match="exceeds the model's multi-token-prediction depth"):
        ServingEngine(p, CFG, spec_k=2)
    plain_cfg = dataclasses.replace(CFG, mtp_depth=0)
    with pytest.raises(ValueError, match="needs a model with a multi-token-prediction module"):
        ServingEngine(p, plain_cfg, spec_k=1)
    draft_cfg = get_preset("tiny").model
    draft = jax.eval_shape(lambda k: tr.init_params(draft_cfg, k), jax.random.key(0))
    with pytest.raises(ValueError, match="a separate draft model's pool"):
        ServingEngine(p, CFG, spec_k=1, draft_params=draft, draft_cfg=draft_cfg)
    with pytest.raises(ValueError, match="without prefill_chunk_tokens"):
        ServingEngine(p, CFG, spec_k=1, prefill_chunk_tokens=8)
    with pytest.raises(ValueError, match="temperature-only"):
        ServingEngine(p, CFG, spec_k=1, top_k=4)
    ling = get_preset("ling-mini").model
    lp = jax.eval_shape(lambda k: tr.init_params(ling, k), jax.random.key(0))
    with pytest.raises(ValueError, match="speculative decoding needs a state rollback"):
        ServingEngine(lp, ling, spec_k=1)
    with pytest.raises(ValueError, match="prefix_cache"):
        ServingEngine(p, CFG, spec_k=1, prefix_cache=True)
    # a per-head model with a module drafts for itself too, without the suffix lane
    gqa = dataclasses.replace(get_preset("tiny").model, pos_embed="rope", mtp_depth=1)
    gp = jax.eval_shape(lambda k: tr.init_params(gqa, k), jax.random.key(0))
    with pytest.raises(ValueError, match="do not prefill the module"):
        ServingEngine(gp, gqa, spec_k=1, prefix_cache=True)


def test_the_preset_serves_with_its_own_draft():
    cfg = dataclasses.replace(get_preset("joyai-mini").model, compute_dtype="float32")
    assert cfg.mtp_depth == 1 and cfg.n_experts_held == 8 and cfg.n_experts == 16
    p = tr.init_params(cfg, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(p)) == cfg.num_params()
    prompts = [tokens(60 + i, n).tolist() for i, n in enumerate((6, 11))]

    def serve(**kw):
        eng = ServingEngine(p, cfg, max_batch=2, n_blocks=16, block_size=8, max_seq=48, **kw)
        rids = [eng.submit(pr, 8) for pr in prompts]
        out = eng.run()
        return [out[r] for r in rids]

    assert serve(spec_k=1) == serve()
