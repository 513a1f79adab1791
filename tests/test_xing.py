"""Xing4.0-29B-A4B's mechanisms at toy widths, against the plain reference
(``benchmark/references/xing.py``: float32 at the highest matmul precision,
sharing no code with the program): latent attention with its page pool and
absorbed decode, dropless sigmoid-routed experts, four-stream mHC residuals,
YaRN — and the dense configurations' traced programs, which must stay the
parent's op for op.

Every tolerance has its reason and a control that fails it beside it: the
program in a lower precision than stated (int8 weights) and the program with
one term dropped (the selection bias, YaRN's m squared, H_post's factor 2).
Weights are seeded with every scale, bias, alpha and ``b_corr`` non-trivial
(``harness/families/xing.py``), so a dropped term shows.
"""

import dataclasses
import functools
import hashlib
import json
import os
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)  # behind the repo root: `tests` must stay this directory's package

from harness import opcount, program, serving_check as sc, weights  # noqa: E402
from harness.families import xing as family  # noqa: E402
from references import xing as ref  # noqa: E402
from references.common import int8_fake_quant  # noqa: E402

from pretraining_llm_tpu.config import ModelConfig, get_preset  # noqa: E402
from pretraining_llm_tpu.generation import paged  # noqa: E402
from pretraining_llm_tpu.generation.serving import ServingEngine  # noqa: E402
from pretraining_llm_tpu.models import hyper, layers, mla, moe, transformer as tr  # noqa: E402

with open(os.path.join(BENCH, "tests", "toy", "xing.json")) as f:
    TOY = dict(json.load(f), name="xing-toy")
# float32 throughout: the program's arithmetic then differs from the reference's
# by the order of its sums alone, and the tolerances below can be tight.
ARCH = dict(TOY, serving_dtype="float32",
            program_model={"attention_impl": "naive", "param_dtype": "float32", "compute_dtype": "float32"})
CFG = program.model_config(ARCH, 128)
SEEDS = (3, 2 ** 31 + 5)

# Relative error of logits, ||program - reference|| / ||reference||. The sound
# float32 program reads 1.6e-7 to 1.8e-7 here, forward and paged alike (sums in
# another order); the least of the controls below reads 1.9e-3 (int8 weights;
# YaRN's m squared 9.6e-3, the reference in int8 1.1e-2, b_corr 4.7e-2, H_post's
# factor 2 0.12). 2e-5 lies 100 x over the one and 100 x under the other.
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


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_matches_the_reference(params, seed):
    toks = tokens(seed, 48)
    logits, _ = tr.forward(params[seed], toks[None], CFG)
    assert rel_err(logits[0], reference_logits(seed, toks)) < LOGITS_TOL


def test_parameter_count_is_the_tree_and_the_familys(params):
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params[SEEDS[0]]))
    assert n == CFG.num_params() == opcount.num_params(ARCH)
    m = opcount.dims(ARCH)
    inactive = (m["experts"] - m["top_k"]) * family.expert_params(m) * m["layers"]
    assert CFG.num_active_params() == n - inactive


# -- 2. prefill into the latent pool, then decode through the page tables -------------


def _traces_the_kernel(fn, *args):
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def _teacher_forced(p, seqs, prompt_lens, steps, readmit_row=None, readmit_at=None):
    """Logits after each row's prompt and after each forced token, through the
    engine's prefill and decode lanes on hand-built tables. ``readmit_row`` is
    preempted before step ``readmit_at``: its pages are freed and what it held
    (prompt and forced tokens so far) is prefilled anew into other pages."""
    bs, max_blocks, rows = 8, 16, len(seqs)
    pools = tr.make_paged_kv_pool(CFG, 64, bs)
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
    # batched prefill: rows of different lengths in one padded bucket
    n_pre = [paged.required_blocks(n, bs) for n in prompt_lens]
    _, pools = paged.prefill_into_pool_batched(
        p, CFG, pools, prompts, [i[:k] for i, k in zip(ids, n_pre)], jax.random.key(0))
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
                p, CFG, pools, held, ids[r][: paged.required_blocks(len(held), bs)])
        tok = np.asarray([s[n + j] for s, n in zip(seqs, prompt_lens)], np.int32)
        logits, pools = paged.paged_decode_logits(
            p, pools, jnp.asarray(tok), jnp.asarray(tables), jnp.asarray(seq_lens), cfg=CFG)
        for r in range(rows):
            out[r].append(np.asarray(logits[r], np.float32))
        seq_lens += 1
    return [np.stack(o) for o in out]


@pytest.mark.parametrize("readmit,form", [(False, "gather"), (True, "gather"), (True, "latent_kernel")],
                         ids=["steady", "preempted-and-readmitted", "readmitted-through-the-kernel"])
def test_paged_decode_matches_the_reference(params, readmit, form, request):
    if form == "latent_kernel":
        request.getfixturevalue("kernel_forced")
    seed = SEEDS[0]
    pools = tr.make_paged_kv_pool(CFG, 8, 8)
    step = (params[seed], pools, jnp.zeros((2,), jnp.int32), jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32))
    assert _traces_the_kernel(functools.partial(paged.paged_decode_logits, cfg=CFG), *step) == (form == "latent_kernel")
    prompt_lens, steps = (21, 9, 33), 6
    seqs = [tokens(seed + r, n + steps) for r, n in enumerate(prompt_lens)]
    got = _teacher_forced(params[seed], seqs, prompt_lens, steps,
                          readmit_row=1 if readmit else None, readmit_at=3 if readmit else None)
    for toks, n, rows in zip(seqs, prompt_lens, got):
        want = reference_logits(seed, toks)[n : n + steps]  # row t scores token t + 1
        assert rel_err(rows, want) < LOGITS_TOL


@pytest.mark.parametrize("t", [3, mla.KERNEL_QUERIES + 1], ids=["a-few-queries", "a-chunk"])
def test_several_queries_a_row_take_the_form_their_count_allows(params, kernel_forced, t):
    """t > 1 through the pool: a few queries a row (a speculative round's)
    through the kernel as one is, more (the chunk lane) in the gather form
    whatever the backend, and either way the same numbers as t = 1 steps."""
    p, bs = params[SEEDS[0]], 8
    toks = tokens(5, 16 + t)
    pools = tr.make_paged_kv_pool(CFG, 16, bs)
    ids = [3, 9, 4]
    _, pools = paged.prefill_into_pool(p, CFG, pools, toks[:16].tolist(), ids[:2])
    tables = jnp.zeros((1, 4), jnp.int32).at[0, :3].set(jnp.asarray(ids))

    def chunk(pools):  # t queries at slots 16.. in one call
        return tr.forward(p, jnp.asarray(toks[None, 16:]), CFG, kv_cache=pools,
                          paged=tr.PagedInfo(tables, jnp.asarray([16], jnp.int32)))[0]

    assert _traces_the_kernel(chunk, pools) == (t <= mla.KERNEL_QUERIES)
    together = np.asarray(chunk(pools)[0], np.float32)
    one_by_one = []
    for i in range(t):
        logits, pools = paged.paged_decode_logits(
            p, pools, jnp.asarray(toks[16 + i : 17 + i]), tables, jnp.asarray([16 + i], jnp.int32), cfg=CFG)
        one_by_one.append(np.asarray(logits[0]))
    assert rel_err(together, np.stack(one_by_one)) < LOGITS_TOL
    assert rel_err(together, reference_logits(SEEDS[0], toks)[16:]) < LOGITS_TOL


# -- 3. the absorbed form is the expanded form ----------------------------------------


def test_absorbed_decode_equals_the_expanded_form(params):
    blk = jax.tree.map(lambda a: a[0], params[SEEDS[0]]["blocks"])
    rng = np.random.default_rng(0)
    t, cdt = 24, jnp.float32
    h = jnp.asarray(rng.normal(size=(2, t, CFG.d_model)), cdt)
    q, c_kv, k_r = mla._project(blk["attn"], h, CFG, cdt)
    cos, sin = layers.rope_table(64, CFG.qk_rope_head_dim, CFG.rope_theta)
    pos = jnp.arange(t)
    q = jnp.concatenate([q[..., :16], layers.apply_rope(q[..., 16:], cos, sin, pos)], axis=-1)
    k_rope = layers.apply_rope(k_r[:, :, None, :], cos, sin, pos)[:, :, 0]
    expanded = mla._expanded(blk["attn"], q, c_kv, k_rope, CFG, cdt, "naive")
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((t, t), bool)), (2, t, t))
    absorbed = mla._absorbed(blk["attn"], q, c_kv, k_rope, causal, CFG, cdt)
    # one function, two orders of the same float32 products: 3e-7 measured
    assert expanded.shape == absorbed.shape == (2, t, CFG.n_heads, CFG.v_head_dim)
    assert rel_err(absorbed, np.asarray(expanded)) < 1e-5


# -- 4. dropless routing --------------------------------------------------------------


@pytest.fixture(scope="module")
def moe_layer(params):
    blk = jax.tree.map(lambda a: a[0], params[SEEDS[0]]["blocks"])
    return blk["mlp"], lambda shared, h: tr._dense_mlp(shared, h, CFG)


def _hidden(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(1, n, CFG.d_model)), jnp.float32)


@pytest.mark.parametrize("company", ["alone", "in-a-batch", "in-a-padded-bucket"])
def test_a_tokens_expert_output_is_its_own(moe_layer, company):
    mlp, dense = moe_layer
    h = _hidden(5)
    alone, _ = moe.moe_mlp_dropless(mlp, h, CFG, dense)
    if company == "alone":
        got = jnp.concatenate([moe.moe_mlp_dropless(mlp, h[:, i : i + 1], CFG, dense)[0] for i in range(5)], 1)
    elif company == "in-a-batch":
        got = moe.moe_mlp_dropless(mlp, jnp.concatenate([_hidden(5, 1), h, _hidden(5, 2)], 0), CFG, dense)[0][1:2]
    else:
        got = moe.moe_mlp_dropless(mlp, jnp.concatenate([h, jnp.zeros((1, 59, CFG.d_model))], 1), CFG, dense)[0][:, :5]
    # the same products per token, grouped otherwise: equal to float32 rounding
    np.testing.assert_allclose(np.asarray(got), np.asarray(alone), rtol=0, atol=2e-6)


def test_selection_takes_the_bias_and_the_gates_do_not(moe_layer):
    mlp, _ = moe_layer
    x = _hidden(40)[0]
    idx, gates = moe.route_dropless(mlp, x, CFG)
    s = jax.nn.sigmoid(x @ mlp["router"])
    want = jax.lax.top_k(s + mlp["router_bias"], CFG.experts_per_token)[1]
    assert np.array_equal(np.sort(idx, -1), np.sort(want, -1))
    plain = jax.lax.top_k(s, CFG.experts_per_token)[1]
    assert not np.array_equal(np.sort(idx, -1), np.sort(plain, -1))  # the bias decides some token
    picked = jnp.take_along_axis(s, idx, -1)
    np.testing.assert_allclose(gates, picked / picked.sum(-1, keepdims=True) * CFG.moe_routed_scale, rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), CFG.moe_routed_scale, rtol=1e-6)  # renormalised, then scaled
    # a bias that forces expert 0 on every token changes who is chosen, never what a score weighs
    forced = dict(mlp, router_bias=mlp["router_bias"].at[0].set(10.0))
    idx0, gates0 = moe.route_dropless(forced, x, CFG)
    assert np.all(np.any(idx0 == 0, axis=-1)) and float(gates0.max()) <= CFG.moe_routed_scale


@pytest.mark.parametrize("drop", ["routed_scaling_factor", "renormalisation"])
def test_scale_and_renormalisation_are_present(moe_layer, drop):
    mlp, dense = moe_layer
    h = _hidden(12)
    full, _ = moe.moe_mlp_dropless(mlp, h, CFG, dense)
    cfg = dataclasses.replace(CFG, moe_routed_scale=1.0) if drop == "routed_scaling_factor" else \
        dataclasses.replace(CFG, moe_norm_topk=False)
    less, _ = moe.moe_mlp_dropless(mlp, h, cfg, dense)
    # the routed part halves without the factor 2; two sigmoid scores near 0.5 sum near 1, so
    # the renormalisation moves the toy layer by 7% only
    assert rel_err(less, np.asarray(full)) > 0.03


def test_a_layer_holding_some_experts_gives_their_share(params, moe_layer):
    """Expert parallelism's contract without the exchange: a layer that holds
    the first 4 of the 8 experts routes over all 8 and adds its own experts'
    parts; the rest of the sum is what the other 4 would add."""
    mlp, dense = moe_layer
    h = _hidden(16)
    full, counts = moe.moe_mlp_dropless(mlp, h, CFG, dense)
    part = lambda sl: dict({k: v for k, v in mlp.items() if k != "shared"},
                           experts={k: v[sl] for k, v in mlp["experts"].items()})
    first, c4 = moe.moe_mlp_dropless(part(slice(0, 4)), h, CFG, dense)
    assert c4.shape == (4,) and np.array_equal(c4, counts[:4]) and int(counts.sum()) == 16 * CFG.experts_per_token
    # the last four, moved to the front: route with the router's columns permuted alike
    perm = np.r_[4:8, 0:4]
    moved = dict(part(slice(4, 8)), router=mlp["router"][:, perm], router_bias=mlp["router_bias"][perm])
    last, _ = moe.moe_mlp_dropless(moved, h, CFG, dense)
    shared = dense(mlp["shared"], h)
    np.testing.assert_allclose(np.asarray(first + last + shared), np.asarray(full), rtol=0, atol=3e-6)


# -- 5. the residual streams ----------------------------------------------------------


@pytest.mark.parametrize("iters,ok", [(20, True), (1, False)])
def test_sinkhorn_makes_the_mixing_doubly_stochastic(params, iters, ok):
    blk = jax.tree.map(lambda a: a[0], params[SEEDS[0]]["blocks"])
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 7, CFG.hc_mult, CFG.d_model)), jnp.float32)
    res = hyper.coefficients(blk["hc_attn"], x, dataclasses.replace(CFG, hc_sinkhorn_iters=iters)).res
    off = max(float(jnp.abs(res.sum(-1) - 1).max()), float(jnp.abs(res.sum(-2) - 1).max()))
    assert (off < 1e-3) == ok and float(res.min()) > 0


def test_streams_copy_in_and_sum_out():
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 3, CFG.d_model)), jnp.float32)
    streams = hyper.copy_in(x, CFG)
    assert streams.shape == (2, 3, CFG.hc_mult, CFG.d_model)
    assert all(np.array_equal(streams[:, :, i], x) for i in range(CFG.hc_mult))
    np.testing.assert_allclose(hyper.sum_out(streams), CFG.hc_mult * x, rtol=1e-6)


# -- 6. YaRN --------------------------------------------------------------------------


def test_yarn_frequencies_and_softmax_scale_match_the_closed_form():
    rs = TOY["rope_scaling"]
    got = layers.yarn_inv_freq(CFG.qk_rope_head_dim, CFG.rope_theta, rs["factor"],
                               rs["original_max_position_embeddings"], rs["beta_fast"], rs["beta_slow"])
    np.testing.assert_allclose(got, ref.yarn_inv_freq(TOY), rtol=1e-6)
    plain = 1.0 / CFG.rope_theta ** (np.arange(4) / 4)
    assert np.isclose(got[0], plain[0]) and np.isclose(got[-1], plain[-1] / rs["factor"])  # fast kept, slow slowed
    m = 0.1 * np.log(64) + 1
    assert np.isclose(m, 1.4159, atol=1e-4)
    assert np.isclose(CFG.softmax_scale, ref.softmax_scale(TOY)) and np.isclose(CFG.softmax_scale, 24 ** -0.5 * m * m)
    # the published sizes: 192-wide scores, factor 64 over 4,096
    full = ModelConfig(d_model=64, n_heads=2, d_head=192, pos_embed="rope", kv_lora_rank=512, q_lora_rank=8,
                       qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, rope_scaling="yarn",
                       rope_factor=64.0, rope_original_context=4096, rope_mscale=1.0, rope_mscale_all_dim=1.0)
    assert np.isclose(full.softmax_scale, 192 ** -0.5 * m * m) and full.latent_dim == 576


# -- 7. the engine --------------------------------------------------------------------


ROOMY = ((24, 16), (9, 12), (33, 8), (17, 16), (12, 10))
GROWING = ((9, 30), (12, 30), (7, 30), (10, 30), (5, 20))  # four rows outgrow eleven pages


@pytest.mark.parametrize("pool_blocks,load,form", [(64, ROOMY, "gather"), (12, GROWING, "gather"),
                                                   (12, GROWING, "latent_kernel")],
                         ids=["roomy", "preempting", "preempting-through-the-kernel"])
def test_engine_serves_the_configuration(params, pool_blocks, load, form, request):
    if form == "latent_kernel":
        request.getfixturevalue("kernel_forced")
    seed = SEEDS[1]
    eng = ServingEngine(params[seed], CFG, max_batch=4, n_blocks=pool_blocks, block_size=8, max_seq=64)
    rng = np.random.default_rng(seed % 2 ** 31)
    prompts = {}
    for n_prompt, n_out in load:
        prompt = rng.integers(0, CFG.vocab_size, n_prompt).tolist()
        prompts[eng.submit(prompt, n_out)] = prompt
    done = eng.run()  # the pipelined scheduler: pipeline_tick until nothing is left
    assert (eng.stats["preemptions"] > 0) == (load is GROWING)
    emitted = [(prompts[rid], list(done[rid])) for rid in sorted(prompts)]
    assert [len(t) for _, t in emitted] == [n for _, n in load]
    own, off = sc.token_regrets(ARCH, seed, emitted, 64)
    # float32 engine, greedy: the reference's own argmax or a tie (0 measured); one position
    # off reads 2-4 standard deviations of a row of logits
    assert own.max() < 0.05 < 0.5 < off.max()
    info = eng.pool_info()
    assert info["bytes_per_token"] == CFG.latent_dim * 4 * CFG.n_layers == family.latent_bytes_per_token(ARCH, 4)
    # the form of the engine's decode windows, as its capacity snapshot carries it
    assert info["decode_attention"] == form
    window = (eng.params, eng.pools, jnp.zeros((4,), jnp.int32), jnp.asarray(eng.tables),
              jnp.zeros((4,), jnp.int32), jax.random.key(0))
    assert _traces_the_kernel(functools.partial(paged.paged_decode_steps, cfg=CFG, n_steps=1), *window) == (
        form == "latent_kernel")
    st = eng.stats
    assert st["moe_steps"] > 0 and st["moe_expert_tokens"].shape == (CFG.n_layers - 1, CFG.n_experts)
    assert st["moe_expert_tokens"].sum() == st["moe_steps"] * 4 * CFG.experts_per_token * (CFG.n_layers - 1)
    assert 0 < st["moe_experts_touched"].max() <= st["moe_steps"] * CFG.n_experts


@pytest.mark.parametrize("kw", [dict(quantize="int8"), dict(prefix_cache=True), dict(kv_checksum=True)],
                         ids=lambda kw: next(iter(kw)))
def test_engine_refuses_what_the_latent_pool_lacks(params, kw):
    with pytest.raises(ValueError, match="latent"):
        ServingEngine(params[SEEDS[0]], CFG, max_batch=2, n_blocks=8, block_size=8, max_seq=32, **kw)


def test_a_per_head_engine_reports_the_form_its_input_allows():
    cfg = ModelConfig(vocab_size=64, context_length=32, d_model=16, n_heads=2, n_layers=1)
    eng = ServingEngine(tr.init_params(cfg, jax.random.key(0)), cfg, max_batch=2, n_blocks=8, block_size=8)
    assert eng.pool_info()["decode_attention"] == "gather"


def test_capacity_routed_experts_are_still_refused():
    cfg = ModelConfig(vocab_size=64, context_length=32, d_model=16, n_heads=2, n_layers=1, n_experts=4)
    with pytest.raises(ValueError, match="capacity"):
        ServingEngine(tr.init_params(cfg, jax.random.key(0)), cfg, max_batch=2, n_blocks=8, block_size=8)


# -- 8. controls: what the tolerance must catch ---------------------------------------


def _int8_weights(p):
    """Every matmul weight of the blocks through symmetric per-channel int8 and back."""
    def q(path, a):
        name = jax.tree_util.keystr(path)
        if a.ndim < 3 or "norm" in name or "hc_" in name or "router" in name:
            return a
        return int8_fake_quant(jnp.swapaxes(a, -1, -2)).swapaxes(-1, -2).astype(a.dtype)
    out = dict(p)
    for group in ("blocks", "dense_blocks"):
        out[group] = jax.tree_util.tree_map_with_path(q, p[group])
    return out


@pytest.mark.parametrize("control", ["int8-weights", "b_corr", "m-squared", "h_post-factor-2"])
def test_controls_fail_the_tolerance(params, monkeypatch, control):
    seed = SEEDS[0]
    toks = tokens(seed, 48)
    p, cfg = params[seed], CFG
    if control == "int8-weights":
        p = _int8_weights(p)
    elif control == "b_corr":
        blocks = dict(p["blocks"], mlp=dict(p["blocks"]["mlp"], router_bias=jnp.zeros_like(p["blocks"]["mlp"]["router_bias"])))
        p = dict(p, blocks=blocks)
    elif control == "m-squared":
        cfg = dataclasses.replace(CFG, rope_mscale=0.0, rope_mscale_all_dim=0.0)
        assert np.isclose(cfg.softmax_scale, CFG.head_dim ** -0.5)
    else:
        whole = hyper.coefficients
        monkeypatch.setattr(hyper, "coefficients", lambda *a: whole(*a)._replace(post=whole(*a).post / 2))
    logits, _ = tr.forward(p, toks[None], cfg)
    # measured: int8 weights 1.9e-3, b_corr 4.7e-2, m squared 9.6e-3, H_post's 2: 0.12
    assert rel_err(logits[0], reference_logits(seed, toks)) > 10 * LOGITS_TOL


def test_reference_in_int8_fails_too():
    seed = SEEDS[0]
    toks = tokens(seed, 48)
    assert rel_err(reference_logits(seed, toks, quant=int8_fake_quant), reference_logits(seed, toks)) > 10 * LOGITS_TOL


# -- 9. the dense configurations trace as the parent's ---------------------------------

MISTRAL = dict(vocab_size=256, context_length=128, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
               mlp_ratio=3.5, activation="swiglu", norm="rmsnorm", pos_embed="rope", rope_theta=10000.0,
               tie_embeddings=False, lm_head_bias=False, qkv_bias=False, mlp_bias=False, norm_eps=1e-5,
               sliding_window=16, attention_impl="flash", param_dtype="bfloat16")
GPT2 = dict(vocab_size=256, context_length=64, n_layers=2, activation="gelu", norm="layernorm",
            pos_embed="learned", tie_embeddings=True, qkv_bias=True, mlp_bias=True, attention_impl="flash",
            remat="full")
DENSE = {"mistral-7b-v0.1": ModelConfig(**MISTRAL), "gpt2-large": ModelConfig(d_model=40, n_heads=4, **GPT2),
         "gpt2-xl": ModelConfig(d_model=50, n_heads=5, **GPT2), "xing4.0-29b-a4b": get_preset("xing-mini").model,
         "ling-3.0-flash": get_preset("ling-mini").model, "joyai-llm-flash": get_preset("joyai-mini").model}
# (equations, hash) of each program as the commit 7c8a3aa (PR 28) traces it, less its `name`
# equations for the remat tags "qkv" and "mlp_hidden", which went with the policies that read
# them (PR 29; before that, 3f5f5eb): the benchmark's three dense configurations' flags at
# toy widths (hc_mult 1, no latent, no experts)
PARENTS = {
    ("mistral-7b-v0.1", "decode"): (523, "16f3f04aad6681aa"),
    ("mistral-7b-v0.1", "prefill"): (271, "1e842ff3fbeb6ac0"),
    # the three programs that write no cache, since PR 50: the parent's (8c127b4: 210 /
    # 86a2e3bb3520ba25, 808 / 98fa975426632a7d, 808 / 092d28b6a34b7b9b) with the projections' heads
    # kept as one axis of H*Dh lanes in their dots - every equation between wqkv (wq, wkv) and the
    # split into q, k, v, and around wo, at (1, H*Dh) where it stood at (H, Dh), and 7 reshapes a
    # layer forward, 22 forward and backward; diffed equation by equation, nothing else differs
    ("mistral-7b-v0.1", "forward"): (217, "3b4f3d5f569b4936"),
    ("gpt2-large", "train"): (830, "e2d18ac32ab2c6e0"),
    ("gpt2-xl", "train"): (830, "60733136754c69d4"),
    # the fourth configuration, at the `xing-mini` preset's widths (latent pool, dropless experts
    # with no group limit, no clamp and every expert held, four streams), as f8a0c12 (PR 30)
    # traces it: PR 31's per-layer type table, state slots, group limit and clamps leave it be
    # (the expert models' decode and round programs since PR 44: the parent's - 2,779 / 81e950d7efcdced8,
    # 2,512 / 241427cc1ac9352d, 1,481 / e28efbc503cecb46 and 2,672 / b4e83b754e2de962 at a5eb60d - and 37 to 39
    # int32 equations over the (steps, expert layers, E) routing counts, the weight reads `expert_visits`;
    # diffed equation by equation against that commit, nothing else differs)
    # (all three expert models' programs since PR 59: the parent's at a346854 - 2,816 / a917014dbbbf7e7e,
    # 1,445 / 1fd884617569206c, 1,354 / 7c021ba348cc52d2; Ling's 2,549 / 4525c94499540e5b, 2,254 /
    # 14eb943ac170ae38, 2,023 / 96aa51f3110c061b; JoyAI's 1,518 / 2c64035367baaf79, 613 / 241d392a485a0b90,
    # 504 / 9d87ef583c8c67f6, 2,711 / 463725f3440a882c - with the dropless expert layer planning its rows
    # once: 36 to 41 equations a traced expert layer leave (the second sort, the `bincount`'s scatter-add, the
    # two N-vector gathers, the un-sort's row gather and the reduce over K) and 78 to 93 come (the count in
    # blocks, the place of each pair, the K slabs weighted and summed one by one); the equations outside the expert layer
    # are the parent's, diffed as multisets on the decode programs of Xing and Ling)
    ("xing4.0-29b-a4b", "decode"): (2900, "e4d88fa2215dddac"),
    ("xing4.0-29b-a4b", "prefill"): (1487, "6c596de135eff457"),
    ("xing4.0-29b-a4b", "forward"): (1396, "a7cecb89b7655ca8"),
    # the fifth and sixth, at the `ling-mini` and `joyai-mini` presets' widths (state slots beside
    # the latent pool; the MTP module's layer and its round), as 6611a27 (PR 40) traces them:
    # PR 41's per-layer attention kind, second page table and window pool leave them be
    ("ling-3.0-flash", "decode"): (2809, "bd5a44c64df2ad29"),
    # (the prefill since PR 54: a state-slot model's admission runs the head on each row's last
    # position alone, `paged._prefill_last_logits`; the parent's 2,254 / 6f0de077b97d34fb with the
    # head's dot over (N, 1, D) where it stood over (N, P, D) and the take of the last row ahead of it)
    ("ling-3.0-flash", "prefill"): (2462, "1ab631339827f697"),
    ("ling-3.0-flash", "forward"): (2231, "29f10cb478aad124"),
    ("joyai-llm-flash", "decode"): (1622, "3b612b6419896fa2"),
    ("joyai-llm-flash", "prefill"): (665, "a3a49066c6daf8df"),
    ("joyai-llm-flash", "forward"): (556, "d50d4ffe09b175b7"),
    ("joyai-llm-flash", "round"): (2867, "9790c0e38988ca1c"),
}


def _equations(jaxpr, out):
    for eqn in jaxpr.eqns:
        kept = []
        for k, v in sorted(eqn.params.items()):
            is_program = lambda x: hasattr(x, "eqns") or hasattr(x, "jaxpr")
            subs = [v] if is_program(v) else ([x for x in v if is_program(x)] if isinstance(v, (tuple, list)) else [])
            for sub in subs:
                _equations(getattr(sub, "jaxpr", sub), out)
            if not subs and not callable(v):
                kept.append(f"{k}={v}")
        out.append(f"{eqn.primitive.name}|{[str(v.aval) for v in eqn.invars]}|"
                   f"{[str(v.aval) for v in eqn.outvars]}|{kept}")
    return out


def _fingerprint(fn, *args):
    """(count, order-free hash) of every equation of the traced program, its
    sub-programs included: primitive, operand and result types, parameters."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # flash falls back to blockwise on the CPU, loudly
        eqs = _equations(jax.make_jaxpr(fn)(*args).jaxpr, [])
    return len(eqs), hashlib.sha256("\n".join(sorted(eqs)).encode()).hexdigest()[:16]


@pytest.mark.parametrize("name,prog", sorted(PARENTS))
def test_dense_programs_trace_as_the_parents(name, prog):
    cfg = DENSE[name]
    p = jax.eval_shape(lambda k: tr.init_params(cfg, k), jax.random.key(0))
    toks, key = jnp.zeros((2, 16), jnp.int32), jax.random.key(1)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    if prog == "train":
        got = _fingerprint(jax.grad(lambda p, x, y: tr.loss_fn(p, x, y, cfg)), p, toks, toks)
    elif prog == "forward":
        got = _fingerprint(lambda p, x: tr.forward(p, x, cfg)[0], p, toks)
    else:
        slots = 2 if cfg.hybrid else 0  # a state-slot model: a slot a row beside the pages
        pools = jax.eval_shape(lambda: tr.make_paged_kv_pool(cfg, 16, 8, state_slots=slots))
        if prog == "decode":
            got = _fingerprint(lambda *a: paged.paged_decode_steps(*a, cfg=cfg, n_steps=1),
                               p, pools, i32(2), i32(2, 4), i32(2), key)
        elif prog == "round":
            got = _fingerprint(lambda *a: paged.paged_mtp_round(*a, cfg=cfg),
                               p, pools, i32(2), i32(2), i32(2, 4), i32(2), key)
        else:
            kw = dict(slots=i32(2)) if slots else {}
            got = _fingerprint(lambda *a: paged._prefill_scatter_sample(*a, cfg=cfg, p_bucket=16, n_pages=2, **kw),
                               p, pools, toks, i32(2), i32(2, 2), key)
    assert got == PARENTS[(name, prog)]


def test_a_long_prompt_under_a_large_vocabulary_heads_the_last_position_only(params, monkeypatch):
    """Past ``_ALL_POSITION_LOGITS_BYTES`` of all-position logits a prefill runs
    the head on each row's last position alone: the same first tokens."""
    p = params[SEEDS[0]]
    prompts = [tokens(1, 21).tolist(), tokens(2, 9).tolist()]

    def first_tokens():
        pools = tr.make_paged_kv_pool(CFG, 16, 8)
        ids = [[1, 2, 3], [4, 5]]
        return np.asarray(paged.prefill_into_pool_batched(p, CFG, pools, prompts, ids, jax.random.key(0))[0])

    whole = first_tokens()
    monkeypatch.setattr(paged, "_ALL_POSITION_LOGITS_BYTES", 0)
    jax.clear_caches()
    try:
        assert np.array_equal(first_tokens(), whole)
    finally:
        jax.clear_caches()
