"""Granite-4.0-H's mechanisms at toy widths, against the plain reference
(``benchmark/references/granite.py``: float32 at the highest matmul precision,
the state-space layer as the token-by-token recurrence, sharing no code with the
program): Mamba-2 in its chunked and recurrent forms, the state slots that live
beside a per-head page pool in one cache manager, the layer table that names
each layer's mixer, position-free grouped-query attention under its own score
multiplier, the embedding, residual and logit multipliers, softmax routing over
a share of the experts, the tied vocabulary slice.

The toy (``benchmark/tests/toy/granite.json``) is one period of five layers (2
Mamba-2, attention, 2 Mamba-2), 4 of 8 experts held, top-3, a shared expert of
twice an expert's width. Every tolerance has its reason and a control that
fails it beside it. Weights are seeded with every scale, bias, ``A_log``,
``dt_bias`` and ``D`` non-trivial (``harness/families/granite.py``), so a
dropped term shows.
"""

import dataclasses
import json
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.append(BENCH)  # behind the repo root: `tests` must stay this directory's package

from harness import opcount, program, weights  # noqa: E402
from harness.families import granite as family  # noqa: E402
from references import granite as ref  # noqa: E402
from references.common import int8_fake_quant  # noqa: E402

from pretraining_llm_tpu.config import ModelConfig, get_preset  # noqa: E402
from pretraining_llm_tpu.generation import paged, serving  # noqa: E402
from pretraining_llm_tpu.generation.generate import generate  # noqa: E402
from pretraining_llm_tpu.generation.serving import ServingEngine  # noqa: E402
from pretraining_llm_tpu.models import mamba, moe, recurrent, transformer as tr  # noqa: E402
from pretraining_llm_tpu.training.optimizer import decay_mask  # noqa: E402

with open(os.path.join(BENCH, "tests", "toy", "granite.json")) as f:
    TOY = dict(json.load(f), name="granite-toy")
# float32 throughout: the program's arithmetic then differs from the reference's
# by the order of its sums alone, and the tolerances below can be tight.
ARCH = dict(TOY, serving_dtype="float32",
            program_model={"attention_impl": "naive", "param_dtype": "float32", "compute_dtype": "float32"})
CFG = program.model_config(ARCH, 128)
SEEDS = (3, 2 ** 31 + 5)

# Relative error of logits, ||program - reference|| / ||reference||. The sound
# float32 program reads 3.0e-7 and 3.1e-7 on the forward pass here (chunked form
# against the reference's recurrence: the same sums in another order); the least
# of the reference's own controls reads 1.2e-5 (the state rounded to bfloat16
# after every token), then 2.7e-2 (scores scaled by 1/sqrt(head_dim); no decay
# 2.7e-2 and 4.2e-2, rotary positions 6.8e-2). 4e-6 lies 13 x over the one and
# 3 x under the other.
LOGITS_TOL = 4e-6


@pytest.fixture(scope="module")
def params():
    return {seed: weights.serving_params(ARCH, seed) for seed in SEEDS}


def reference_logits(seed, toks, arch=ARCH, quant=None, control=""):
    key = weights.seed_key(seed)
    gw = weights.globals_(arch, key, jnp.float32)
    return np.asarray(ref.forward(
        jnp.asarray(np.asarray(toks, np.int32)), lambda l: weights.layer(arch, key, l, jnp.float32), gw, arch,
        quant=quant, control=control), np.float32)


def rel_err(a, b):
    return float(np.linalg.norm(np.asarray(a, np.float64) - b) / np.linalg.norm(b))


def tokens(seed, n):
    return np.random.default_rng([seed % 2 ** 31, 9]).integers(0, CFG.vocab_size, n, dtype=np.int32)


# -- 1. the layer table and the full forward pass -------------------------------------


def test_the_table_names_each_layers_mixer_and_the_tree_stacks_them_by_kind(params):
    assert CFG.layer_mixers == ("mamba", "mamba", "attn", "mamba", "mamba")
    assert CFG.layer_kinds == tuple((m, "moe") for m in CFG.layer_mixers)
    assert CFG.layer_runs == ((0, 2), (2, 3), (3, 5)) and CFG.state_mixer == "mamba" and CFG.hybrid
    assert CFG.n_state_layers == 4 and CFG.n_kda_layers == 0 and CFG.pos_embed == "none"
    p = params[SEEDS[0]]
    stacked = {k: jax.tree.leaves(v)[0].shape[0] for k, v in p.items() if k.endswith("blocks")}
    assert stacked == {"blocks": 4, "attn_blocks": 1}
    assert "w_in" in p["blocks"]["attn"] and "wkv" in p["attn_blocks"]["attn"] and "lm_head" not in p
    shapes = lambda t: jax.tree.map(lambda a: a.shape, t)
    assert shapes(jax.eval_shape(lambda k: tr.init_params(CFG, k), jax.random.key(0))) == shapes(p)
    groups = tr.layer_groups(p, CFG)
    assert [(list(layers_of), first) for layers_of, _, first in groups] == [([0, 1], 0), ([2], 0), ([3, 4], 2)]


def test_the_shorthand_fills_the_same_table_for_ling():
    """``layer_group_size`` is the table's shorthand: Ling's preset names no
    ``layer_mixers`` and reads the table it always had; the table written out
    gives the same kinds, runs and parameter count."""
    ling = get_preset("ling-mini").model
    assert ling.layer_mixers == () and ling.layer_group_size == 3 and ling.state_mixer == "kda"
    spelled = dataclasses.replace(ling, layer_group_size=0, layer_mixers=("kda", "kda", "attn") * 2)
    assert spelled.layer_kinds == ling.layer_kinds and spelled.layer_runs == ling.layer_runs
    assert spelled.num_params() == ling.num_params() and spelled.n_kda_layers == ling.n_kda_layers == 4
    assert not get_preset("trinity-toy").model.hybrid and get_preset("trinity-toy").model.state_mixer is None


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_matches_the_reference(params, seed):
    toks = tokens(seed, 100)  # whole chunks of 16 and a ragged one
    logits, _ = tr.forward(params[seed], toks[None], CFG)
    assert rel_err(logits[0], reference_logits(seed, toks)) < LOGITS_TOL


@pytest.mark.parametrize("seed", SEEDS)
def test_loss_and_its_gradient_match_the_reference(params, seed):
    """``loss_fn`` against the cross entropy of the reference's logits, and its
    gradient by autodiff through the chunked form against autodiff through the
    reference's recurrence (in the program's tree: ``weights.program_tree``)."""
    toks = tokens(seed, 41)
    x, y = jnp.asarray(toks[None, :-1]), jnp.asarray(toks[None, 1:])
    loss, grads = jax.value_and_grad(lambda p: tr.loss_fn(p, x, y, CFG))(params[seed])
    key = weights.seed_key(seed)
    idx = jnp.arange(opcount.dims(ARCH)["layers"])
    stacked = jax.vmap(lambda l: weights.layer(ARCH, key, l, jnp.float32))(idx)
    gw = weights.globals_(ARCH, key, jnp.float32)

    def ref_loss(stacked, gw):
        logits = ref.forward(x[0], lambda l: jax.tree.map(lambda a: a[l], stacked), gw, ARCH)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[0][:, None], axis=-1))

    want, (g_layers, g_gw) = jax.value_and_grad(ref_loss, argnums=(0, 1))(stacked, gw)
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    want_tree = weights.program_tree(ARCH, g_layers, g_gw)
    flat = lambda t: {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(t)}
    got, want_flat = flat(grads), flat(want_tree)
    assert set(got) == set(want_flat)
    for name, g in got.items():
        if name.endswith("['bo']"):
            continue  # the program's zero output bias: the reference has none to differentiate
        w = np.asarray(want_flat[name], np.float64)
        assert np.linalg.norm(np.asarray(g, np.float64) - w) <= 2e-4 * np.linalg.norm(w) + 1e-9, name


def test_parameter_count_is_the_tree_and_a_hand_count(params):
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params[SEEDS[0]]))
    m = opcount.dims(ARCH)
    # the program's attention layer carries a zero output bias of d that the model does not have
    assert n == CFG.num_params() == opcount.num_params(ARCH) + m["attn_layers"] * m["d"]
    assert family.ssm_params(m) == CFG._mamba_params()
    d, w, c, h = 64, 8 * 16, 8 * 16 + 2 * 16, 8  # hidden, inner, conv channels, heads
    by_hand = d * (w + c + h) + c * 4 + c + 3 * h + w + w * d
    assert family.ssm_params(m) == by_hand
    expert, shared, router = 3 * d * 32, 3 * d * 64, d * 8
    attn = 2 * d * 4 * 16 + 2 * d * 2 * 16
    layer_by_hand = lambda mixer: mixer + 2 * d + router + 4 * expert + shared
    assert opcount.num_params(ARCH) == 4 * layer_by_hand(by_hand) + layer_by_hand(attn) + 256 * d + d


@pytest.mark.parametrize("control", ref.CONTROLS[1:] + ("int8",))
def test_each_control_of_the_reference_fails(control):
    seed = SEEDS[0]
    toks = tokens(seed, 100)
    kw = dict(quant=int8_fake_quant) if control == "int8" else dict(control=control)
    # measured: bf16 state 1.2e-5, 1/sqrt(head_dim) 2.7e-2, no decay 2.7e-2, rotary 6.8e-2, int8 operands 1.5e-2
    assert rel_err(reference_logits(seed, toks, **kw), reference_logits(seed, toks)) > 2.5 * LOGITS_TOL


@pytest.mark.parametrize("dropped", ["embed_scale", "residual_multiplier", "attention_multiplier", "logits_scaling"])
def test_a_dropped_multiplier_fails(params, dropped):
    """The program with one of the four multipliers at its neutral value is not
    the reference (the logit scaling alone divides every logit by 4: 0.75)."""
    seed = SEEDS[0]
    toks = tokens(seed, 48)
    neutral = {"embed_scale": 0.0, "residual_multiplier": 1.0, "attention_multiplier": 0.0, "logits_scaling": 1.0}
    logits, _ = tr.forward(params[seed], toks[None], dataclasses.replace(CFG, **{dropped: neutral[dropped]}))
    # measured: attention_multiplier 1.2e-2 (1/sqrt(16) = 2 x 0.125), the others 0.3 and more
    assert rel_err(logits[0], reference_logits(seed, toks)) > 1000 * LOGITS_TOL


# -- 2. the chunked form against the recurrence ---------------------------------------


def _recurrence_inputs(rng, rows, t, h=8, p=16, g=1, n=16):
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    dt = jax.nn.softplus(f(rows, t, h) - 2.0)
    return f(rows, t, h, p), f(rows, t, g, n), f(rows, t, g, n), dt, -jnp.exp(f(h)), f(h)


@pytest.mark.parametrize("start", ["zero", "nonzero"])
@pytest.mark.parametrize("t,chunk", [(64, 16), (37, 16), (100, 64), (9, 16)],
                         ids=["whole-chunks", "ends-inside-a-chunk", "one-and-a-part", "shorter-than-a-chunk"])
def test_chunked_is_the_recurrence(start, t, chunk):
    rng = np.random.default_rng(t)
    x, b, c, dt, a, d = _recurrence_inputs(rng, 2, t)
    s0 = jnp.zeros((2, 8, 16, 16)) if start == "zero" else jnp.asarray(rng.normal(size=(2, 8, 16, 16)), jnp.float32)
    s, ys = s0, []
    for i in range(t):
        y, s = mamba.recurrent_step(s, x[:, i], b[:, i], c[:, i], dt[:, i], a, d)
        ys.append(y)
    with jax.default_matmul_precision("highest"):
        y2, s2 = mamba.chunked(s0, x, b, c, dt, a, d, chunk)
    assert rel_err(y2, np.asarray(jnp.stack(ys, axis=1))) < 2e-6 and rel_err(s2, np.asarray(s)) < 2e-6


def test_chunked_over_two_groups_of_heads():
    """B and C shared by the heads of one of two groups, as ``mamba_n_groups`` allows."""
    rng = np.random.default_rng(2)
    x, b, c, dt, a, d = _recurrence_inputs(rng, 1, 40, g=2)
    s, ys = jnp.zeros((1, 8, 16, 16)), []
    for i in range(40):
        y, s = mamba.recurrent_step(s, x[:, i], b[:, i], c[:, i], dt[:, i], a, d)
        ys.append(y)
    # against the recurrence with every head given its group's B and C outright
    rep = lambda v: jnp.repeat(v, 4, axis=2)
    y_full, s_full = mamba.chunked(jnp.zeros((1, 8, 16, 16)), x, rep(b), rep(c), dt, a, d, 16)
    y2, s2 = mamba.chunked(jnp.zeros((1, 8, 16, 16)), x, b, c, dt, a, d, 16)
    assert rel_err(y2, np.asarray(jnp.stack(ys, axis=1))) < 2e-6 and rel_err(s2, np.asarray(s)) < 2e-6
    assert rel_err(y_full, np.asarray(y2)) < 2e-6 and rel_err(s_full, np.asarray(s2)) < 2e-6


def test_a_bucket_padded_prompt_leaves_the_state_and_the_tail_of_its_last_real_token(params):
    """The mixer over 37 real tokens padded to 64, with the true lengths: the
    state, the conv tail and the real positions' outputs are the unpadded run's,
    row by row (rows of unlike lengths in one bucket, one ending inside a chunk,
    one shorter than the conv's reach); without the lengths they are not."""
    p = jax.tree.map(lambda a: a[0], params[SEEDS[0]]["blocks"])["attn"]
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(2, 64, CFG.d_model)), jnp.float32)
    shapes = mamba.state_shapes(CFG, 2)
    state = jnp.asarray(rng.normal(size=shapes["state"][0]), jnp.float32)
    tail = jnp.asarray(rng.normal(size=shapes["conv"][0]), jnp.float32)
    lens = jnp.asarray([37, 2], jnp.int32)
    valid = jnp.arange(64)[None, :] < lens[:, None]
    y, s, c = mamba.mix(p, h, CFG, state, tail, valid, lens)
    for row, n in enumerate((37, 2)):
        y1, s1, c1 = mamba.mix(p, h[row : row + 1, :n], CFG, state[row : row + 1], tail[row : row + 1])
        assert rel_err(y[row, :n], np.asarray(y1[0])) < 1e-5
        assert rel_err(s[row], np.asarray(s1[0])) < 1e-5 and rel_err(c[row], np.asarray(c1[0])) < 1e-6
    _, s_blind, c_blind = mamba.mix(p, h, CFG, state, tail)
    assert rel_err(s_blind[0], np.asarray(s[0])) > 1e-2 and rel_err(c_blind[0], np.asarray(c[0])) > 1e-2


def test_the_decode_step_is_one_more_token_of_the_prefill(params):
    """``mix`` over n tokens then one (the recurrence) is ``mix`` over n + 1 (the chunked form)."""
    p = jax.tree.map(lambda a: a[1], params[SEEDS[0]]["blocks"])["attn"]
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(3, 21, CFG.d_model)), jnp.float32)
    zeros = {k: jnp.zeros(*v) for k, v in mamba.state_shapes(CFG, 3).items()}
    y_all, s_all, c_all = mamba.mix(p, h, CFG, zeros["state"], zeros["conv"])
    _, s, c = mamba.mix(p, h[:, :20], CFG, zeros["state"], zeros["conv"])
    y_last, s, c = mamba.mix(p, h[:, 20:], CFG, s, c)
    assert rel_err(y_last[:, 0], np.asarray(y_all[:, 20])) < 1e-5
    assert rel_err(s, np.asarray(s_all)) < 1e-5 and rel_err(c, np.asarray(c_all)) < 1e-6


# -- 3. prefill then decode through slots and pool ------------------------------------


def _teacher_forced(p, seqs, prompt_lens, steps, readmit_row=None, readmit_at=None, cfg=None):
    """Logits after each forced token, through the engine's prefill and decode
    lanes on hand-built tables, a row's state slot its index. ``readmit_row`` is
    preempted before step ``readmit_at``: its pages are freed and what it held
    is prefilled anew into other pages and, as the engine does, its own slot.
    ``cfg``: another state-slot model's (tests/test_olmo_hybrid.py)."""
    cfg = cfg or CFG
    bs, max_blocks, rows = 8, 16, len(seqs)
    pools = tr.make_paged_kv_pool(cfg, 64, bs, state_slots=rows)
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
        p, cfg, pools, [prompts[r] for r in order], [ids[r][: n_pre[r]] for r in order],
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
                p, cfg, pools, held, ids[r][: paged.required_blocks(len(held), bs)], slot=r)
        tok = np.asarray([s[n + j] for s, n in zip(seqs, prompt_lens)], np.int32)
        logits, pools = paged.paged_decode_logits(
            p, pools, jnp.asarray(tok), jnp.asarray(tables), jnp.asarray(seq_lens), cfg=cfg)
        for r in range(rows):
            out[r].append(np.asarray(logits[r], np.float32))
        seq_lens += 1
    return [np.stack(o) for o in out]


@pytest.mark.parametrize("readmit", [False, True], ids=["steady", "preempted-and-readmitted"])
def test_paged_decode_matches_the_reference(params, readmit):
    seed = SEEDS[0]
    prompt_lens, steps = (21, 9, 70), 6  # 70: several chunks of prompt, the last one ragged
    seqs = [tokens(seed + r, n + steps) for r, n in enumerate(prompt_lens)]
    got = _teacher_forced(params[seed], seqs, prompt_lens, steps,
                          readmit_row=1 if readmit else None, readmit_at=3 if readmit else None)
    for toks, n, rows in zip(seqs, prompt_lens, got):
        want = reference_logits(seed, toks)[n : n + steps]  # row t scores token t + 1
        assert rel_err(rows, want) < LOGITS_TOL


def test_the_state_slots_are_the_reference_scans_state(params):
    """What the cell's `correct` holds beside the logits (``harness/ssm_check``):
    after a prefill and teacher-forced steps through slots and pool, each sampled
    row's slot in every state-space layer is the reference scan's state after the
    same tokens, head by head; a state rounded to bfloat16 after every token is
    not, by a hundred times the float32 program's distance and more."""
    from harness import serving_check as sc, ssm_check

    seed = SEEDS[0]
    sample = [(21, 6), (70, 6)]  # 70: several chunks of prompt, the last one ragged
    seqs = sc.sample_tokens(seed, CFG.vocab_size, sample)
    eng = ServingEngine(params[seed], CFG, max_batch=4, n_blocks=64, block_size=8)
    prog, pools = sc.program_logits(params[seed], CFG, eng.pools, eng.alloc, eng.max_batch, eng.max_blocks,
                                    eng.block_size, sample, seqs)
    held = ssm_check.slot_states(pools, len(sample))
    want, states, rate = ssm_check.reference(ARCH, seed, sample, seqs)
    assert held.shape == states.shape == (2, 4, CFG.mamba_heads, CFG.mamba_head_dim, CFG.mamba_d_state)
    assert rate.shape == (4, CFG.mamba_heads) and sc.rel_err(prog, want) < LOGITS_TOL
    sound = ssm_check.head_errors(held, states)
    assert sound.max() < 1e-5  # measured: 6.2e-7 at the largest head, 2.0e-7 over the slowest of each layer
    _, rounded, _ = ssm_check.reference(ARCH, seed, sample, seqs, control="bf16_state")
    departed = ssm_check.head_errors(rounded, states)
    # measured: 1.6e-3 at the least head, 5.8e-3 over the slowest of each layer, 6.1e-3 in the first layer
    assert departed.min() > 100 * sound.max()
    assert ssm_check.state_rel_err(departed, rate) > 100 * ssm_check.state_rel_err(sound, rate)
    assert ssm_check.state_rel_err(departed, rate, slice(0, 1)) > 100 * ssm_check.state_rel_err(sound, rate, slice(0, 1))
    # the slow share is each layer's heads of the smallest decay a token
    one = np.zeros((1, 4, CFG.mamba_heads))
    slowest = np.argmin(rate, axis=-1)
    one[0, np.arange(4), slowest] = 1.0
    n = max(1, round(ssm_check.SLOW_SHARE * CFG.mamba_heads))
    assert ssm_check.state_rel_err(one, rate) == pytest.approx((1.0 / n) ** 0.5)


def test_pools_give_pages_to_the_attention_layer_and_a_slot_a_row_to_the_rest():
    pools = jax.eval_shape(lambda: tr.make_paged_kv_pool(CFG, 16, 8, state_slots=3))
    kinds = [sorted(layer) for layer in pools["layers"]]
    assert kinds == [["conv_pool", "state_pool"]] * 2 + [["k_pool", "v_pool"]] + [["conv_pool", "state_pool"]] * 2
    assert pools["layers"][0]["state_pool"].shape == (4, 8, 16, 16)  # 3 rows and the scratch slot
    assert pools["layers"][0]["state_pool"].dtype == jnp.float32
    assert pools["layers"][0]["conv_pool"].shape == (4, 3, 8 * 16 + 2 * 16)
    assert pools["layers"][2]["k_pool"].shape == (16, 8, 2, 16) and "state_cursor" in pools
    assert paged.state_slots(pools) == 3 and paged.pool_block_size(pools, CFG) == 8
    cache = jax.eval_shape(lambda: tr.make_kv_cache(CFG, 2, 32))
    assert [sorted(layer) for layer in cache["layers"]] == [["conv", "state"]] * 2 + [["k", "v"]] + [["conv", "state"]] * 2
    with pytest.raises(ValueError, match="unlike caches"):
        tr.make_kv_cache(CFG, 2, 32, stacked=True)
    with pytest.raises(ValueError, match="state_slots"):
        tr.make_paged_kv_pool(CFG, 16, 8)
    assert recurrent.state_shapes(CFG, 3) == mamba.state_shapes(CFG, 3)
    assert recurrent.state_shapes(get_preset("trinity-toy").model, 3) is None


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
    are still in flight; the chunk lane builds a row's state chunk by chunk from
    its slot while decode windows pass it by; a pool of 13 pages preempts and
    recomputes."""
    prompts, want = greedy
    eng = ServingEngine(params[SEEDS[0]], CFG, max_batch=3, block_size=8, **kw)
    rids = [eng.submit(pr, 10) for pr in prompts]
    out = eng.run()
    assert [out[r] for r in rids] == want
    assert eng.stats["state_slots_peak"] == 3
    assert (eng.stats["preemptions"] > 0) == (kw["n_blocks"] == 14)
    info = eng.pool_info()
    per_slot = 4 * (4 * 8 * 16 * 16 + 4 * 3 * (8 * 16 + 2 * 16))  # 4 Mamba-2 layers, float32: a state and a 3-row tail
    assert info["state_slots"] == 3 and info["bytes_per_slot"] == per_slot and info["state_bytes"] == 4 * per_slot
    assert info["pool_bytes"] == kw["n_blocks"] * 8 * 2 * 2 * 16 * 4  # the one attention layer's K and V pages alone
    assert (info["state_mixer"], info["state_layers"], info["page_layers"]) == ("mamba", 4, 1)
    assert info["decode_state"] == "jnp" and info["decode_experts"] == "grouped" and info["decode_attention"] == "gather"


def test_a_mid_prefill_row_rides_no_decode_window_and_its_slot_waits(params):
    eng = ServingEngine(params[SEEDS[0]], CFG, max_batch=2, n_blocks=32, block_size=8, prefill_chunk_tokens=8)
    eng.submit(tokens(1, 5).tolist(), 4)
    eng.submit(tokens(2, 30).tolist(), 4)
    eng._admit()
    eng._dispatch_prefill_chunks(defer=False)  # 8 tokens: the short row's whole prompt, 3 of the long one's
    assert [r.prefill_pos for r in eng.rows] == [None, 3]
    assert eng.tables[1].any() and not eng._decode_tables()[1].any() and eng._decode_tables()[0].any()
    # a decode step under those tables moves row 0's state and leaves the mid-prefill row's where its chunk left it
    before = jax.tree.map(np.asarray, eng.pools["layers"][0])
    _, pools = paged.paged_decode_logits(
        eng.params, eng.pools, jnp.asarray([1, 2], jnp.int32), jnp.asarray(eng._decode_tables()),
        jnp.asarray([5, 3], jnp.int32), cfg=CFG)
    after = jax.tree.map(np.asarray, pools["layers"][0])
    for name in before:
        np.testing.assert_array_equal(after[name][1], before[name][1])
        assert not np.array_equal(after[name][0], before[name][0])


@pytest.mark.parametrize("preset", ["granite-toy", "ling-mini", "olmo-hybrid-toy"])
def test_the_engine_refuses_by_name_what_is_not_built_on_state_slots(preset):
    """Any recurrent mixer: the refusals read the table, not a mixer's name."""
    cfg = get_preset(preset).model
    p = tr.init_params(cfg, jax.random.key(0))
    for kw, name in ((dict(prefix_cache=True), "prefix_cache"), (dict(kv_checksum=True), "kv_checksum"),
                     (dict(quantize="int8-kv"), "quantize"), (dict(quantize="int8"), "quantize"),
                     (dict(spec_k=2, draft_params=p, draft_cfg=cfg), "spec_k")):
        with pytest.raises(ValueError, match=rf"state-slot model \({cfg.state_mixer} layers\) is served "
                                             rf"without {name}: .*kv_transfer"):
            ServingEngine(p, cfg, max_batch=2, n_blocks=16, block_size=8, **kw)


@pytest.mark.parametrize("kw,message", [
    (dict(layer_mixers=("mamba", "attn")), "n_layers"),
    (dict(layer_mixers=("mamba",) * 5), "attention layers"),
    (dict(layer_mixers=("mamba", "kda", "attn", "mamba", "mamba")), "recurrent layers of one kind"),
    (dict(layer_group_size=5), "layer_group_size"),
    (dict(mamba_heads=0), "mamba_heads"),
    (dict(mamba_n_groups=3), "mamba_n_groups"),
    (dict(kv_lora_rank=8, qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=8, d_head=8, pos_embed="rope"),
     "per-head attention"),
    (dict(pos_embed="sinusoid"), "pos_embed"),
    (dict(logits_scaling=0.0), "logits_scaling"),
    (dict(residual_multiplier=0.5, sandwich_norm=True), "sandwich"),
    (dict(attn_kinds=("full",) * 5), "attn_kinds"),
    (dict(kv_cache_dtype="int8"), "int8"),
])
def test_the_configuration_refuses_by_name(kw, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(get_preset("granite-toy").model, **kw)


def test_a_json_round_trip_keeps_the_table_and_true_stands_for_sqrt_d():
    cfg = get_preset("granite-toy").model
    again = ModelConfig(**json.loads(json.dumps(dataclasses.asdict(cfg))))
    assert again == cfg and hash(again) == hash(cfg) and isinstance(again.layer_mixers, tuple)
    assert ModelConfig(d_model=64, n_heads=4, embed_scale=True).embed_scale == 8.0
    assert get_preset("trinity-toy").model.embed_scale == 8.0  # sqrt(64), as the bool meant


def test_routing_counters_count_the_pairs_that_met_an_expert_held(params):
    eng = ServingEngine(params[SEEDS[0]], CFG, max_batch=2, n_blocks=32, block_size=8)
    eng.submit(tokens(1, 9).tolist(), 6)
    eng.run()
    st = eng.stats
    assert st["moe_expert_tokens"].shape == (5, 4)  # five expert layers, the 4 experts held of 8
    here = st["moe_expert_tokens"].sum() / (st["moe_steps"] * 2 * 3 * 5)  # rows x choices x layers
    assert 0.2 < here < 0.8  # half the experts are here; random weights route about half the pairs to them


def test_weight_reads_are_the_plans_live_visits_on_the_commit_span_and_in_the_closing_line(params, monkeypatch, caplog):
    """``moe_visits``: the visits the expert kernel's ``plan`` lays out for each
    step's groups, summed over layers and steps (one function counts both); a
    toy step's groups each fit one visit, so it reads what ``moe_touched`` reads."""
    import logging

    from pretraining_llm_tpu.ops import pallas_moe
    from pretraining_llm_tpu.observability import spans

    rec = spans.SpanRecorder()
    monkeypatch.setattr(spans, "_default", rec)
    eng = ServingEngine(params[SEEDS[0]], CFG, max_batch=2, n_blocks=32, block_size=8)
    windows, count = [], eng._count_moe
    monkeypatch.setattr(eng, "_count_moe", lambda c, n, **kw: windows.append((np.asarray(c["expert_tokens"]), n)) or count(c, n, **kw))
    eng.submit(tokens(1, 9).tolist(), 6)
    with caplog.at_level(logging.INFO, logger="pretraining_llm_tpu.serving"):
        eng.run()
    st = eng.stats
    pairs = 2 * CFG.experts_per_token  # a step's sorted rows a layer: two rows, idle or not
    w = pallas_moe.windows(pairs, CFG.n_experts)
    assert w == 2 and windows and all(n == 1 for _, n in windows)  # one step a window: the counts are a step's
    live = sum(int(pallas_moe.plan(jnp.asarray(layer), pairs + -pairs % pallas_moe.ROW_TILE, w)[2][0])
               for step, _ in windows for layer in step)
    assert st["moe_visits"] == live == st["moe_experts_touched"].sum() > 0
    events, _ = rec.drain()
    commits = [meta for name, *_, meta in events if name == "serving.commit" and "moe_steps" in meta]
    assert commits and sum(m["moe_visits"] for m in commits) == live
    assert all(m["moe_visits"] == m["moe_touched"] for m in commits)
    line, = [r.getMessage() for r in caplog.records if "engine empty" in r.getMessage()]
    # (written when the last row leaves, ahead of the windows still in flight)
    said = re.search(r"experts \(grouped\) took \d+ pairs, (\d+) touched, (\d+) weight reads over \d+ steps", line)
    assert said and 0 < int(said[1]) == int(said[2]) <= live


@pytest.mark.parametrize("counts,pairs,visits", [
    ([[3, 0, 2, 1]], 6, [3]),  # a toy step: every group one visit, the touched
    ([[17, 0, 15, 0]], 32, [2]),  # a group of a row tile and one more is one visit wherever it starts
    ([[40, 0, 3, 0]], 6, [3]),  # a group past the span of two windows reads its weights again
    ([[15, 18, 0, 0]], 6, [3]),  # 18 rows from a window's last row cross the span
    ([[15, 18, 30, 49]], 8 * 18, [4]),  # past a row tile an expert the visit widens: each group once
    ([[15, 18, 30, 50]], 8 * 18, [5]),  # one row more than twice the mean's span holds from a last row
], ids=["toy", "tile-and-one", "past-the-span", "late-start", "wide-visit", "wide-visit-crossed"])
def test_weight_reads_are_the_touched_exactly_when_every_group_fits_a_visit(counts, pairs, visits):
    from pretraining_llm_tpu.ops import pallas_moe

    counts = jnp.asarray(counts, jnp.int32)[None]  # (one step, one layer, the 4 experts held of 8)
    got = paged._routing_counters(CFG, counts, pairs)
    touched = np.asarray(got["experts_touched"])
    np.testing.assert_array_equal(got["expert_visits"], visits)
    n_rows = int(counts.sum()) + -int(counts.sum()) % pallas_moe.ROW_TILE
    w = pallas_moe.windows(pairs, CFG.n_experts)
    assert int(pallas_moe.plan(counts[0, 0], n_rows, w)[2][0]) == visits[0]
    fits = all(start % pallas_moe.ROW_TILE + n <= w * pallas_moe.ROW_TILE
               for start, n in zip(np.cumsum(counts[0, 0]) - np.asarray(counts[0, 0]), np.asarray(counts[0, 0])))
    assert (visits[0] == touched[0]) == fits


@pytest.mark.parametrize("ragged", [False, True])
def test_generate_runs_the_ragged_and_the_bucketed_path(params, greedy, ragged):
    """``generate``: a prompt bucketed past its length, and rows of unlike
    lengths in one batch, leave each state as of its row's last real token."""
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


def test_softmax_routing_against_a_hand_case():
    """8 experts, top-3: the gates are the softmax over the three largest logits
    alone (equal to the softmax over all eight, the three kept renormalised)."""
    cfg = dataclasses.replace(CFG, n_experts_held=0)
    logit = jnp.asarray([[0.1, 2.0, -1.0, 1.0, 0.5, 3.0, -2.0, 0.0]], jnp.float32)
    mlp = {"router": jnp.concatenate([logit, jnp.zeros((CFG.d_model - 1, 8))])}
    x = jnp.zeros((1, CFG.d_model), jnp.float32).at[0, 0].set(1.0)
    idx, gates = moe.route_dropless(mlp, x, cfg)
    order = np.argsort(np.asarray(idx[0]))
    assert np.asarray(idx[0])[order].tolist() == [1, 3, 5]
    e = np.exp([2.0, 1.0, 3.0])
    np.testing.assert_allclose(np.asarray(gates[0])[order], e / e.sum(), rtol=1e-6)
    full = np.exp(np.asarray(logit[0])) / np.exp(np.asarray(logit[0])).sum()
    np.testing.assert_allclose(np.asarray(gates[0])[order], full[[1, 3, 5]] / full[[1, 3, 5]].sum(), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.route(x, mlp["router"], 3))[0, [1, 3, 5]], e / e.sum(), rtol=1e-6)


def test_the_four_shares_add_up_to_the_whole_layer():
    """Four chips hold experts 0-1, 2-3, 4-5, 6-7 of one layer; their parts, the
    shared expert counted once, sum to the reference's whole layer. The program
    holds the router's *first* experts, so share j is the layer with its router
    turned by j shares: the choices are the same."""
    arch = dict(ARCH, num_local_experts=8)  # the whole layer's weights, made once
    m = opcount.dims(arch)
    c = family.layer(m, jax.random.fold_in(weights.seed_key(SEEDS[0]), 5), jnp.float32)
    h = jnp.asarray(np.random.default_rng(0).normal(size=(40, CFG.d_model)), jnp.float32)
    whole = ref.experts(h, c, arch, None)
    shared = ref.swiglu(h, c["s_gate"], c["s_up"], c["s_down"], None)
    cfg = dataclasses.replace(CFG, n_experts_held=2)
    dense = lambda sh, hh: tr._dense_mlp(sh, hh, cfg)
    total = jnp.zeros_like(whole)
    for j in range(4):
        share = {name: c[name][2 * j : 2 * j + 2] for name in ("e_gate", "e_up", "e_down")}
        mlp = family._program_ffn({**c, **share, "router": jnp.roll(c["router"], -2 * j, axis=-1)})
        y, counts = moe.moe_mlp_dropless(mlp, h[None], cfg, dense)
        assert counts.shape == (2,)
        want = ref.experts(h, {**c, **share}, arch, None, held=range(2 * j, 2 * j + 2))
        assert rel_err(y[0], np.asarray(want)) < 1e-5
        total = total + y[0] - shared
    assert rel_err(total + shared, np.asarray(whole)) < 1e-5
    assert rel_err(total, np.asarray(whole)) > 0.1  # the shared expert, left out, shows


def test_the_new_leaves_that_set_time_scales_do_not_decay(params):
    mask = decay_mask(params[SEEDS[0]])
    a = mask["blocks"]["attn"]
    assert a["w_in"] and a["w_out"]
    assert not any((a["conv"], a["conv_bias"], a["A_log"], a["dt_bias"], a["D"], a["norm"]["scale"]))


def test_an_engine_halves_its_prefill_split_when_the_compiler_has_no_room(params, greedy, monkeypatch):
    """A batched admission program the device refuses at compile time (no room
    beside the weights and pools) is run again as smaller programs, and the
    engine keeps the smaller figure: the same tokens, no option."""
    prompts, want = greedy
    whole, sizes, room = paged.prefill_into_pool_batched, [], [128]

    def refusing(params_, cfg, pools, batch, ids, *a, **kw):
        rows, pages = paged.prefill_bucket(cfg, len(batch), max(map(len, ids)), 8)
        sizes.append(rows * pages * 8)
        if sizes[-1] > room[0]:
            raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of memory")
        return whole(params_, cfg, pools, batch, ids, *a, **kw)

    monkeypatch.setattr(paged, "prefill_into_pool_batched", refusing)
    eng = ServingEngine(params[SEEDS[0]], CFG, max_batch=4, n_blocks=64, block_size=8)
    rids = [eng.submit(pr, 10) for pr in prompts[:4]]  # 5, 19, 33 and 12 tokens: one program of 4 x 64
    out = eng.run()
    assert [out[r] for r in rids] == want[:4]
    assert sizes[0] == 256 and max(sizes[1:]) <= 128 and eng.prefill_program_tokens == 128
    assert eng.stats["prefill_program_tokens"] == 128
    # a lone prompt that does not fit is the caller's to hear of
    eng = ServingEngine(params[SEEDS[0]], CFG, max_batch=4, n_blocks=64, block_size=8)
    eng.submit(tokens(1, 120).tolist(), 2)
    eng.submit(tokens(2, 120).tolist(), 2)
    room[0] = 64  # not even one 128-token row
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        eng.run()


def test_a_refusal_that_took_the_pools_is_not_run_again(params, greedy, monkeypatch):
    """Only a refusal that left the pools alone (the compiler's, before anything
    ran) is answered with smaller programs. One raised once the program had been
    given the pools (a load or a run that found no room) has nothing to run
    again on, and is the caller's to hear of."""
    prompts, _ = greedy

    def taking(params_, cfg, pools, *a, **kw):
        for leaf in jax.tree.leaves(pools):
            leaf.delete()  # what donation leaves of an argument
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: Error allocating device buffer")

    monkeypatch.setattr(paged, "prefill_into_pool_batched", taking)
    eng = ServingEngine(params[SEEDS[0]], CFG, max_batch=4, n_blocks=64, block_size=8)
    for pr in prompts[:4]:
        eng.submit(pr, 10)
    with pytest.raises(jax.errors.JaxRuntimeError, match="allocating device buffer"):
        eng.run()
    assert eng.prefill_program_tokens == serving.PREFILL_PROGRAM_TOKENS and "prefill_program_tokens" not in eng.stats


V5E_BYTES = 15.75 * 2 ** 30  # what a v5e chip's memory_stats() gives as bytes_limit


@pytest.mark.parametrize("name,d_model,top_k,resident_gb,tokens", [
    # resident: hbm_resident_gb of the cell (ledger, PR 42; Granite: my chip runs, PR 43)
    ("granite-4.0-h-small", 4096, 10, 12.73, 8192),  # the compiler refuses 16,384 (8 x 2,048) and takes 8,192
    ("ling-3.0-flash", 2560, 8, 10.883, 32768),  # 8 x 4,096 fits with 0.8 GiB to spare
    ("trinity-mini", 2048, 8, 10.988, 32768),
    ("xing4.0-29b-a4b", 3584, 4, 11.479, 32768),
    ("joyai-llm-flash", 2048, 8, 9.4826, 32768),
    # no expert layer: sized by the staged pages and the FFN's rows of an 18-layer Mistral. With the
    # serving layout's 4.23 GB beside the benchmark's stored tree (my chip run, PR 45) a program of 8,192
    # tokens finds no room on the chip and 4 x 1,024 runs; without that copy the chip refuses 32,768
    ("mistral-7b-v0.1", 4096, 0, 15.629, 4096),
    ("mistral-7b-v0.1", 4096, 0, 11.401, 16384),
])
def test_the_prefill_split_is_sized_from_the_free_memory(name, d_model, top_k, resident_gb, tokens):
    """An engine sizes its admission programs before it compiles one: every
    expert configuration the benchmark serves keeps the split it ran at, the
    state-space hybrid starts at the 8,192 tokens the compiler's refusal used to
    teach it, the dense one at what ran beside its serving layout, and each
    stands a factor 1.15 or more of free memory from the next power of two. A
    device that does not tell its memory keeps the constant."""
    cfg = types.SimpleNamespace(n_experts=8 * bool(top_k), experts_per_token=top_k, d_model=d_model,
                                compute_dtype="bfloat16", n_layers=18, kv_heads=8, head_dim=128, d_ff=14336)
    free = V5E_BYTES - resident_gb * 1e9
    assert serving.prefill_program_tokens(cfg, int(free)) == tokens
    assert serving.prefill_program_tokens(cfg, int(free / 1.15)) == tokens
    if tokens < serving.PREFILL_PROGRAM_TOKENS:
        assert serving.prefill_program_tokens(cfg, int(free * 1.15)) == tokens
    assert serving.prefill_program_tokens(cfg, None) == serving.PREFILL_PROGRAM_TOKENS
