"""Olmo-Hybrid's mechanisms at toy widths, against the plain reference
(``benchmark/references/olmo_hybrid.py``: float32 at the highest matmul
precision, the delta rule as the token-by-token recurrence, sharing no code with
the program): Gated DeltaNet in its chunked and recurrent forms (a rectangular
state, one scalar decay a head, beta in (0, 2)), the state slots that live
beside a per-head page pool in one cache manager, position-free multi-head
attention under whole-width q/k norms, norms on every sublayer's output, and
the page pool of a head count that neither fills nor divides the 8-sublane tile.

The toy (``benchmark/tests/toy/olmo_hybrid.json``) is two periods of three Gated
DeltaNet layers (3 heads of 8 keys and 16 values) to one attention layer (3
heads of 16). Every tolerance has its reason and a control that fails it beside
it. Weights are seeded with every scale, ``A_log`` and ``dt_bias`` non-trivial
(``harness/families/olmo_hybrid.py``), so a dropped term shows.
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

from harness import opcount, program, registry, weights  # noqa: E402
from harness.families import olmo_hybrid as family  # noqa: E402
from references import olmo_hybrid as ref  # noqa: E402
from references.common import int8_fake_quant  # noqa: E402

from pretraining_llm_tpu.config import ModelConfig, get_preset  # noqa: E402
from pretraining_llm_tpu.generation import paged  # noqa: E402
from pretraining_llm_tpu.generation.generate import generate  # noqa: E402
from pretraining_llm_tpu.generation.serving import ServingEngine  # noqa: E402
from pretraining_llm_tpu.models import gdn, kda, recurrent, transformer as tr  # noqa: E402
from pretraining_llm_tpu.ops import pallas_paged  # noqa: E402
from pretraining_llm_tpu.training.optimizer import decay_mask  # noqa: E402

from tests import test_pallas_kda as kernel_paths  # noqa: E402  the decode paths and the jaxpr walk of the kernel's own tests
from tests.test_granite import _teacher_forced  # noqa: E402  one helper for both state-slot families

with open(os.path.join(BENCH, "tests", "toy", "olmo_hybrid.json")) as f:
    TOY = dict(json.load(f), name="olmo-hybrid-toy")
# float32 throughout: the program's arithmetic then differs from the reference's
# by the order of its sums alone, and the tolerances below can be tight.
ARCH = dict(TOY, serving_dtype="float32",
            program_model={"attention_impl": "naive", "param_dtype": "float32", "compute_dtype": "float32"})
CFG = program.model_config(ARCH, 128)
SEEDS = (3, 2 ** 31 + 5)

# Relative error of logits, ||program - reference|| / ||reference||. The sound
# float32 program reads 4e-6 to 1.1e-5 on the forward pass here (chunked form
# against the reference's recurrence: the same sums in another order, carried
# through eight layers that read the residual un-normed); the least of the
# reference's own controls reads 1.3e-3 (the softmax rounded to bfloat16), then
# 2.8e-2 (the state rounded to bfloat16 after every token) and 0.25 and more
# for the rest. 1e-4 lies 9 x over the one and 13 x under the other.
LOGITS_TOL = 1e-4


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
    assert CFG.layer_mixers == ("gdn", "gdn", "gdn", "attn") * 2
    assert CFG.layer_kinds == tuple((m, "dense") for m in CFG.layer_mixers)
    assert CFG.layer_runs == ((0, 3), (3, 4), (4, 7), (7, 8)) and CFG.state_mixer == "gdn" and CFG.hybrid
    assert CFG.n_state_layers == 6 and CFG.pos_embed == "none" and CFG.norm_placement == "output"
    assert CFG == dataclasses.replace(get_preset("olmo-hybrid-toy").model, context_length=128, param_dtype="float32",
                                      compute_dtype="float32")
    p = params[SEEDS[0]]
    stacked = {k: jax.tree.leaves(v)[0].shape[0] for k, v in p.items() if k.endswith("blocks")}
    assert stacked == {"blocks": 6, "attn_blocks": 2}
    assert "w_in" in p["blocks"]["attn"] and "wqkv" in p["attn_blocks"]["attn"] and "lm_head" in p
    assert p["attn_blocks"]["attn"]["q_norm"]["scale"].shape == (2, 3 * 16)  # the whole width, not a head's
    shapes = lambda t: jax.tree.map(lambda a: a.shape, t)
    assert shapes(jax.eval_shape(lambda k: tr.init_params(CFG, k), jax.random.key(0))) == shapes(p)


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_matches_the_reference(params, seed):
    toks = tokens(seed, 100)  # a whole chunk of 64 and a ragged one
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
        assert np.linalg.norm(np.asarray(g, np.float64) - w) <= 1e-3 * np.linalg.norm(w) + 1e-9, name


def test_parameter_count_is_the_tree_and_a_hand_count(params):
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params[SEEDS[0]]))
    m = opcount.dims(ARCH)
    # the program's attention layers carry a zero output bias of d that the model does not have
    assert n == CFG.num_params() == opcount.num_params(ARCH) + m["attn_layers"] * m["d"]
    assert family.gdn_params(m) == CFG._gdn_params()
    # the published widths, by hand (ISSUE 54): a Gated DeltaNet layer, a full layer, embedding + head + final norm
    real = registry.load_config("olmo-hybrid-7b")
    big = opcount.dims(real)
    d, h, dk, dv, f, v = 3840, 30, 96, 192, 11008, 100352
    c = h * (2 * dk + dv)
    mixer = d * c + 4 * c + 2 * d * h + 2 * d * h * dv + 2 * h + dv
    assert (mixer, c) == (88_750_332, 11_520) and family.gdn_params(big) == mixer
    assert family.layer_params(big) == mixer + 3 * d * f + 2 * d == 215_570_172
    assert family.attn_layer_params(big) == 4 * d * d + 2 * d + 3 * d * f + 2 * d == 185_809_920
    assert family.other_params(big)[0] - 2 * family.attn_layer_params(big) == 2 * v * d + d == 770_707_200
    cfg = program.model_config(real, 4096)
    assert cfg.num_params() == opcount.num_params(real) + 2 * d == 2_435_748_072 + 2 * d
    assert family.state_bytes_per_row(real) == 6 * (2_211_840 + 69_120)
    assert recurrent.state_shapes(cfg, 129)["state"][0] == (129, 30, 96, 192)


@pytest.mark.parametrize("control", ref.CONTROLS[1:] + ("int8",))
def test_each_control_of_the_reference_fails(control):
    seed = SEEDS[0]
    toks = tokens(seed, 100)
    kw = dict(quant=int8_fake_quant) if control == "int8" else dict(control=control)
    # measured at 150 tokens: bf16 softmax 1.3e-3, bf16 state 2.8e-2, per-head q/k norm 0.25, rotary 0.55,
    # per-channel decay 0.62, beta in (0, 1) 0.96, pre-norm 1.2, a dropped norm 1.3
    assert rel_err(reference_logits(seed, toks, **kw), reference_logits(seed, toks)) > 5 * LOGITS_TOL


@pytest.mark.parametrize("changed", [
    dict(norm_placement="input"), dict(gdn_allow_neg_eigval=False), dict(pos_embed="rope"),
], ids=["pre-norm", "beta-in-0-1", "rotary"])
def test_the_program_with_a_mechanism_changed_is_not_the_reference(params, changed):
    seed = SEEDS[0]
    toks = tokens(seed, 48)
    logits, _ = tr.forward(params[seed], toks[None], dataclasses.replace(CFG, **changed))
    assert rel_err(logits[0], reference_logits(seed, toks)) > 1000 * LOGITS_TOL


# -- 2. the chunked form against the recurrence ---------------------------------------


def _recurrence_inputs(rng, rows, t, h=3, k=8, v=16):
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, kk = kda._l2(f(rows, t, h, k)) * k ** -0.5, kda._l2(f(rows, t, h, k))
    g = -jnp.exp(f(h)) * jax.nn.softplus(f(rows, t, h) - 2.0)
    beta = 2.0 * jax.nn.sigmoid(f(rows, t, h))
    return q, kk, f(rows, t, h, v), g, beta


@pytest.mark.parametrize("start", ["zero", "nonzero"])
@pytest.mark.parametrize("t", [128, 100, 64, 9],
                         ids=["whole-chunks", "ends-inside-a-chunk", "one-chunk", "shorter-than-a-chunk"])
def test_chunked_is_the_recurrence(start, t):
    rng = np.random.default_rng(t)
    q, k, v, g, beta = _recurrence_inputs(rng, 2, t)
    assert float(beta.max()) > 1.5 and float(beta.min()) < 0.5  # both sides of 1: negative eigenvalues present
    s0 = jnp.zeros((2, 3, 8, 16)) if start == "zero" else jnp.asarray(rng.normal(size=(2, 3, 8, 16)), jnp.float32)
    s, os_ = s0, []
    for i in range(t):
        o, s = kda.recurrent_step(s, q[:, i], k[:, i], v[:, i], g[:, i, :, None], beta[:, i])
        os_.append(o)
    o2, s2 = gdn.chunked(s0, q, k, v, g, beta)
    assert rel_err(o2, np.asarray(jnp.stack(os_, axis=1))) < 5e-6 and rel_err(s2, np.asarray(s)) < 5e-6


def test_a_scalar_gate_is_kdas_gate_the_same_on_every_channel():
    """One delta rule, two gates: ``kda.chunked`` handed the head's scalar on every
    channel is ``gdn.chunked``, rectangular state and all."""
    q, k, v, g, beta = _recurrence_inputs(np.random.default_rng(5), 1, 80)
    s0 = jnp.zeros((1, 3, 8, 16))
    o1, s1 = gdn.chunked(s0, q, k, v, g, beta)
    o2, s2 = kda.chunked(s0, q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta)
    assert rel_err(o1, np.asarray(o2)) < 5e-6 and rel_err(s1, np.asarray(s2)) < 5e-6


def test_a_bucket_padded_prompt_leaves_the_state_and_the_tail_of_its_last_real_token(params):
    """The mixer over 70 real tokens padded to 128, with the true lengths: the
    state, the conv tail and the real positions' outputs are the unpadded run's,
    row by row (rows of unlike lengths in one bucket, one ending inside a chunk,
    one shorter than the conv's reach); without the lengths they are not."""
    p = jax.tree.map(lambda a: a[0], params[SEEDS[0]]["blocks"])["attn"]
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(2, 128, CFG.d_model)), jnp.float32)
    shapes = gdn.state_shapes(CFG, 2)
    state = jnp.asarray(rng.normal(size=shapes["state"][0]), jnp.float32)
    tail = jnp.asarray(rng.normal(size=shapes["conv"][0]), jnp.float32)
    lens = jnp.asarray([70, 2], jnp.int32)
    valid = jnp.arange(128)[None, :] < lens[:, None]
    y, s, c = gdn.mix(p, h, CFG, state, tail, valid, lens)
    for row, n in enumerate((70, 2)):
        y1, s1, c1 = gdn.mix(p, h[row : row + 1, :n], CFG, state[row : row + 1], tail[row : row + 1])
        assert rel_err(y[row, :n], np.asarray(y1[0])) < 1e-5
        assert rel_err(s[row], np.asarray(s1[0])) < 1e-5 and rel_err(c[row], np.asarray(c1[0])) < 1e-6
    _, s_blind, c_blind = gdn.mix(p, h, CFG, state, tail)
    assert rel_err(s_blind[0], np.asarray(s[0])) > 1e-2 and rel_err(c_blind[0], np.asarray(c[0])) > 1e-2


def test_the_decode_step_is_one_more_token_of_the_prefill(params):
    """``mix`` over n tokens then one (the recurrence) is ``mix`` over n + 1 (the chunked form)."""
    p = jax.tree.map(lambda a: a[1], params[SEEDS[0]]["blocks"])["attn"]
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(3, 21, CFG.d_model)), jnp.float32)
    zeros = {k: jnp.zeros(*v) for k, v in gdn.state_shapes(CFG, 3).items()}
    y_all, s_all, c_all = gdn.mix(p, h, CFG, zeros["state"], zeros["conv"])
    _, s, c = gdn.mix(p, h[:, :20], CFG, zeros["state"], zeros["conv"])
    y_last, s, c = gdn.mix(p, h[:, 20:], CFG, s, c)
    assert rel_err(y_last[:, 0], np.asarray(y_all[:, 20])) < 1e-5
    assert rel_err(s, np.asarray(s_all)) < 1e-5 and rel_err(c, np.asarray(c_all)) < 1e-6
    for pool in ((129, 30, 96, 192), (129, 32, 128, 128)):  # the cell's pool as it lies, and Ling's
        assert gdn.step_form(jax.ShapeDtypeStruct(pool, jnp.float32), backend="tpu") == "kernel"


# -- 2b. the one-token step as ``ops/pallas_kda.py``'s kernel (interpreted here) --------

# the toy with the published state a head: 96 keys (12 sublane tiles) by 192 values (a lane tile and a half)
WIDE_ARCH = dict(ARCH, linear_key_head_dim=96, linear_value_head_dim=192)
WIDE = program.model_config(WIDE_ARCH, 128)


@pytest.fixture(scope="module")
def wide_params():
    return weights.serving_params(WIDE_ARCH, SEEDS[0])


@pytest.fixture
def on_a_tpu(monkeypatch):
    """``gdn.step_form`` answers as on a TPU. A jitted program keeps the form it
    was traced with, so the caches go before and after."""
    monkeypatch.setattr(gdn, "step_form", functools.partial(kda.step_form, backend="tpu"))
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("path", kernel_paths.PATHS.values(), ids=kernel_paths.PATHS)
def test_decode_through_the_kernel_is_decode_through_the_jnp_form(wide_params, on_a_tpu, monkeypatch, path):
    """Three rows and a dead one prefilled and stepped three times through the
    state slots, over the pools as they lie and through gathered slots: logits
    and slots of the kernel are those of ``kda.recurrent_step``'s four lines."""
    got, got_pools = path(wide_params, cfg=WIDE)
    monkeypatch.setattr(gdn, "step_form", lambda *a, **k: "jnp")
    jax.clear_caches()
    want, want_pools = path(wide_params, cfg=WIDE)
    assert rel_err(got, want) < 1e-5
    states = [(g["state_pool"], w["state_pool"]) for g, w in zip(got_pools["layers"], want_pools["layers"])
              if "state_pool" in g]
    assert len(states) == 6 and states[0][0].shape == (5, 3, 96, 192)
    for held, wanted in states:
        assert rel_err(held[:3], np.asarray(wanted[:3])) < 1e-5
        np.testing.assert_array_equal(np.asarray(held[3:]), 0.0)  # the dead row's slot and the scratch slot: nothing written


def test_the_decode_step_traces_the_kernel_under_gdn_step(wide_params, on_a_tpu):
    pools, tables, lens = kernel_paths._prefilled(wide_params, cfg=WIDE)
    step = lambda: paged.paged_decode_logits(
        wide_params, pools, jnp.zeros((4,), jnp.int32), jnp.asarray(tables), jnp.asarray(lens), cfg=WIDE)
    calls = kernel_paths._pallas_calls(step)
    assert len(calls) == 6 and all("gdn.step" in path for path, _ in calls)  # one a Gated DeltaNet layer
    assert all(call.invars[5].aval.shape == (5, 3, 96, 192) and dict(call.params["input_output_aliases"]) == {5: 1}
               for _, call in calls)


# -- 3. prefill then decode through slots and pool ------------------------------------


@pytest.mark.parametrize("readmit", [False, True], ids=["steady", "preempted-and-readmitted"])
def test_paged_decode_matches_the_reference(params, readmit):
    seed = SEEDS[0]
    prompt_lens, steps = (21, 9, 70), 6  # 70: a whole chunk of prompt and a ragged one
    seqs = [tokens(seed + r, n + steps) for r, n in enumerate(prompt_lens)]
    got = _teacher_forced(params[seed], seqs, prompt_lens, steps, cfg=CFG,
                          readmit_row=1 if readmit else None, readmit_at=3 if readmit else None)
    for toks, n, rows in zip(seqs, prompt_lens, got):
        want = reference_logits(seed, toks)[n : n + steps]  # row t scores token t + 1
        assert rel_err(rows, want) < LOGITS_TOL


def test_the_state_slots_are_the_reference_scans_state(params):
    """What the cell's `correct` holds beside the logits (``harness/ssm_check``,
    unedited: the family meets its hooks): after a prefill and teacher-forced
    steps through slots and pool, each sampled row's slot in every Gated DeltaNet
    layer is the reference scan's state after the same tokens, head by head; a
    state rounded to bfloat16 after every token is not."""
    from harness import serving_check as sc, ssm_check

    seed = SEEDS[0]
    sample = [(21, 6), (70, 6)]
    seqs = sc.sample_tokens(seed, CFG.vocab_size, sample)
    eng = ServingEngine(params[seed], CFG, max_batch=4, n_blocks=64, block_size=8)
    prog, pools = sc.program_logits(params[seed], CFG, eng.pools, eng.alloc, eng.max_batch, eng.max_blocks,
                                    eng.block_size, sample, seqs)
    held = ssm_check.slot_states(pools, len(sample))
    want, states, rate = ssm_check.reference(ARCH, seed, sample, seqs)
    assert held.shape == states.shape == (2, 6, CFG.gdn_heads, CFG.gdn_key_dim, CFG.gdn_value_dim)
    assert rate.shape == (6, CFG.gdn_heads) and sc.rel_err(prog, want) < LOGITS_TOL
    sound = ssm_check.head_errors(held, states)
    assert sound.max() < 1e-4
    _, rounded, _ = ssm_check.reference(ARCH, seed, sample, seqs, control="bf16_state")
    departed = ssm_check.head_errors(rounded, states)
    assert departed.min() > 10 * sound.max()
    assert ssm_check.state_rel_err(departed, rate) > 30 * ssm_check.state_rel_err(sound, rate)
    assert ssm_check.state_rel_err(departed, rate, slice(0, 1)) > 30 * ssm_check.state_rel_err(sound, rate, slice(0, 1))


def test_pools_give_pages_to_the_attention_layers_and_a_slot_a_row_to_the_rest():
    pools = jax.eval_shape(lambda: tr.make_paged_kv_pool(CFG, 16, 8, state_slots=3))
    kinds = [sorted(layer) for layer in pools["layers"]]
    assert kinds == ([["conv_pool", "state_pool"]] * 3 + [["k_pool", "v_pool"]]) * 2
    assert pools["layers"][0]["state_pool"].shape == (4, 3, 8, 16)  # 3 rows and the scratch slot
    assert pools["layers"][0]["state_pool"].dtype == jnp.float32
    assert pools["layers"][0]["conv_pool"].shape == (4, 3, 3 * (8 + 8 + 16))
    assert pools["layers"][3]["k_pool"].shape == (16, 8, 3, 16) and "state_cursor" in pools
    assert paged.state_slots(pools) == 3 and paged.pool_block_size(pools, CFG) == 8
    assert recurrent.state_shapes(CFG, 3) == gdn.state_shapes(CFG, 3)


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
    """Six requests through three rows, with nothing in the engine that knows
    this mixer by name: every slot is reused by a later row, under the pipelined
    scheduler, through the chunk lane, and with a pool of 13 pages that preempts
    and recomputes."""
    prompts, want = greedy
    eng = ServingEngine(params[SEEDS[0]], CFG, max_batch=3, block_size=8, **kw)
    rids = [eng.submit(pr, 10) for pr in prompts]
    out = eng.run()
    assert [out[r] for r in rids] == want
    assert eng.stats["state_slots_peak"] == 3
    assert (eng.stats["preemptions"] > 0) == (kw["n_blocks"] == 14)
    info = eng.pool_info()
    per_slot = 6 * (4 * 3 * 8 * 16 + 4 * 3 * 3 * (8 + 8 + 16))  # 6 layers, float32: a state and a 3-row tail
    assert info["state_slots"] == 3 and info["bytes_per_slot"] == per_slot and info["state_bytes"] == 4 * per_slot
    assert info["pool_bytes"] == kw["n_blocks"] * 8 * 2 * 2 * 3 * 16 * 4  # two attention layers' K and V pages
    assert (info["state_mixer"], info["state_layers"], info["page_layers"]) == ("gdn", 6, 2)
    assert info["decode_state"] == "jnp" and info["decode_attention"] == "gather" and info["pool_kv_heads"] == 3
    # the serving layout lays out the dense SwiGLU of both kinds of layer (PR 45's halves)
    assert all("w1_gate" in eng.params[key]["mlp"] and "w1" not in eng.params[key]["mlp"]
               for key in ("blocks", "attn_blocks"))


@pytest.mark.parametrize("ragged", [False, True])
def test_generate_runs_the_ragged_and_the_bucketed_path(params, greedy, ragged):
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


@pytest.mark.parametrize("kw,message", [
    (dict(layer_mixers=("gdn",) * 8), "attention layers"),
    (dict(layer_mixers=("gdn", "mamba", "gdn", "attn") * 2), "recurrent layers of one kind"),
    (dict(gdn_heads=0), "gdn_heads"),
    (dict(gdn_conv_kernel=1), "gdn_conv_kernel"),
    (dict(kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, pos_embed="rope",
          qk_norm_whole=False, norm_placement="input"), "per-head attention"),
    (dict(norm_placement="sandwich"), "norm_placement"),
    (dict(sandwich_norm=True), "sandwich"),
    (dict(qk_norm=True), "qk_norm"),
    (dict(kv_cache_dtype="int8"), "int8"),
])
def test_the_configuration_refuses_by_name(kw, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(get_preset("olmo-hybrid-toy").model, **kw)


@pytest.mark.parametrize("kw,message", [
    (dict(norm_placement="output", hc_mult=4), "norm_placement"),
    (dict(norm_placement="output", mtp_depth=1, pos_embed="rope"), "norm_placement"),
    (dict(qk_norm_whole=True, qk_norm=True), "qk_norm_whole"),
])
def test_the_two_norm_fields_refuse_what_they_are_not_built_with(kw, message):
    with pytest.raises(ValueError, match=message):
        ModelConfig(d_model=64, n_heads=4, **kw)
    with pytest.raises(ValueError, match="norm_placement"):
        dataclasses.replace(get_preset("ling-mini").model, norm_placement="output")


def test_a_json_round_trip_keeps_the_table():
    cfg = get_preset("olmo-hybrid-toy").model
    again = ModelConfig(**json.loads(json.dumps(dataclasses.asdict(cfg))))
    assert again == cfg and hash(again) == hash(cfg) and isinstance(again.layer_mixers, tuple)


def test_the_new_leaves_that_set_time_scales_do_not_decay(params):
    mask = decay_mask(params[SEEDS[0]])
    a = mask["blocks"]["attn"]
    assert all((a["w_in"], a["wa"], a["wbeta"], a["wg"], a["wo"]))
    assert not any((a["conv"], a["A_log"], a["dt_bias"], a["o_norm"]["scale"]))
    q = mask["attn_blocks"]["attn"]
    assert q["wqkv"] and not q["q_norm"]["scale"] and not q["k_norm"]["scale"]


# -- 5. a page pool of a head count that neither fills nor divides 8 -------------------


@pytest.mark.parametrize("kv_heads,head_dim,stored", [
    (30, 128, 32), (9, 128, 16), (12, 256, 16),  # padded to the next multiple of 8
    (8, 128, 8), (32, 128, 32), (2, 128, 2), (4, 128, 4), (1, 128, 1),  # fill or divide 8: as they are
    (3, 128, 3), (6, 128, 6),  # up to 8 heads: a pad would multiply the pool
    (30, 64, 30), (12, 16, 12),  # narrow heads: the kernel takes them neither way
])
def test_the_pools_head_axis(kv_heads, head_dim, stored):
    assert pallas_paged.pool_kv_heads(kv_heads, head_dim) == stored
    cfg = ModelConfig(d_model=64, n_heads=kv_heads * 2, n_kv_heads=kv_heads, d_head=head_dim, n_layers=1)
    pools = jax.eval_shape(lambda: tr.make_paged_kv_pool(cfg, 4, 8))
    assert pools["layers"][0]["k_pool"].shape == (4, 8, stored, head_dim)
    takes = head_dim % 128 == 0 and (stored % 8 == 0 or 8 % stored == 0)
    assert tr.paged_attention_form(cfg, 1, False, backend="tpu") == ("kernel" if takes else "gather")
    assert tr.paged_attention_form(cfg, 2, False, backend="tpu") == "gather"
    x = jnp.ones((2, 5, kv_heads, head_dim))
    padded = pallas_paged.pad_kv_heads(x, stored)
    assert padded.shape == (2, 5, stored, head_dim) and (padded is x) == (stored == kv_heads)
    assert float(jnp.abs(padded[:, :, kv_heads:]).sum()) == 0.0


@pytest.mark.parametrize("config,stored,form", [
    ("mistral-7b-v0.1", (8, 128), "kernel"), ("trinity-mini", (4, 128), "kernel"),
    ("granite-4.0-h-small", (8, 128), "kernel"), ("olmo-hybrid-7b", (32, 128), "kernel"),
])
def test_the_cells_per_head_pools(config, stored, form):
    """The accepted per-head cells' pools are what they were (Mistral's and
    Granite's 8 KV heads of 128, Trinity's 4: the model's own head count, the
    in-place kernel); this model's 30 are stored as 32 and read in place too."""
    cfg = program.model_config(registry.load_config(config), 4096)
    kw = dict(state_slots=2) if cfg.hybrid else dict(window_blocks=4) if cfg.two_lifetimes else {}
    pools = jax.eval_shape(lambda: tr.make_paged_kv_pool(cfg, 4, 64, **kw))
    for f in pools["layers"]:
        if "k_pool" in f:
            assert f["k_pool"].shape[-2:] == f["v_pool"].shape[-2:] == stored
    assert stored[0] == cfg.kv_heads or config == "olmo-hybrid-7b"
    assert tr.paged_attention_form(cfg, 1, False, backend="tpu") == form
    assert tr.paged_attention_form(cfg, 1, False, backend="cpu") == "gather"


def test_a_padded_pool_gives_the_kernel_its_pages_and_the_gather_forms_logits(monkeypatch):
    """Ten KV heads of 128 (neither filling nor dividing 8, as the published 30)
    are stored as 16: the decode step takes the in-place kernel (interpreted
    here) and gives the logits of the gather form, prefill pages and the step's
    own token alike, and the padding heads hold zeros."""
    cfg = ModelConfig(vocab_size=64, context_length=64, d_model=32, n_heads=10, d_head=128, n_layers=2,
                      activation="swiglu", norm="rmsnorm", pos_embed="none", tie_embeddings=False, mlp_bias=False,
                      qk_norm_whole=True, norm_placement="output", param_dtype="float32", compute_dtype="float32")
    p = tr.init_params(cfg, jax.random.key(1))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, n).tolist() for n in (19, 7)]
    logits = {}
    for form in ("gather", "kernel"):
        if form == "kernel":
            monkeypatch.setattr(tr, "paged_attention_form",
                                functools.partial(tr.paged_attention_form, backend="tpu"))
            jax.clear_caches()
        pools = tr.make_paged_kv_pool(cfg, 16, 8)
        assert pools["layers"][0]["k_pool"].shape == (16, 8, 16, 128)
        tables = np.zeros((2, 4), np.int32)
        for r, (pr, ids) in enumerate(zip(prompts, ([1, 2, 3, 4], [5, 6]))):
            _, pools = paged.prefill_into_pool(p, cfg, pools, pr, ids[: paged.required_blocks(len(pr), 8)])
            tables[r, : len(ids)] = ids
        seq = np.asarray([19, 7], np.int32)
        steps = []
        for j in range(3):
            out, pools = paged.paged_decode_logits(
                p, pools, jnp.asarray([3 + j, 9 + j], jnp.int32), jnp.asarray(tables), jnp.asarray(seq + j), cfg=cfg)
            steps.append(np.asarray(out))
        logits[form] = np.stack(steps)
        k_pool = np.asarray(pools["layers"][0]["k_pool"])
        assert np.abs(k_pool[1:3, :, :10]).max() > 0 and np.abs(k_pool[:, :, 10:]).max() == 0
    jax.clear_caches()
    assert rel_err(logits["kernel"], logits["gather"]) < 1e-5
    # the kernel itself: 10 real heads in a head axis of 16, against the plain reference of paged attention
    q = jnp.asarray(rng.normal(size=(2, 10, 128)), jnp.float32)
    kp, vp = (jnp.asarray(rng.normal(size=(6, 8, 16, 128)), jnp.float32) for _ in range(2))
    tbl, sl = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32), jnp.asarray([20, 9], jnp.int32)
    got = pallas_paged.paged_decode_attention(q, kp, vp, tbl, sl, kv_heads=10, interpret=True)
    want = pallas_paged.gather_attention(q[:, None], kp[:, :, :10], vp[:, :, :10], tbl, sl, jnp.asarray([1, 1]))
    assert rel_err(got, np.asarray(want[:, 0])) < 1e-5
