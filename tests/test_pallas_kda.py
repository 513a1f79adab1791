"""ops/pallas_kda.py (interpreted here): one token of the KDA recurrence as a
kernel against ``kda.recurrent_step``, the four ``jnp`` lines it replaces on
the TPU, over KDA's square state of whole lane tiles and over Gated DeltaNet's
96 x 192 under one decay a head; the rule that picks the form
(``kda.step_form``); and a toy Ling stack decoded through its state slots with
the kernel forced, on both paths that reach ``kda.mix`` with one token a row.
The compiled kernel is heard on the chip and, at the cells' sizes, by the
at-size compiles in ``tests/test_pallas_latent.py``."""

import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.generation import paged
from pretraining_llm_tpu.generation.generate import generate
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import kda, transformer
from pretraining_llm_tpu.ops import pallas_kda as pk

K = V = 128
LOWER = -5.0  # kda_gate_lower_bound of the family: the strongest decay a token

# float32 on both sides and the same products: what differs is the order of the
# two sums over K (128 terms of size <= |S| |k|). 1e-5 of the largest value is
# 100 x the rounding of such a sum and 1,000 x under a dropped term.
TOL = 1e-5


GDN = (96, 192)  # a Gated DeltaNet head of the Olmo-Hybrid cell: 12 sublane tiles by a lane tile and a half


def _inputs(rows, heads, seed=0, state="random", g="random", shape=(K, V)):
    """``shape`` other than KDA's square: Gated DeltaNet's operands, one decay a
    head broadcast over K as ``models/gdn.py`` hands it, beta in (0, 2)."""
    kdim, vdim = shape
    gdn = shape != (K, V)
    ks = jax.random.split(jax.random.key(seed), 6)
    s = jax.random.normal(ks[0], (rows, heads, kdim, vdim), jnp.float32)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[1], (rows, heads, kdim))) * kdim ** -0.5
    k = unit(jax.random.normal(ks[2], (rows, heads, kdim)))
    v = jax.random.normal(ks[3], (rows, heads, vdim))
    gate = LOWER * jax.nn.sigmoid(jax.random.normal(ks[4], (rows, heads, 1 if gdn else kdim)))
    gate = jnp.broadcast_to(gate, (rows, heads, kdim))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (rows, heads))) * (2.0 if gdn else 1.0)
    if state == "zero":
        s = jnp.zeros_like(s)
    if g != "random":
        gate = jnp.full_like(gate, {"lower-bound": LOWER, "zero": 0.0}[g])
    return s, q, k, v, gate, beta


def _pallas_calls(fn):
    """[(name stack, equation)] of every ``pallas_call`` in the jaxpr of ``fn()``."""
    found = []

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            path = f"{outer}/{eqn.source_info.name_stack}"  # a call's body names its scopes from the call on
            if eqn.primitive.name == "pallas_call":
                found.append((path, eqn))
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, path)

    walk(jax.make_jaxpr(fn)().jaxpr, "")
    return found


def _close(got, want):
    scale = max(float(jnp.abs(want).max()), 1e-6)
    assert float(jnp.abs(got - want).max()) <= TOL * scale


CASES = {
    "random-state": dict(state="random"),
    "zero-state": dict(state="zero"),
    "g-at-its-lower-bound": dict(g="lower-bound"),
    "g-zero": dict(g="zero"),
}


# heads and the state a head: KDA's, one head and the Ling cell's 32; Gated DeltaNet's at the Olmo-Hybrid cell's 30
HEADS = {"1": (1, (K, V)), "32": (32, (K, V)), "30-of-96x192-under-a-scalar-gate": (30, GDN)}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES)
@pytest.mark.parametrize("heads,shape", HEADS.values(), ids=HEADS)
@pytest.mark.parametrize("rows", [1, 3, 129])
def test_kernel_is_the_recurrent_step(rows, heads, shape, case):
    args = _inputs(rows, heads, seed=rows + heads, shape=shape, **case)
    o, s = pk.recurrent_step(*args)
    want_o, want_s = kda.recurrent_step(*args)
    assert o.shape == want_o.shape and s.shape == want_s.shape and o.dtype == s.dtype == jnp.float32
    _close(o, want_o)
    _close(s, want_s)


SHAPES = {"kda-128x128": (K, V), "gdn-96x192": GDN}
PART_GROUPS = {"half-a-group": (4, (K, V)), "a-group-and-a-half": (12, (K, V)), "gdn-a-group-and-six": (14, GDN),
               "a-toys-16x16": (4, (16, 16)), "one-sublane-tile-of-K": (3, (8, 16)), "K-past-a-lane-tile": (2, (136, 64))}


@pytest.mark.parametrize("heads,shape", PART_GROUPS.values(), ids=PART_GROUPS)
def test_heads_that_fill_no_whole_group(heads, shape):
    args = _inputs(2, heads, seed=5, shape=shape)
    for got, want in zip(pk.recurrent_step(*args), kda.recurrent_step(*args)):
        _close(got, want)


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES)
def test_a_dropped_term_fails_the_tolerance(shape):
    """The control of ``TOL``: the step with the rank-one update left out."""
    s, q, k, v, g, beta = _inputs(3, 4, seed=9, shape=shape)
    o, new = pk.recurrent_step(s, q, k, v, g, beta)
    decayed = s * jnp.exp(g)[..., None]
    with pytest.raises(AssertionError):
        _close(new, decayed)
    with pytest.raises(AssertionError):
        _close(o, jnp.einsum("bhkv,bhk->bhv", decayed, q))


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES)
def test_a_dead_row_leaves_its_state_bit_for_bit(shape):
    s, q, k, v, g, beta = _inputs(3, 8, seed=2, shape=shape)
    g, beta = g.at[1].set(0.0), beta.at[1].set(0.0)
    _, new = pk.recurrent_step(s, q, k, v, g, beta)
    np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(s[1]))
    assert float(jnp.abs(new[0] - s[0]).max()) > 0 and float(jnp.abs(new[2] - s[2]).max()) > 0


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES)
def test_the_new_state_takes_the_states_buffer(shape):
    """The call aliases the state onto its second result, so a caller that
    donates the state (the decode programs donate the pools) gets the new state
    where the old one lay and nothing of the state's size beside it."""
    args = _inputs(3, 8, seed=4, shape=shape)
    want_o, want_s = kda.recurrent_step(*args)
    ((_, call),) = _pallas_calls(lambda: pk.recurrent_step(*args))
    assert dict(call.params["input_output_aliases"]) == {5: 1}
    assert call.invars[5].aval.shape == call.outvars[1].aval.shape == args[0].shape
    step = jax.jit(lambda s, *rest: pk.recurrent_step(s, *rest), donate_argnums=0)
    state = jnp.array(args[0])  # a copy to give away
    o, new = step(state, *args[1:])
    assert state.is_deleted()
    _close(o, want_o)
    _close(new, want_s)


REFUSED = {
    "K-half-a-sublane-tile": lambda s, q, k, v, g, b: (s[:, :, :4], q[..., :4], k[..., :4], v, g[..., :4], b),
    "K-not-whole-sublane-tiles": lambda s, q, k, v, g, b: (s[:, :, :100], q[..., :100], k[..., :100], v, g[..., :100], b),
    "bfloat16-state": lambda s, q, k, v, g, b: (s.astype(jnp.bfloat16), q, k, v, g, b),
    "a-rows-heads-past-the-block": lambda s, q, k, v, g, b: tuple(
        jnp.concatenate([a] * 9, axis=1) for a in (s, q, k, v, g, b)),
    # 48 heads of 128 x 129 are 3.2 MB as numbers and 6.3 MB as they lie, V padded to two lane tiles
    "a-padded-block-past-the-block": lambda s, q, k, v, g, b: tuple(
        jnp.concatenate([a] * 6, axis=1) for a in (jnp.pad(s, ((0, 0),) * 3 + ((0, 1),)), q, k,
                                                   jnp.pad(v, ((0, 0),) * 2 + ((0, 1),)), g, b)),
    "no-row-axis": lambda s, q, k, v, g, b: (s[0], q, k, v, g, b),
    "q-of-another-shape": lambda s, q, k, v, g, b: (s, q[:, :4], k, v, g, b),
    "beta-a-channel": lambda s, q, k, v, g, b: (s, q, k, v, g, g),
}


@pytest.mark.parametrize("wrong", REFUSED.values(), ids=REFUSED)
def test_what_the_kernel_cannot_take_is_refused_by_name(wrong):
    with pytest.raises(ValueError, match="K whole 8-sublane tiles"):
        pk.recurrent_step(*wrong(*_inputs(2, 8)))


# -- who picks the form ---------------------------------------------------------------


def _state(shape=(3, 4, K, V), dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


FORMS = {
    "float32-whole-tiles-on-a-tpu": (_state(), None, "tpu", "kernel"),
    "the-cells-pool": (_state((129, 32, 128, 128)), None, "tpu", "kernel"),
    "two-tiles-of-K": (_state((3, 4, 256, 128)), None, "tpu", "kernel"),
    "on-a-cpu": (_state(), None, "cpu", "jnp"),
    "on-a-gpu": (_state(), None, "gpu", "jnp"),
    "the-backend-it-runs-on": (_state(), None, None, "jnp"),  # the tests run on the CPU
    "under-a-mesh": (_state(), "mesh8", "tpu", "jnp"),
    "bfloat16-state": (_state(dtype=jnp.bfloat16), None, "tpu", "jnp"),
    "the-toys-heads-of-16": (_state((3, 4, 16, 16)), None, "tpu", "kernel"),
    "K-half-a-tile": (_state((3, 4, 64, 128)), None, "tpu", "kernel"),
    "V-a-tile-and-a-half": (_state((3, 4, 128, 192)), None, "tpu", "kernel"),
    "the-olmo-hybrid-cells-pool": (_state((129, 30, 96, 192)), None, "tpu", "kernel"),
    "the-olmo-hybrid-cells-pool-under-a-mesh": (_state((129, 30, 96, 192)), "mesh8", "tpu", "jnp"),
    "the-olmo-hybrid-cells-pool-on-a-cpu": (_state((129, 30, 96, 192)), None, "cpu", "jnp"),
    "the-olmo-hybrid-cells-pool-in-bfloat16": (_state((129, 30, 96, 192), jnp.bfloat16), None, "tpu", "jnp"),
    "K-not-whole-sublane-tiles": (_state((3, 4, 100, 128)), None, "tpu", "jnp"),
    "K-half-a-sublane-tile": (_state((3, 4, 4, 128)), None, "tpu", "jnp"),
    "a-rows-heads-past-the-block": (_state((3, 72, 128, 128)), None, "tpu", "jnp"),
    # 3,932,160 B of numbers, 5,242,880 B as they lie: V = 192 takes two lane tiles a row of K
    "a-padded-block-past-the-block": (_state((3, 40, 128, 192)), None, "tpu", "jnp"),
    "the-same-heads-in-whole-tiles": (_state((3, 40, 128, 128)), None, "tpu", "kernel"),
    "no-heads": (_state((3, 0, 128, 128)), None, "tpu", "jnp"),
}


@pytest.mark.parametrize("state,mesh,backend,form", FORMS.values(), ids=FORMS)
def test_step_form_is_read_from_dtype_shape_mesh_and_backend(state, mesh, backend, form, request):
    mesh = request.getfixturevalue(mesh) if mesh else None
    assert kda.step_form(state, mesh, backend) == form
    assert pk.takes(state.shape, state.dtype) == (form == "kernel" or mesh is not None or backend != "tpu")


# -- a toy Ling stack through its state slots -----------------------------------------

# ling-mini with KDA heads of one lane tile, float32 as the kernel's state is:
# two periods of 2 KDA layers to 1 latent layer, 4 heads, experts and all.
CFG = dataclasses.replace(
    get_preset("ling-mini").model, kda_head_dim=128, param_dtype="float32", compute_dtype="float32",
)
BLOCK = 8


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


@pytest.fixture
def on_a_tpu(monkeypatch):
    """``kda.step_form`` answers as on a TPU (the kernel is interpreted here). A
    jitted program keeps the form it was traced with, so the caches go before
    and after."""
    monkeypatch.setattr(kda, "step_form", functools.partial(kda.step_form, backend="tpu"))
    jax.clear_caches()
    yield
    jax.clear_caches()


def _prompts(rows, cfg=CFG):
    return [np.asarray(jax.random.randint(jax.random.key(r), (5 + 3 * r,), 0, cfg.vocab_size)).tolist()
            for r in range(rows)]


def _prefilled(p, rows=3, cfg=CFG):
    """Pools with ``rows`` prompts prefilled, row r's state in slot r; the last
    row stays dead (its table names no page)."""
    pools = transformer.make_paged_kv_pool(cfg, 32, BLOCK, state_slots=rows + 1)
    tables = np.zeros((rows + 1, 4), np.int32)
    lens = np.zeros((rows + 1,), np.int32)
    for r, toks in enumerate(_prompts(rows, cfg)):
        tables[r, :3] = [1 + 3 * r, 2 + 3 * r, 3 + 3 * r]
        lens[r] = len(toks)
        _, pools = paged.prefill_into_pool(
            p, cfg, pools, toks, tables[r, : paged.required_blocks(len(toks), BLOCK)].tolist(), slot=r)
    return pools, tables, lens


def _slot_as_row(p, steps=3, cfg=CFG):
    """Decode steps over the pools as they lie (``paged.slots`` None)."""
    pools, tables, lens = _prefilled(p, cfg=cfg)
    out = []
    for j in range(steps):
        tok = jnp.asarray([7 + j, 11 + j, 13 + j, 0], jnp.int32)
        logits, pools = paged.paged_decode_logits(p, pools, tok, jnp.asarray(tables), jnp.asarray(lens), cfg=cfg)
        out.append(np.asarray(logits[:3]))
        lens[:3] += 1
    return np.stack(out), pools


def _gathered_slots(p, steps=3, cfg=CFG):
    """The same steps with the rows' slots handed in, out of order: each row's
    state gathered, stepped and scattered back (``paged.slots`` given)."""
    pools, tables, lens = _prefilled(p, cfg=cfg)
    order = np.asarray([2, 0, 1])
    out = []
    for j in range(steps):
        tok = jnp.asarray([7 + j, 11 + j, 13 + j], jnp.int32)[order]
        logits, pools = transformer.forward(
            p, tok[:, None], cfg, kv_cache=pools,
            paged=transformer.PagedInfo(jnp.asarray(tables[order]), jnp.asarray(lens[order]), slots=jnp.asarray(order)),
        )
        out.append(np.asarray(logits[:, 0], np.float32)[np.argsort(order)])
        lens[:3] += 1
    return np.stack(out), pools


PATHS = {"slot-as-row": _slot_as_row, "gathered-slots": _gathered_slots}


@pytest.mark.parametrize("path", PATHS.values(), ids=PATHS)
def test_decode_through_the_kernel_is_decode_through_the_jnp_form(params, on_a_tpu, monkeypatch, path):
    got, got_pools = path(params)
    monkeypatch.setattr(kda, "step_form", lambda *a, **k: "jnp")
    jax.clear_caches()
    want, want_pools = path(params)
    assert float(np.abs(got - want).max()) <= 1e-4 * float(np.abs(want).max())
    for got_layer, want_layer in zip(got_pools["layers"], want_pools["layers"]):
        if "state_pool" in got_layer:
            _close(got_layer["state_pool"], want_layer["state_pool"])
            # the dead row's slot and the scratch slot behind it: nothing written
            np.testing.assert_array_equal(np.asarray(got_layer["state_pool"][3:]), 0.0)


def test_both_paths_give_the_same_logits(params, on_a_tpu):
    rows, _ = _slot_as_row(params)
    gathered, _ = _gathered_slots(params)
    assert float(np.abs(rows - gathered).max()) <= 1e-4 * float(np.abs(rows).max())


def test_the_decode_step_traces_the_kernel_under_kda_step(params, on_a_tpu):
    pools, tables, lens = _prefilled(params)
    step = lambda: paged.paged_decode_logits(
        params, pools, jnp.zeros((4,), jnp.int32), jnp.asarray(tables), jnp.asarray(lens), cfg=CFG)
    calls = _pallas_calls(step)
    assert len(calls) == 4 and all("kda.step" in path for path, _ in calls)  # one a KDA layer


def test_off_the_tpu_every_program_keeps_the_jnp_form(params):
    pools, tables, lens = _prefilled(params)
    step = lambda: paged.paged_decode_logits(
        params, pools, jnp.zeros((4,), jnp.int32), jnp.asarray(tables), jnp.asarray(lens), cfg=CFG)
    assert not _pallas_calls(step)


def test_generate_steps_its_contiguous_cache_through_the_same_choice(params, on_a_tpu, monkeypatch):
    prompt = jnp.asarray([_prompts(2)[1]], jnp.int32)
    run = lambda: np.asarray(generate(params, CFG, prompt, 6, jax.random.key(0), temperature=0.0))
    got = run()
    monkeypatch.setattr(kda, "step_form", lambda *a, **k: "jnp")
    jax.clear_caches()
    np.testing.assert_array_equal(got, run())


def test_engine_reports_the_state_steps_form_on_a_tpu(params, on_a_tpu, caplog):
    eng = ServingEngine(params, CFG, max_batch=2, n_blocks=16, block_size=BLOCK)
    assert eng.decode_state == eng.pool_info()["decode_state"] == "kernel"
    eng.submit([1, 2, 3, 4, 5], 4)
    with caplog.at_level(logging.INFO, logger="pretraining_llm_tpu.serving"):
        eng.run()
    lines = [r.getMessage() for r in caplog.records if "engine empty" in r.getMessage()]
    assert len(lines) == 1 and "state slots stepped as kernel" in lines[0]


def test_engine_off_the_tpu_at_the_toys_width_and_without_state_slots(params):
    assert ServingEngine(params, CFG, max_batch=2, n_blocks=16, block_size=BLOCK).pool_info()["decode_state"] == "jnp"
    toy = get_preset("ling-mini").model  # KDA heads of 16: the kernel's on a TPU, not here
    eng = ServingEngine(transformer.init_params(toy, jax.random.key(0)), toy, max_batch=2, n_blocks=16, block_size=BLOCK)
    assert eng.pool_info()["decode_state"] == "jnp"
    dense = get_preset("tiny").model
    eng = ServingEngine(transformer.init_params(dense, jax.random.key(0)), dense, max_batch=2, n_blocks=16, block_size=BLOCK)
    assert eng.decode_state is None and "decode_state" not in eng.pool_info()
