"""The serving layout of a dense SwiGLU's w1 (``transformer.serving_layout``):
the halves the engine and the paged entry points read give the stored tree's
logits, are made once a stored leaf and die with it, leave int8 and sharded
trees alone, leave a stored tree's programs as the parent traced them, and
are counted in ``eng.stats["layout_bytes"]``."""

import dataclasses
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from pretraining_llm_tpu.config import ModelConfig, get_preset
from pretraining_llm_tpu.generation import paged
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import quantize
from pretraining_llm_tpu.models import transformer as tr
from test_xing import _fingerprint

BS = 8
F32 = dict(param_dtype="float32", compute_dtype="float32")
SWIGLU = dict(vocab_size=256, context_length=64, d_model=64, n_heads=4, n_kv_heads=2, n_layers=3,
              activation="swiglu", norm="rmsnorm", pos_embed="rope", tie_embeddings=False)
CONFIGS = {
    "swiglu_gqa": ModelConfig(**SWIGLU, **F32),
    "swiglu_gqa_biased": ModelConfig(**SWIGLU, mlp_bias=True, qkv_bias=True, **F32),
    "gelu": ModelConfig(vocab_size=256, context_length=64, d_model=48, n_heads=4, n_layers=2, activation="gelu",
                        norm="layernorm", pos_embed="learned", tie_embeddings=True, mlp_bias=True, **F32),
    # window and full attention layers, gated: two cache lifetimes over a dense FFN
    "two_lifetimes": ModelConfig(**{**SWIGLU, "n_layers": 4}, sliding_window=16, attn_output_gate=True,
                                 attn_kinds=("window", "window", "full", "window"), **F32),
    # experts behind one leading dense layer (window and full layers too)
    "experts_dense_lead": dataclasses.replace(get_preset("trinity-toy").model, **F32),
}
# the stacks whose w1 the rule lays out, by configuration
LAID = {"swiglu_gqa": ["blocks"], "swiglu_gqa_biased": ["blocks"], "gelu": [], "two_lifetimes": ["blocks"],
        "experts_dense_lead": ["dense_blocks"]}


def stored_tree(name, seed=0):
    params = tr.init_params(CONFIGS[name], jax.random.key(seed))
    # biases start at zero: give them values a dropped or misplaced bias would show
    k = iter(jax.random.split(jax.random.key(seed + 1), 64))
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(next(k), a.shape, a.dtype)
        if str(path[-1].key).startswith("b") and a.ndim >= 2 else a, params)


def paged_logits(params, cfg, toks, p):
    """Logits after a ``p``-token prompt and after each forced token behind it,
    through ``prefill_into_pool`` and ``paged_decode_logits`` (both pools where
    the stack keeps two; every position inside the window)."""
    two = cfg.two_lifetimes
    pools = tr.make_paged_kv_pool(cfg, 8, BS, window_blocks=8 if two else 0)
    ids = list(range(1, paged.required_blocks(len(toks) + 1, BS) + 1))
    n_pre = paged.required_blocks(p, BS)
    last, pools = paged.prefill_into_pool(params, cfg, pools, toks[:p].tolist(), ids[:n_pre],
                                          window_block_ids=ids[:n_pre] if two else None)
    tables = np.zeros((2, 4), np.int32)
    tables[0, : len(ids)] = ids
    out = [np.asarray(last)]
    for j in range(p, len(toks)):
        logits, pools = paged.paged_decode_logits(
            params, pools, jnp.asarray([toks[j], 0], jnp.int32), jnp.asarray(tables),
            jnp.asarray([j, 0], jnp.int32), cfg=cfg, window_tables=jnp.asarray(tables) if two else None)
        out.append(np.asarray(logits[0]))
    return np.stack(out)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_layout_gives_the_stored_trees_logits(name):
    cfg, params = CONFIGS[name], stored_tree(name)
    laid = tr.serving_layout(params, cfg)
    assert [k for k in params if laid[k] is not params[k]] == LAID[name]
    for key in LAID[name]:
        mlp = laid[key]["mlp"]
        assert "w1" not in mlp and mlp["w1_gate"].shape == mlp["w1_up"].shape == params[key]["mlp"]["w1"].shape[:2] + (cfg.d_ff,)
        np.testing.assert_array_equal(np.asarray(mlp["w1_up"]), np.asarray(params[key]["mlp"]["w1"][:, :, 1]))
    toks, p = np.asarray(jax.random.randint(jax.random.key(3), (14,), 0, cfg.vocab_size)), 10
    whole = np.asarray(tr.forward(params, jnp.asarray(toks)[None], cfg)[0][0, p - 1:])
    from_stored = paged_logits(params, cfg, toks, p)  # the entry points lay it out themselves
    from_laid = paged_logits(laid, cfg, toks, p)
    np.testing.assert_array_equal(from_stored, from_laid)  # the same arrays, the same programs
    np.testing.assert_allclose(from_laid, whole, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(tr.forward(laid, jnp.asarray(toks)[None], cfg)[0][0, p - 1:]), whole,
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("what", ["same_arrays", "passes_through", "freed_with_the_stored_tree"])
def test_the_halves_are_made_once_a_stored_leaf_and_die_with_it(what):
    cfg, params = CONFIGS["swiglu_gqa"], stored_tree("swiglu_gqa", seed=7)
    laid = tr.serving_layout(params, cfg)
    if what == "same_arrays":
        again = tr.serving_layout(dict(params), cfg)  # another tree over the same stored leaves
        assert again["blocks"]["mlp"]["w1_gate"] is laid["blocks"]["mlp"]["w1_gate"]
        assert again["blocks"]["mlp"]["w1_up"] is laid["blocks"]["mlp"]["w1_up"]
        for path, leaf in jax.tree_util.tree_leaves_with_path(laid):
            if "w1_" not in str(path):  # every other leaf is the stored tree's own
                assert any(leaf is stored for stored in jax.tree.leaves(params)), path
        other = tr.serving_layout(stored_tree("swiglu_gqa", seed=8), cfg)
        assert other["blocks"]["mlp"]["w1_gate"] is not laid["blocks"]["mlp"]["w1_gate"]
    elif what == "passes_through":
        assert tr.serving_layout(laid, cfg) is laid
        gelu = stored_tree("gelu")
        assert tr.serving_layout(gelu, CONFIGS["gelu"]) is gelu
        shapes = jax.eval_shape(lambda: params)  # nothing on a device: a tree of shapes, a traced tree
        assert tr.serving_layout(shapes, cfg) is shapes
        assert jax.jit(lambda p: tr.serving_layout(p, cfg) is p)(params)
    else:
        held = len(tr._SERVING_COPIES)
        gate = weakref.ref(laid["blocks"]["mlp"]["w1_gate"])
        del laid
        gc.collect()
        assert gate() is not None  # kept for as long as the stored leaf lives
        del params
        gc.collect()
        assert gate() is None and len(tr._SERVING_COPIES) == held - 1


@pytest.mark.parametrize("what", ["int8", "mesh"])
def test_int8_leaves_and_a_sharded_tree_are_left_as_they_are(what, mesh8):
    cfg, params = CONFIGS["swiglu_gqa"], stored_tree("swiglu_gqa")
    if what == "int8":
        tree = quantize.quantize_params_for_serving(params, cfg)
        eng = ServingEngine(params, cfg, max_batch=2, n_blocks=9, block_size=BS, quantize="int8")
        assert eng.stats["layout_bytes"] == 0 and eng.params["blocks"]["mlp"]["w1"].dtype == jnp.int8
    else:
        tree = jax.device_put(params, NamedSharding(mesh8, PartitionSpec()))
    assert tr.serving_layout(tree, cfg) is tree


# (equations, order-free hash) of the stored tree's programs as the parent commit c350700 (PR 44) traces
# them - 343 / 77d25608d9a0a70c, 143 / 1af97aa27b1aee22, 353 / c65d5acae457bc98, 147 / d30f29fd98cda2ca,
# 993 / 8a617d22a4085f06, 437 / 8acc577d133cbb57 in this order, still so at 8c127b4 (PR 47) - with what PR 50
# did to every program that writes no cache: the projections' heads kept as one axis of H*Dh lanes in their
# dots (the same equations at (1, H*Dh) where they stood at (H, Dh)) and the reshapes around them, 7 to 9 a
# layer forward; diffed equation by equation against 8c127b4, by primitive only reshapes were added (and two
# broadcast_in_dim where a bias's cotangent is reshaped back)
PARENTS = {
    ("swiglu_gqa", "train"): (357, "43c0f1b877d45a11"),
    ("swiglu_gqa", "forward"): (150, "a5c26c0ec51f0794"),
    ("swiglu_gqa_biased", "train"): (373, "ce28029dcc240383"),
    ("swiglu_gqa_biased", "forward"): (156, "5bfe9115dcf4c4a0"),
    ("two_lifetimes", "train"): (1035, "5c3372aa914079d6"),
    ("two_lifetimes", "forward"): (458, "a37db01f0f7e961b"),
}


@pytest.mark.parametrize("name,prog", sorted(PARENTS))
def test_a_stored_trees_programs_trace_as_the_parents(name, prog):
    cfg = CONFIGS[name]
    p = jax.eval_shape(lambda k: tr.init_params(cfg, k), jax.random.key(0))
    toks = jnp.zeros((2, 16), jnp.int32)
    if prog == "train":
        got = _fingerprint(jax.grad(lambda p, x, y: tr.loss_fn(p, x, y, cfg)), p, toks, toks)
    else:
        got = _fingerprint(lambda p, x: tr.forward(p, x, cfg)[0], p, toks)
    assert got == PARENTS[(name, prog)]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_engine_counts_the_bytes_it_laid_out(name, caplog):
    cfg, params = CONFIGS[name], stored_tree(name)
    with caplog.at_level("INFO", logger="pretraining_llm_tpu.serving"):
        eng = ServingEngine(params, cfg, max_batch=2, n_blocks=9, block_size=BS)
    want = sum(params[key]["mlp"]["w1"].nbytes for key in LAID[name])
    assert eng.stats["layout_bytes"] == want
    lines = [r.getMessage() for r in caplog.records if "serving layout" in r.getMessage()]
    assert len(lines) == (1 if want else 0)
    for key in LAID[name]:
        assert f"{key}.mlp.w1_gate" in lines[0] and f"{key}.mlp.w1_up" in lines[0]
        assert eng.params[key]["mlp"]["w1_gate"] is tr.serving_layout(params, cfg)[key]["mlp"]["w1_gate"]
