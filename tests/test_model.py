"""Model layer: shapes, param-count parity, numerics vs a hand reference."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.config import ModelConfig, get_preset
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.models.layers import apply_rope, rope_table
from pretraining_llm_tpu.utils.pytree import tree_num_params

TINY = get_preset("tiny").model


def _fp32(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(cfg, compute_dtype="float32")


def test_forward_shapes():
    params = transformer.init_params(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, TINY.context_length), 0, TINY.vocab_size)
    logits, cache = transformer.forward(params, tokens, TINY)
    assert logits.shape == (2, TINY.context_length, TINY.vocab_size)
    assert logits.dtype == jnp.float32
    assert cache is None


@pytest.mark.parametrize(
    "preset", ["tiny", "gpt2-124m", "llama-1b", "reference-3b", "gpt2-8k-sp"]
)
def test_param_count_matches_analytic(preset):
    cfg = get_preset(preset).model
    # Shrink to a countable size but keep the structural flags.
    small = dataclasses.replace(
        cfg,
        vocab_size=128,
        context_length=32,
        d_model=16,
        n_heads=2,
        n_layers=3,
        d_head=None,
    )
    params = transformer.init_params(small, jax.random.key(0))
    assert tree_num_params(params) == small.num_params()


def test_loss_at_init_near_uniform():
    cfg = _fp32(TINY)
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size)
    targets = jax.random.randint(jax.random.key(2), (4, 32), 0, cfg.vocab_size)
    loss = transformer.loss_fn(params, tokens, targets, cfg)
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 0.5


def test_causality():
    """Changing a future token must not change past logits."""
    cfg = _fp32(TINY)
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 16), 0, cfg.vocab_size)
    logits1, _ = transformer.forward(params, tokens, cfg)
    tokens2 = tokens.at[0, 10].set((tokens[0, 10] + 1) % cfg.vocab_size)
    logits2, _ = transformer.forward(params, tokens2, cfg)
    np.testing.assert_allclose(logits1[0, :10], logits2[0, :10], atol=1e-5)
    assert not np.allclose(logits1[0, 10:], logits2[0, 10:], atol=1e-5)


def test_forward_matches_hand_reference():
    """One-block fp32 model vs an independent numpy implementation."""
    cfg = ModelConfig(
        vocab_size=31,
        context_length=8,
        d_model=16,
        n_heads=2,
        n_layers=1,
        activation="relu",
        norm="layernorm",
        pos_embed="learned",
        use_output_proj=False,
        tie_embeddings=False,
        lm_head_bias=True,
        qkv_bias=False,
        mlp_bias=True,
        compute_dtype="float32",
    )
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)
    got, _ = transformer.forward(params, tokens, cfg)

    p = jax.tree.map(np.asarray, params)
    x = p["tok_embed"]["embedding"][np.asarray(tokens)] + p["pos_embed"]["embedding"][None, :8]

    def ln(v, scale, bias):
        mu = v.mean(-1, keepdims=True)
        var = v.var(-1, keepdims=True)
        return (v - mu) / np.sqrt(var + cfg.norm_eps) * scale + bias

    blk = jax.tree.map(lambda a: a[0], p["blocks"])  # unstack layer 0
    h = ln(x, blk["ln1"]["scale"], blk["ln1"]["bias"])
    qkv = np.einsum("btd,dchn->bcthn", h, blk["attn"]["wqkv"])
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(cfg.head_dim)
    mask = np.tril(np.ones((8, 8), bool))
    scores = np.where(mask[None, None], scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    attn = np.einsum("bhqk,bkhd->bqhd", probs, v).reshape(2, 8, cfg.d_model)
    x = x + attn
    h = ln(x, blk["ln2"]["scale"], blk["ln2"]["bias"])
    hidden = np.maximum(h @ blk["mlp"]["w1"] + blk["mlp"]["b1"], 0)
    x = x + hidden @ blk["mlp"]["w2"] + blk["mlp"]["b2"]
    x = ln(x, p["final_norm"]["scale"], p["final_norm"]["bias"])
    want = x @ p["lm_head"]["kernel"] + p["lm_head"]["bias"]

    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_rope_properties():
    cos, sin = rope_table(16, 8, 10000.0)
    x = jax.random.normal(jax.random.key(0), (1, 16, 2, 8))
    pos = jnp.arange(16)
    rotated = apply_rope(x, cos, sin, pos)
    # Norm-preserving per pair
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(rotated), axis=-1),
        rtol=1e-5,
    )
    # Position 0 is identity
    np.testing.assert_allclose(np.asarray(rotated[:, 0]), np.asarray(x[:, 0]), atol=1e-6)


def test_rope_relative_dot_products():
    """q.k after RoPE depends only on relative distance."""
    cos, sin = rope_table(32, 8, 10000.0)
    q = jax.random.normal(jax.random.key(0), (1, 1, 1, 8))
    k = jax.random.normal(jax.random.key(1), (1, 1, 1, 8))
    q_rep = jnp.tile(q, (1, 32, 1, 1))
    k_rep = jnp.tile(k, (1, 32, 1, 1))
    pos = jnp.arange(32)
    qr = np.asarray(apply_rope(q_rep, cos, sin, pos))
    kr = np.asarray(apply_rope(k_rep, cos, sin, pos))
    d1 = (qr[0, 5, 0] * kr[0, 3, 0]).sum()
    d2 = (qr[0, 25, 0] * kr[0, 23, 0]).sum()
    np.testing.assert_allclose(d1, d2, rtol=1e-4)


def test_swiglu_rmsnorm_rope_variant_runs():
    cfg = ModelConfig(
        vocab_size=64,
        context_length=16,
        d_model=32,
        n_heads=4,
        n_layers=2,
        activation="swiglu",
        norm="rmsnorm",
        pos_embed="rope",
        tie_embeddings=False,
        mlp_bias=False,
        compute_dtype="float32",
    )
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)
    logits, _ = transformer.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, 64)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize(
    "policy", ["full", "dots_saveable", "save_attn", "save_attn_res"]
)
def test_remat_matches_no_remat(policy):
    """Every remat policy is a pure scheduling choice: identical gradients.

    The named-saveable policies (save_attn / save_attn_res) rely on
    checkpoint_name tags inside the attention block and the flash kernel;
    this pins the tags to the math staying equivalent.
    """
    cfg = _fp32(TINY)
    cfg_remat = dataclasses.replace(cfg, remat=policy)
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    g1 = jax.grad(transformer.loss_fn)(params, tokens, targets, cfg)
    g2 = jax.grad(transformer.loss_fn)(params, tokens, targets, cfg_remat)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5), g1, g2
    )


def test_return_hidden_activations():
    """Feature-extraction hook (reference forward_embedding equivalent)."""
    cfg = _fp32(TINY)
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    logits, cache, hidden = transformer.forward(params, tokens, cfg, return_hidden=True)
    assert hidden["block_outputs"].shape == (cfg.n_layers, 2, 16, cfg.d_model)
    assert hidden["final_hidden"].shape == (2, 16, cfg.d_model)
    # The last block output, final-normed, produces the same logits path.
    logits2, _ = transformer.forward(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits2), atol=1e-6)


def test_llama_variant_kv_cache_decode_matches_full():
    """RoPE + cache positions: incremental decode == full forward (llama path)."""
    cfg = ModelConfig(
        vocab_size=64, context_length=32, d_model=32, n_heads=4, n_layers=2,
        activation="swiglu", norm="rmsnorm", pos_embed="rope",
        tie_embeddings=False, mlp_bias=False, compute_dtype="float32",
    )
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 12), 0, 64)
    full_logits, _ = transformer.forward(params, tokens, cfg)
    cache = transformer.make_kv_cache(cfg, 1, 12, dtype="float32")
    logits_p, cache = transformer.forward(
        params, tokens[:, :6], cfg, kv_cache=cache, cache_index=jnp.int32(0)
    )
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(full_logits[:, :6]), rtol=2e-4, atol=2e-4
    )
    for i in range(6, 12):
        step_logits, cache = transformer.forward(
            params, tokens[:, i : i + 1], cfg, kv_cache=cache, cache_index=jnp.int32(i)
        )
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]), np.asarray(full_logits[:, i]),
            rtol=2e-4, atol=2e-4,
        )
