"""Grouped-query attention: param shapes, MHA equivalence, cached decode.

Beyond-parity feature (the reference is MHA-only with per-head Linears,
attention.py:29-31). The decisive numeric check: a GQA model whose KV heads
are replicated into a full MHA weight tensor must produce identical logits.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from pretraining_llm_tpu.config import ModelConfig
from pretraining_llm_tpu.models import transformer


def _cfg(**kw):
    base = dict(
        vocab_size=97,
        context_length=32,
        d_model=32,
        n_heads=4,
        n_kv_heads=2,
        n_layers=2,
        pos_embed="rope",
        param_dtype="float32",
        compute_dtype="float32",
    )
    base.update(kw)
    return ModelConfig(**base)


def test_gqa_validation():
    import pytest

    with pytest.raises(ValueError):
        ModelConfig(n_heads=4, n_kv_heads=3)
    with pytest.raises(ValueError):
        ModelConfig(n_heads=4, n_kv_heads=8)
    ModelConfig(n_heads=4, n_kv_heads=1)  # MQA is valid


def test_gqa_param_count_matches_analytic():
    for g in (1, 2):
        cfg = _cfg(n_kv_heads=g)
        params = transformer.init_params(cfg, jax.random.key(0))
        actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        assert actual == cfg.num_params(), (g, actual, cfg.num_params())
    # GQA must be smaller than MHA
    assert _cfg(n_kv_heads=2).num_params() < _cfg(n_kv_heads=None).num_params()


def test_gqa_equals_mha_with_replicated_kv():
    cfg = _cfg(n_kv_heads=2, qkv_bias=True)
    mha = dataclasses.replace(cfg, n_kv_heads=None)
    params = transformer.init_params(cfg, jax.random.key(0))

    # Build MHA params: replicate each KV head group-size times into wqkv.
    n_rep = cfg.n_heads // cfg.kv_heads
    blocks = dict(params["blocks"])
    attn = blocks["attn"]
    wq = attn["wq"]  # (L, D, H, Dh)
    wkv = attn["wkv"]  # (L, D, 2, G, Dh)
    wk = jnp.repeat(wkv[:, :, 0], n_rep, axis=2)  # (L, D, H, Dh)
    wv = jnp.repeat(wkv[:, :, 1], n_rep, axis=2)
    wqkv = jnp.stack([wq, wk, wv], axis=2)  # (L, D, 3, H, Dh)
    bq = attn["bq"]  # (L, H, Dh)
    bkv = attn["bkv"]  # (L, 2, G, Dh)
    bqkv = jnp.stack(
        [bq, jnp.repeat(bkv[:, 0], n_rep, axis=1), jnp.repeat(bkv[:, 1], n_rep, axis=1)],
        axis=1,
    )
    keep = {k: v for k, v in attn.items() if k in ("wo", "bo")}
    blocks["attn"] = {**keep, "wqkv": wqkv, "bqkv": bqkv}
    mha_params = {**params, "blocks": blocks}

    tokens = jax.random.randint(jax.random.key(1), (2, cfg.context_length), 0, cfg.vocab_size)
    logits_gqa, _ = transformer.forward(params, tokens, cfg)
    logits_mha, _ = transformer.forward(mha_params, tokens, mha)
    np.testing.assert_allclose(
        np.asarray(logits_gqa), np.asarray(logits_mha), rtol=1e-5, atol=1e-5
    )


def test_gqa_cache_shape_and_decode_matches_full_forward():
    cfg = _cfg(n_kv_heads=1)  # MQA: maximal cache shrink
    params = transformer.init_params(cfg, jax.random.key(0))
    b, t = 2, 8
    tokens = jax.random.randint(jax.random.key(1), (b, t), 0, cfg.vocab_size)

    cache = transformer.make_kv_cache(cfg, b, cfg.context_length)
    # Default container is the per-layer tuple; MQA caches ONE
    # kv head per layer either way (the GQA memory win under test).
    assert cache["layers"][0]["k"].shape == (
        b, cfg.context_length, 1, cfg.head_dim
    )
    stacked = transformer.make_kv_cache(cfg, b, cfg.context_length, stacked=True)
    assert stacked["k"].shape == (
        cfg.n_layers, b, cfg.context_length, 1, cfg.head_dim
    )

    full_logits, _ = transformer.forward(params, tokens, cfg)

    # Incremental decode: feed one token at a time through the cache.
    step_logits = []
    idx = jnp.zeros((), jnp.int32)
    for i in range(t):
        logits, cache = transformer.forward(
            params, tokens[:, i : i + 1], cfg, kv_cache=cache, cache_index=idx
        )
        step_logits.append(logits[:, 0])
        idx = idx + 1
    stacked = jnp.stack(step_logits, axis=1)
    np.testing.assert_allclose(
        np.asarray(stacked), np.asarray(full_logits), rtol=2e-4, atol=2e-4
    )


def test_gqa_grads_flow():
    cfg = _cfg()
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, cfg.context_length), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    grads = jax.grad(transformer.loss_fn)(params, tokens, targets, cfg)
    attn = grads["blocks"]["attn"]
    assert float(jnp.abs(attn["wq"]).max()) > 0
    assert float(jnp.abs(attn["wkv"]).max()) > 0


def test_gqa_wkv_tp_sharding_decision():
    """wkv shards its G head dim over 'tensor' iff G divides the axis.

    VERDICT r2 #10: G % tp == 0 -> shard (each TP rank computes only its KV
    heads); otherwise replicate and pay the documented gradient all-reduce.
    """
    from pretraining_llm_tpu.parallel.sharding import param_pspec_tree

    cfg = _cfg(n_kv_heads=2, qkv_bias=True)  # wkv (D, 2, 2, Dh)
    params = transformer.init_params(cfg, jax.random.key(0))

    # tp=2 divides G=2: head dim sharded for wkv AND its bias.
    specs = param_pspec_tree(params, tensor_size=2)
    assert tuple(specs["blocks"]["attn"]["wkv"]) == (None, "fsdp", None, "tensor", None)
    assert tuple(specs["blocks"]["attn"]["bkv"]) == (None, None, "tensor", None)

    # tp=4 does not divide G=2: replicated G (the deliberate fallback).
    specs = param_pspec_tree(params, tensor_size=4)
    assert tuple(specs["blocks"]["attn"]["wkv"]) == (None, "fsdp", None, None, None)
    assert tuple(specs["blocks"]["attn"]["bkv"]) == (None, None, None, None)

    # No tensor axis (default): replicated G, same as before.
    specs = param_pspec_tree(params)
    assert tuple(specs["blocks"]["attn"]["wkv"]) == (None, "fsdp", None, None, None)
