"""z-loss (model.z_loss_coef): value + gradients vs a dense autodiff
reference, for both custom-VJP CE heads."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.config import ModelConfig, get_preset
from pretraining_llm_tpu.models import transformer

Z = 1e-3


def _ref_loss(params, toks, targets, cfg):
    """Plain autodiff reference: CE + z * mean(lse^2) over full logits."""
    logits, _ = transformer.forward(params, toks, cfg)
    logits = logits.astype(jnp.float32)
    b, t, v = logits.shape
    flat = logits.reshape(b * t, v)
    lse = jax.nn.logsumexp(flat, axis=-1)
    label = jnp.take_along_axis(flat, targets.reshape(-1)[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - label) + Z * jnp.mean(jnp.square(lse))


@pytest.mark.parametrize("ce_impl", ["chunked", "dense"])
def test_z_loss_value_and_grads_match_reference(ce_impl):
    cfg = dataclasses.replace(
        get_preset("tiny").model, compute_dtype="float32",
        ce_impl=ce_impl, z_loss_coef=Z,
    )
    params = transformer.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, cfg.context_length),
                              0, cfg.vocab_size)
    targets = jnp.roll(toks, -1, axis=1)

    got, got_g = jax.value_and_grad(
        lambda p: transformer.loss_fn(p, toks, targets, cfg)
    )(params)
    want, want_g = jax.value_and_grad(
        lambda p: _ref_loss(p, toks, targets, cfg)
    )(params)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-3)


def test_z_loss_changes_the_objective():
    cfg0 = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
    cfgz = dataclasses.replace(cfg0, z_loss_coef=1e-2)
    params = transformer.init_params(cfg0, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, cfg0.context_length),
                              0, cfg0.vocab_size)
    targets = jnp.roll(toks, -1, axis=1)
    l0 = float(transformer.loss_fn(params, toks, targets, cfg0))
    lz = float(transformer.loss_fn(params, toks, targets, cfgz))
    assert lz > l0  # lse^2 is positive at init


def test_z_loss_validation():
    with pytest.raises(ValueError, match=">= 0"):
        ModelConfig(z_loss_coef=-0.1)
    # both heads that remain implement the z term
    for ce_impl in ("chunked", "dense"):
        assert ModelConfig(z_loss_coef=1e-3, ce_impl=ce_impl).z_loss_coef == 1e-3
    with pytest.raises(ValueError, match="ce_impl must be"):
        ModelConfig(z_loss_coef=1e-3, ce_impl="fused")


def test_z_loss_excluded_from_eval():
    """include_aux=False (the eval path) reports PURE cross-entropy."""
    cfg0 = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")
    cfgz = dataclasses.replace(cfg0, z_loss_coef=1e-2)
    params = transformer.init_params(cfg0, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, cfg0.context_length),
                              0, cfg0.vocab_size)
    targets = jnp.roll(toks, -1, axis=1)
    pure = float(transformer.loss_fn(params, toks, targets, cfg0,
                                     include_aux=False))
    with_z_eval = float(transformer.loss_fn(params, toks, targets, cfgz,
                                            include_aux=False))
    assert with_z_eval == pure


def test_z_loss_multi_chunk_scan_matches_dense_head():
    """The chunked head's z accumulation across an ACTUAL multi-chunk scan
    (forward sum + backward rescale per chunk) must equal the dense head."""
    from pretraining_llm_tpu.models.transformer import (
        _dense_lse_ce, _lse_saved_ce,
    )

    s, d, v, z = 64, 16, 97, 1e-2
    x = jax.random.normal(jax.random.key(2), (s, d), jnp.float32)
    w = jax.random.normal(jax.random.key(3), (d, v), jnp.float32) * 0.1
    ts_ = jax.random.randint(jax.random.key(4), (s,), 0, v)

    def chunked(x, w):
        return _lse_saved_ce(
            x.reshape(4, s // 4, d), w, None, ts_.reshape(4, s // 4),
            jnp.float32, z=z,
        )

    def dense(x, w):
        return _dense_lse_ce(x, w, None, ts_, jnp.float32, z=z)

    (vc, gc), (vd, gd) = (
        jax.value_and_grad(chunked, (0, 1))(x, w),
        jax.value_and_grad(dense, (0, 1))(x, w),
    )
    np.testing.assert_allclose(float(vc), float(vd), rtol=1e-6)
    for a, b in zip(gc, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)
