"""Pallas flash attention kernels vs the naive path (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.ops.attention import naive_attention
from pretraining_llm_tpu.ops.pallas_flash import pallas_flash_attention


def _qkv(key, b=2, t=64, h=2, dh=16, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(k, (b, t, h, dh), dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_q,block_kv", [(16, 16), (32, 16), (16, 32), (64, 64)])
def test_forward_matches_naive(causal, block_q, block_kv):
    q, k, v = _qkv(jax.random.key(0))
    want = naive_attention(q, k, v, causal=causal)
    got = pallas_flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_kv=block_kv, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_backward_matches_naive(causal):
    q, k, v = _qkv(jax.random.key(1), t=32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_naive = jax.grad(loss(lambda q, k, v: naive_attention(q, k, v, causal=causal)), (0, 1, 2))(
        q, k, v
    )
    g_flash = jax.grad(
        loss(
            lambda q, k, v: pallas_flash_attention(
                q, k, v, causal=causal, block_q=16, block_kv=16, interpret=True
            )
        ),
        (0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_naive, g_flash):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_uneven_block_shapes_fall_back_to_divisors():
    # t=48 is not divisible by the default 512 -> block sizes must self-adjust.
    q, k, v = _qkv(jax.random.key(2), t=48)
    want = naive_attention(q, k, v)
    got = pallas_flash_attention(q, k, v, block_q=32, block_kv=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_bf16_inputs():
    q, k, v = _qkv(jax.random.key(3), dtype=jnp.bfloat16)
    want = naive_attention(q, k, v)
    got = pallas_flash_attention(q, k, v, block_q=16, block_kv=16, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=3e-2, atol=3e-2
    )


def test_long_sequence_memory_shape():
    # 1k tokens with small blocks: exercises many grid steps.
    q, k, v = _qkv(jax.random.key(4), b=1, t=1024, h=1, dh=8)
    got = pallas_flash_attention(q, k, v, block_q=128, block_kv=128, interpret=True)
    want = naive_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def _gqa_qkv(key, b=2, t=64, h=4, g=2, dh=16, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, t, h, dh), dtype)
    k = jax.random.normal(ks[1], (b, t, g, dh), dtype)
    v = jax.random.normal(ks[2], (b, t, g, dh), dtype)
    return q, k, v


@pytest.mark.parametrize("g", [1, 2])
def test_gqa_forward_matches_grouped_naive(g):
    """GQA through the kernel (no KV repeat) == the grouped naive einsum."""
    q, k, v = _gqa_qkv(jax.random.key(7), h=4, g=g)
    got = pallas_flash_attention(q, k, v, causal=True, block_q=16, block_kv=16, interpret=True)
    want = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_gqa_backward_matches_grouped_naive():
    q, k, v = _gqa_qkv(jax.random.key(8), t=32, h=4, g=2)

    def loss_naive(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(
            pallas_flash_attention(q, k, v, causal=True, block_q=16, block_kv=16, interpret=True) ** 2
        )

    g_naive = jax.grad(loss_naive, (0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    for a, b in zip(g_naive, g_flash):
        assert a.shape == b.shape  # dk/dv keep the G-head shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5)


def test_gqa_bf16_forward():
    q, k, v = _gqa_qkv(jax.random.key(9), h=4, g=2, dtype=jnp.bfloat16)
    got = pallas_flash_attention(q, k, v, block_q=16, block_kv=16, interpret=True)
    want = naive_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("g", [4, 2, 1])
def test_fused_single_block_backward_matches_naive(g):
    """t <= block triggers the fused dQ/dK/dV kernel (one pass, shared S/P)."""
    q, k, v = _gqa_qkv(jax.random.key(11), t=64, h=4, g=g)

    def loss_naive(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        # default blocks (1024) >= t=64 -> nq == nk == 1 -> fused kernel
        return jnp.sum(pallas_flash_attention(q, k, v, causal=True, interpret=True) ** 2)

    g_naive = jax.grad(loss_naive, (0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    for a, b in zip(g_naive, g_flash):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("policy_names", [("attn_o_res", "attn_lse"), ()])
def test_remat_saved_residuals_match_recompute(policy_names):
    """The 'save_attn_res' policy saves the kernel's VJP residuals
    (o + squeezed lse, tagged in _flash_fwd) instead of re-running the forward
    in the backward. Gradients must be identical either way — this pins the
    tag names and the lse squeeze/re-expand pair in _flash_fwd/_bwd."""
    q, k, v = _qkv(jax.random.key(4), t=32)

    def loss(q, k, v):
        out = pallas_flash_attention(
            q, k, v, causal=True, block_q=16, block_kv=16, interpret=True
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g_plain = jax.grad(loss, (0, 1, 2))(q, k, v)
    ckpt = jax.checkpoint(
        loss, policy=jax.checkpoint_policies.save_only_these_names(*policy_names)
    )
    g_ckpt = jax.jit(jax.grad(ckpt, (0, 1, 2)))(q, k, v)
    for a, b in zip(g_plain, g_ckpt):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
