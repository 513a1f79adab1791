"""Attention ops: naive vs blockwise/flash numerics, masking semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.ops.attention import multihead_attention, naive_attention
from pretraining_llm_tpu.ops.flash_attention import blockwise_attention


def _qkv(key, b=2, t=64, h=4, dh=16, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(k, (b, t, h, dh), dtype) for k in ks)


def test_naive_matches_explicit_softmax():
    q, k, v = _qkv(jax.random.key(0))
    out = naive_attention(q, k, v)
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    t = q.shape[1]
    mask = np.tril(np.ones((t, t), bool))
    scores = np.where(mask[None, None], scores, -np.inf)
    e = np.exp(scores - scores.max(-1, keepdims=True))
    probs = e / e.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", probs, v)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_q,block_kv", [(16, 16), (32, 8), (8, 32), (64, 64)])
def test_blockwise_matches_naive(causal, block_q, block_kv):
    q, k, v = _qkv(jax.random.key(1))
    want = naive_attention(q, k, v, causal=causal)
    got = blockwise_attention(q, k, v, causal=causal, block_q=block_q, block_kv=block_kv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_blockwise_gradients_match_naive():
    q, k, v = _qkv(jax.random.key(2), t=32)

    def loss_naive(q, k, v):
        return jnp.sum(naive_attention(q, k, v) ** 2)

    def loss_block(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, block_q=8, block_kv=8) ** 2)

    g1 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_block, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_flash_dispatch_via_multihead():
    q, k, v = _qkv(jax.random.key(3))
    want = multihead_attention(q, k, v, impl="naive")
    got = multihead_attention(q, k, v, impl="flash", block_q=16, block_kv=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_kv_cache_masking_matches_full_context():
    """Decode semantics: attending over a padded cache == attending the prefix."""
    b, t, h, dh = 1, 16, 2, 8
    q, k, v = _qkv(jax.random.key(4), b=b, t=t, h=h, dh=dh)
    full = naive_attention(q, k, v)
    # Simulate cache of capacity 32 holding only t valid entries.
    pad = 32 - t
    k_pad = jnp.concatenate([k, jnp.ones((b, pad, h, dh))], axis=1)
    v_pad = jnp.concatenate([v, jnp.ones((b, pad, h, dh))], axis=1)
    kv_mask = (jnp.arange(32) < t)[None, :]
    cached = naive_attention(
        q,
        k_pad,
        v_pad,
        q_positions=jnp.arange(t),
        kv_positions=jnp.arange(32),
        kv_mask=kv_mask,
    )
    np.testing.assert_allclose(np.asarray(cached), np.asarray(full), rtol=1e-5, atol=1e-5)


def test_bf16_inputs_fp32_softmax():
    q, k, v = _qkv(jax.random.key(5), dtype=jnp.bfloat16)
    out = naive_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = naive_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=2e-2, atol=2e-2
    )


def test_shard_mapped_flash_kernel_matches_dense(mesh8):
    """The pallas kernel wrapped per-shard over (data, fsdp, tensor) ==
    dense attention — and incompatible layouts return None (fallback)."""
    import functools

    from pretraining_llm_tpu.ops.flash_attention import shard_mapped_kernel
    from pretraining_llm_tpu.ops.pallas_flash import pallas_flash_attention

    b, t, h, dh = 4, 32, 4, 8
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (b, t, h, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, h, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, h, dh), jnp.float32)
    kernel = functools.partial(
        pallas_flash_attention, causal=True, block_q=16, block_kv=16,
        interpret=True,
    )
    got = jax.jit(
        lambda q, k, v: shard_mapped_kernel(kernel, q, k, v, mesh8)
    )(q, k, v)
    want = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    # Head count not divisible by the tensor axis -> None (caller falls back).
    q3 = q[:, :, :3]
    assert shard_mapped_kernel(kernel, q3, k[:, :, :3], v[:, :, :3], mesh8) is None


def test_flash_dispatch_manual_region_classification(monkeypatch):
    """Dispatch must distinguish FULLY-manual from PARTIAL-manual regions.

    Inside a partial-manual region (the pipeline: manual over 'pipe' only)
    activations are still auto-sharded over data/fsdp, so a direct
    pallas_call would be replicated by GSPMD (all-gathering the global
    batch) — the dispatcher must use the blockwise fallback there, and only
    call the kernel directly when every nontrivial mesh axis is manual
    (ADVICE r2 low #2).
    """
    import pretraining_llm_tpu.ops.flash_attention as fa
    import pretraining_llm_tpu.ops.pallas_flash as pf
    from jax.sharding import Mesh, PartitionSpec as P
    from pretraining_llm_tpu.parallel.sharding import activation_mesh

    calls = []

    def fake_kernel(q, k, v, *, causal=True, block_q=0, block_kv=0, **kw):
        calls.append(q.shape)
        return blockwise_attention(q, k, v, causal=causal)

    monkeypatch.setattr(fa, "_pallas_available", lambda: True)
    monkeypatch.setattr(pf, "pallas_flash_attention", fake_kernel)

    from tests.conftest import AXES

    devs = np.asarray(jax.devices()).reshape(2, 1, 1, 1, 1, 4)
    mesh = Mesh(devs, AXES)  # 2 data x 4 pipe
    ks = jax.random.split(jax.random.key(13), 3)
    q, k, v = (jax.random.normal(kk, (4, 32, 4, 8), jnp.float32) for kk in ks)
    want = naive_attention(q, k, v, causal=True)

    def body(q, k, v):
        return fa.flash_attention(q, k, v, causal=True)

    # Partial-manual ('pipe' only, data stays auto): kernel must NOT be
    # called directly — blockwise fallback handles the auto axes via GSPMD.
    with activation_mesh(mesh):
        got = jax.jit(
            jax.shard_map(
                body, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
                axis_names={"pipe"}, check_vma=False,
            )
        )(q, k, v)
    assert calls == [], "direct kernel call inside a partial-manual region"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    # Fully-manual (every nontrivial axis manual): operands are per-device
    # local arrays — the direct kernel call is the correct path.
    with activation_mesh(mesh):
        got2 = jax.jit(
            jax.shard_map(
                body, mesh=mesh,
                in_specs=(P("data"), P("data"), P("data")), out_specs=P("data"),
                axis_names={"data", "pipe"}, check_vma=False,
            )
        )(q, k, v)
    assert len(calls) == 1, "fully-manual region must take the direct kernel path"
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_flash_blockwise_fallback_warns(monkeypatch, mesh_seq4):
    """VERDICT r2 #9: when the Pallas dispatch can't express the layout
    per-shard it must WARN that the blockwise JAX path took over."""
    import pretraining_llm_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "_pallas_available", lambda: True)
    from pretraining_llm_tpu.parallel.sharding import activation_mesh

    ks = jax.random.split(jax.random.key(14), 3)
    q, k, v = (jax.random.normal(kk, (4, 32, 4, 8), jnp.float32) for kk in ks)
    with activation_mesh(mesh_seq4):  # seq-sharded: not expressible per-shard
        with pytest.warns(UserWarning, match="falling back to blockwise"):
            got = fa.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(naive_attention(q, k, v, causal=True)),
        rtol=1e-5, atol=1e-5,
    )


def test_shard_mapped_kernel_rejects_indivisible_batch(mesh8):
    """Batch not divisible by the data x fsdp shards -> None (fallback),
    never a shard_map trace error."""
    import functools

    from pretraining_llm_tpu.ops.flash_attention import shard_mapped_kernel
    from pretraining_llm_tpu.ops.pallas_flash import pallas_flash_attention

    ks = jax.random.split(jax.random.key(12), 3)
    q, k, v = (jax.random.normal(kk, (2, 32, 4, 8), jnp.float32) for kk in ks)
    kernel = functools.partial(pallas_flash_attention, causal=True, interpret=True)
    assert shard_mapped_kernel(kernel, q, k, v, mesh8) is None  # 2 % 4 != 0
