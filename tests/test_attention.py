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


# -- the fused QKV projection's one array goes to the tiled flash kernels as it is -----------
# (models/transformer.py::_qkv_stays_whole; ops/flash_attention.py::flash_attention_qkv)


def _toy(**kw):
    from pretraining_llm_tpu.config import ModelConfig

    base = dict(vocab_size=128, context_length=512, d_model=128, n_heads=2, n_layers=2,
                pos_embed="learned", attention_impl="flash", compute_dtype="bfloat16", remat="full")
    return ModelConfig(**{**base, **kw})


def _program(cfg, t, cached=False):
    """The traced loss gradient (or a prefill that writes a cache) of a toy and its inputs."""
    from pretraining_llm_tpu.models import transformer

    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, t), 1, cfg.vocab_size)
    if cfg.doc_mask_token >= 0:
        tokens = tokens.at[:, t // 3].set(cfg.doc_mask_token)
    if cached:
        cache = transformer.make_kv_cache(cfg, 2, t)
        fn = lambda p: transformer.forward(p, tokens, cfg, kv_cache=cache, cache_index=0)[0]
    else:
        fn = jax.value_and_grad(lambda p: transformer.loss_fn(p, tokens, jnp.roll(tokens, -1, 1), cfg))
    return fn, params


def _flash_operands(fn, params):
    """Shapes of the first operand of every flash pallas_call of a traced program, and the
    shapes every slice in it cuts from."""
    from tests.test_pallas_flash import _equations

    eqns = _equations(jax.make_jaxpr(fn)(params).jaxpr)
    calls = [e.invars[0].aval.shape for e in eqns if e.primitive.name == "pallas_call"]
    sliced = [e.invars[0].aval.shape for e in eqns if e.primitive.name == "slice"]
    return calls, sliced


_RULE_CASES = {
    # (config overrides, T, cached): does the projection's result stay whole?
    "gpt2_toy_no_cache": (dict(), 512, False, True),
    "gpt2_toy_heads_of_128": (dict(n_heads=1), 1024, False, True),
    "rotary_toy": (dict(pos_embed="rope"), 512, False, False),
    "segments_batch": (dict(doc_mask_token=0), 512, False, False),
    "cached_prefill": (dict(), 512, True, False),
    "t_of_2048": (dict(context_length=2048), 2048, False, False),
    "grouped_heads_hold_no_wqkv": (dict(n_heads=4, n_kv_heads=2, d_model=256), 512, False, False),
    "qk_norm_between": (dict(qk_norm=True), 512, False, False),
    "explicit_block_under_t": (dict(flash_block_q=256), 512, False, False),
    "naive_implementation": (dict(attention_impl="naive"), 512, False, False),
}


@pytest.mark.parametrize("case", sorted(_RULE_CASES))
def test_who_hands_the_flash_kernels_the_projections_one_array(monkeypatch, case):
    import pretraining_llm_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "_pallas_available", lambda: True)  # interpreted off the TPU
    overrides, t, cached, whole = _RULE_CASES[case]
    cfg = _toy(**overrides)
    calls, sliced = _flash_operands(*_program(cfg, t, cached))
    lanes = cfg.n_heads * cfg.head_dim
    one_array, projected = (2, 3, t, lanes), (2, 3, t, 1, lanes)
    if whole:
        # forward, recomputed forward and backward, all on the one array; nothing slices it
        assert calls == [one_array] * 3, calls
        assert one_array not in sliced and projected not in sliced
    else:
        assert one_array not in calls
        if cfg.attention_impl == "flash":
            assert calls, "the case must still reach the Pallas kernels"
        if "wqkv" in _program(cfg, t, cached)[1]["blocks"]["attn"]:
            assert any(s[:3] == (2, 3, t) for s in sliced), sliced  # today's three slices


def test_off_the_tpu_nothing_takes_the_one_array_path():
    calls, sliced = _flash_operands(*_program(_toy(), 512))
    assert calls == [] and (2, 3, 512, 1, 128) in sliced


@pytest.mark.parametrize("bias", [True, False])
def test_one_array_path_gives_the_sliced_paths_loss_and_gradients_bit_for_bit(monkeypatch, bias):
    import pretraining_llm_tpu.ops.flash_attention as fa
    from pretraining_llm_tpu.models import transformer

    monkeypatch.setattr(fa, "_pallas_available", lambda: True)
    cfg = _toy(qkv_bias=bias, mlp_bias=bias)
    fn, params = _program(cfg, 512)
    loss_one, grads_one = jax.jit(fn)(params)
    monkeypatch.setattr(transformer, "flash_takes_qkv", lambda *a, **k: False)  # the parent's lines
    fn, _ = _program(cfg, 512)
    assert _flash_operands(fn, params)[0] == [(2, 512, 128)] * 3
    loss_three, grads_three = jax.jit(fn)(params)
    assert np.isfinite(float(loss_one)) and float(loss_one) == float(loss_three)
    same = jax.tree.map(
        lambda a, b: np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32)),
        grads_one, grads_three)
    assert all(jax.tree.leaves(same)), same
    assert float(jnp.abs(grads_one["blocks"]["attn"]["wqkv"].astype(jnp.float32)).max()) > 0


def test_one_array_entry_under_a_batch_sharded_mesh_matches_the_three_array_entry(monkeypatch, mesh8):
    """The shard_map case the four-chip cell takes: batch over data x fsdp, heads over tensor."""
    import pretraining_llm_tpu.ops.flash_attention as fa
    from pretraining_llm_tpu.parallel.sharding import activation_mesh

    monkeypatch.setattr(fa, "_pallas_available", lambda: True)
    b, t, h, d = 4, 512, 4, 64
    qkv = jax.random.normal(jax.random.key(21), (b, 3, t, h * d), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(22), (b, t, h, d), jnp.float32)
    three = lambda x: fa.flash_attention(*(x[:, c].reshape(b, t, h, d) for c in range(3)), causal=True)
    one = lambda x: fa.flash_attention_qkv(x, h)
    grad = lambda fn: jax.jit(jax.value_and_grad(lambda x: jnp.sum(fn(x).astype(jnp.float32) * w)))
    with activation_mesh(mesh8):
        assert fa.flash_takes_qkv(qkv.shape, h)
        assert not fa.flash_takes_qkv((2,) + qkv.shape[1:], h)  # 2 rows over 4 batch shards
        assert not fa.flash_takes_qkv((b, 3, t, 3 * d), 3)  # 3 heads over 2 tensor shards
        (l1, g1), (l3, g3) = grad(one)(qkv), grad(three)(qkv)
    assert float(l1) == float(l3)
    assert np.array_equal(np.asarray(g1, np.float32), np.asarray(g3, np.float32))
    with pytest.raises(ValueError, match="no Pallas kernel takes"):
        fa.flash_attention_qkv(qkv[:, :, :256], h)
