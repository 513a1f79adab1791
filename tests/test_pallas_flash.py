"""Pallas flash attention kernels vs the naive path (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.ops import pallas_flash
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


# -- a lone causal block walked in causal sub-tiles (pallas_flash.causal_tiles) -------


def _pallas_calls(jaxpr, out=None):
    """Every pallas_call of a jaxpr, nested ones too, as (name, kernel function)."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"], eqn.params["jaxpr"].debug_info.func_name))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_calls(sub, out)
    return out


def _ref_lse(q, k, causal=True):
    """(B, T, H, D), (B, T, G, D) -> the rows' log-sum-exp of the scaled masked scores, (B*H, T)."""
    b, t, h, d = q.shape
    kk = jnp.repeat(k, h // k.shape[2], axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kk) / d**0.5
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1).reshape(b * h, t)


@pytest.mark.parametrize("t", [64, 128])  # 4 and 8 sub-tiles a side at tile 16
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("g", [4, 2, 1])
def test_causal_tiles_forward_and_backward_match_naive(monkeypatch, g, dtype, tol, t):
    monkeypatch.setattr(pallas_flash, "CAUSAL_TILE", 16)
    q, k, v = _gqa_qkv(jax.random.key(20 + g), t=t, h=4, g=g, dtype=dtype)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    flash = lambda q, k, v: pallas_flash_attention(q, k, v, causal=True, interpret=True)
    naive = lambda q, k, v: naive_attention(q, k, v, causal=True)
    calls = _pallas_calls(jax.make_jaxpr(jax.grad(loss(flash), (0, 1, 2)))(q, k, v).jaxpr)
    assert [name for name, _ in calls] == ["flash_fwd_tiles", "flash_bwd_tiles"]

    o, lse = pallas_flash._fwd(
        pallas_flash._heads_first(q), pallas_flash._heads_first(k), pallas_flash._heads_first(v),
        4, g, causal=True, block_q=0, block_kv=0, interpret=True,
    )
    want = {"o": naive(q, k, v), "lse": _ref_lse(q, k)}
    got = {"o": pallas_flash._heads_last(o, 2, 4), "lse": lse[..., 0]}
    for name, grad_naive, grad_flash in zip(
        ("dq", "dk", "dv"),
        jax.grad(loss(naive), (0, 1, 2))(q, k, v),
        jax.grad(loss(flash), (0, 1, 2))(q, k, v),
    ):
        want[name], got[name] = grad_naive, grad_flash
    for name in want:
        a, b = np.asarray(want[name], np.float32), np.asarray(got[name], np.float32)
        assert a.shape == b.shape, name
        assert np.abs(a - b).max() <= tol * np.abs(a).max(), name


def test_causal_tiles_saved_residuals_match_recompute(monkeypatch):
    """save_attn_res with the tiled kernels: the tags and the lse squeeze are _flash_fwd's."""
    monkeypatch.setattr(pallas_flash, "CAUSAL_TILE", 16)
    q, k, v = _qkv(jax.random.key(5), t=64)
    w = jax.random.normal(jax.random.key(6), q.shape)

    def loss(q, k, v):  # linear in o, so only the kernel's own backward asks for o again
        return jnp.sum(pallas_flash_attention(q, k, v, causal=True, interpret=True) * w)

    grads = {}
    for names in (("attn_o_res", "attn_lse"), ()):
        ckpt = jax.checkpoint(loss, policy=jax.checkpoint_policies.save_only_these_names(*names))
        step = jax.jit(jax.grad(ckpt, (0, 1, 2)))
        calls = [name for name, _ in _pallas_calls(jax.make_jaxpr(step)(q, k, v).jaxpr)]
        # Saved residuals: the backward never re-runs the forward kernel.
        assert calls.count("flash_fwd_tiles") == (1 if names else 2), calls
        assert calls.count("flash_bwd_tiles") == 1
        grads[names] = step(q, k, v)
    for a, b in zip(*grads.values()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


# What the tiled form does not cover, each at the tile (16, or the module's own) it is traced
# under: (t, tile, keyword arguments of the call, the static facts causal_tiles is asked).
_BLOCK_FORM_CASES = {
    "non_causal": (64, 16, {"causal": False}, (64, 64, 64, False, 0, None)),
    "window": (64, 16, {"window": 24}, (64, 64, 64, True, 24, None)),
    "segments": (64, 16, {"segments": True}, (64, 64, 64, True, 0, "ids")),
    "t_over_1024_at_default_blocks": (2048, None, {}, (2048, 1024, 1024, True, 0, None)),
    "explicit_block_q_under_t": (64, 16, {"block_q": 32}, (64, 32, 64, True, 0, None)),
    "explicit_block_kv_under_t": (64, 16, {"block_kv": 32}, (64, 64, 32, True, 0, None)),
    "t_no_multiple_of_two_tiles": (48, 16, {}, (48, 48, 48, True, 0, None)),
    "t_of_one_tile": (16, 16, {}, (16, 16, 16, True, 0, None)),
}


@pytest.mark.parametrize("case", sorted(_BLOCK_FORM_CASES))
def test_calls_outside_the_tiled_form_trace_the_block_kernels(monkeypatch, case):
    t, tile, kwargs, facts = _BLOCK_FORM_CASES[case]
    if tile:
        monkeypatch.setattr(pallas_flash, "CAUSAL_TILE", tile)
    assert pallas_flash.causal_tiles(*facts) == 0
    kwargs = dict(kwargs)
    if kwargs.pop("segments", False):
        kwargs["segments"] = jnp.asarray(np.arange(t)[None] // 24, jnp.int32).repeat(2, axis=0)
    q, k, v = _gqa_qkv(jax.random.key(30), t=t, h=4, g=2, dh=8)

    def grads(q, k, v):
        loss = lambda q, k, v: jnp.sum(pallas_flash_attention(q, k, v, interpret=True, **kwargs) ** 2)
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    here = jax.make_jaxpr(grads)(q, k, v)
    # The parent's code path: the same trace with the tiled form taken out of the module.
    monkeypatch.setattr(pallas_flash, "causal_tiles", lambda *a: 0)
    parent = jax.make_jaxpr(grads)(q, k, v)
    assert str(here) == str(parent)
    one_block = kwargs.get("block_q", t) >= t and kwargs.get("block_kv", t) >= t and t <= 1024
    backward = ["_bwd_fused_kernel"] if one_block else ["_bwd_dq_kernel", "_bwd_dkv_kernel"]
    assert _pallas_calls(here.jaxpr) == [(None, fn) for fn in ["_fwd_kernel"] + backward]


def test_causal_tiles_counts_the_sub_tiles_of_a_lone_causal_block():
    tile = pallas_flash.CAUSAL_TILE
    assert pallas_flash.causal_tiles(1024, 1024, 1024, True, 0, None) == 1024 // tile
    assert pallas_flash.causal_tiles(2 * tile, 2 * tile, 2 * tile, True, 0, None) == 2
    assert pallas_flash.causal_tiles(tile, tile, tile, True, 0, None) == 0
