"""Pallas flash attention kernels vs the naive path (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.ops import pallas_flash
from pretraining_llm_tpu.ops.attention import naive_attention
from pretraining_llm_tpu.ops.pallas_flash import pallas_flash_attention, pallas_flash_attention_qkv


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


# -- the tiled kernels read q, k, v and write o in place (pallas_flash.heads_in_place) --------

# (h, g, d): heads a 128-lane block that the rule gives (0: the heads are folded first)
_IN_PLACE_CASES = {
    "d64_even_heads": ((4, 4, 64), 2),
    "d64_odd_heads_the_last_block_half_outside": ((3, 3, 64), 2),
    "d64_one_head_alone": ((1, 1, 64), 2),
    "d128_grouped_heads": ((4, 2, 128), 1),
    "d256": ((2, 2, 256), 1),
    "d64_grouped_heads_folded_first": ((4, 2, 64), 0),
    "d32_folded_first": ((4, 4, 32), 0),
}


def _grad_jaxpr(q, k, v, **kwargs):
    def grads(q, k, v):
        loss = lambda q, k, v: jnp.sum(
            pallas_flash_attention(q, k, v, interpret=True, **kwargs).astype(jnp.float32) ** 2)
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    return jax.make_jaxpr(grads)(q, k, v)


def _equations(jaxpr, out=None):
    """Every equation of a jaxpr outside the kernels' own bodies, nested programs included."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append(eqn)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _equations(sub, out)
    return out


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("case", sorted(_IN_PLACE_CASES))
def test_heads_in_place_forward_lse_and_backward_match_naive(monkeypatch, case, dtype, tol):
    monkeypatch.setattr(pallas_flash, "CAUSAL_TILE", 16)
    (h, g, d), heads = _IN_PLACE_CASES[case]
    b, t = 2, 64
    assert pallas_flash.heads_in_place(d, h, g, pallas_flash.causal_tiles(t, t, t, True, 0, None)) == heads
    q, k, v = _gqa_qkv(jax.random.key(40 + h + d), b=b, t=t, h=h, g=g, dh=d, dtype=dtype)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    flash = lambda q, k, v: pallas_flash_attention(q, k, v, causal=True, interpret=True)
    naive = lambda q, k, v: naive_attention(q, k, v, causal=True)
    if heads:
        fold = lambda x: x.reshape(b, t, -1)
        o, lse = pallas_flash._fwd(fold(q), fold(k), fold(v), h, g, causal=True, block_q=0,
                                   block_kv=0, interpret=True, heads=heads)
        # a row a head of every block; an odd head count's last row is nobody's
        got = {"o": o.reshape(b, t, h, d), "lse": lse.reshape(b, -1, t)[:, :h].reshape(b * h, t)}
    else:
        first = pallas_flash._heads_first
        o, lse = pallas_flash._fwd(first(q), first(k), first(v), h, g, causal=True, block_q=0,
                                   block_kv=0, interpret=True)
        got = {"o": pallas_flash._heads_last(o, b, h), "lse": lse[..., 0]}
    want = {"o": naive(q, k, v), "lse": _ref_lse(q, k)}
    for name, grad_naive, grad_flash in zip(
        ("dq", "dk", "dv"),
        jax.grad(loss(naive), (0, 1, 2))(q, k, v),
        jax.grad(loss(flash), (0, 1, 2))(q, k, v),
    ):
        want[name], got[name] = grad_naive, grad_flash
    for name in want:
        x, y = np.asarray(want[name], np.float32), np.asarray(got[name], np.float32)
        assert x.shape == y.shape, name
        assert np.abs(x - y).max() <= tol * np.abs(x).max(), name


def test_an_odd_head_counts_last_block_reads_poison_that_the_masks_keep_out(monkeypatch):
    """25 heads of 64 are twelve and a half blocks of 128 lanes. The interpreter fills what a
    block reads outside its array with NaN, as the chip may: with the lane masks taken out the
    lone head's results hold it, with them nothing does."""
    monkeypatch.setattr(pallas_flash, "CAUSAL_TILE", 16)
    q, k, v = _qkv(jax.random.key(50), b=1, t=32, h=3, dh=64)
    loss = lambda q, k, v: jnp.sum(pallas_flash_attention(q, k, v, interpret=True) ** 2)
    sound = jax.grad(loss, (0, 1, 2))(q, k, v)
    assert all(np.isfinite(np.asarray(x)).all() for x in sound)
    monkeypatch.setattr(pallas_flash, "_own", lambda mine, x: x)
    poisoned = jax.grad(loss, (0, 1, 2))(q, k, v)
    assert all(np.isnan(np.asarray(x)[:, :, 2]).any() for x in poisoned)


@pytest.mark.parametrize("h,d", [(4, 64), (3, 64), (2, 128)])
def test_a_call_in_place_transposes_nothing(monkeypatch, h, d):
    """No transpose on either side of the calls, forward or backward, and the kernels'
    operands are the (B, T, H*D) arrays themselves."""
    monkeypatch.setattr(pallas_flash, "CAUSAL_TILE", 16)
    b, t = 2, 64
    q, k, v = _qkv(jax.random.key(0), b=b, t=t, h=h, dh=d, dtype=jnp.bfloat16)
    eqns = _equations(_grad_jaxpr(q, k, v).jaxpr)
    assert "transpose" not in {e.primitive.name for e in eqns}
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] == ["flash_fwd_tiles", "flash_bwd_tiles"]
    for e in calls:
        wide = [x.aval.shape for x in list(e.invars) + list(e.outvars) if x.aval.shape[-1] != 1]
        assert wide and set(wide) == {(b, t, h * d)}, wide
    # the same shape with its heads grouped folds them first, as every call did
    folded = _equations(_grad_jaxpr(q, k[:, :, :1], v[:, :, :1]).jaxpr)
    if d == 64 and h > 1:
        assert "transpose" in {e.primitive.name for e in folded}


# Calls the in-place rule leaves alone though their head size and count are ones it takes,
# because causal_tiles does not take them: the text of the traced gradient program, hashed at
# 8c127b4 (PR 47, the parent of the PR that brought the rule), bfloat16 (B, T, H, D) operands.
_PARENT_PROGRAMS = {
    "multi_block_grid": ((1, 2048, 2, 64), {}, "eb58c441a458ba1d"),
    "window": ((2, 512, 2, 64), {"window": 200}, "b46053fbafe51803"),
    "segments": ((2, 512, 2, 64), {"segments": True}, "dbe1f088c46c417e"),
    "non_causal": ((2, 512, 2, 64), {"causal": False}, "60a20b649559c460"),
}


@pytest.mark.parametrize("case", sorted(_PARENT_PROGRAMS))
def test_calls_outside_the_rule_trace_the_parents_program(case):
    import hashlib

    shape, kwargs, parent = _PARENT_PROGRAMS[case]
    kwargs = dict(kwargs)
    if kwargs.pop("segments", False):
        kwargs["segments"] = jnp.zeros(shape[:2], jnp.int32)
    q = jnp.zeros(shape, jnp.bfloat16)
    assert pallas_flash.heads_in_place(64, 2, 2, 0) == 0
    text = str(_grad_jaxpr(q, q, q, **kwargs))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == parent


# -- q, k and v taken out of a fused projection's one array (pallas_flash_attention_qkv) ------


def _one_and_three(b, t, h, d):
    """The one-array entry and the three-array entry over slices of the same array, as
    functions of a bfloat16 (B, 3, T, H*D)."""
    one = lambda qkv: pallas_flash_attention_qkv(qkv, h, interpret=True)
    three = lambda qkv: pallas_flash_attention(
        *(qkv[:, c].reshape(b, t, h, d) for c in range(3)), causal=True, interpret=True)
    return one, three


@pytest.mark.parametrize("t", [512, 1024])  # 2 and 4 sub-tiles a side at the module's own tile
@pytest.mark.parametrize("h,d", [(4, 64), (5, 64), (2, 128)])  # 5: the edge block
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_one_array_entry_equals_the_three_array_entry_bit_for_bit(what, h, d, t):
    b = 2
    qkv = jax.random.normal(jax.random.key(60 + h), (b, 3, t, h * d), jnp.bfloat16)
    fns = _one_and_three(b, t, h, d)
    if what == "gradient":
        w = jax.random.normal(jax.random.key(61), (b, t, h, d), jnp.float32)
        fns = [jax.grad(lambda qkv, fn=fn: jnp.sum(fn(qkv).astype(jnp.float32) * w)) for fn in fns]
    got, want = (np.asarray(fn(qkv), np.float32) for fn in fns)
    assert got.shape == ((b, t, h, d) if what == "forward" else qkv.shape)
    assert np.isfinite(want).all() and np.abs(want).max() > 0
    assert np.array_equal(got, want)


@pytest.mark.parametrize("h,d", [(4, 64), (3, 64), (2, 128)])
def test_the_one_array_entry_slices_and_stacks_nothing(monkeypatch, h, d):
    """Forward and backward are the tiled calls alone: their q, k, v operands are the one
    array three times over, the residual is that array, and d(qkv) leaves the backward call as
    one result of its shape; no slice, concatenate, pad or transpose beside them."""
    monkeypatch.setattr(pallas_flash, "CAUSAL_TILE", 16)
    b, t = 2, 64
    qkv = jnp.zeros((b, 3, t, h * d), jnp.bfloat16)
    one, three = _one_and_three(b, t, h, d)
    loss = lambda fn: (lambda x: jnp.sum(fn(x).astype(jnp.float32) ** 2))
    eqns = _equations(jax.make_jaxpr(jax.grad(loss(one)))(qkv).jaxpr)
    wide = lambda e: any(x.aval.shape[-1:] == (h * d,) for x in list(e.invars) + list(e.outvars))
    copies = {"slice", "dynamic_slice", "concatenate", "pad", "transpose", "dynamic_update_slice", "gather"}
    assert not copies & {e.primitive.name for e in eqns if wide(e)}  # (lse's squeeze is a slice)
    fwd, bwd = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in (fwd, bwd)] == ["flash_fwd_tiles", "flash_bwd_tiles"]
    assert fwd.invars[0] is fwd.invars[1] is fwd.invars[2] is bwd.invars[0] is bwd.invars[1] is bwd.invars[2]
    assert [x.aval.shape for x in bwd.outvars] == [qkv.shape]
    # the three-array entry over the same array slices it, and puts d(qkv) together from three
    apart = {e.primitive.name for e in _equations(jax.make_jaxpr(jax.grad(loss(three)))(qkv).jaxpr) if wide(e)}
    assert {"slice", "pad"} <= apart or {"slice", "concatenate"} <= apart, apart


@pytest.mark.parametrize("case", ["t_over_1024", "t_no_multiple_of_512", "d32", "block_q_under_t"])
def test_the_one_array_entry_refuses_what_no_tiled_kernel_reads_in_place(case):
    t, d, kwargs = {"t_over_1024": (2048, 64, {}), "t_no_multiple_of_512": (768, 64, {}),
                    "d32": (512, 32, {}), "block_q_under_t": (512, 64, {"block_q": 256})}[case]
    assert pallas_flash.qkv_heads_in_place(t, d, 2, **kwargs) == 0
    assert pallas_flash.qkv_heads_in_place(512, 64, 2) == 2 and pallas_flash.qkv_heads_in_place(1024, 128, 3) == 1
    with pytest.raises(ValueError, match="no tiled kernel reads"):
        jax.eval_shape(lambda x: pallas_flash_attention_qkv(x, 2, interpret=True, **kwargs),
                       jax.ShapeDtypeStruct((1, 3, t, 2 * d), jnp.bfloat16))


def test_the_log_names_the_layout_once_a_shape(monkeypatch, caplog):
    monkeypatch.setattr(pallas_flash, "CAUSAL_TILE", 16)
    pallas_flash._log_form.cache_clear()
    with caplog.at_level("INFO", logger=pallas_flash.logger.name):
        for h, g, d in [(4, 4, 64), (4, 4, 64), (3, 3, 64), (2, 1, 128), (4, 2, 64), (4, 2, 64)]:
            q, k, v = _gqa_qkv(jax.random.key(0), t=64, h=h, g=g, dh=d)
            jax.eval_shape(lambda q, k, v: pallas_flash_attention(q, k, v, interpret=True), q, k, v)
    lines = [r.getMessage() for r in caplog.records]
    tiles = "causal tiles of 16, 10 of 16 sub-tiles computed"
    assert lines == [
        f"flash attention (B*H, T, D) = (8, 64, 64): {tiles}; heads in place, 2 a block of 128 lanes",
        f"flash attention (B*H, T, D) = (6, 64, 64): {tiles}; heads in place, 2 a block of 128 lanes",
        f"flash attention (B*H, T, D) = (4, 64, 128): {tiles}; heads in place, 1 a block of 128 lanes",
        f"flash attention (B*H, T, D) = (8, 64, 64): {tiles}; heads first",
    ]
    # the one-array entry says where it found q, k and v, once a shape too
    caplog.clear()
    with caplog.at_level("INFO", logger=pallas_flash.logger.name):
        for h, d in [(4, 64), (4, 64), (2, 128)]:
            qkv = jax.ShapeDtypeStruct((2, 3, 64, h * d), jnp.bfloat16)
            jax.eval_shape(lambda x: pallas_flash_attention_qkv(x, h, interpret=True), qkv)
    assert [r.getMessage() for r in caplog.records] == [
        f"flash attention (B*H, T, D) = (8, 64, 64): {tiles}; heads in place, 2 a block of 128 lanes, "
        "q, k and v from one array",
        f"flash attention (B*H, T, D) = (4, 64, 128): {tiles}; heads in place, 1 a block of 128 lanes, "
        "q, k and v from one array",
    ]
    pallas_flash._log_form.cache_clear()
