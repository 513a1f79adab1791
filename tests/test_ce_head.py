"""The two CE heads that remain (ce_impl="chunked" | "dense") against a plain
jnp log-softmax: loss and gradients, at vocabularies that are and are not a
multiple of 128, under a cotangent that differs from token group to token
group, and with bf16 inputs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.models.transformer import _dense_lse_ce, _lse_saved_ce


def _dense_ce(h, w, labels):
    logits = (h.astype(jnp.float32) @ w.astype(jnp.float32))
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
    return lse - gold


def _inputs(key, s=64, d=32, v=200, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    h = jax.random.normal(ks[0], (s, d), dtype)
    w = jax.random.normal(ks[1], (d, v), dtype) * 0.2
    labels = jax.random.randint(ks[2], (s,), 0, v)
    return h, w, labels


def _head(impl, cdt, chunks=4):
    """(h (S, d), w, labels (S,)) -> summed CE through the named head."""
    if impl == "dense":
        return lambda h, w, labels: _dense_lse_ce(h, w, None, labels, cdt)

    def chunked(h, w, labels):
        s, d = h.shape
        return _lse_saved_ce(
            h.reshape(chunks, s // chunks, d), w, None, labels.reshape(chunks, s // chunks), cdt
        )

    return chunked


HEADS = ["chunked", "dense"]


@pytest.mark.parametrize("v", [200, 256, 384])  # incl. non-multiple-of-128
@pytest.mark.parametrize("impl", HEADS)
def test_head_matches_log_softmax(impl, v):
    h, w, labels = _inputs(jax.random.key(0), v=v)
    head = _head(impl, jnp.float32)
    want, g_want = jax.value_and_grad(
        lambda h, w: jnp.sum(_dense_ce(h, w, labels)), (0, 1)
    )(h, w)
    got, g_got = jax.jit(jax.value_and_grad(head, (0, 1)))(h, w, labels)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b in zip(g_want, g_got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("impl", HEADS)
def test_head_nonuniform_cotangent(impl):
    """The heads return a sum, so a weighted loss calls them once a token
    group: each call's VJP must scale by the cotangent it is handed, not
    assume 1."""
    h, w, labels = _inputs(jax.random.key(2), s=64, d=16, v=160)
    head = _head(impl, jnp.float32, chunks=2)
    weights = (0.0, 0.5, 1.0, 2.0)
    groups = [slice(16 * i, 16 * (i + 1)) for i in range(4)]

    def weighted(ce_sum):
        return lambda h, w: sum(
            wt * ce_sum(h[g], w, labels[g]) for wt, g in zip(weights, groups)
        )

    g_want = jax.grad(weighted(lambda h, w, t: jnp.sum(_dense_ce(h, w, t))), (0, 1))(h, w)
    g_got = jax.grad(weighted(head), (0, 1))(h, w)
    assert not np.any(np.asarray(g_got[0][groups[0]]))  # weight 0: no gradient
    for a, b in zip(g_want, g_got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("impl", HEADS)
def test_head_bf16_inputs(impl):
    """bf16 hidden states and weights, bf16 compute: the loss comes from
    f32-accumulated logits and stays close to the f32 reference."""
    h, w, labels = _inputs(jax.random.key(3), dtype=jnp.bfloat16)
    want = jnp.sum(_dense_ce(h, w, labels))
    got, grads = jax.value_and_grad(_head(impl, jnp.bfloat16), (0, 1))(h, w, labels)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-2)
    g_want = jax.grad(lambda h, w: jnp.sum(_dense_ce(h, w, labels)), (0, 1))(h, w)
    for a, b in zip(g_want, grads):
        assert b.dtype == a.dtype
        np.testing.assert_allclose(
            np.asarray(b, np.float32), np.asarray(a, np.float32), rtol=5e-2, atol=5e-2
        )


# ---------------------------------------------------------------------------
# The lse-saved chunked head (the default ce_impl="chunked" backward)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("with_bias", [False, True])
def test_lse_saved_chunked_matches_dense(with_bias):
    """_lse_saved_ce (custom VJP saving per-token lse) == whole-logits CE,
    loss AND all gradients, with and without an lm_head bias."""
    s, d, v, chunks = 64, 32, 160, 4
    h, w, labels = _inputs(jax.random.key(7), s=s, d=d, v=v)
    bias = (jax.random.normal(jax.random.key(8), (v,)) * 0.2) if with_bias else None

    def dense(h, w, bias):
        logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
        if bias is not None:
            logits = logits + bias
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
        return jnp.sum(lse - gold)

    def chunked(h, w, bias):
        xs = h.reshape(chunks, s // chunks, d)
        ts = labels.reshape(chunks, s // chunks)
        return _lse_saved_ce(xs, w, bias, ts, jnp.float32)

    argnums = (0, 1, 2) if with_bias else (0, 1)
    l_ref, g_ref = jax.value_and_grad(dense, argnums=argnums)(h, w, bias)
    l_new, g_new = jax.value_and_grad(chunked, argnums=argnums)(h, w, bias)
    np.testing.assert_allclose(float(l_new), float(l_ref), rtol=1e-5)
    for a, b in zip(g_ref, g_new):
        np.testing.assert_allclose(
            np.asarray(b).reshape(np.asarray(a).shape), np.asarray(a),
            rtol=2e-4, atol=2e-5,
        )


# ---------------------------------------------------------------------------
# The dense saved-logits head (ce_impl="dense": zero backward recompute)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("with_bias", [False, True])
def test_dense_lse_ce_matches_reference(with_bias):
    """_dense_lse_ce (custom VJP saving compute-dtype logits + lse) ==
    whole-logits autodiff CE, loss AND all gradients. At compute dtype f32
    the saved logits are exact, so this pins the VJP math itself."""
    s, d, v = 64, 32, 160
    h, w, labels = _inputs(jax.random.key(11), s=s, d=d, v=v)
    bias = (jax.random.normal(jax.random.key(12), (v,)) * 0.2) if with_bias else None

    def ref(h, w, bias):
        logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
        if bias is not None:
            logits = logits + bias
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=1)[:, 0]
        return jnp.sum(lse - gold)

    def dense_head(h, w, bias):
        return _dense_lse_ce(h, w, bias, labels, jnp.float32)

    argnums = (0, 1, 2) if with_bias else (0, 1)
    l_ref, g_ref = jax.value_and_grad(ref, argnums=argnums)(h, w, bias)
    l_new, g_new = jax.value_and_grad(dense_head, argnums=argnums)(h, w, bias)
    np.testing.assert_allclose(float(l_new), float(l_ref), rtol=1e-5)
    for a, b in zip(g_ref, g_new):
        np.testing.assert_allclose(
            np.asarray(b).reshape(np.asarray(a).shape), np.asarray(a),
            rtol=2e-4, atol=2e-5,
        )


def test_model_loss_dense_matches_chunked():
    """ce_impl='dense' through the whole model == the chunked head, loss
    and gradients (fp32 compute: saved logits are exact)."""
    import dataclasses

    from pretraining_llm_tpu.config import ModelConfig
    from pretraining_llm_tpu.models import transformer

    cfg = ModelConfig(
        vocab_size=96, context_length=32, d_model=32, n_heads=4, n_layers=2,
        param_dtype="float32", compute_dtype="float32",
    )
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)

    cfg_d = dataclasses.replace(cfg, ce_impl="dense")
    l_c, g_c = jax.value_and_grad(transformer.loss_fn)(params, tokens, targets, cfg)
    l_d, g_d = jax.value_and_grad(transformer.loss_fn)(params, tokens, targets, cfg_d)
    np.testing.assert_allclose(float(l_d), float(l_c), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        ),
        g_c, g_d,
    )


def test_model_loss_dense_bf16_compute_close_to_chunked():
    """At bf16 compute the dense backward reads bf16-rounded saved logits
    where chunked recomputes f32-accum ones: grads agree to bf16 rounding."""
    import dataclasses

    from pretraining_llm_tpu.config import ModelConfig
    from pretraining_llm_tpu.models import transformer

    cfg = ModelConfig(
        vocab_size=96, context_length=32, d_model=32, n_heads=4, n_layers=2,
        param_dtype="float32", compute_dtype="bfloat16",
    )
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)

    cfg_d = dataclasses.replace(cfg, ce_impl="dense")
    l_c, g_c = jax.value_and_grad(transformer.loss_fn)(params, tokens, targets, cfg)
    l_d, g_d = jax.value_and_grad(transformer.loss_fn)(params, tokens, targets, cfg_d)
    # Forward loss is f32-accum logits both ways: tight.
    np.testing.assert_allclose(float(l_d), float(l_c), rtol=1e-5)
    # Gradients: bf16 logits rounding in the dense backward only.
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-2, atol=2e-3
        ),
        g_c, g_d,
    )
