"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880).

A token carries ``n = cfg.hc_mult`` residual streams ``X (n, d)``. Each
sublayer F (attention, FFN) has its own wrapper: from the token's flattened,
RMS-normalised streams it computes a read vector ``H_pre (n)``, a write vector
``H_post (n)`` and a doubly-stochastic mixing matrix ``H_res (n, n)``
(Sinkhorn-Knopp on ``exp`` of the clamped logits), reads ``u = H_pre X``, runs
``y = F(norm(u))`` and writes ``X' = H_res X + H_post^T y``. The coefficient
path is float32 whatever the compute dtype; the streams stay in it.

Scopes: ``hc.coef`` (norm statistic, phi matmul, Sinkhorn) and ``hc.mix``
(read, write, stream mixing).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from pretraining_llm_tpu.config import ModelConfig

Params = Dict[str, Any]


class Coefficients(NamedTuple):
    pre: jax.Array  # (B, T, n) float32
    post: jax.Array  # (B, T, n) float32
    res: jax.Array  # (B, T, n, n) float32, rows and columns sum to 1


def init_hc_params(cfg: ModelConfig, key: jax.Array, dtype: Any) -> Params:
    """phi (n*d, n*n + 2n), b (n*n + 2n) and alpha (pre, post, res). The bias
    starts the mixing near the identity and the read near one stream's share,
    so a fresh model is close to a plain residual."""
    n, d = cfg.hc_mult, cfg.d_model
    phi = (jax.random.normal(key, (n * d, n * n + 2 * n), jnp.float32) * 0.02).astype(dtype)
    b = jnp.concatenate([jnp.zeros((2 * n,)), 4.0 * jnp.eye(n).reshape(-1)]).astype(dtype)
    return {"phi": phi, "b": b, "alpha": jnp.full((3,), 0.01, dtype)}


def sinkhorn(logits: jax.Array, iters: int, eps: float) -> jax.Array:
    """``iters`` rounds of row then column normalisation of ``exp(logits)``."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def coefficients(p: Params, x: jax.Array, cfg: ModelConfig) -> Coefficients:
    """x: (B, T, n, d) streams -> the sublayer's three coefficient sets."""
    n = cfg.hc_mult
    b, t = x.shape[:2]
    with jax.named_scope("hc.coef"):
        flat = x.reshape(b, t, n * cfg.d_model)
        # x_hat phi = (x phi) / rms(x): the matmul reads the streams as stored
        # and the float32 statistic is a fused reduction, so no float32 copy
        # of the streams is made.
        rinv = jax.lax.rsqrt(
            jnp.mean(jnp.square(flat.astype(jnp.float32)), axis=-1, keepdims=True) + cfg.hc_eps
        )
        z = jnp.einsum(
            "btk,kc->btc", flat, p["phi"].astype(jnp.float32),
            preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST,
        ) * rinv
        alpha, bias = p["alpha"].astype(jnp.float32), p["b"].astype(jnp.float32)
        pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n : 2 * n] + bias[n : 2 * n])
        res = (alpha[2] * z[..., 2 * n :] + bias[2 * n :]).reshape(b, t, n, n)
        res = sinkhorn(
            jnp.clip(res, -cfg.hc_res_clamp, cfg.hc_res_clamp), cfg.hc_sinkhorn_iters, cfg.hc_eps
        )
        return Coefficients(pre, post, res)


def read(c: Coefficients, x: jax.Array) -> jax.Array:
    """u = H_pre X: (B, T, n, d) -> (B, T, d) in the streams' dtype."""
    with jax.named_scope("hc.mix"):
        return jnp.einsum(
            "btn,btnd->btd", c.pre, x, preferred_element_type=jnp.float32
        ).astype(x.dtype)


def write(c: Coefficients, x: jax.Array, y: jax.Array) -> jax.Array:
    """X' = H_res X + H_post^T y."""
    with jax.named_scope("hc.mix"):
        mixed = jnp.einsum("btij,btjd->btid", c.res, x, preferred_element_type=jnp.float32)
        return (mixed + c.post[..., None] * y[:, :, None, :].astype(jnp.float32)).astype(x.dtype)


def copy_in(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The embedding copied into every stream."""
    b, t, d = x.shape
    return jnp.broadcast_to(x[:, :, None, :], (b, t, cfg.hc_mult, d))


def sum_out(x: jax.Array) -> jax.Array:
    """The streams summed into the model's output hidden state."""
    return jnp.sum(x.astype(jnp.float32), axis=2).astype(x.dtype)
