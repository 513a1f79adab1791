"""Gated DeltaNet (arXiv:2412.06464): a linear-attention mixer whose cache is a
fixed-size state a row, not pages. The third recurrent mixer, under the cache
discipline of ``models/recurrent.py::mixer_block``; the delta rule is
``models/kda.py``'s, the gate is its own.

Per token and head (H heads of d_k keys and d_v values), with ``x`` the
sublayer's input::

    q', k', v' = W_q x, W_k x, W_v x                 (one projection, columns [q | k | v])
    q, k, v = SiLU(conv(q')), SiLU(conv(k')), SiLU(conv(v'))
    q = q / |q| / sqrt(d_k),   k = k / |k|
    g = -exp(A_log) * softplus(W_a x + dt_bias)      (one scalar a head, <= 0, float32)
    beta = sigmoid(W_b x),  times 2 with gdn_allow_neg_eigval (arXiv:2411.12537)
    S_t = exp(g) S_{t-1};  u = beta (v - S_t^T k);  S_t = S_t + k u^T;  o = S_t^T q
    y = W_o [RMSNorm_head(o) * SiLU(W_g x)]

which is ``kda.recurrent_step`` with ``g`` the same on every channel of a head
(handed over as (B, H, 1): it broadcasts), a state of d_k x d_v that need not be
square, and a SiLU where KDA's output gate has a sigmoid. ``conv`` is a causal
depthwise convolution over time (``kda._conv``: one over the 2 H d_k + H d_v
channels is three side by side); no positional encoding. What a row keeps
between calls is ``S`` (H, d_k, d_v), float32, and the convolution's tail, the
last ``kernel - 1`` projected inputs of every channel.

Two forms of the recurrence, one function of the inputs:

- one token a row, ``gdn.step``: whichever form ``kda.step_form`` reads from
  the state (on a TPU, with no mesh, the Pallas kernel takes a float32 state
  whose K is whole 8-sublane tiles, 96 x 192 as it lies in the pool among
  them, the head's one decay broadcast over its K channels; everywhere else
  the four ``jnp`` lines);
- ``chunked``, ``gdn.chunk``: many tokens a row, chunk by chunk in the WY/UT
  form. A scalar gate makes it plain: with ``G_i`` the cumulative log-decay
  inside a chunk of ``kda.CHUNK`` tokens, ``A_ij = beta_i (k_i . k_j) exp(G_i -
  G_j)`` is one matmul under one (C, C) mask of decays (KDA's per-channel gate
  needs a (SUB, SUB, K) tensor and reference points); ``(I + A) U = beta (V -
  exp(G) K S_0)``, ``O = exp(G) Q S_0 + [(Q K^T) exp(G_i - G_j)]_{j<=i} U``,
  ``S_C = exp(G_C) S_0 + (K exp(G_C - G))^T U``. ``g <= 0``, and the mask goes
  on the exponent, so every ``exp`` is of a number that is never positive.

Both take a validity mask: an invalid position (bucket padding past a row's
true length, a ragged row's left padding, a dead row's token) gets ``g = 0``,
``beta = 0`` and a zero convolution input, so it changes neither the state nor
the tail, and a bucket-padded prompt leaves both as of its last real token.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from pretraining_llm_tpu.config import ModelConfig
from pretraining_llm_tpu.models import kda, layers
from pretraining_llm_tpu.ops import pallas_kda
from pretraining_llm_tpu.parallel.sharding import current_mesh

Params = Dict[str, Any]

SCOPE = "gdn"  # the mixer's device scopes: gdn.proj, gdn.conv, gdn.gate, gdn.step | gdn.chunk, gdn.out

_HI = jax.lax.Precision.HIGHEST  # float32 inside a chunk: no bfloat16 passes on the MXU


def init_params(cfg: ModelConfig, key: jax.Array, resid_std: float, dtype: Any) -> Params:
    d, h, dv, c = cfg.d_model, cfg.gdn_heads, cfg.gdn_value_dim, cfg.gdn_conv_dim
    ks = jax.random.split(key, 8)

    def normal(k, shape, s=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    # softplus(dt_bias) between 1e-3 and 1e-1 at a zero input, exp(A_log)
    # between 1 and 16: as the open implementation starts
    delta = jnp.exp(jax.random.uniform(ks[2], (h,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "w_in": normal(ks[0], (d, c)),
        "conv": normal(ks[1], (cfg.gdn_conv_kernel, c), cfg.gdn_conv_kernel ** -0.5),
        "wa": normal(ks[3], (d, h)),
        "A_log": jnp.log(jax.random.uniform(ks[4], (h,), jnp.float32, 1.0, 16.0)).astype(dtype),
        "dt_bias": (delta + jnp.log(-jnp.expm1(-delta))).astype(dtype),  # softplus's inverse
        "wbeta": normal(ks[5], (d, h)),
        "wg": normal(ks[6], (d, h, dv)),
        "o_norm": layers.init_norm("rmsnorm", dv, dtype),
        "wo": normal(ks[7], (h, dv, d), resid_std),
    }


def state_shapes(cfg: ModelConfig, rows: int) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """What ``rows`` rows keep: {"state": float32, "conv": compute dtype}."""
    return {
        "state": ((rows, cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim), jnp.float32),
        "conv": ((rows, cfg.gdn_conv_kernel - 1, cfg.gdn_conv_dim), jnp.dtype(cfg.compute_dtype)),
    }


step_form = kda.step_form  # the delta rule's one-token step is KDA's, and so is its choice of form


def chunked(state, q, k, v, g, beta):
    """Many tokens: state (B,H,K,V), q/k (B,T,H,K), v (B,T,H,V), g/beta (B,T,H),
    all float32, T any length (padded here to whole chunks with g = 0, beta = 0)
    -> (o (B,T,H,V), state after the last token)."""
    b, t, h, _ = q.shape
    dv = v.shape[-1]
    c = kda.CHUNK
    n = -(-t // c)
    pad = n * c - t
    # (B, T, H, .) -> (N, B, H, C, .): the scan walks the chunks
    cut = lambda a: jnp.moveaxis(
        jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)).reshape((b, n, c) + a.shape[2:]),
        (1, 2), (0, 3))
    col = jnp.arange(c)
    upto = col[:, None] >= col[None, :]  # j <= i
    before = col[:, None] > col[None, :]  # j < i

    def step(s, xs):
        """One chunk, (B,H,C,.) each, from state s: one chunk's (B,H,C,C)
        factors live at a time."""
        q, k, v, g, beta = xs
        gc = jnp.cumsum(g[..., 0], axis=-1)  # G_i, inclusive: (B,H,C)
        decay = jnp.exp(jnp.where(upto, gc[..., :, None] - gc[..., None, :], -jnp.inf))  # exp(G_i - G_j)
        a_mat = jnp.where(before, jnp.einsum("bhik,bhjk->bhij", k, k, precision=_HI) * decay, 0.0) * beta
        p_mat = jnp.einsum("bhik,bhjk->bhij", q, k, precision=_HI) * decay
        decay_in = jnp.exp(gc)[..., None]  # exp(G_i): from the chunk's start to token i
        rhs = beta * (v - jnp.einsum("bhck,bhkv->bhcv", k * decay_in, s, precision=_HI))
        u = jax.scipy.linalg.solve_triangular(
            a_mat + jnp.eye(c, dtype=a_mat.dtype), rhs, lower=True, unit_diagonal=True)
        o = jnp.einsum("bhck,bhkv->bhcv", q * decay_in, s, precision=_HI) + jnp.einsum(
            "bhcj,bhjv->bhcv", p_mat, u, precision=_HI)
        total = gc[..., -1:]  # G_C
        k_out = k * jnp.exp(total - gc)[..., None]  # from token j to the chunk's end
        s = s * jnp.exp(total)[..., None] + jnp.einsum("bhck,bhcv->bhkv", k_out, u, precision=_HI)
        return s, o

    state, o = jax.lax.scan(step, state, (cut(q), cut(k), cut(v), cut(g[..., None]), cut(beta[..., None])))
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(b, n * c, h, dv)
    return o[:, :t], state


def mix(
    p: Params, x: jax.Array, cfg: ModelConfig, state: jax.Array, tail: jax.Array,
    valid: Optional[jax.Array] = None, ends: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The mixer on the sublayer's input x (B,T,D) from ``state`` (B,H,K,V) and
    conv ``tail`` -> (y (B,T,D), new state, new tail). ``valid`` (B,T) bool marks
    the real tokens (None = all); ``ends`` (B,) is each row's index past its last
    real token, for the tail (None = T)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    f32 = jnp.float32
    w = layers.weight
    b, t, _ = x.shape
    nh, dk, dv = cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    xc = x.astype(cdt)
    with jax.named_scope("gdn.proj"):
        qkv = jnp.einsum("btd,dc->btc", xc, w(p, "w_in", cdt), preferred_element_type=f32).astype(cdt)
    with jax.named_scope("gdn.conv"):
        if valid is not None:
            qkv = jnp.where(valid[:, :, None], qkv, 0)
        qkv, tail = kda._conv(p, qkv, tail, ends)
        q, k = (qkv[..., i * nh * dk : (i + 1) * nh * dk].reshape(b, t, nh, dk) for i in range(2))
        v = qkv[..., 2 * nh * dk :].reshape(b, t, nh, dv)
        q, k = kda._l2(q) * dk ** -0.5, kda._l2(k)
    with jax.named_scope("gdn.gate"):
        a = jnp.einsum("btd,dh->bth", xc, w(p, "wa", cdt), preferred_element_type=f32)
        beta = jnp.einsum("btd,dh->bth", xc, w(p, "wbeta", cdt), preferred_element_type=f32)
        g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(a + p["dt_bias"].astype(f32))
        beta = jax.nn.sigmoid(beta) * (2.0 if cfg.gdn_allow_neg_eigval else 1.0)
        if valid is not None:
            g = jnp.where(valid[:, :, None], g, 0.0)
            beta = jnp.where(valid[:, :, None], beta, 0.0)
    if t == 1:
        with jax.named_scope("gdn.step"):
            gk = g[:, 0, :, None]  # (B,H,1): the same decay on every channel of a head
            if step_form(state, current_mesh()) == "kernel":
                o, state = pallas_kda.recurrent_step(
                    state, q[:, 0], k[:, 0], v[:, 0], jnp.broadcast_to(gk, (b, nh, dk)), beta[:, 0])
            else:
                o, state = kda.recurrent_step(state, q[:, 0], k[:, 0], v[:, 0], gk, beta[:, 0])
            o = o[:, None]
    else:
        with jax.named_scope("gdn.chunk"):
            o, state = chunked(state, q, k, v, g, beta)
    with jax.named_scope("gdn.out"):
        # the gate's projection waits until here: a prefill holds one (B,T,H,V) less
        gate = jnp.einsum("btd,dhn->bthn", xc, w(p, "wg", cdt), preferred_element_type=f32)
        o = layers.rmsnorm(p["o_norm"], o, cfg.norm_eps) * jax.nn.silu(gate)
        y = jnp.einsum(
            "bthn,hnd->btd", o.astype(cdt), w(p, "wo", cdt), preferred_element_type=f32
        ).astype(cdt)
    return y, state, tail
