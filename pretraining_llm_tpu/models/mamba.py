"""Mamba-2 (arXiv:2405.21060): a state-space mixer whose cache is a fixed-size
state a row, not pages. The second recurrent mixer beside ``models/kda.py``,
under the same cache discipline (``models/recurrent.py::mixer_block``).

Per token, with ``u`` the sublayer's normed input, H heads of P channels
(``d_in`` = H P, ``cfg.mamba_heads * cfg.mamba_head_dim``: the product, never
an expansion factor times the hidden size — Granite's 8,192 happens to be
2 x 4,096, Nemotron-H's 4,096 is not 2 x 2,688), a state of N a channel, G
groups of H / G consecutive heads sharing B and C (1 in Granite, 8 in
Nemotron-H)::

    [z | xBC | dt] = u W_in                     widths d_in | d_in + 2 G N | H
    xBC = SiLU(conv(xBC) + b)                   causal depthwise, over time
    [x | B | C] = xBC                           widths d_in | G N | G N
    delta = softplus(dt + dt_bias)              a head
    S_t = exp(delta A) S_{t-1} + delta x_t (x) B_t,   A = -exp(A_log)   (a head, scalar)
    y_t = S_t C_t + D x_t
    out = W_out [RMSNorm_group(y * SiLU(z)) * weight]

No positional encoding. What a row keeps between calls is ``S`` (H, P, N),
float32, and the convolution's tail, the last ``kernel - 1`` pre-activation
``xBC`` rows.

Two forms of the recurrence, one function of the inputs:

- ``recurrent_step``: one token a row, the equations as written, as one
  elementwise pass over the state and a reduction over N (no matmul: the state
  is read once and written once if XLA fuses the two, ``ssm.step`` in a trace);
- ``chunked``: many tokens a row, chunk by chunk (the SSD form). Inside a chunk
  of ``Q`` tokens, with ``a_t = delta_t A`` and ``L_t`` its inclusive running
  sum, ``y_t = sum_{s<=t} exp(L_t - L_s) (C_t . B_s) delta_s x_s + exp(L_t) S_in
  C_t + D x_t`` and ``S_out = exp(L_Q) S_in + sum_s exp(L_Q - L_s) delta_s x_s (x)
  B_s``. ``a <= 0``, so every ``exp`` is of a difference that is never positive
  (pairs above the diagonal are masked before the ``exp``, not after). The scan
  walks the chunks: one chunk's (heads, Q, Q) decay matrix lives at a time.

Both take a validity mask: an invalid position (bucket padding past a row's true
length, a ragged row's left padding, a dead row's token) gets ``delta = 0`` (no
decay, no input) and a zero convolution input, so it changes neither the state
nor the tail, and a bucket-padded prompt leaves both as of its last real token.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from pretraining_llm_tpu.config import ModelConfig
from pretraining_llm_tpu.models import layers

Params = Dict[str, Any]

SCOPE = "ssm"  # the mixer's device scopes: ssm.proj, ssm.conv, ssm.step | ssm.chunk, ssm.norm, ssm.out

_HI = jax.lax.Precision.HIGHEST  # float32 inside a chunk: no bfloat16 passes on the MXU


def init_params(cfg: ModelConfig, key: jax.Array, resid_std: float, dtype: Any) -> Params:
    d, h, w, c = cfg.d_model, cfg.mamba_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
    ks = jax.random.split(key, 6)

    def normal(k, shape, s=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    # delta = softplus(dt_bias) between 1e-3 and 1e-1 at a zero input, exp(A_log)
    # between 1 and 16, D = 1: as the open implementation starts
    delta = jnp.exp(jax.random.uniform(ks[2], (h,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "w_in": normal(ks[0], (d, w + c + h)),
        "conv": normal(ks[1], (cfg.mamba_conv_kernel, c), cfg.mamba_conv_kernel ** -0.5),
        "conv_bias": jnp.zeros((c,), dtype),
        "dt_bias": (delta + jnp.log(-jnp.expm1(-delta))).astype(dtype),  # softplus's inverse
        "A_log": jnp.log(jax.random.uniform(ks[3], (h,), jnp.float32, 1.0, 16.0)).astype(dtype),
        "D": jnp.ones((h,), dtype),
        "norm": layers.init_norm("rmsnorm", w, dtype),
        "w_out": normal(ks[4], (w, d), resid_std),
    }


def state_shapes(cfg: ModelConfig, rows: int) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """What ``rows`` rows keep: {"state": float32, "conv": compute dtype}."""
    return {
        "state": ((rows, cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state), jnp.float32),
        "conv": ((rows, cfg.mamba_conv_kernel - 1, cfg.mamba_conv_dim), jnp.dtype(cfg.compute_dtype)),
    }


def step_form(state: Any, mesh: Any = None, backend: Optional[str] = None) -> str:
    """The form one token of the recurrence takes over ``state``: ``"jnp"``
    (``recurrent_step``) for every state and backend; the engine reports it in
    ``pool_info()`` as it reports ``kda.step_form``'s."""
    return "jnp"


# -- the two forms of the recurrence -----------------------------------------------


def recurrent_step(state, x, b, c, dt, a, d):
    """One token: state (R,H,P,N) float32, x (R,H,P), b/c (R,G,N), dt (R,H)
    (softplus applied), a/d (H,) -> (y (R,H,P), new state)."""
    r, h, p, n = state.shape
    g = b.shape[1]
    heads = lambda v: jnp.repeat(v, h // g, axis=1) if g != h else v  # (R,G,N) -> (R,H,N)
    decay = jnp.exp(dt * a)  # (R,H)
    s = state * decay[:, :, None, None] + (dt[:, :, None] * x)[..., None] * heads(b)[:, :, None, :]
    y = jnp.sum(s * heads(c)[:, :, None, :], axis=-1) + d[None, :, None] * x
    return y, s


def chunked(state, x, b, c, dt, a, d, chunk: int):
    """Many tokens: state (R,H,P,N), x (R,T,H,P), b/c (R,T,G,N), dt (R,T,H), all
    float32, T any length (padded here to whole chunks with dt = 0) -> (y
    (R,T,H,P), state after the last token)."""
    r, t, h, p = x.shape
    g, n = b.shape[2:]
    j = h // g  # heads a group
    q = min(chunk, t)
    k = -(-t // q)
    pad = k * q - t

    def cut(v, heads):
        """(R, T, *heads, .) -> (K, R, *heads, Q, .): the scan walks the chunks,
        the ``heads`` head axes lie ahead of a chunk's tokens."""
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)).reshape((r, k, q) + v.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(v, 1, 0), 2, 2 + heads)

    lower = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]

    def step(s, xs):
        """One chunk from state s (R,G,J,P,N). Everything the chunk needs is
        made here, so no more than one chunk's (R,H,Q,Q) factors live at once."""
        x, b, c, dt = xs  # (R,G,J,Q,P), (R,G,Q,N), (R,G,Q,N), (R,G,J,Q)
        lam = jnp.cumsum(dt * a[:, :, None], axis=-1)  # L_t, inclusive
        # exp(L_t - L_s), s <= t: the mask goes on the exponent, whose upper
        # triangle is positive and may overflow
        decay = jnp.exp(jnp.where(lower, lam[..., :, None] - lam[..., None, :], -jnp.inf))  # (R,G,J,Qt,Qs)
        cb = jnp.einsum("rgtn,rgsn->rgts", c, b, precision=_HI)
        xd = x * dt[..., None]  # delta_s x_s
        y = jnp.einsum("rgjts,rgjsp->rgjtp", decay * cb[:, :, None], xd, precision=_HI)
        y = y + jnp.exp(lam)[..., None] * jnp.einsum("rgtn,rgjpn->rgjtp", c, s, precision=_HI)
        total = lam[..., -1:]  # L_Q
        s = s * jnp.exp(total)[..., None] + jnp.einsum(
            "rgjsp,rgsn->rgjpn", xd * jnp.exp(total - lam)[..., None], b, precision=_HI)
        return s, y

    a = a.reshape(g, j)
    xs = (cut(x.reshape(r, t, g, j, p), 2), cut(b, 1), cut(c, 1), cut(dt.reshape(r, t, g, j), 2))
    state, y = jax.lax.scan(step, state.reshape(r, g, j, p, n), xs)
    # (K, R, G, J, Q, P) -> (R, K Q, H, P)
    y = jnp.moveaxis(y, (0, 4), (1, 2)).reshape(r, k * q, h, p)[:, :t]
    return y + d[None, None, :, None] * x, state.reshape(r, h, p, n)


# -- the mixer ----------------------------------------------------------------------


def _conv(p: Params, x: jax.Array, tail: jax.Array, ends: Optional[jax.Array]):
    """Causal depthwise convolution + bias + SiLU of x (R,T,C) behind ``tail``
    (R,kernel-1,C) -> (y (R,T,C) float32, the tail after token ``ends`` - 1 of
    each row; the last tokens' when ``ends`` is None)."""
    kernel = p["conv"].shape[0]
    w = p["conv"].astype(jnp.float32)
    t = x.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # token i at i + kernel - 1
    y = sum(ext[:, i : i + t].astype(jnp.float32) * w[i] for i in range(kernel))
    if ends is None:
        new_tail = ext[:, t:]
    else:
        at = ends[:, None] + jnp.arange(kernel - 1, dtype=ends.dtype)[None, :]
        new_tail = jnp.take_along_axis(ext, at[:, :, None], axis=1)
    return jax.nn.silu(y + p["conv_bias"].astype(jnp.float32)), new_tail.astype(tail.dtype)


def mix(
    p: Params, u: jax.Array, cfg: ModelConfig, state: jax.Array, tail: jax.Array,
    valid: Optional[jax.Array] = None, ends: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The mixer on normed input u (R,T,D) from ``state`` (R,H,P,N) and conv
    ``tail`` -> (out (R,T,D), new state, new tail). ``valid`` (R,T) bool marks
    the real tokens (None = all); ``ends`` (R,) is each row's index past its
    last real token, for the tail (None = T)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    f32 = jnp.float32
    r, t, _ = u.shape
    h, hp, n, g = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state, cfg.mamba_n_groups
    w, c = cfg.mamba_d_inner, cfg.mamba_conv_dim
    with jax.named_scope("ssm.proj"):
        zxbcdt = jnp.einsum(
            "rtd,de->rte", u.astype(cdt), layers.weight(p, "w_in", cdt), preferred_element_type=f32)
        z, xbc, dt = zxbcdt[..., :w].astype(cdt), zxbcdt[..., w : w + c].astype(cdt), zxbcdt[..., w + c :]
    with jax.named_scope("ssm.conv"):
        if valid is not None:
            xbc = jnp.where(valid[:, :, None], xbc, 0)
        xbc, tail = _conv(p, xbc, tail, ends)
        x = xbc[..., :w].reshape(r, t, h, hp)
        b, cc = (xbc[..., w + i * g * n : w + (i + 1) * g * n].reshape(r, t, g, n) for i in range(2))
        dt = jax.nn.softplus(dt + p["dt_bias"].astype(f32))
        if valid is not None:
            dt = jnp.where(valid[:, :, None], dt, 0.0)
        a, d = -jnp.exp(p["A_log"].astype(f32)), p["D"].astype(f32)
    if t == 1:
        with jax.named_scope("ssm.step"):
            y, state = recurrent_step(state, x[:, 0], b[:, 0], cc[:, 0], dt[:, 0], a, d)
            y = y[:, None]
    else:
        with jax.named_scope("ssm.chunk"):
            y, state = chunked(state, x, b, cc, dt, a, d, cfg.mamba_chunk_size)
    with jax.named_scope("ssm.norm"):
        # the gate first, then the norm, over each group's channels
        y = y.reshape(r, t, w) * jax.nn.silu(z.astype(f32))
        yg = y.reshape(r, t, g, w // g)
        yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), axis=-1, keepdims=True) + cfg.norm_eps)
        y = yg.reshape(r, t, w) * p["norm"]["scale"].astype(f32)
    with jax.named_scope("ssm.out"):
        out = jnp.einsum(
            "rtw,wd->rtd", y.astype(cdt), layers.weight(p, "w_out", cdt), preferred_element_type=f32
        ).astype(cdt)
    return out, state, tail
