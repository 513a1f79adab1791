"""Kimi Delta Attention (arXiv:2510.26692): a linear-attention mixer whose
cache is a fixed-size state a row, not pages.

Per token and head, with ``x`` the sublayer's normed input::

    q', k', v = SiLU(conv(W_q x)), SiLU(conv(W_k x)), SiLU(conv(W_v x))
    q = q' / |q'| / sqrt(d_k),   k = k' / |k'|
    g = lower_bound * sigmoid(exp(A_log) * (W_f x + dt_bias))    (per channel, <= 0)
    beta = sigmoid(W_beta x)
    S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} + beta k v^T,    o_t = S_t^T q
    y = W_o [RMSNorm_head(o) * sigmoid(W_g x)]

``conv`` is a causal depthwise convolution over time; no positional encoding.
What a row keeps between calls is ``S`` (H, d_k, d_v), float32, and the
convolution's tail, the last ``kernel - 1`` projected inputs of every channel.

Two forms of the recurrence, one function of the inputs:

- one token a row, the equations as written (``kda.step``). ``recurrent_step``
  is them in four ``jnp`` lines; ``ops/pallas_kda.py`` is the same four lines as
  one kernel that brings a row's state into VMEM once and writes it back to the
  buffer it came from (XLA's two fusions of the ``jnp`` form cross HBM three
  and a half times). ``step_form`` reads which of the two runs from the state
  itself, as ``mla.decode_form`` and ``moe.experts_form`` read theirs: the
  kernel for a float32 state whose K is whole 8-sublane tiles, that no mesh
  shards, on a TPU; the ``jnp`` lines everywhere else, and as the reference the tests hold
  the kernel to. Every ``t == 1`` call of ``mix`` (the decode step over the
  pools as they lie, the gathered slots of ``paged.slots``, ``generate``'s
  contiguous cache) goes through that one choice. The pool keeps its
  (slots, H, K, V) layout for both: ``chunked``, ``paged._scatter_pages`` and
  the benchmark's check and byte count read it so;
- ``chunked``: many tokens a row, chunk by chunk in the WY/UT form
  (``kda.chunk``). Inside a chunk of ``CHUNK`` tokens, with ``G_i`` the
  cumulative log-decay, ``u_i = beta_i (v_i - S'^T k_i)`` solves the unit lower
  triangular system ``(I + A) U = beta (V - K~ S_0)``, ``A_ij = beta_i sum_c
  k_ic k_jc exp(G_ic - G_jc)``; then ``O = Q~ S_0 + P U`` and ``S_C =
  Diag(exp G_C) S_0 + K^^T U``. The decay is bounded below by ``exp(lower_bound)``
  a token, so ``G`` reaches -320 inside a chunk of 64 and ``1 / exp(G)``
  overflows float32: every ``exp`` here is of a *difference* of cumulative logs
  that is never positive. Pairs of tokens in one sub-block of ``SUB`` take the
  difference itself; pairs further apart go through a reference point between
  them, two factors of at most 1 that a matmul multiplies (a factor that
  underflows is the zero the true product rounds to).

``mix`` is the mixer on a sublayer's normed input; the cache it runs from and
leaves behind (none, a contiguous cache, a slot of the state pools) is
``models/recurrent.py``'s, shared with the other recurrent mixer.

Both take a validity mask: an invalid position (bucket padding past a row's
true length, a ragged row's left padding) gets ``g = 0``, ``beta = 0`` and a
zero convolution input, so it changes neither the state nor the tail, and a
bucket-padded prompt leaves both as of its last real token.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from pretraining_llm_tpu.config import ModelConfig
from pretraining_llm_tpu.models import layers
from pretraining_llm_tpu.ops import pallas_kda
from pretraining_llm_tpu.parallel.sharding import current_mesh

Params = Dict[str, Any]

SCOPE = "kda"  # the mixer's device scopes: kda.proj, kda.conv, kda.gate, kda.step | kda.chunk, kda.out
CHUNK = 64  # tokens a chunk of the chunked form
SUB = 16  # tokens that share one reference point inside a chunk

_HI = jax.lax.Precision.HIGHEST  # float32 inside a chunk: no bfloat16 passes on the MXU


def init_params(cfg: ModelConfig, key: jax.Array, resid_std: float, dtype: Any) -> Params:
    d, h, n = cfg.d_model, cfg.n_heads, cfg.kda_head_dim
    ks = jax.random.split(key, 8)

    def normal(k, shape, s=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    return {
        "wqkv": normal(ks[0], (d, 3, h, n)),
        "conv": normal(ks[1], (cfg.kda_conv_kernel, 3, h, n), cfg.kda_conv_kernel ** -0.5),
        "wf": normal(ks[2], (d, h, n)),
        # exp(A_log) between 1 and 16 and a decay of a few per cent a token, as
        # the open implementation starts
        "A_log": jnp.log(jax.random.uniform(ks[3], (h,), jnp.float32, 1.0, 16.0)).astype(dtype),
        "dt_bias": normal(ks[4], (h, n), 1.0),
        "wbeta": normal(ks[5], (d, h)),
        "wg": normal(ks[6], (d, h, n)),
        "o_norm": layers.init_norm("rmsnorm", n, dtype),
        "wo": normal(ks[7], (h, n, d), resid_std),
    }


def state_shapes(cfg: ModelConfig, rows: int) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """What ``rows`` rows keep: {"state": float32, "conv": compute dtype}."""
    h, n = cfg.n_heads, cfg.kda_head_dim
    return {
        "state": ((rows, h, n, n), jnp.float32),
        "conv": ((rows, cfg.kda_conv_kernel - 1, 3 * h * n), jnp.dtype(cfg.compute_dtype)),
    }


# -- the two forms of the recurrence -----------------------------------------------


def recurrent_step(state, q, k, v, g, beta):
    """One token: state (B,H,K,V) float32, q/k/g (B,H,K), v (B,H,V), beta (B,H)
    -> (o (B,H,V), new state)."""
    s = state * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k, precision=_HI))
    s = s + k[..., None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI), s


def step_form(state: Any, mesh: Any = None, backend: Optional[str] = None) -> str:
    """The form one token of the recurrence takes over ``state`` (anything with
    a shape and a dtype): ``"kernel"`` (``ops/pallas_kda.py``: a row's state
    read once and written once, in place) for a float32 state (rows, H, K, V)
    that ``pallas_kda.takes`` (K whole 8-sublane tiles) and no mesh shards, where Mosaic compiles;
    ``"jnp"`` (``recurrent_step``) for every other state and backend. Read from
    the input, never from an option; the engine reports the form of its decode
    program in ``pool_info()``."""
    if (
        pallas_kda.takes(tuple(state.shape), state.dtype)
        and mesh is None
        and (backend or jax.default_backend()) == "tpu"
    ):
        return "kernel"
    return "jnp"


def chunked(state, q, k, v, g, beta):
    """Many tokens: state (B,H,K,V), q/k/g (B,T,H,K), v (B,T,H,V), beta (B,T,H),
    all float32, T any length (padded here to whole chunks with g = 0, beta = 0)
    -> (o (B,T,H,V), state after the last token)."""
    b, t, h, _ = q.shape
    dv = v.shape[-1]
    n = -(-t // CHUNK)
    pad = n * CHUNK - t
    # (B, T, H, .) -> (N, B, H, C, .): the scan walks the chunks
    cut = lambda a: jnp.moveaxis(
        jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)).reshape(
            (b, n, CHUNK) + a.shape[2:]), (1, 2), (0, 3))
    nsub = CHUNK // SUB
    col, sub = jnp.arange(CHUNK), jnp.arange(SUB)
    before = col[None, :] < (jnp.arange(nsub)[:, None]) * SUB  # (nsub, C): ahead of the sub-block
    lower = col[:, None] > col[None, :]
    diag = col[:, None] == col[None, :]
    rows = lambda a: a.reshape(a.shape[:-2] + (nsub, SUB, a.shape[-1]))

    def step(s, xs):
        """One chunk, (B,H,C,.) each, from state s. Everything the chunk needs
        is made here, so no more than one chunk's factors live at once."""
        q, k, v, g, beta = xs
        gc = jnp.cumsum(g, axis=-2)  # G_i, inclusive
        # sum_c left_ic k_jc exp(G_ic - G_jc), j <= i, in two parts. Token j ahead
        # of token i's sub-block of SUB rows: through one reference a sub-block,
        # R = G just before its first token, as a matmul of left_i exp(G_i - R)
        # and k_j exp(R - G_j); both exponents are <= 0, so a term that matters
        # is made of two factors near 1. Token j inside i's sub-block: the
        # difference itself, (SUB, SUB, K) a sub-block; a product of exp(-80)
        # and exp(75) there would carry both factors' rounding into a term of
        # size exp(-5).
        ref = (gc - g)[..., ::SUB, :]  # (B,H,nsub,K)
        from_ref = jnp.exp(rows(gc) - ref[..., None, :])  # (B,H,nsub,SUB,K)
        to_ref = jnp.exp(jnp.where(
            before[..., None], ref[..., None, :] - gc[..., None, :, :], -jnp.inf
        ))  # (B,H,nsub,C,K)
        k_cols = k[..., None, :, :] * to_ref
        gs, ks = rows(gc), rows(k)
        within = jnp.exp(jnp.where(
            (sub[:, None] >= sub[None, :])[..., None], gs[..., :, None, :] - gs[..., None, :, :], -jnp.inf
        ))  # (B,H,nsub,SUB,SUB,K)

        def pair(left):
            far = jnp.einsum("bhasc,bhajc->bhasj", rows(left) * from_ref, k_cols, precision=_HI)
            near = jnp.einsum("bhasc,bhatc,bhastc->bhast", rows(left), ks, within, precision=_HI)
            # place each sub-block's (SUB, SUB) square on the chunk's diagonal
            placed = jnp.zeros(far.shape, far.dtype)
            for a in range(nsub):
                placed = placed.at[..., a, :, a * SUB : (a + 1) * SUB].set(near[..., a, :, :])
            return (far + placed).reshape(left.shape[:-1] + (CHUNK,))

        a_mat = jnp.where(lower, pair(k) * beta, 0.0)
        p_mat = jnp.where(lower | diag, pair(q), 0.0)
        decay_in = jnp.exp(gc)  # exp(G_i): from the chunk's start to token i
        rhs = beta * (v - jnp.einsum("bhck,bhkv->bhcv", k * decay_in, s, precision=_HI))
        u = jax.scipy.linalg.solve_triangular(
            a_mat + jnp.eye(CHUNK, dtype=a_mat.dtype), rhs, lower=True, unit_diagonal=True
        )
        o = jnp.einsum("bhck,bhkv->bhcv", q * decay_in, s, precision=_HI) + jnp.einsum(
            "bhcj,bhjv->bhcv", p_mat, u, precision=_HI)
        total = gc[..., -1:, :]  # G_C
        k_out = k * jnp.exp(total - gc)  # from token j to the chunk's end
        s = s * jnp.exp(total)[..., 0, :, None] + jnp.einsum(
            "bhck,bhcv->bhkv", k_out, u, precision=_HI)
        return s, o

    state, o = jax.lax.scan(step, state, (cut(q), cut(k), cut(v), cut(g), cut(beta[..., None])))
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(b, n * CHUNK, h, dv)
    return o[:, :t], state


# -- the mixer ----------------------------------------------------------------------


def _conv(p: Params, x: jax.Array, tail: jax.Array, ends: Optional[jax.Array]):
    """Causal depthwise convolution + SiLU of x (B,T,C) behind ``tail``
    (B,kernel-1,C) -> (y (B,T,C) float32, the tail after token ``ends`` - 1 of
    each row; the last tokens' when ``ends`` is None)."""
    kernel = p["conv"].shape[0]
    w = p["conv"].reshape(kernel, -1).astype(jnp.float32)
    t = x.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # token i at i + kernel - 1
    y = sum(ext[:, i : i + t].astype(jnp.float32) * w[i] for i in range(kernel))
    if ends is None:
        new_tail = ext[:, t:]
    else:
        at = ends[:, None] + jnp.arange(kernel - 1, dtype=ends.dtype)[None, :]
        new_tail = jnp.take_along_axis(ext, at[:, :, None], axis=1)
    return jax.nn.silu(y), new_tail.astype(tail.dtype)


def _l2(a: jax.Array) -> jax.Array:
    return a * jax.lax.rsqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)


def mix(
    p: Params, h: jax.Array, cfg: ModelConfig, state: jax.Array, tail: jax.Array,
    valid: Optional[jax.Array] = None, ends: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The mixer on normed input h (B,T,D) from ``state`` (B,H,K,V) and conv
    ``tail`` -> (y (B,T,D), new state, new tail). ``valid`` (B,T) bool marks the
    real tokens (None = all); ``ends`` (B,) is each row's index past its last
    real token, for the tail (None = T)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    f32 = jnp.float32
    w = layers.weight
    b, t, _ = h.shape
    nh, n = cfg.n_heads, cfg.kda_head_dim
    hc = h.astype(cdt)
    with jax.named_scope("kda.proj"):
        qkv = jnp.einsum(
            "btd,dchn->btchn", hc, w(p, "wqkv", cdt), preferred_element_type=f32
        ).astype(cdt).reshape(b, t, 3 * nh * n)
    with jax.named_scope("kda.conv"):
        if valid is not None:
            qkv = jnp.where(valid[:, :, None], qkv, 0)
        qkv, tail = _conv(p, qkv, tail, ends)
        q, k, v = (qkv.reshape(b, t, 3, nh, n)[:, :, i] for i in range(3))
        q, k = _l2(q) * n ** -0.5, _l2(k)
    with jax.named_scope("kda.gate"):
        f = jnp.einsum("btd,dhn->bthn", hc, w(p, "wf", cdt), preferred_element_type=f32)
        beta = jnp.einsum("btd,dh->bth", hc, w(p, "wbeta", cdt), preferred_element_type=f32)
        rate = jnp.exp(p["A_log"].astype(f32))[:, None]
        g = cfg.kda_gate_lower_bound * jax.nn.sigmoid(rate * (f + p["dt_bias"].astype(f32)))
        beta = jax.nn.sigmoid(beta)
        if valid is not None:
            g = jnp.where(valid[:, :, None, None], g, 0.0)
            beta = jnp.where(valid[:, :, None], beta, 0.0)
    if t == 1:
        with jax.named_scope("kda.step"):
            step = pallas_kda.recurrent_step if step_form(state, current_mesh()) == "kernel" else recurrent_step
            o, state = step(state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
            o = o[:, None]
    else:
        with jax.named_scope("kda.chunk"):
            o, state = chunked(state, q, k, v, g, beta)
    with jax.named_scope("kda.out"):
        # the gate's projection waits until here: a prefill holds one (B,T,H,K) less
        gate = jnp.einsum("btd,dhn->bthn", hc, w(p, "wg", cdt), preferred_element_type=f32)
        o = layers.rmsnorm(p["o_norm"], o, cfg.norm_eps) * jax.nn.sigmoid(gate)
        y = jnp.einsum(
            "bthn,hnd->btd", o.astype(cdt), w(p, "wo", cdt), preferred_element_type=f32
        ).astype(cdt)
    return y, state, tail
