"""Granite-4.0-H-Small forward pass as its config keys and the published
descriptions they map onto define it (``granitemoehybrid``; Mamba-2,
arXiv:2405.21060, for the state-space layers), in plain float32 jax.numpy at the
highest matmul precision. ``d`` the hidden size; RMSNorm in float32 with a
learned weight; no bias anywhere but the convolution's::

    h_0 = embedding_multiplier * E[ids];   logits = (RMSNorm(h_L) E^T) / logits_scaling   (tied)
    layer l (layer_types[l] in {mamba, attention}), every layer with experts:
        h <- h + residual_multiplier * Mixer(RMSNorm_1(h))
        h <- h + residual_multiplier * (MoE(u) + Shared(u)),   u = RMSNorm_2(h)

    attention (no position of any kind, grouped queries):
        q = u W_q, k = u W_k, v = u W_v;  scores q.k * attention_multiplier
        (1/128 here, NOT 1/sqrt(128)); causal softmax; o = concat(heads) W_o

    Mamba-2, H heads of P channels (d_in = H P = mamba_expand d), state N, G groups:
        [z | xBC | dt] = u W_in                       widths d_in | d_in + 2 G N | H
        xBC <- SiLU(conv(xBC) + b)                    causal depthwise, mamba_d_conv taps
        [x | B | C] = xBC                             widths d_in | G N | G N
        delta_t = softplus(dt_t + dt_bias),  A = -exp(A_log)           (a head)
        S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t;   y_t = S_t C_t + D x_t
        y <- RMSNorm(y * SiLU(z)) over each group's channels, times its weight
        out = y W_out

    experts: r = u W_r (float32); the num_experts_per_tok largest; gates = softmax
        over those logits; expert e: W_down,e (SiLU(a) * b), [a | b] = W_in,e u;
        Shared: the same form, width shared_intermediate_size, always on.

No cache, kernel or batching: one sequence; a Python loop over the layers and,
inside a layer, one jitted loop over the experts held; the state-space layer as
the token-by-token recurrence under ``lax.scan``, never a chunked form;
attention in blocks of queries so that 5,184 positions fit. Independent of
``models/``: it reads only the canonical weights of
``harness/families/granite.py``, one layer at a time.

Departures from the published model, all of the harness and none of the
equations:

- weights are seeded; the depth is what the configuration file states; each
  attention layer arrives among the globals (``L<i>_*``) because the harness
  hands a family no layer index;
- the experts are those the weights carry (``e_gate.shape[0]``), the router's
  first ones: this chip's share of an expert-parallel layer. The router scores
  all ``n_experts_routed`` and picks among all of them; a choice that lives
  elsewhere adds nothing here (``experts``; ``held`` names another share);
- the vocabulary is the slice the table carries;
- the logits are computed in blocks of vocabulary columns.

``control`` names one deliberate departure from the equations, for the
controls of `correct` (``benchmark/control.py``, the tests): ``"rope"`` rotates
the attention layer's queries and keys (``rope_theta``), ``"sqrt_scale"``
scales its scores by 1/sqrt(head_dim), ``"no_decay"`` leaves exp(delta A) at
1, ``"bf16_state"`` rounds the state to bfloat16 after every token.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from references.common import Quant, mm

F32 = jnp.float32
Q_BLOCK = 512  # queries scored at a time
V_BLOCK = 8192  # vocabulary columns of logits made at a time
HI = jax.lax.Precision.HIGHEST
CONTROLS = ("", "rope", "sqrt_scale", "no_decay", "bf16_state")


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


# -- the state-space layer -----------------------------------------------------------


def conv(x, w, b):
    """Causal depthwise convolution over time: x (T, C), w (C, taps), the last
    tap on the current token, zeros before the first; plus the bias, then SiLU."""
    taps = w.shape[1]
    xp = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(xp[i : i + x.shape[0]] * w[:, i] for i in range(taps)) + b)


@functools.partial(jax.jit, static_argnames=("control",))
def recurrence(x, b, c, delta, a, control=""):
    """S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t, y_t = S_t C_t, one
    token at a time from S = 0. x (T, H, P); b, c (T, H, N) (a head's group's);
    delta (T, H); a (H,). Returns (y (T, H, P), S_T (H, P, N))."""
    def step(s, u):
        x, b, c, delta = u
        decay = jnp.ones_like(delta) if control == "no_decay" else jnp.exp(delta * a)
        s = decay[:, None, None] * s + (delta[:, None] * x)[:, :, None] * b[:, None, :]
        if control == "bf16_state":
            # not .astype(bfloat16).astype(float32): XLA removes that pair (excess precision is allowed)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("hpn,hn->hp", s, c, precision=HI)

    s0 = jnp.zeros((x.shape[1], x.shape[2], b.shape[-1]), F32)
    s, y = jax.lax.scan(step, s0, (x, b, c, delta))
    return y, s


def mamba(u, w, arch: Dict[str, Any], quant: Quant, control: str = ""):
    """The Mamba-2 mixer on normed input (T, d), and its state after the last token."""
    t = u.shape[0]
    h, p, n, g = arch["mamba_n_heads"], arch["mamba_d_head"], arch["mamba_d_state"], arch["mamba_n_groups"]
    d_in = h * p
    zxbcdt = mm(u, w["w_in"], quant)
    z, xbc, dt = zxbcdt[:, :d_in], zxbcdt[:, d_in : 2 * d_in + 2 * g * n], zxbcdt[:, 2 * d_in + 2 * g * n :]
    xbc = conv(xbc, w["conv"], w["conv_bias"])
    x = xbc[:, :d_in].reshape(t, h, p)
    # B and C of a group serve all its heads
    b, c = (jnp.repeat(xbc[:, d_in + i * g * n : d_in + (i + 1) * g * n].reshape(t, g, n), h // g, axis=1)
            for i in range(2))
    delta = jax.nn.softplus(dt + w["dt_bias"])  # no clamp on delta (assumed: the family's public code)
    y, state = recurrence(x, b, c, delta, -jnp.exp(w["A_log"]), control)
    y = y + w["D"][None, :, None] * x
    # the gate first, then the norm, over each group's channels (assumed: norm_before_gate false)
    y = (y.reshape(t, d_in) * jax.nn.silu(z)).reshape(t, g, d_in // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + arch["rms_norm_eps"])
    return mm(y.reshape(t, d_in) * w["norm_scale"], w["w_out"], quant), state


# -- attention -----------------------------------------------------------------------


def rope(x, theta: float):
    """The control's rotation: x (T, heads, dim), split halves (j, j + dim/2)
    turn by position * theta^(-2j/dim)."""
    t, dim = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    ang = jnp.arange(t, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : dim // 2], x[..., dim // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(u, w, arch: Dict[str, Any], quant: Quant, control: str = ""):
    """Grouped-query attention on normed input (T, d): no rotation, no position
    of any kind; scores times ``attention_multiplier``."""
    t, d = u.shape
    nh, g, dh = w["wq"].shape[1], w["wk"].shape[1], w["wq"].shape[2]
    q = mm(u, w["wq"].reshape(d, nh * dh), quant).reshape(t, nh, dh)
    k = mm(u, w["wk"].reshape(d, g * dh), quant).reshape(t, g, dh)
    v = mm(u, w["wv"].reshape(d, g * dh), quant).reshape(t, g, dh)
    if control == "rope":
        q, k = rope(q, float(arch["rope_theta"])), rope(k, float(arch["rope_theta"]))
    scale = dh ** -0.5 if control == "sqrt_scale" else float(arch["attention_multiplier"])
    # query head i reads KV head i // (heads / kv_heads)
    kt = jnp.repeat(k, nh // g, axis=1).transpose(1, 2, 0)  # (H, dh, T)
    vt = jnp.repeat(v, nh // g, axis=1).transpose(1, 0, 2)  # (H, T, dh)
    outs = []
    for start in range(0, t, Q_BLOCK):
        qb = q[start : start + Q_BLOCK].transpose(1, 0, 2)  # (H, B, dh)
        s = mm(qb, kt, quant) * scale
        i = start + jnp.arange(qb.shape[1])[:, None]
        pr = jax.nn.softmax(jnp.where(jnp.arange(t)[None, :] <= i, s, -jnp.inf), axis=-1)
        outs.append(mm(pr, vt, quant).transpose(1, 0, 2))  # (B, H, dh)
    return mm(jnp.concatenate(outs, axis=0).reshape(t, nh * dh), w["wo"].reshape(nh * dh, d), quant)


# -- the experts ---------------------------------------------------------------------


def swiglu(u, gate, up, down, quant: Quant):
    return mm(jax.nn.silu(mm(u, gate, quant)) * mm(u, up, quant), down, quant)


@functools.partial(jax.jit, static_argnames=("k",))
def route(u, router, k):
    """(T, E) gate of every expert for every token, zero where not selected: the
    k largest router logits, softmax over those k (equal to the softmax over all,
    the top k renormalised). Float32, never quantised."""
    logits = jnp.matmul(u, router.astype(F32), precision=HI)
    top, idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top, axis=-1)
    return jnp.zeros_like(logits).at[jnp.arange(u.shape[0])[:, None], idx].set(gates)


@functools.partial(jax.jit, static_argnames=("cap", "quant"))
def held_experts(u, gates, e_gate, e_up, e_down, cap, quant):
    """The sum over the experts held of each one's output for the tokens that
    chose it (at most ``cap`` an expert), weighted: one jitted loop, so that an
    expert's weights are never sliced by a Python integer (a compile an expert)."""
    up = jnp.concatenate([u, jnp.zeros((1, u.shape[1]), u.dtype)])

    def one(y, e):
        col = gates[:, e]
        rows = jnp.nonzero(col > 0, size=cap, fill_value=u.shape[0])[0]
        gp = jnp.concatenate([col, jnp.zeros((1,), col.dtype)])[rows]
        out = swiglu(up[rows], e_gate[e].astype(F32), e_up[e].astype(F32), e_down[e].astype(F32), quant)
        return y.at[rows].add(out * gp[:, None], mode="drop"), None

    return jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(e_gate.shape[0]))[0]


def experts(u, w, arch: Dict[str, Any], quant: Quant, held=None):
    """The expert layer's part that the experts ``held`` give (expert indices
    whose weights are ``w["e_*"]`` in that order; None = the first
    ``e_gate.shape[0]``, the chip's share), plus the shared expert, ungated."""
    gates = route(u, w["router"], arch["num_experts_per_tok"])
    if held is not None:  # another share: its experts' columns where the first ones' stood
        gates = gates[:, jnp.asarray(list(held))]
    gates = gates[:, : w["e_gate"].shape[0]]
    most = int(jnp.max(jnp.sum(gates > 0, axis=0)))
    cap = max(8, 1 << (most - 1).bit_length()) if most else 8
    shared = swiglu(u, w["s_gate"].astype(F32), w["s_up"].astype(F32), w["s_down"].astype(F32), quant)
    return shared + held_experts(u, gates, w["e_gate"], w["e_up"], w["e_down"], cap, quant)


# -- the stack -----------------------------------------------------------------------

SMALL = ("ln1_scale", "ln2_scale", "w_in", "conv", "conv_bias", "A_log", "dt_bias", "D", "norm_scale", "w_out",
         "wq", "wk", "wv", "wo")


def layer(x, w, arch: Dict[str, Any], quant: Quant, mixers, held=None):
    """One pre-norm decoder layer on (T, d): ``w`` has a Mamba-2 mixer (``w_in``)
    or an attention one, and an expert layer's FFN. Returns the layer's output
    and the mixer's state after the last token (None for attention)."""
    f = {k: w[k].astype(F32) for k in SMALL if k in w}
    x, state = mixers["mamba" if "w_in" in w else "attention"](x, f)
    u = rmsnorm(x, f["ln2_scale"], arch["rms_norm_eps"])
    return x + float(arch["residual_multiplier"]) * experts(u, w, arch, quant, held), state


def forward(tokens: jax.Array, layer_weights: Callable[[int], Dict[str, jax.Array]],
            global_weights: Dict[str, jax.Array], arch: Dict[str, Any],
            quant: Quant = None, control: str = "", states: bool = False) -> Any:
    """Logits (T, V) of one sequence. ``layer_weights(l)`` makes the l-th
    Mamba-2 layer; each attention layer is ``global_weights["L<i>_*"]``. With
    ``states``, also every Mamba-2 layer's state after the last token, in layer
    order ((H, P, N) float32 each): what a row's state slot holds then."""
    if control not in CONTROLS:
        raise ValueError(f"control is one of {CONTROLS}")
    gw = global_weights
    eps, res = arch["rms_norm_eps"], float(arch["residual_multiplier"])
    x = float(arch["embedding_multiplier"]) * gw["embed"][tokens].astype(F32)
    def mamba_block(x, f):
        out, state = mamba(rmsnorm(x, f["ln1_scale"], eps), f, arch, quant, control)
        return x + res * out, state

    mixers = {
        "mamba": jax.jit(mamba_block),
        "attention": jax.jit(
            lambda x, f: (x + res * attention(rmsnorm(x, f["ln1_scale"], eps), f, arch, quant, control), None)),
    }
    mine, kept = 0, []
    for i in range(arch["num_hidden_layers"]):
        own = {k[len(f"L{i}_"):]: v for k, v in gw.items() if k.startswith(f"L{i}_")}
        if not own:
            own, mine = layer_weights(mine), mine + 1
        x, state = layer(x, own, arch, quant, mixers)
        if state is not None:
            kept.append(state)
    h = rmsnorm(x, gw["final_scale"].astype(F32), eps)
    head = jax.jit(lambda h, rows: mm(h, rows.astype(F32).T, quant) / float(arch["logits_scaling"]))
    logits = jnp.concatenate([
        head(h, gw["embed"][start : start + V_BLOCK]) for start in range(0, gw["embed"].shape[0], V_BLOCK)
    ], axis=1)
    return (logits, kept) if states else logits
