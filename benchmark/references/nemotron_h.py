"""NVIDIA-Nemotron-3-Nano-30B-A3B forward pass as its config keys and the
published descriptions they map onto define it (``nemotron_h``; Mamba-2,
arXiv:2405.21060, for the state-space layers; Nemotron-H, arXiv:2504.03624, for
the stack), in plain float32 jax.numpy at the highest matmul precision. ``d``
the hidden size; RMSNorm ``N`` in float32 with a learned weight, eps
``layer_norm_epsilon``; no bias in any linear map; no positional encoding of
any kind; the embedding unscaled; no multipliers::

    h_0 = E[ids];   logits = W_head N_f(h_L)                    (untied)
    layer l is ONE sublayer under ONE norm, by hybrid_override_pattern[l]:
        h <- h + f(N(h)),   f a Mamba-2 mixer (M), an attention (*) or an expert FFN (E)

    M, H heads of P channels (d_in = H P, NOT expand x d), state N, G groups:
        [z | xBC | dt] = u W_in                       widths d_in | d_in + 2 G N | H
        xBC <- SiLU(conv(xBC) + b)                    causal depthwise, conv_kernel taps
        [x | B | C] = xBC                             widths d_in | G N | G N
        delta_t = softplus(dt_t + dt_bias),  A = -exp(A_log)           (a head)
        S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t[g(h)];   y_t = S_t C_t[g(h)] + D x_t
        y <- RMSNorm(y * SiLU(z)) over each group's d_in / G channels, times its weight
        f = y W_out

    *, grouped queries, no rotation:
        q = u W_q, k = u W_k, v = u W_v;  causal softmax(q k^T / sqrt(head_dim)) v;  f = concat(heads) W_o

    E, u = N(h):
        s = sigmoid(u W_r) (float32);  chosen = the num_experts_per_tok largest of s + b_r
        g_e = routed_scaling_factor * s_e / (sum over chosen of s + 1e-20)    (the bias chooses, it does not weigh)
        expert e: W2_e relu(W1_e u)^2   (two matrices, no gate);  f = sum g_e E_e(u) + W2_s relu(W1_s u)^2

No cache, kernel or batching: one sequence; a Python loop over the layers and,
inside an expert layer, one jitted loop over the experts held; the state-space
layer as the token-by-token recurrence under ``lax.scan``, never a chunked
form; attention in blocks of queries so that 5,184 positions fit. Independent
of ``models/``: it reads only the canonical weights of
``harness/families/nemotron_h.py``, one unit at a time.

Departures from the published model, all of the harness and none of the
equations:

- weights are seeded; the depth is what the configuration file states;
- the harness hands a family no layer index and asks every unit for a state
  space layer's ``dt_bias`` and ``A_log``, so a *unit* of weights
  (``layer_weights(u)``) is a Mamba-2 layer **and the expert layer that follows
  it** (with the attention layers taken out the pattern alternates M, E), and
  each attention layer arrives among the globals (``L<i>_*``, i its place in
  the pattern);
- the experts are those the weights carry (``e_up.shape[0]``), the router's
  first ones: this chip's share of an expert-parallel layer. The router scores
  all ``n_experts_routed`` and picks among all of them; a choice that lives
  elsewhere adds nothing here (``experts``; ``held`` names another share). The
  shared expert is computed here whole;
- the vocabulary is the slice the table carries;
- the logits are computed in blocks of vocabulary columns.

``control`` names one deliberate departure from the equations, for the
controls of `correct` (the tests, ``chipjobs/``): ``"rope"`` rotates the
attention layers' queries and keys (``rope_theta``: the config carries it and
the family applies none), ``"no_decay"`` leaves exp(delta A) at 1,
``"bf16_state"`` rounds the state to bfloat16 after every token,
``"bf16_router"`` computes the router's scores from bfloat16 operands and
rounds them to bfloat16, ``"biased_gates"`` weighs with the biased scores,
``"swiglu_act"`` puts SiLU where relu^2 stands, ``"int8_experts"`` feeds the
experts' matmuls (routed and shared; never the router) int8 operands.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from references.common import Quant, int8_fake_quant, mm

F32 = jnp.float32
Q_BLOCK = 512  # queries scored at a time
V_BLOCK = 8192  # vocabulary columns of logits made at a time
HI = jax.lax.Precision.HIGHEST
CONTROLS = ("", "rope", "no_decay", "bf16_state", "bf16_router", "biased_gates", "swiglu_act", "int8_experts")


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def bf16(x):
    # not .astype(bfloat16).astype(float32): XLA removes that pair (excess precision is allowed)
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


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
            s = bf16(s)
        return s, jnp.einsum("hpn,hn->hp", s, c, precision=HI)

    s0 = jnp.zeros((x.shape[1], x.shape[2], b.shape[-1]), F32)
    s, y = jax.lax.scan(step, s0, (x, b, c, delta))
    return y, s


def mamba(u, w, arch: Dict[str, Any], quant: Quant, control: str = ""):
    """The Mamba-2 mixer on normed input (T, d), and its state after the last token."""
    t = u.shape[0]
    h, p, n, g = arch["mamba_num_heads"], arch["mamba_head_dim"], arch["ssm_state_size"], arch["n_groups"]
    d_in = h * p  # assumed: the family's code takes the product; ``expand`` is unused
    zxbcdt = mm(u, w["w_in"], quant)
    z, xbc, dt = zxbcdt[:, :d_in], zxbcdt[:, d_in : 2 * d_in + 2 * g * n], zxbcdt[:, 2 * d_in + 2 * g * n :]
    xbc = conv(xbc, w["conv"], w["conv_bias"])
    x = xbc[:, :d_in].reshape(t, h, p)
    # B and C of a group serve its h / g heads: g(head) = head // (h / g)
    b, c = (jnp.repeat(xbc[:, d_in + i * g * n : d_in + (i + 1) * g * n].reshape(t, g, n), h // g, axis=1)
            for i in range(2))
    delta = jax.nn.softplus(dt + w["dt_bias"])  # no clamp (assumed: time_step_limit (0, inf))
    y, state = recurrence(x, b, c, delta, -jnp.exp(w["A_log"]), control)
    y = y + w["D"][None, :, None] * x
    # the gate first, then the norm, over each group's channels (assumed: norm_before_gate false)
    y = (y.reshape(t, d_in) * jax.nn.silu(z)).reshape(t, g, d_in // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + arch["layer_norm_epsilon"])
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
    of any kind; scores over sqrt(head_dim). The query width (heads x head_dim)
    is not d."""
    t, d = u.shape
    nh, g, dh = w["wq"].shape[1], w["wk"].shape[1], w["wq"].shape[2]
    q = mm(u, w["wq"].reshape(d, nh * dh), quant).reshape(t, nh, dh)
    k = mm(u, w["wk"].reshape(d, g * dh), quant).reshape(t, g, dh)
    v = mm(u, w["wv"].reshape(d, g * dh), quant).reshape(t, g, dh)
    if control == "rope":
        q, k = rope(q, float(arch["rope_theta"])), rope(k, float(arch["rope_theta"]))
    # query head i reads KV head i // (heads / kv_heads)
    kt = jnp.repeat(k, nh // g, axis=1).transpose(1, 2, 0)  # (H, dh, T)
    vt = jnp.repeat(v, nh // g, axis=1).transpose(1, 0, 2)  # (H, T, dh)
    outs = []
    for start in range(0, t, Q_BLOCK):
        qb = q[start : start + Q_BLOCK].transpose(1, 0, 2)  # (H, B, dh)
        s = mm(qb, kt, quant) * dh ** -0.5
        i = start + jnp.arange(qb.shape[1])[:, None]
        pr = jax.nn.softmax(jnp.where(jnp.arange(t)[None, :] <= i, s, -jnp.inf), axis=-1)
        outs.append(mm(pr, vt, quant).transpose(1, 0, 2))  # (B, H, dh)
    return mm(jnp.concatenate(outs, axis=0).reshape(t, nh * dh), w["wo"].reshape(nh * dh, d), quant)


# -- the experts ---------------------------------------------------------------------


def ungated(u, up, down, quant: Quant, control: str = ""):
    """W2 relu(W1 u)^2: two matrices, no gate."""
    h = mm(u, up, quant)
    return mm(jax.nn.silu(h) if control == "swiglu_act" else jnp.square(jax.nn.relu(h)), down, quant)


@functools.partial(jax.jit, static_argnames=("k", "scale", "control"))
def route(u, router, bias, k, scale, control=""):
    """(T, E) gate of every expert for every token, zero where not selected:
    sigmoid scores in float32, never quantised; the k largest of score + bias
    are chosen; a gate is the chosen expert's UNBIASED score over the sum of the
    chosen ones' (+ 1e-20), times ``scale``."""
    if control == "bf16_router":
        scores = bf16(jax.nn.sigmoid(jnp.matmul(bf16(u), bf16(router.astype(F32)), precision=HI)))
    else:
        scores = jax.nn.sigmoid(jnp.matmul(u, router.astype(F32), precision=HI))
    _, idx = jax.lax.top_k(scores + bias.astype(F32), k)
    weigh = scores + bias.astype(F32) if control == "biased_gates" else scores
    top = jnp.take_along_axis(weigh, idx, axis=-1)
    gates = scale * top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(scores).at[jnp.arange(u.shape[0])[:, None], idx].set(gates)


@functools.partial(jax.jit, static_argnames=("cap", "quant", "control"))
def held_experts(u, gates, e_up, e_down, cap, quant, control=""):
    """The sum over the experts held of each one's output for the tokens that
    chose it (at most ``cap`` an expert), weighted: one jitted loop, so that an
    expert's weights are never sliced by a Python integer (a compile an expert)."""
    up = jnp.concatenate([u, jnp.zeros((1, u.shape[1]), u.dtype)])

    def one(y, e):
        col = gates[:, e]
        rows = jnp.nonzero(col != 0, size=cap, fill_value=u.shape[0])[0]
        gp = jnp.concatenate([col, jnp.zeros((1,), col.dtype)])[rows]
        out = ungated(up[rows], e_up[e].astype(F32), e_down[e].astype(F32), quant, control)
        return y.at[rows].add(out * gp[:, None], mode="drop"), None

    return jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(e_up.shape[0]))[0]


def experts(u, w, arch: Dict[str, Any], quant: Quant, held=None, control: str = "", shared: bool = True):
    """The expert layer's part that the experts ``held`` give (expert indices
    whose weights are ``w["e_*"]`` in that order; None = the first
    ``e_up.shape[0]``, the chip's share), plus the shared expert, unscaled and
    computed whole (``shared=False`` leaves it out: a further share of a layer
    whose shared expert another share has counted)."""
    gates = route(u, w["router"], w["router_bias"], arch["num_experts_per_tok"],
                  float(arch["routed_scaling_factor"]), control)
    if control == "int8_experts":
        quant = int8_fake_quant
    if held is not None:  # another share: its experts' columns where the first ones' stood
        gates = gates[:, jnp.asarray(list(held))]
    gates = gates[:, : w["e_up"].shape[0]]
    most = int(jnp.max(jnp.sum(gates != 0, axis=0)))
    cap = max(8, 1 << (most - 1).bit_length()) if most else 8
    out = held_experts(u, gates, w["e_up"], w["e_down"], cap, quant, control)
    if shared:
        out = out + ungated(u, w["s_up"].astype(F32), w["s_down"].astype(F32), quant, control)
    return out


# -- the stack -----------------------------------------------------------------------

MIXER = ("ln_m_scale", "w_in", "conv", "conv_bias", "A_log", "dt_bias", "D", "norm_scale", "w_out")
ATTN = ("ln_scale", "wq", "wk", "wv", "wo")


def forward(tokens: jax.Array, layer_weights: Callable[[int], Dict[str, jax.Array]],
            global_weights: Dict[str, jax.Array], arch: Dict[str, Any],
            quant: Quant = None, control: str = "", states: bool = False, expert_rows: int = 0) -> Any:
    """Logits (T, V) of one sequence. ``layer_weights(u)`` makes the u-th unit, a
    Mamba-2 layer and the expert layer that follows it; each attention layer is
    ``global_weights["L<i>_*"]``. With ``states``, also every Mamba-2 layer's
    state after the last token, in layer order ((H, P, N) float32 each): what a
    row's state slot holds then. With ``expert_rows``, also every expert layer's
    normed input at the last ``expert_rows`` positions, in layer order
    ((expert_rows, d) float32 each): what ``experts`` was handed there."""
    if control not in CONTROLS:
        raise ValueError(f"control is one of {CONTROLS}")
    gw, eps = global_weights, arch["layer_norm_epsilon"]
    x = gw["embed"][tokens].astype(F32)

    @jax.jit
    def mamba_layer(x, f):
        out, state = mamba(rmsnorm(x, f["ln_m_scale"], eps), f, arch, quant, control)
        return x + out, state

    attn_layer = jax.jit(lambda x, f: x + attention(rmsnorm(x, f["ln_scale"], eps), f, arch, quant, control))
    normed = jax.jit(lambda x, scale: rmsnorm(x, scale.astype(F32), eps))
    unit, w, kept, handed = -1, None, [], []
    for i, letter in enumerate(arch["hybrid_override_pattern"][: arch["num_hidden_layers"]]):
        if letter == "M":
            unit += 1
            w = layer_weights(unit)
            x, state = mamba_layer(x, {k: w[k].astype(F32) for k in MIXER})
            kept.append(state)
        elif letter == "*":
            x = attn_layer(x, {k: gw[f"L{i}_{k}"].astype(F32) for k in ATTN})
        elif letter == "E":  # the experts of the unit whose Mamba-2 layer came last
            u = normed(x, w["ln_e_scale"])
            if expert_rows:
                handed.append(u[-expert_rows:])
            x = x + experts(u, w, arch, quant, control=control)
        else:
            raise ValueError(f"layer {i} of the pattern is {letter!r}: M, * or E")
    h = normed(x, gw["final_scale"])
    head = jax.jit(lambda h, cols: mm(h, cols.astype(F32), quant))
    logits = jnp.concatenate([
        head(h, gw["head"][:, start : start + V_BLOCK]) for start in range(0, gw["head"].shape[1], V_BLOCK)
    ], axis=1)
    out = (logits,) + ((kept,) if states else ()) + ((handed,) if expert_rows else ())
    return out if len(out) > 1 else logits
