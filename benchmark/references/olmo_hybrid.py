"""Olmo-Hybrid-7B forward pass as its config keys and the published descriptions
they map onto define it (``olmo_hybrid``: Gated DeltaNet, arXiv:2412.06464, with
``allow_neg_eigval``, arXiv:2411.12537, for the ``linear_attention`` layers; the
OLMo 2 block, arXiv:2501.00656, around both kinds), in plain float32 jax.numpy
at the highest matmul precision. ``d`` the hidden size (3,840); RMSNorm in
float32 with a learned weight, eps ``rms_norm_eps``; no bias anywhere; no
positional encoding of any kind::

    a block, either kind (outputs normed, inputs not):
        h = x + N_1(Mix(x));   y = h + N_2(SwiGLU(h))
        SwiGLU(h) = W_down (SiLU(W_gate h) * W_up h)             width intermediate_size
    after the last block:  logits = W_head N_f(y)                (untied)

    Mix of a linear_attention layer (H = linear_num_value_heads heads,
    d_k = linear_key_head_dim, d_v = linear_value_head_dim), per token t, head h:
        q', k', v' = W_q x, W_k x, W_v x                          H d_k | H d_k | H d_v channels
        q, k, v = SiLU(conv(q')), SiLU(conv(k')), SiLU(conv(v'))  causal depthwise, linear_conv_kernel_dim taps, no bias
        q = q / |q| / sqrt(d_k),   k = k / |k|                    per head, L2
        g = -exp(A_log_h) softplus(W_a x + dt_bias_h)             one scalar a head, <= 0
        beta = 2 sigmoid(W_b x)                                   linear_allow_neg_eigval: in (0, 2)
        S_t = exp(g) S_{t-1};  u = beta (v - S_t^T k);  S_t = S_t + k u^T      S: d_k x d_v
        o = S_t^T q
        Mix(x) = W_o [RMSNorm_head(o) * SiLU(W_g x)]              norm over a head's d_v, one weight of d_v

    Mix of a full_attention layer:
        q, k, v = W_q x, W_k x, W_v x;   q = N_q(q), k = N_k(k)   RMSNorm over ALL heads' channels, before the heads are cut
        causal softmax attention at 1/sqrt(head_dim), no rotation;   Mix(x) = W_o o

What the source's ``config.json`` does not settle (``assumed`` in the
configuration file, each with its reason):

(a) no rotary embedding: ``rope_parameters.rope_theta`` is null, there is no
    base to rotate by, and the recurrent layers carry order;
(b) output-side norms and whole-width q/k norms on both kinds of layer: the
    family's published block (OLMo 2 and 3); the source has no key for either;
(c) the gate's form, and the SiLU output gate with the head norm before it: the
    public Gated DeltaNet code the ``linear_*`` keys name;
(d) q, k and v each through a 4-tap convolution with SiLU: depthwise, so one
    convolution over all 11,520 channels is the same function;
(e) the seeded values (``harness/families/olmo_hybrid.py``).
The L2 norm is x / sqrt(sum x^2 + 1e-6), as the public code writes it.

No cache, kernel or batching: one sequence; a Python loop over the layers; the
recurrence token by token under ``lax.scan``, never a chunked form; attention in
blocks of queries. Independent of ``models/``: it reads only the canonical
weights of ``harness/families/olmo_hybrid.py``, one layer at a time.

Departures from the published model, all of the harness and none of the
equations: weights are seeded; the depth is what the configuration file states;
each ``full_attention`` layer arrives among the globals (``L<i>_*``) because the
harness hands a family no layer index and stacks the layers of one kind; the
logits are computed in blocks of vocabulary columns.

``control`` names one deliberate departure from the equations, for the controls
of `correct` (``benchmark/control.py``, the tests): ``"pre_norm"`` norms each
sublayer's input and not its output, ``"dropped_norm"`` leaves the mixer's
output un-normed (x + Mix(x)), ``"head_qk_norm"`` norms q and k a head at
a time (the weight's first ``head_dim`` entries), ``"beta_one"`` leaves beta in
(0, 1), ``"channel_decay"`` decays each key channel at its own rate (the head's
g times 0.5 .. 1.5 across the channels), ``"bf16_state"`` rounds the state to
bfloat16 after every token, ``"rope"`` rotates the attention layers' queries and
keys at theta 10,000, ``"bf16_softmax"`` rounds the attention probabilities to
bfloat16 before they meet the values.
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
CONTROLS = ("", "pre_norm", "dropped_norm", "head_qk_norm", "beta_one", "channel_decay", "bf16_state", "rope", "bf16_softmax")


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def bf16(x):
    """Round to bfloat16 and stay float32 (not an ``astype`` pair: XLA removes
    that on the TPU, where excess precision is allowed)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


# -- the Gated DeltaNet layer --------------------------------------------------------


def short_conv(x, w):
    """Causal depthwise convolution over time: x (T, C), w (C, taps), the last
    tap on the current token, zeros before the first; then SiLU."""
    taps = w.shape[1]
    xp = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(xp[i : i + x.shape[0]] * w[:, i] for i in range(taps)))


@functools.partial(jax.jit, static_argnames=("control",))
def delta_rule(q, k, v, g, beta, control=""):
    """S_t = exp(g_t) S_{t-1}; u = beta_t (v_t - S_t^T k_t); S_t += k_t u^T;
    o_t = S_t^T q_t, one token at a time from S = 0. q, k (T, H, K); v (T, H, V);
    g, beta (T, H). Returns (o (T, H, V), S_T (H, K, V))."""
    dk = q.shape[-1]
    spread = jnp.linspace(0.5, 1.5, dk) if control == "channel_decay" else jnp.ones((dk,), F32)

    def step(s, x):
        q, k, v, g, beta = x
        s = s * jnp.exp(g[:, None] * spread[None, :])[:, :, None]
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", s, k, precision=HI))
        s = s + k[:, :, None] * u[:, None, :]
        if control == "bf16_state":
            s = bf16(s)
        return s, jnp.einsum("hkv,hk->hv", s, q, precision=HI)

    s0 = jnp.zeros((q.shape[1], dk, v.shape[-1]), F32)
    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, s


def gdn(x, w, arch: Dict[str, Any], quant: Quant, control: str = ""):
    """The Gated DeltaNet mixer on its input (T, d), and its state after the last token."""
    t = x.shape[0]
    nh, dk, dv = arch["linear_num_value_heads"], arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    q, k, v = (short_conv(mm(x, w["w" + n], quant), w["conv_" + n]).reshape(t, nh, width)
               for n, width in (("q", dk), ("k", dk), ("v", dv)))
    q, k = l2norm(q) * dk ** -0.5, l2norm(k)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(mm(x, w["wa"], quant) + w["dt_bias"])
    beta = jax.nn.sigmoid(mm(x, w["wb"], quant)) * (1.0 if control == "beta_one" else 2.0)
    o, state = delta_rule(q, k, v, g, beta, control)
    o = rmsnorm(o, w["o_norm_scale"], arch["rms_norm_eps"]) * jax.nn.silu(mm(x, w["wg"], quant).reshape(t, nh, dv))
    return mm(o.reshape(t, nh * dv), w["wo"], quant), state


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


def attention(x, w, arch: Dict[str, Any], quant: Quant, control: str = ""):
    """Multi-head attention on its input (T, d): q and k normed over their whole
    width, no rotation, no position of any kind."""
    t, d = x.shape
    nh, g, dh = w["wq"].shape[1], w["wk"].shape[1], w["wq"].shape[2]
    eps = arch["rms_norm_eps"]
    q = mm(x, w["wq"].reshape(d, nh * dh), quant)
    k = mm(x, w["wk"].reshape(d, g * dh), quant)
    v = mm(x, w["wv"].reshape(d, g * dh), quant)
    if control == "head_qk_norm":
        q = rmsnorm(q.reshape(t, nh, dh), w["q_norm_scale"][:dh], eps)
        k = rmsnorm(k.reshape(t, g, dh), w["k_norm_scale"][:dh], eps)
    else:
        q = rmsnorm(q, w["q_norm_scale"], eps).reshape(t, nh, dh)
        k = rmsnorm(k, w["k_norm_scale"], eps).reshape(t, g, dh)
    v = v.reshape(t, g, dh)
    if control == "rope":
        q, k = rope(q, 10000.0), rope(k, 10000.0)
    # query head i reads KV head i // (heads / kv_heads)
    kt = jnp.repeat(k, nh // g, axis=1).transpose(1, 2, 0)  # (H, dh, T)
    vt = jnp.repeat(v, nh // g, axis=1).transpose(1, 0, 2)  # (H, T, dh)
    outs = []
    for start in range(0, t, Q_BLOCK):
        qb = q[start : start + Q_BLOCK].transpose(1, 0, 2)  # (H, B, dh)
        s = mm(qb, kt, quant) * dh ** -0.5
        i = start + jnp.arange(qb.shape[1])[:, None]
        pr = jax.nn.softmax(jnp.where(jnp.arange(t)[None, :] <= i, s, -jnp.inf), axis=-1)
        if control == "bf16_softmax":
            pr = bf16(pr)
        outs.append(mm(pr, vt, quant).transpose(1, 0, 2))  # (B, H, dh)
    return mm(jnp.concatenate(outs, axis=0).reshape(t, nh * dh), w["wo"].reshape(nh * dh, d), quant)


# -- the stack -----------------------------------------------------------------------


def swiglu(u, gate, up, down, quant: Quant):
    return mm(jax.nn.silu(mm(u, gate, quant)) * mm(u, up, quant), down, quant)


def layer(x, w, arch: Dict[str, Any], quant: Quant, control: str = ""):
    """One decoder layer on (T, d): ``w`` has a Gated DeltaNet mixer (``wa``) or an
    attention one. Returns the layer's output and the mixer's state after the
    last token (None for attention)."""
    f = {k: v.astype(F32) for k, v in w.items()}
    eps, pre = arch["rms_norm_eps"], control == "pre_norm"
    # the norm's place: on the sublayer's output, or (the control) on its input
    n_in = lambda h, s: rmsnorm(h, s, eps) if pre else h
    n_out = lambda y, s: y if pre else rmsnorm(y, s, eps)
    u = n_in(x, f["ln1_scale"])
    y, state = gdn(u, f, arch, quant, control) if "wa" in f else (attention(u, f, arch, quant, control), None)
    x = x + (y if control == "dropped_norm" else n_out(y, f["ln1_scale"]))
    y = swiglu(n_in(x, f["ln2_scale"]), f["w_gate"], f["w_up"], f["w_down"], quant)
    return x + n_out(y, f["ln2_scale"]), state


def forward(tokens: jax.Array, layer_weights: Callable[[int], Dict[str, jax.Array]],
            global_weights: Dict[str, jax.Array], arch: Dict[str, Any],
            quant: Quant = None, control: str = "", states: bool = False) -> Any:
    """Logits (T, V) of one sequence. ``layer_weights(l)`` makes the l-th Gated
    DeltaNet layer; each attention layer is ``global_weights["L<i>_*"]``. With
    ``states``, also every Gated DeltaNet layer's state after the last token, in
    layer order ((H, d_k, d_v) float32 each): what a row's state slot holds then."""
    if control not in CONTROLS:
        raise ValueError(f"control is one of {CONTROLS}")
    gw = global_weights
    run = jax.jit(lambda x, w: layer(x, w, arch, quant, control))
    x = gw["embed"][tokens].astype(F32)
    mine, kept = 0, []
    for i in range(arch["num_hidden_layers"]):
        own = {k[len(f"L{i}_"):]: v for k, v in gw.items() if k.startswith(f"L{i}_")}
        if not own:
            own, mine = layer_weights(mine), mine + 1
        x, state = run(x, own)
        if state is not None:
            kept.append(state)
    h = rmsnorm(x, gw["final_scale"].astype(F32), arch["rms_norm_eps"])
    head = jax.jit(lambda h, cols: mm(h, cols.astype(F32), quant))
    logits = jnp.concatenate([
        head(h, gw["head"][:, start : start + V_BLOCK]) for start in range(0, gw["head"].shape[1], V_BLOCK)
    ], axis=1)
    return (logits, kept) if states else logits
