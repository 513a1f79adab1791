"""Ling-3.0-flash forward pass as its config keys and the published
descriptions they map onto define it (arXiv:2510.26692, "Kimi Linear", and the
``safe_gate`` branch of its open implementation for the KDA layers;
DeepSeek-V2/V3 for the latent attention and the ``noaux_tc`` group-limited
router), in plain float32 jax.numpy at the highest matmul precision.

No cache, kernel or batching: one sequence; a Python loop over the layers and,
inside an expert layer, over the experts held (each expert sees exactly the
tokens that chose it); KDA as the token-by-token recurrence under ``lax.scan``,
never a chunked form; latent attention expanded, every head's keys and values
made from the latent, in blocks of queries so that 5,184 positions fit.
Independent of ``models/``: it reads only the canonical weights of
``harness/families/ling.py``, one layer at a time.

Departures from the published model, all of the harness and none of the
equations:

- weights are seeded; the depth is what the configuration file states; every
  layer that is not a KDA layer with experts arrives among the globals
  (``L<i>_*``) because the harness hands a family no layer index; the
  multi-token-prediction module is left out (it changes no logit);
- the experts are those the weights carry (``e_gate.shape[0]``), the router's
  first ones: this chip's share of an expert-parallel layer. The router scores
  all ``num_experts_routed`` and picks among all of them; a choice that lives
  elsewhere adds nothing here (``experts``);
- the vocabulary is the slice the tables carry;
- the logits are computed in blocks of vocabulary columns.
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


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


# -- KDA -----------------------------------------------------------------------------


def short_conv(x, w):
    """Causal depthwise convolution over time: x (T, C), w (C, taps), the last
    tap on the current token, zeros before the first; then SiLU (``linear_silu``)."""
    taps = w.shape[1]
    xp = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(xp[i : i + x.shape[0]] * w[:, i] for i in range(taps)))


@functools.partial(jax.jit, static_argnames=("heads",))
def delta_rule(q, k, v, g, beta, heads):
    """S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T, o_t = S_t^T q_t,
    one token at a time from S = 0. q, k, g: (T, H, K); v: (T, H, V); beta (T, H)."""
    def step(s, x):
        q, k, v, g, beta = x
        s = s * jnp.exp(g)[:, :, None]
        s = s - beta[:, None, None] * k[:, :, None] * jnp.einsum("hk,hkv->hv", k, s, precision=HI)[:, None, :]
        s = s + beta[:, None, None] * k[:, :, None] * v[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q, s, precision=HI)

    s0 = jnp.zeros((heads, q.shape[-1], v.shape[-1]), F32)
    return jax.lax.scan(step, s0, (q, k, v, g, beta))[1]


def kda(h, w, arch: Dict[str, Any], quant: Quant):
    """The KDA mixer on normed input (T, d)."""
    t = h.shape[0]
    nh, n, eps = arch["num_attention_heads"], arch["head_dim"], arch["rms_norm_eps"]
    q, k, v = (short_conv(mm(h, w["w" + x], quant), w["conv_" + x]).reshape(t, nh, n) for x in "qkv")
    q, k = l2norm(q) * n ** -0.5, l2norm(k)  # use_qk_norm: the L2 norm of q and k (assumed)
    # kda_safe_gate: the log-decay is lower_bound * sigmoid(.), so exp(g) in (e^-5, 1)
    rate = jnp.exp(w["A_log"])[None, :, None]
    f = (mm(h, w["wf"], quant) + w["dt_bias"]).reshape(t, nh, n)  # no_kda_lora: one full-rank W_f
    g = arch["kda_lower_bound"] * jax.nn.sigmoid(rate * f)
    beta = jax.nn.sigmoid(mm(h, w["wbeta"], quant))
    o = delta_rule(q, k, v, g, beta, nh)
    # group_norm_size 1: the norm is over each head's channels, its scale shared by the heads
    o = rmsnorm(o, w["o_norm_scale"], eps) * jax.nn.sigmoid(mm(h, w["wg"], quant).reshape(t, nh, n))
    return mm(o.reshape(t, nh * n), w["wo"], quant)


# -- latent attention ----------------------------------------------------------------


def rope(x, theta: float):
    """x: (T, heads, rope_dim); interleaved pairs (2i, 2i+1) turn by position *
    theta^(-2i/dim), no scaling (``rope_scaling`` null)."""
    t, dim = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    ang = jnp.arange(t, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def attention(h, w, arch: Dict[str, Any], quant: Quant):
    """Latent attention on normed input (T, d), expanded form, ``q_lora_rank``
    null, a head-wise sigmoid gate on the output."""
    t = h.shape[0]
    nh, nope, rdim, dv = (arch["num_attention_heads"], arch["qk_nope_head_dim"],
                          arch["qk_rope_head_dim"], arch["v_head_dim"])
    c, eps, theta = arch["kv_lora_rank"], arch["rms_norm_eps"], float(arch["rope_theta"])
    q = mm(h, w["wq"], quant).reshape(t, nh, nope + rdim)
    kv_a = mm(h, w["wkv_a"], quant)
    c_kv = rmsnorm(kv_a[:, :c], w["kv_norm_scale"], eps)  # use_qk_norm: the latent's norm, none after wkv_b (assumed)
    k_rope = rope(kv_a[:, None, c:], theta)  # one head, shared by all
    kv = mm(c_kv, w["wkv_b"], quant).reshape(t, nh, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (t, nh, rdim))], axis=-1)
    qf = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], axis=-1)
    v, scale = kv[..., nope:], (nope + rdim) ** -0.5
    kt, vt = k.transpose(1, 2, 0), v.transpose(1, 0, 2)  # (H, D, T), (H, T, dv)
    outs = []
    for start in range(0, t, Q_BLOCK):
        qb = qf[start : start + Q_BLOCK].transpose(1, 0, 2)  # (H, B, D)
        s = mm(qb, kt, quant) * scale
        i = start + jnp.arange(qb.shape[1])[:, None]
        p = jax.nn.softmax(jnp.where(jnp.arange(t)[None, :] <= i, s, -jnp.inf), axis=-1)
        outs.append(mm(p, vt, quant).transpose(1, 0, 2))  # (B, H, dv)
    o = jnp.concatenate(outs, axis=0) * jax.nn.sigmoid(mm(h, w["w_head_gate"], quant))[:, :, None]
    return mm(o.reshape(t, nh * dv), w["wo_attn"], quant)


# -- the FFNs ------------------------------------------------------------------------


def swiglu(h, gate, up, down, quant: Quant, limit: float = 0.0):
    """down(SiLU(min(gate h, L)) * clip(up h, -L, L)); L = 0 is no clamp."""
    a, b = mm(h, gate, quant), mm(h, up, quant)
    if limit:
        a, b = jnp.minimum(a, limit), jnp.clip(b, -limit, limit)
    return mm(jax.nn.silu(a) * b, down, quant)


@functools.partial(jax.jit, static_argnames=("k", "n_group", "topk_group", "norm", "scale"))
def route(h, router, b_corr, k, n_group, topk_group, norm, scale):
    """(T, E) gate of every expert for every token, zero where not selected:
    sigmoid scores; the bias enters the selection only; the experts are
    ``n_group`` groups of consecutive experts, a group scores the sum of its two
    best biased scores, the ``topk_group`` best groups stay, and the top-k is
    taken among their experts; gates from the unbiased scores, renormalised,
    times the routed scaling factor. Float32, never quantised."""
    s = jax.nn.sigmoid(jnp.matmul(h, router.astype(F32), precision=HI))
    biased = s + b_corr.astype(F32)
    grouped = biased.reshape(h.shape[0], n_group, -1)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, keep = jax.lax.top_k(group_score, topk_group)
    kept = jnp.zeros_like(group_score).at[jnp.arange(h.shape[0])[:, None], keep].set(1.0)
    masked = jnp.where(kept[:, :, None] > 0, grouped, -jnp.inf).reshape(biased.shape)
    _, idx = jax.lax.top_k(masked, k)
    chosen = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], idx].set(1.0)
    g = s * chosen
    if norm:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return g * scale


@functools.partial(jax.jit, static_argnames=("cap", "quant", "limit"))
def one_expert(h, gate_col, w_gate, w_up, w_down, cap, quant, limit):
    """The tokens that chose this expert (at most ``cap``), through it, weighted."""
    rows = jnp.nonzero(gate_col > 0, size=cap, fill_value=h.shape[0])[0]
    hp = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)])[rows]
    gp = jnp.concatenate([gate_col, jnp.zeros((1,), gate_col.dtype)])[rows]
    y = swiglu(hp, w_gate.astype(F32), w_up.astype(F32), w_down.astype(F32), quant, limit) * gp[:, None]
    return jnp.zeros_like(h).at[rows].add(y, mode="drop")


def experts(h, w, arch: Dict[str, Any], quant: Quant, layer_index: int, held=None):
    """The expert layer's part that the experts ``held`` give (a list of expert
    indices whose weights are ``w["e_*"]`` in that order; None = the first
    ``e_gate.shape[0]``, the chip's share), plus the shared expert, ungated."""
    gates = route(h, w["router"], w["b_corr"], arch["num_experts_per_tok"], arch["n_group"],
                  arch["topk_group"], bool(arch["norm_topk_prob"]), float(arch["routed_scaling_factor"]))
    held = list(range(w["e_gate"].shape[0])) if held is None else list(held)
    most = int(jnp.max(jnp.sum(gates > 0, axis=0)))
    cap = max(8, 1 << (most - 1).bit_length()) if most else 8
    lim = float(arch["expert_swiglu_limit_list"][layer_index])
    y = swiglu(h, w["s_gate"].astype(F32), w["s_up"].astype(F32), w["s_down"].astype(F32), quant,
               float(arch["share_expert_swiglu_limit_list"][layer_index]))
    for slot, e in enumerate(held):
        y = y + one_expert(h, gates[:, e], w["e_gate"][slot], w["e_up"][slot], w["e_down"][slot], cap, quant, lim)
    return y


# -- the stack -----------------------------------------------------------------------

SMALL = ("ln1_scale", "ln2_scale", "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "wf", "A_log", "dt_bias",
         "wbeta", "wg", "o_norm_scale", "wo", "wkv_a", "kv_norm_scale", "wkv_b", "w_head_gate", "wo_attn")


def layer(x, w, arch: Dict[str, Any], quant: Quant, layer_index: int, mixers):
    """One pre-norm decoder layer on (T, d): ``w`` has a KDA mixer (``wf``) or a
    latent-attention one, a dense FFN (``w_gate``) or an expert layer's (``router``)."""
    f = {k: w[k].astype(F32) for k in SMALL if k in w}
    x = mixers["kda" if "wf" in w else "mla"](x, f)
    h = rmsnorm(x, f["ln2_scale"], arch["rms_norm_eps"])
    if "w_gate" in w:
        return x + swiglu(h, w["w_gate"].astype(F32), w["w_up"].astype(F32), w["w_down"].astype(F32), quant)
    return x + experts(h, w, arch, quant, layer_index)


def forward(tokens: jax.Array, layer_weights: Callable[[int], Dict[str, jax.Array]],
            global_weights: Dict[str, jax.Array], arch: Dict[str, Any],
            quant: Quant = None) -> jax.Array:
    """Logits (T, V) of one sequence. ``layer_weights(l)`` makes the l-th KDA
    layer with experts; every other layer is ``global_weights["L<i>_*"]``."""
    gw = global_weights
    x = gw["embed"][tokens].astype(F32)
    eps = arch["rms_norm_eps"]
    mixers = {
        "kda": jax.jit(lambda x, f: x + kda(rmsnorm(x, f["ln1_scale"], eps), f, arch, quant)),
        "mla": jax.jit(lambda x, f: x + attention(rmsnorm(x, f["ln1_scale"], eps), f, arch, quant)),
    }
    mine = 0
    for i in range(arch["num_hidden_layers"]):
        own = {k[len(f"L{i}_"):]: v for k, v in gw.items() if k.startswith(f"L{i}_")}
        if not own:
            own, mine = layer_weights(mine), mine + 1
        x = layer(x, own, arch, quant, i, mixers)
    h = rmsnorm(x, gw["final_scale"].astype(F32), eps)
    head = jax.jit(lambda h, cols: mm(h, cols.astype(F32), quant))
    return jnp.concatenate([
        head(h, gw["head"][:, start : start + V_BLOCK]) for start in range(0, gw["head"].shape[1], V_BLOCK)
    ], axis=1)
