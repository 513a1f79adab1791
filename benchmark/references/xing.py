"""Xing4.0-29B-A4B forward pass as its config keys and the published
descriptions they map onto define it (DeepSeek-V2/V3 for the latent attention,
YaRN and the ``noaux_tc`` router; arXiv:2512.24880 for the ``hc_*``/``mhc_*``
residual streams), in plain float32 jax.numpy at the highest matmul precision.

No cache, kernel or batching: one sequence; a Python loop over the layers and,
inside an expert layer, over the experts (each expert sees exactly the tokens
that chose it); attention in the expanded form, every head's keys and values
made from the latent, computed in blocks of queries so that 8,256 positions
fit. Independent of ``models/``: it reads only the canonical weights of
``harness/families/xing.py``, one layer at a time.

Departures from the published model, all of the harness and none of the
equations:

- weights are seeded; the depth is what the configuration file states; the
  leading dense layer's weights arrive among the globals (``dense0_*``) because
  the harness hands a family no layer index; the multi-token-prediction module
  is left out (it changes no logit);
- RoPE rotates DeepSeek's interleaved pairs ``(2i, 2i+1)``;
- the logits are computed in blocks of vocabulary columns and returned on the
  host's CPU device where there is one: 8,256 x 131,072 float32 logits, and
  the rolled copy ``serving_check.token_regrets`` makes of them, do not fit a
  chip beside the program's weights. What is compared is unchanged.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from references.common import Quant, mm

F32 = jnp.float32
Q_BLOCK = 512  # queries scored at a time
V_BLOCK = 8192  # vocabulary columns of logits made at a time


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


# -- YaRN ----------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(arch: Dict[str, Any]) -> np.ndarray:
    """DeepSeek's ``yarn`` frequencies for the rotated slice, closed form."""
    rs, dim, base = arch["rope_scaling"], arch["qk_rope_head_dim"], float(arch["rope_theta"])
    orig = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float64)
    extrapolated = 1.0 / base ** (2 * i / dim)
    interpolated = extrapolated / rs["factor"]
    keep = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)  # 1: a fast pair, left as it is
    return (interpolated * (1.0 - keep) + extrapolated * keep).astype(np.float32)


def softmax_scale(arch: Dict[str, Any]) -> float:
    rs = arch["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, arch):
    """x: (T, heads, rope_dim); interleaved pairs (2i, 2i+1) turn by position * freq_i."""
    rs = arch["rope_scaling"]
    t = x.shape[0]
    ang = jnp.arange(t, dtype=F32)[:, None] * jnp.asarray(yarn_inv_freq(arch))[None, :]
    scale = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    cos, sin = jnp.cos(ang)[:, None, :] * scale, jnp.sin(ang)[:, None, :] * scale
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


# -- the two sublayers ---------------------------------------------------------------


def attention(h, w, arch: Dict[str, Any], quant: Quant):
    """Latent attention on normed input (T, d), expanded form."""
    t = h.shape[0]
    nh, nope, rdim, dv = (arch["num_attention_heads"], arch["qk_nope_head_dim"],
                          arch["qk_rope_head_dim"], arch["v_head_dim"])
    c, eps = arch["kv_lora_rank"], arch["rms_norm_eps"]
    cq = rmsnorm(mm(h, w["wq_a"], quant), w["q_norm_scale"], eps)
    q = mm(cq, w["wq_b"], quant).reshape(t, nh, nope + rdim)
    kv_a = mm(h, w["wkv_a"], quant)
    c_kv = rmsnorm(kv_a[:, :c], w["kv_norm_scale"], eps)
    k_rope = rope(kv_a[:, None, c:], arch)  # one head, shared by all
    q_rope = rope(q[..., nope:], arch)
    kv = mm(c_kv, w["wkv_b"], quant).reshape(t, nh, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (t, nh, rdim))], axis=-1)
    qf = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    v, scale = kv[..., nope:], softmax_scale(arch)
    kt, vt = k.transpose(1, 2, 0), v.transpose(1, 0, 2)  # (H, D, T), (H, T, dv)
    outs = []
    for start in range(0, t, Q_BLOCK):
        qb = qf[start : start + Q_BLOCK].transpose(1, 0, 2)  # (H, B, D)
        s = mm(qb, kt, quant) * scale
        i = start + jnp.arange(qb.shape[1])[:, None]
        p = jax.nn.softmax(jnp.where(jnp.arange(t)[None, :] <= i, s, -jnp.inf), axis=-1)
        outs.append(mm(p, vt, quant).transpose(1, 0, 2).reshape(-1, nh * dv))
    return mm(jnp.concatenate(outs, axis=0), w["wo"], quant)


def swiglu(h, gate, up, down, quant: Quant):
    return mm(jax.nn.silu(mm(h, gate, quant)) * mm(h, up, quant), down, quant)


@functools.partial(jax.jit, static_argnames=("k", "norm", "scale"))
def route(h, router, b_corr, k, norm, scale):
    """(T, E) gate of every expert for every token, zero where not selected:
    sigmoid scores, top-k of score + bias, gates from the unbiased scores,
    renormalised, times the routed scaling factor. Float32, never quantised."""
    s = jax.nn.sigmoid(jnp.matmul(h, router.astype(F32), precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + b_corr.astype(F32), k)
    chosen = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], idx].set(1.0)
    g = s * chosen
    if norm:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return g * scale


@functools.partial(jax.jit, static_argnames=("cap", "quant"))
def one_expert(h, gate_col, w_gate, w_up, w_down, cap, quant):
    """The tokens that chose this expert (at most ``cap``), through it, weighted."""
    rows = jnp.nonzero(gate_col > 0, size=cap, fill_value=h.shape[0])[0]
    hp = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)])[rows]
    gp = jnp.concatenate([gate_col, jnp.zeros((1,), gate_col.dtype)])[rows]
    y = swiglu(hp, w_gate.astype(F32), w_up.astype(F32), w_down.astype(F32), quant) * gp[:, None]
    return jnp.zeros_like(h).at[rows].add(y, mode="drop")


def experts(h, w, arch: Dict[str, Any], quant: Quant):
    gates = route(h, w["router"], w["b_corr"], arch["num_experts_per_tok"], bool(arch["norm_topk_prob"]),
                  float(arch["routed_scaling_factor"]))
    most = int(jnp.max(jnp.sum(gates > 0, axis=0)))
    cap = max(8, 1 << (most - 1).bit_length()) if most else 8
    y = swiglu(h, w["s_gate"].astype(F32), w["s_up"].astype(F32), w["s_down"].astype(F32), quant)
    for e in range(w["e_gate"].shape[0]):  # only the experts this chip holds
        y = y + one_expert(h, gates[:, e], w["e_gate"][e], w["e_up"][e], w["e_down"][e], cap, quant)
    return y


# -- the residual streams ------------------------------------------------------------


def hc_coefficients(x, phi, b, alpha, arch: Dict[str, Any]):
    """x: (T, n, d) -> H_pre (T, n), H_post (T, n), H_res (T, n, n)."""
    n, eps = arch["hc_mult"], arch["hc_eps"]
    flat = x.reshape(x.shape[0], -1)
    xh = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    z = jnp.matmul(xh, phi, precision=jax.lax.Precision.HIGHEST)
    pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[:, n : 2 * n] + b[n : 2 * n])
    res = (alpha[2] * z[:, 2 * n :] + b[2 * n :]).reshape(-1, n, n)
    m = jnp.exp(jnp.clip(res, arch["mhc_h_res_clamp_min"], arch["mhc_h_res_clamp_max"]))
    for _ in range(arch["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)  # rows
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)  # then columns
    return pre, post, m


def wrapped(x, w, prefix, norm_scale, sublayer, arch):
    """X' = H_res X + H_post^T F(RMSNorm(H_pre X))."""
    pre, post, res = hc_coefficients(x, w[prefix + "_phi"], w[prefix + "_b"], w[prefix + "_alpha"], arch)
    u = jnp.einsum("tn,tnd->td", pre, x, precision=jax.lax.Precision.HIGHEST)
    y = sublayer(rmsnorm(u, norm_scale, arch["rms_norm_eps"]))
    return jnp.einsum("tij,tjd->tid", res, x, precision=jax.lax.Precision.HIGHEST) + post[:, :, None] * y[:, None, :]


SMALL = ("ln1_scale", "ln2_scale", "wq_a", "q_norm_scale", "wq_b", "wkv_a", "kv_norm_scale", "wkv_b", "wo",
         "hca_phi", "hca_b", "hca_alpha", "hcm_phi", "hcm_b", "hcm_alpha")


def layer(x, w, arch: Dict[str, Any], quant: Quant, attention_part):
    """One decoder layer on (T, n, d) streams; ``w`` has a dense FFN
    (``w_gate``) or an expert layer's (``router``)."""
    f = {k: w[k].astype(F32) for k in SMALL}
    x = attention_part(x, f)
    if "w_gate" in w:
        ffn = lambda h: swiglu(h, w["w_gate"].astype(F32), w["w_up"].astype(F32), w["w_down"].astype(F32), quant)
    else:
        ffn = lambda h: experts(h, w, arch, quant)
    return wrapped(x, f, "hcm", f["ln2_scale"], ffn, arch)


def forward(tokens: jax.Array, layer_weights: Callable[[int], Dict[str, jax.Array]],
            global_weights: Dict[str, jax.Array], arch: Dict[str, Any],
            quant: Quant = None) -> jax.Array:
    """Logits (T, V) of one sequence. ``layer_weights(l)`` makes expert layer ``l``."""
    gw = global_weights
    x = gw["embed"][tokens].astype(F32)
    x = jnp.repeat(x[:, None, :], arch["hc_mult"], axis=1)  # the embedding into every stream
    dense0 = {k[len("dense0_"):]: v for k, v in gw.items() if k.startswith("dense0_")}
    attention_part = jax.jit(lambda x, f: wrapped(
        x, f, "hca", f["ln1_scale"], lambda h: attention(h, f, arch, quant), arch))
    for _ in range(arch["first_k_dense_replace"]):
        x = layer(x, dense0, arch, quant, attention_part)
    for l in range(arch["num_hidden_layers"] - arch["first_k_dense_replace"]):
        x = layer(x, layer_weights(l), arch, quant, attention_part)
    h = rmsnorm(jnp.sum(x, axis=1), gw["final_scale"].astype(F32), arch["rms_norm_eps"])
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:
        host = None
    head = jax.jit(lambda h, cols: mm(h, cols.astype(F32), quant))
    blocks = []
    for start in range(0, gw["head"].shape[1], V_BLOCK):
        block = head(h, gw["head"][:, start : start + V_BLOCK])
        blocks.append(block if host is None else jax.device_put(block, host))
    return jnp.concatenate(blocks, axis=1)
