"""Trinity's forward pass (``model_type`` ``afmoe``) as its config keys and the
family's published modelling code define it, in plain float32 jax.numpy at the
highest matmul precision.

Layer l has kind ``sliding`` or ``full`` (``layer_types``); d = ``hidden_size``;
RMSNorm N(.) in float32 with a learned weight, eps ``rms_norm_eps``:

- ``x_0 = E[token] * sqrt(d)`` (``mup_enabled``).
- ``a = N_in(x)``; ``q = a W_q`` (heads x head_dim), ``k = a W_k``, ``v = a W_v``
  (kv heads x head_dim each), ``g = a W_g`` (heads x head_dim);
  ``q <- N_q(q)``, ``k <- N_k(k)``, a head at a time over its head_dim.
- ``sliding``: RoPE on q and k (theta ``rope_theta``, the whole head, split
  halves), keys ``j <= i`` with ``i - j < sliding_window``. ``full``: **no
  position encoding**, every key ``j <= i``.
- ``o = softmax(q k^T / sqrt(head_dim)) v`` (query head h reads KV head
  ``h // (heads / kv heads)``); ``o <- o * sigmoid(g)``;
  ``x <- x + N_post_attn(o W_o)``.
- ``m = N_pre_mlp(x)``. Layers below ``num_dense_layers``:
  ``f = (silu(m W_gate) * m W_up) W_down`` of width ``intermediate_size``.
  Others: ``s = sigmoid(m W_r)`` in float32 over the experts; the
  ``num_experts_per_tok`` largest of ``s + b`` (b enters the selection only);
  ``w = s_sel / (sum s_sel + 1e-20) * route_scale``;
  ``f = Shared(m) + sum_e w_e Expert_e(m)``, each a SwiGLU of width
  ``moe_intermediate_size``.
- ``x <- x + N_post_mlp(f)``. Logits ``= N_f(x) W_head``, untied.

No cache, kernel or batching: one sequence; a Python loop over the layers and,
inside an expert layer, a loop over the experts (every token through each,
weighted by its gate, zero where the token did not choose it); attention in
blocks of queries, so that nine thousand positions fit. A layer is one jitted
program a sequence length and kind (dense sliding, expert sliding, expert
full), so a caller that pads its sequences to one length compiles each once.
Independent of ``models/``: it reads only the canonical weights of
``harness/families/trinity.py``.

Entry points: ``forward`` (tokens -> logits, what ``serving_check`` calls) and
its parts, for a caller that wants some rows only: ``hidden`` (the stack's
output after its final norm) and ``head``.

Controls, by ``arch["control"]`` (a checker's copy of the configuration, never
the file): ``"all_full"`` lets the sliding layers see every earlier key (what
one block list a row for all layers, with no window mask, would compute);
``"rope_on_full"`` rotates the full layers' queries and keys too.

Departures from the published model, all of the harness: seeded weights, the
depth the configuration file states, the leading dense layers among the
globals (``dense<i>_*``), a zero load-balance term (the forward pass has none);
logits in blocks of vocabulary columns, on the host's CPU device where there
is one.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from references.common import Quant, mm

F32 = jnp.float32
Q_BLOCK = 256  # queries scored at a time
V_BLOCK = 8192  # vocabulary columns of logits made at a time
HIGHEST = jax.lax.Precision.HIGHEST


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (T, heads, head_dim); rotate-half convention over the whole head."""
    t, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(a, w, arch: Dict[str, Any], quant: Quant, sliding: bool):
    """Gated QK-normed grouped-query attention on normed input (T, d)."""
    t = a.shape[0]
    h, g, dh = arch["num_attention_heads"], arch["num_key_value_heads"], arch["head_dim"]
    eps, control = arch["rms_norm_eps"], arch.get("control")
    q = rmsnorm(mm(a, w["wq"], quant).reshape(t, h, dh), w["q_norm_scale"], eps)
    k = rmsnorm(mm(a, w["wk"], quant).reshape(t, g, dh), w["k_norm_scale"], eps)
    v = mm(a, w["wv"], quant).reshape(t, g, dh)
    gate = mm(a, w["wg"], quant)
    if sliding or control == "rope_on_full":
        q, k = rope(q, float(arch["rope_theta"])), rope(k, float(arch["rope_theta"]))
    window = arch["sliding_window"] if sliding and control != "all_full" else t
    kt = jnp.repeat(k, h // g, axis=1).transpose(1, 2, 0)  # (H, Dh, T): query head i reads KV head i // (h/g)
    vt = jnp.repeat(v, h // g, axis=1).transpose(1, 0, 2)  # (H, T, Dh)
    n_blocks = -(-t // Q_BLOCK)
    qp = jnp.pad(q, ((0, n_blocks * Q_BLOCK - t), (0, 0), (0, 0))).reshape(n_blocks, Q_BLOCK, h, dh)

    def block(args):
        start, qb = args
        s = mm(qb.transpose(1, 0, 2), kt, quant) / jnp.sqrt(F32(dh))  # (H, Q, T)
        i = start + jnp.arange(Q_BLOCK)[:, None]
        j = jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where((j <= i) & (i - j < window), s, -jnp.inf), axis=-1)
        return mm(p, vt, quant).transpose(1, 0, 2).reshape(Q_BLOCK, h * dh)

    o = jax.lax.map(block, (jnp.arange(n_blocks) * Q_BLOCK, qp)).reshape(n_blocks * Q_BLOCK, h * dh)[:t]
    return mm(o * jax.nn.sigmoid(gate), w["wo"], quant)


def swiglu(m, gate, up, down, quant: Quant):
    return mm(jax.nn.silu(mm(m, gate, quant)) * mm(m, up, quant), down, quant)


def route(m, router, b_corr, k: int, norm: bool, scale: float):
    """(T, E) gate of every expert for every token, zero where not selected:
    sigmoid scores, the k largest of score + bias, gates from the unbiased
    scores, renormalised, times the route scale. Float32, never quantised."""
    s = jax.nn.sigmoid(jnp.matmul(m, router, precision=HIGHEST))
    _, idx = jax.lax.top_k(s + b_corr, k)
    chosen = jnp.zeros_like(s).at[jnp.arange(m.shape[0])[:, None], idx].set(1.0)
    g = s * chosen
    if norm:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return g * scale


def experts(m, w, arch: Dict[str, Any], quant: Quant):
    """The shared expert plus ``sum_e gate_e(t) * Expert_e(m_t)``: every token
    goes through every expert and the gate decides, the plain reading of the
    equation, with no shape that depends on the routing; the experts one after
    another inside one loop of the layer's program."""
    gates = route(m, w["router"].astype(F32), w["b_corr"].astype(F32), arch["num_experts_per_tok"],
                  bool(arch["route_norm"]), float(arch["route_scale"]))
    pick = lambda a, e: jax.lax.dynamic_index_in_dim(a, e, axis=0, keepdims=False).astype(F32)

    def add(e, y):
        gate = jax.lax.dynamic_index_in_dim(gates, e, axis=1, keepdims=True)
        return y + gate * swiglu(m, pick(w["e_gate"], e), pick(w["e_up"], e), pick(w["e_down"], e), quant)

    y = swiglu(m, w["s_gate"].astype(F32), w["s_up"].astype(F32), w["s_down"].astype(F32), quant)
    return jax.lax.fori_loop(0, w["e_gate"].shape[0], add, y)


SMALL = ("ln1_scale", "ln1_post_scale", "ln2_scale", "ln2_post_scale", "wq", "wk", "wv", "wg",
         "q_norm_scale", "k_norm_scale", "wo")
LAYER_KEYS = SMALL + ("router", "b_corr", "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down",
                      "w_gate", "w_up", "w_down")


def layer(x, w, arch: Dict[str, Any], quant: Quant, sliding: bool):
    """One decoder layer on (T, d); ``w`` has a dense FFN (``w_gate``) or an
    expert layer's (``router``)."""
    eps = arch["rms_norm_eps"]
    f = {k: w[k].astype(F32) for k in SMALL}
    o = attention(rmsnorm(x, f["ln1_scale"], eps), f, arch, quant, sliding)
    x = x + rmsnorm(o, f["ln1_post_scale"], eps)
    m = rmsnorm(x, f["ln2_scale"], eps)
    if "w_gate" in w:
        y = swiglu(m, w["w_gate"].astype(F32), w["w_up"].astype(F32), w["w_down"].astype(F32), quant)
    else:
        y = experts(m, w, arch, quant)
    return x + rmsnorm(y, f["ln2_post_scale"], eps)


_JITTED: Dict[Any, Any] = {}


def _layer(x, w, arch, quant, sliding):
    """``layer`` jitted once a configuration, precision and kind; a sequence
    length compiles each once (the check pads every sequence to one length)."""
    key = (json.dumps(arch, sort_keys=True, default=str), quant, sliding)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(functools.partial(layer, arch=arch, quant=quant, sliding=sliding))
    return _JITTED[key](x, {k: w[k] for k in LAYER_KEYS if k in w})


def hidden(tokens: jax.Array, layer_weights: Callable[[int], Dict[str, jax.Array]],
           global_weights: Dict[str, jax.Array], arch: Dict[str, Any], quant: Quant = None) -> jax.Array:
    """The stack's output (T, d) after its final norm: what the head reads."""
    gw, dense = global_weights, arch["num_dense_layers"]
    x = gw["embed"][tokens].astype(F32)
    if arch["mup_enabled"]:
        x = x * jnp.sqrt(F32(arch["hidden_size"]))
    for l, kind in enumerate(arch["layer_types"]):
        if l < dense:
            w = {k[len(f"dense{l}_"):]: v for k, v in gw.items() if k.startswith(f"dense{l}_")}
        else:
            w = layer_weights(l - dense)
        x = _layer(x, w, arch, quant, kind == "sliding_attention")
    return rmsnorm(x, gw["final_scale"].astype(F32), arch["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("quant",))
def _head_block(h, cols, quant):
    return mm(h, cols.astype(F32), quant)


def head(h: jax.Array, global_weights: Dict[str, jax.Array], quant: Quant = None,
         on_host: bool = True) -> jax.Array:
    """Logits (rows, V) of normed hidden states. ``on_host``: in blocks of
    vocabulary columns gathered on the host's CPU device where there is one (a
    whole sequence's logits beside the weights); else one matmul where the
    hidden states are (the few rows a check compares)."""
    gw = global_weights
    if not on_host:
        return _head_block(h, gw["head"], quant)
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:
        host = None
    blocks = []
    for start in range(0, gw["head"].shape[1], V_BLOCK):
        block = _head_block(h, gw["head"][:, start : start + V_BLOCK], quant)
        blocks.append(block if host is None else jax.device_put(block, host))
    return jnp.concatenate(blocks, axis=1)


def forward(tokens: jax.Array, layer_weights: Callable[[int], Dict[str, jax.Array]],
            global_weights: Dict[str, jax.Array], arch: Dict[str, Any],
            quant: Quant = None) -> jax.Array:
    """Logits (T, V) of one sequence. ``layer_weights(l)`` makes expert layer ``l``."""
    return head(hidden(tokens, layer_weights, global_weights, arch, quant), global_weights, quant)
