"""JoyAI-LLM-Flash's forward pass, stack and multi-token-prediction module, as
its config keys (DeepSeek-V3's) and arXiv:2412.19437 sections 2.1-2.2 define
them, in plain float32 jax.numpy at the highest matmul precision.

No cache, kernel or batching: one sequence; a Python loop over the layers and,
inside an expert layer, a loop over the experts this chip holds (every token
through each, weighted by its gate, zero where the token did not choose it; a
choice of an expert held elsewhere adds nothing, as in the program); attention
in the expanded form, every head's keys and values made from the latent, in
blocks of queries. A layer is one jitted program a sequence length (dense,
expert; the module's block is the expert layer's), so a caller that pads its
sequences to one length compiles each once. Independent of ``models/``: it reads only the canonical
weights of ``harness/families/joyai.py``.

Entry points: ``forward`` (tokens -> the stack's logits, what
``serving_check`` calls), and the parts it is made of, for a caller that wants
some rows only or the module: ``hidden`` (the stack's output after its final
norm), ``head``, and ``mtp_hidden`` (the module over the reference's own
hidden states: row p from ``h_p`` and token ``p + 1``, its logits predict
token ``p + 2``).

The module, as the configuration file's ``assumed`` lists it:
``h'_p = W_eh [RMSNorm_e(emb(x_{p+1})) ; RMSNorm_h(h_p)]`` with the embedding
half first and ``h_p`` the stack's output *after* its final norm; one whole
expert layer over ``h'`` at positions ``p``; the module's own final norm; the
stack's head. ``hidden_shift`` is the control's one wrong wire: the hidden
state of the position before (``h_{p-1}``) in ``h_p``'s place.

Departures from the published model, all of the harness: seeded weights, the
depth and the experts held that the configuration file states, the leading
dense layer and the module among the globals (``dense0_*``, ``mtp_*``); logits
in blocks of vocabulary columns, on the host's CPU device where there is one.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from references.common import Quant, mm

F32 = jnp.float32
Q_BLOCK = 512  # queries scored at a time
V_BLOCK = 8192  # vocabulary columns of logits made at a time
HIGHEST = jax.lax.Precision.HIGHEST


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, arch):
    """x: (T, heads, rope_dim); interleaved pairs (2i, 2i+1) turn by position * theta^(-2i/dim)."""
    t, dim = x.shape[0], x.shape[-1]
    inv = 1.0 / float(arch["rope_theta"]) ** (jnp.arange(0, dim, 2, dtype=F32) / dim)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


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
    kv = mm(c_kv, w["wkv_b"], quant).reshape(t, nh, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (t, nh, rdim))], axis=-1)
    qf = jnp.concatenate([q[..., :nope], rope(q[..., nope:], arch)], axis=-1)
    scale = (nope + rdim) ** -0.5
    kt, vt = k.transpose(1, 2, 0), kv[..., nope:].transpose(1, 0, 2)  # (H, D, T), (H, T, dv)
    # blocks of Q_BLOCK queries, one compiled body (rows past T are padding, dropped)
    n_blocks = -(-t // Q_BLOCK)
    qp = jnp.pad(qf, ((0, n_blocks * Q_BLOCK - t), (0, 0), (0, 0))).reshape(n_blocks, Q_BLOCK, nh, nope + rdim)

    def block(args):
        start, qb = args
        s = mm(qb.transpose(1, 0, 2), kt, quant) * scale  # (H, Q, T)
        i = start + jnp.arange(Q_BLOCK)[:, None]
        p = jax.nn.softmax(jnp.where(jnp.arange(t)[None, :] <= i, s, -jnp.inf), axis=-1)
        return mm(p, vt, quant).transpose(1, 0, 2).reshape(Q_BLOCK, nh * dv)

    out = jax.lax.map(block, (jnp.arange(n_blocks) * Q_BLOCK, qp)).reshape(n_blocks * Q_BLOCK, nh * dv)[:t]
    return mm(out, w["wo"], quant)


def swiglu(h, gate, up, down, quant: Quant):
    return mm(jax.nn.silu(mm(h, gate, quant)) * mm(h, up, quant), down, quant)


@functools.partial(jax.jit, static_argnames=("k", "norm", "scale"))
def route(h, router, b_corr, k, norm, scale):
    """(T, E) gate of every expert the router scores for every token, zero
    where not selected: sigmoid scores, top-k of score + bias (one routing
    group), gates from the unbiased scores, renormalised, times the routed
    scaling factor. Float32, never quantised."""
    s = jax.nn.sigmoid(jnp.matmul(h, router.astype(F32), precision=HIGHEST))
    _, idx = jax.lax.top_k(s + b_corr.astype(F32), k)
    chosen = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], idx].set(1.0)
    g = s * chosen
    if norm:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return g * scale


@functools.partial(jax.jit, static_argnames=("quant",))
def held_experts(h, gates, w_gate, w_up, w_down, first, quant):
    """The sum over the experts held of ``gate_e(t) * expert_e(h_t)``: expert
    ``e`` of the weights (held, ...) is expert ``first + e`` of the router, and
    a token that did not choose it has gate 0. Every token goes through every
    held expert and the gate decides: the plain reading of the equation, with
    no shape that depends on the routing. The experts one after another inside
    one loop of the layer's program (a Python loop that indexes the weights
    compiles a slice program an expert, about a second each cold; a per-expert
    gather of the tokens that chose it, at a capacity read from the routing,
    compiles a program a capacity)."""
    pick = lambda a, e: jax.lax.dynamic_index_in_dim(a, e, axis=0, keepdims=False).astype(F32)

    def add(e, y):
        gate = jax.lax.dynamic_index_in_dim(gates, first + e, axis=1, keepdims=True)
        return y + gate * swiglu(h, pick(w_gate, e), pick(w_up, e), pick(w_down, e), quant)

    return jax.lax.fori_loop(0, w_gate.shape[0], add, jnp.zeros_like(h))


def experts(h, w, arch: Dict[str, Any], quant: Quant, held: Optional[range] = None):
    """The shared expert plus the routed experts this chip holds: expert ``e``
    of the weights is expert ``held[e]`` of the router (the first ones by
    default; another range is another chip's share of the same layer)."""
    gates = route(h, w["router"], w["b_corr"], arch["num_experts_per_tok"], bool(arch["norm_topk_prob"]),
                  float(arch["routed_scaling_factor"]))
    first = jnp.int32(held.start if held is not None else 0)
    y = swiglu(h, w["s_gate"].astype(F32), w["s_up"].astype(F32), w["s_down"].astype(F32), quant)
    return y + held_experts(h, gates, w["e_gate"], w["e_up"], w["e_down"], first, quant)


SMALL = ("ln1_scale", "ln2_scale", "wq_a", "q_norm_scale", "wq_b", "wkv_a", "kv_norm_scale", "wkv_b", "wo")


def layer(x, w, arch: Dict[str, Any], quant: Quant):
    """One pre-norm decoder layer on (T, d); ``w`` has a dense FFN (``w_gate``)
    or an expert layer's (``router``)."""
    f = {k: w[k].astype(F32) for k in SMALL}
    x = x + attention(rmsnorm(x, f["ln1_scale"], arch["rms_norm_eps"]), f, arch, quant)
    h = rmsnorm(x, f["ln2_scale"], arch["rms_norm_eps"])
    if "w_gate" in w:
        return x + swiglu(h, w["w_gate"].astype(F32), w["w_up"].astype(F32), w["w_down"].astype(F32), quant)
    return x + experts(h, w, arch, quant)


LAYER_KEYS = SMALL + ("router", "b_corr", "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down",
                      "w_gate", "w_up", "w_down")
_JITTED: Dict[Any, Any] = {}


def _jitted(name: str, fn: Callable, arch: Dict[str, Any], quant: Quant):
    """``fn(*arrays, arch, quant)`` jitted once a configuration and precision:
    the dense layer, the expert layer (the stack's and the module's alike) and
    the module's input are three programs a sequence length, whatever calls
    them (a compile at 4 k tokens in float32 at the highest precision takes
    its time cold; the check pads every sequence to one length)."""
    key = (name, json.dumps(arch, sort_keys=True, default=str), quant)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(lambda *a: fn(*a, arch, quant))
    return _JITTED[key]


def _layer(x, w, arch, quant):
    return _jitted("layer", layer, arch, quant)(x, {k: w[k] for k in LAYER_KEYS if k in w})


def _under(gw: Dict[str, Any], prefix: str) -> Dict[str, Any]:
    return {k[len(prefix):]: v for k, v in gw.items() if k.startswith(prefix)}


def hidden(tokens: jax.Array, layer_weights: Callable[[int], Dict[str, jax.Array]],
           global_weights: Dict[str, jax.Array], arch: Dict[str, Any], quant: Quant = None) -> jax.Array:
    """The stack's output (T, d) after its final norm: what the head and the module read."""
    gw = global_weights
    x = gw["embed"][tokens].astype(F32)
    dense0 = _under(gw, "dense0_")
    for _ in range(arch["first_k_dense_replace"]):
        x = _layer(x, dense0, arch, quant)
    for l in range(arch["num_hidden_layers"] - arch["first_k_dense_replace"]):
        x = _layer(x, layer_weights(l), arch, quant)
    return rmsnorm(x, gw["final_scale"].astype(F32), arch["rms_norm_eps"])


def _mtp_input(emb, hp, enorm, hnorm, eh_proj, arch, quant):
    eps = arch["rms_norm_eps"]
    both = jnp.concatenate([rmsnorm(emb.astype(F32), enorm.astype(F32), eps), rmsnorm(hp, hnorm.astype(F32), eps)], axis=-1)
    return mm(both, eh_proj.astype(F32), quant)


def mtp_hidden(tokens: jax.Array, h: jax.Array, global_weights: Dict[str, jax.Array],
               arch: Dict[str, Any], quant: Quant = None, hidden_shift: int = 0) -> jax.Array:
    """The module's output (T - 1, d) after its own final norm, from the
    stack's hidden states ``h`` (T, d) of ``tokens`` (T,): row p reads ``h_p``
    and token p + 1. ``hidden_shift`` 1 is the wrong-wiring control: row p
    reads ``h_{p-1}`` (row 0 its own)."""
    gw, eps = global_weights, arch["rms_norm_eps"]
    w = _under(gw, "mtp_")
    # all T rows, the last with a token that does not exist (causal: it changes
    # no row before it, and is dropped): the stack's compiled shapes serve
    following = jnp.concatenate([tokens[1:], tokens[:1]])
    hp = h
    if hidden_shift:
        hp = jnp.concatenate([hp[:hidden_shift], hp[:-hidden_shift]], axis=0)
    x = _jitted("mtp_input", _mtp_input, arch, quant)(
        gw["embed"][following], hp, w["enorm_scale"], w["hnorm_scale"], w["eh_proj"])
    return rmsnorm(_layer(x, w, arch, quant), w["final_scale"].astype(F32), eps)[:-1]


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
