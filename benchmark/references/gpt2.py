"""GPT-2 (large, XL) as published, in plain float32 jax.numpy: forward pass,
mean next-token cross-entropy and its gradient.

Pre-LN blocks, learned positions, fused-in-the-paper QKV written as three
matmuls, causal mask written out, tanh-approximated GELU (``gelu_new``),
output head tied to the embedding. No kernels, no cache, no batching beyond
``vmap`` over sequences; each layer is rematerialised in the backward pass so
that a sequence's activations fit beside the weights. Departures from the
source: dropout is not applied (the configuration sets it to 0); the
embedding has the configuration's padded row count, and the loss is taken
over all of its rows, as the program's is.

Independent of ``models/transformer.py``: it reads only the canonical
weights of ``harness/weights.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from references.common import Quant, mm


def layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def block(x, w, n_head: int, eps: float, quant: Quant):
    """One block on (T, d) activations."""
    t, d = x.shape
    dh = d // n_head
    a = layernorm(x, w["ln1_scale"], w["ln1_bias"], eps)
    heads = lambda m: m.reshape(t, n_head, dh).transpose(1, 0, 2)
    q = heads(mm(a, w["wq"], quant) + w["bq"])
    k = heads(mm(a, w["wk"], quant) + w["bk"])
    v = heads(mm(a, w["wv"], quant) + w["bv"])
    s = mm(q, k.transpose(0, 2, 1), quant) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = mm(p, v, quant).transpose(1, 0, 2).reshape(t, d)
    x = x + mm(o, w["wo"], quant) + w["bo"]
    a = layernorm(x, w["ln2_scale"], w["ln2_bias"], eps)
    return x + mm(gelu_new(mm(a, w["w_up"], quant) + w["b_up"]), w["w_down"], quant) + w["b_down"]


def sequence_loss(w: Dict[str, Any], x: jax.Array, y: jax.Array, arch: Dict[str, Any],
                  quant: Quant) -> jax.Array:
    """Summed cross-entropy of one sequence; ``w`` = {"layers": stacked, "globals": ...}."""
    gw = w["globals"]
    h = gw["embed"][x] + gw["pos"][: x.shape[0]]
    step = jax.checkpoint(
        lambda h, lw: (block(h, lw, arch["n_head"], arch["layer_norm_epsilon"], quant), None)
    )
    h, _ = jax.lax.scan(step, h, w["layers"])
    h = layernorm(h, gw["final_scale"], gw["final_bias"], arch["layer_norm_epsilon"])
    logits = mm(h, gw["embed"].T, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, y[:, None], axis=-1))


def loss_and_grads(w: Dict[str, Any], xs: jax.Array, ys: jax.Array, arch: Dict[str, Any],
                   quant: Quant = None) -> Tuple[jax.Array, Dict[str, Any]]:
    """Mean loss over all tokens of ``xs`` (n_micro, m, T) and its gradient,
    accumulated microbatch by microbatch."""
    n_tokens = xs.size

    def micro(w, x, y):
        return jnp.sum(jax.vmap(lambda a, b: sequence_loss(w, a, b, arch, quant))(x, y))

    def body(carry, xy):
        loss, grads = jax.value_and_grad(micro)(w, *xy)
        return (carry[0] + loss, jax.tree.map(jnp.add, carry[1], grads)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, w))
    (loss, grads), _ = jax.lax.scan(body, zero, (xs, ys))
    return loss / n_tokens, jax.tree.map(lambda g: g / n_tokens, grads)


def global_norm(grads: Dict[str, Any]) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
