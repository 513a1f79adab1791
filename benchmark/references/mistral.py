"""Mistral-7B-v0.1 forward pass as published, in plain float32 jax.numpy.

No kernels, cache or batching: one sequence, every position attends over the
whole prefix through an explicit mask (causal AND inside the sliding window),
grouped-query attention written out by repeating each KV head. Departures
from the published model: none in the equations; weights are seeded, and the
depth is whatever the configuration file states.

Independent of ``models/transformer.py``: it reads only the canonical
weights of ``harness/weights.py``, one layer at a time.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from references.common import Quant, mm


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (T, heads, head_dim); rotate-half convention of the published code."""
    t, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def block(x, w, arch: Dict[str, Any], quant: Quant):
    """One decoder layer on (T, d) activations."""
    t = x.shape[0]
    h, g = arch["num_attention_heads"], arch["num_key_value_heads"]
    dh = arch["hidden_size"] // h
    eps, theta, window = arch["rms_norm_eps"], arch["rope_theta"], arch["sliding_window"]
    a = rmsnorm(x, w["ln1_scale"], eps)
    q = rope(mm(a, w["wq"], quant).reshape(t, h, dh), theta)
    k = rope(mm(a, w["wk"], quant).reshape(t, g, dh), theta)
    v = mm(a, w["wv"], quant).reshape(t, g, dh)
    k = jnp.repeat(k, h // g, axis=1)  # query head i reads KV head i // (h/g)
    v = jnp.repeat(v, h // g, axis=1)
    s = mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0), quant) / jnp.sqrt(jnp.float32(dh))
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    mask = (j <= i) & (j > i - window)
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    o = mm(p, v.transpose(1, 0, 2), quant).transpose(1, 0, 2).reshape(t, h * dh)
    x = x + mm(o, w["wo"], quant)
    a = rmsnorm(x, w["ln2_scale"], eps)
    ff = jax.nn.silu(mm(a, w["w_gate"], quant)) * mm(a, w["w_up"], quant)
    return x + mm(ff, w["w_down"], quant)


def forward(tokens: jax.Array, layer_weights: Callable[[int], Dict[str, jax.Array]],
            global_weights: Dict[str, jax.Array], arch: Dict[str, Any],
            quant: Quant = None) -> jax.Array:
    """Logits (T, V) of one sequence. ``layer_weights(l)`` makes layer ``l``."""
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    step = jax.jit(functools.partial(block, arch=arch, quant=quant))
    gw = f32(global_weights)
    x = gw["embed"][tokens]
    for l in range(arch["num_hidden_layers"]):
        x = step(x, f32(layer_weights(l)))
    x = rmsnorm(x, gw["final_scale"], arch["rms_norm_eps"])
    return mm(x, gw["head"], quant)

