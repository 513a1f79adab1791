"""Seeded weights: one canonical definition, read by the plain references
layer by layer and handed to the program as its own parameter tree.

Canonical layout (what the published equations use): matrices are
``(in, out)``; a layer is a flat dict. ``layer(arch, key, l)`` makes layer
``l`` alone, so a reference never holds more than one layer. The program's
tree is built from the same function under ``lax.map``: the values are equal
bit for bit, and nothing the program computed enters the reference.

Scale: N(0, 0.02) matrices, residual outputs scaled by 1/sqrt(2L) (GPT-2's
published init); norm scales 1 + N(0, 0.1) and biases N(0, 0.02), so that a
dropped norm scale or bias changes the output.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from harness import families, opcount

STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number, including ones past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (2 ** 31)), seed // (2 ** 31))


def normal(key, i, shape, std, dtype):
    return (jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std).astype(dtype)


def dense_block(m: Dict[str, int], k: jax.Array, dtype: Any) -> Dict[str, jax.Array]:
    """What every pre-norm dense decoder block has: four attention matrices,
    two norm scales, an up and a down projection. A family adds its own."""
    d, f, h, g, dh = m["d"], m["ffn"], m["heads"], m["kv_heads"], m["head_dim"]
    rs = STD / (2 * m["layers"]) ** 0.5
    return {
        "wq": normal(k, 0, (d, h * dh), STD, dtype),
        "wk": normal(k, 1, (d, g * dh), STD, dtype),
        "wv": normal(k, 2, (d, g * dh), STD, dtype),
        "wo": normal(k, 3, (h * dh, d), rs, dtype),
        "ln1_scale": 1 + normal(k, 4, (d,), 0.1, dtype),
        "ln2_scale": 1 + normal(k, 5, (d,), 0.1, dtype),
        "w_down": normal(k, 6, (f, d), rs, dtype),
        "w_up": normal(k, 7, (d, f), STD, dtype),
    }


def layer(arch: Dict[str, Any], key: jax.Array, l: Any, dtype: Any) -> Dict[str, jax.Array]:
    """Canonical weights of block ``l`` (``l`` may be traced)."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1), l)
    return families.of(arch).layer(opcount.dims(arch), k, dtype)


def globals_(arch: Dict[str, Any], key: jax.Array, dtype: Any) -> Dict[str, jax.Array]:
    """Embedding, position table, final norm, output head (canonical)."""
    return families.of(arch).globals_(opcount.dims(arch), jax.random.fold_in(key, 0), dtype)


def program_tree(arch: Dict[str, Any], layers: Any, gl: Dict[str, Any]) -> Dict[str, Any]:
    """Canonical stacked layers and globals (weights, or a gradient of the same
    shape) in the program's layout: pure reshapes."""
    fam, m = families.of(arch), opcount.dims(arch)
    return fam.program_tree(jax.vmap(lambda c: fam.program_layer(m, c))(layers), gl)


def program_params(arch: Dict[str, Any], key: jax.Array, dtype: Any,
                   layers: Any = None) -> Dict[str, Any]:
    """The program's parameter tree (``models/transformer.py::init_params``
    structure) holding the canonical values. Traceable: call under ``jit``.
    ``layers`` (an index array) restricts the blocks to those layers."""
    fam, m = families.of(arch), opcount.dims(arch)
    idx = jnp.arange(m["layers"]) if layers is None else layers
    blocks = jax.lax.map(lambda l: fam.program_layer(m, layer(arch, key, l, dtype)), idx)
    return fam.program_tree(blocks, globals_(arch, key, dtype))


def serving_params(arch: Dict[str, Any], seed: int) -> Any:
    """The program's tree on the device, in one jitted call, in the served dtype."""
    dtype = jnp.dtype(arch["serving_dtype"])
    # the key is an argument, so one compiled program serves every seed
    params = jax.jit(lambda key: program_params(arch, key, dtype))(seed_key(seed))
    return jax.block_until_ready(params)
