"""What the two references share: precision, the control's fake quantisation."""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

Quant = Optional[Callable[[jax.Array], jax.Array]]


def int8_fake_quant(x: jax.Array) -> jax.Array:
    """Symmetric int8 over the last axis, straight-through gradient: what an
    int8 matmul path would feed the MXU. The control's precision."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(q - x)


def fp8_fake_quant(x: jax.Array) -> jax.Array:
    """float8 e4m3 (3 mantissa bits) scaled over the last axis to the format's
    largest value, straight-through gradient: the other precision the contract
    names below bfloat16."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


QUANTS = {"int8": int8_fake_quant, "fp8": fp8_fake_quant}


def mm(a: jax.Array, b: jax.Array, quant: Quant) -> jax.Array:
    """a @ b in float32 at the highest matmul precision; ``quant`` (the
    control) degrades both operands first, b along its contracted axis."""
    if quant is not None:
        a = quant(a)
        b = jnp.swapaxes(quant(jnp.swapaxes(b, -1, -2)), -1, -2)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
