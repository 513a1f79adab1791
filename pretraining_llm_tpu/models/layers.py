"""Layer primitives: norms, activations, RoPE — pure functions on pytrees.

Capability superset of the reference's `src/models/{mlp,attention}.py` layer
zoo, redesigned functional: no module state, explicit params, fp32 norm math
with bf16 matmul inputs (TPU MXU native), and pluggable position encodings.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, jax.Array]


def weight(sub: Params, name: str, cdt) -> jax.Array:
    """Matmul weight read in compute dtype — the single dequant point for
    int8 serving params (models/quantize.py). A quantized projection is an
    int8 leaf plus a sibling ``{name}_scale`` fp32 leaf (per-output-channel
    symmetric); dequant is one fp32 multiply, then the SAME compute-dtype
    cast the bf16 path takes, so the matmul accumulates identically."""
    w = sub[name]
    if w.dtype == jnp.int8:
        return (w.astype(jnp.float32) * sub[name + "_scale"]).astype(cdt)
    return w.astype(cdt)


# ---------------------------------------------------------------------------
# Normalization — computed in fp32, output cast back to the input dtype.
# ---------------------------------------------------------------------------


def layernorm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rmsnorm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    y = y * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def apply_norm(kind: str, p: Params, x: jax.Array, eps: float) -> jax.Array:
    return layernorm(p, x, eps) if kind == "layernorm" else rmsnorm(p, x, eps)


def norm_in(cfg, p: Params, x: jax.Array) -> jax.Array:
    """What a sublayer reads of the residual ``x``: N(x) under its norm ``p``,
    or ``x`` as it is where the norm stands on the output
    (``cfg.norm_placement``)."""
    if cfg.norm_placement == "output":
        return x
    with jax.named_scope("blk.norm"):
        return apply_norm(cfg.norm, p, x, cfg.norm_eps)


def norm_out(cfg, p: Params, y: jax.Array) -> jax.Array:
    """What a sublayer hands the residual: its output ``y``, normed by ``p``
    where the norm stands there (x + N(f(x)))."""
    if cfg.norm_placement != "output":
        return y
    with jax.named_scope("blk.norm"):
        return apply_norm(cfg.norm, p, y, cfg.norm_eps)


def init_norm(kind: str, d: int, dtype: jnp.dtype) -> Params:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def join_residual(x: jax.Array, out: jax.Array, multiplier: float = 1.0, residual: bool = True) -> jax.Array:
    """A sublayer's output as it joins the residual: x + out in x's dtype, the
    output times ``multiplier`` (``ModelConfig.residual_multiplier``) first where
    the model has one. ``residual=False`` hands back the (scaled) output alone."""
    out = out.astype(x.dtype)
    if multiplier != 1.0:
        out = out * jnp.asarray(multiplier, x.dtype)
    return x + out if residual else out


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def activation_fn(kind: str, x: jax.Array) -> jax.Array:
    if kind == "relu":
        return jax.nn.relu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if kind == "relu2":
        return jnp.square(jax.nn.relu(x))
    raise ValueError(f"activation_fn does not handle {kind!r} (swiglu is fused in mlp)")


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def yarn_inv_freq(
    head_dim: int, theta: float, factor: float, original_context: int,
    beta_fast: float, beta_slow: float,
) -> jax.Array:
    """YaRN's frequencies (head_dim // 2,): pairs that turn more than
    ``beta_fast`` times inside the original context keep their frequency,
    pairs that turn fewer than ``beta_slow`` times are slowed by ``factor``,
    and a linear ramp over the pair index lies between."""
    half = head_dim // 2

    def pair_of(turns: float) -> float:
        return head_dim * math.log(original_context / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), head_dim - 1)
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0
    )
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def rope_table(
    context_length: int, head_dim: int, theta: float,
    yarn: Optional[Tuple[float, int, float, float, float]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(cos, sin) tables of shape (T, head_dim // 2), fp32. ``yarn`` =
    (factor, original context, beta_fast, beta_slow, cos/sin scale)."""
    half = head_dim // 2
    if yarn is not None:
        freqs = yarn_inv_freq(head_dim, theta, *yarn[:4])
        angles = jnp.arange(context_length, dtype=jnp.float32)[:, None] * freqs[None, :]
        return jnp.cos(angles) * yarn[4], jnp.sin(angles) * yarn[4]
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(context_length, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(
    x: jax.Array, cos: jax.Array, sin: jax.Array, positions: jax.Array,
) -> jax.Array:
    """Rotate (B, T, H, Dh) by position.

    positions: (T,) int32 into the table — shared across the batch — or
    (B, T) for per-row positions (ragged left-padded decode, where row i's
    token at slot s has logical position s - pad_offset_i).
    """
    cos_t, sin_t = cos[positions], sin[positions]  # (B, T, Dh/2) or (T, Dh/2)
    if positions.ndim == 2:
        cos_t, sin_t = cos_t[:, :, None], sin_t[:, :, None]  # head dim broadcast
    else:
        cos_t, sin_t = cos_t[None, :, None], sin_t[None, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos_t - x2 * sin_t, x2 * cos_t + x1 * sin_t], axis=-1)
    return rotated.astype(x.dtype)
