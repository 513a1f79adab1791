"""Post-load int8 weight quantization for serving.

Decode is HBM-bandwidth-bound: every decode window re-reads the whole
weight set, so bytes-per-weight — not FLOPs — is the lever. This module
implements the serving-prep pass behind ``serving.quantize``:

  - **per-channel symmetric int8** over the matmul projections of every
    transformer block (attention qkv/q/kv/o and FFN w1/w2), reducing over
    the *contracted* (input) axes of each einsum so every output channel
    keeps its own fp32 scale,
  - each quantized weight is replaced in-place by its int8 tensor plus a
    sibling ``{name}_scale`` fp32 leaf in the same subtree — the scale
    keeps the leading ``(n_layers,)`` dim, so the pair rides the existing
    depth ``lax.scan`` over ``params['blocks']`` unchanged, and
    ``generate.shard_params_for_inference`` shards both through the same
    name-keyed partition rules (scales are per-output-channel, so they
    follow their weight's output-axis sharding),
  - embeddings, lm_head, norms, biases and MoE experts stay in their
    original dtype: embeddings/lm_head dominate quality per bit at small
    vocab-heavy models, norm/bias math is deliberately fp32/bf16 in the
    forward, and expert matmuls route through capacity-gathered einsums
    this pass does not cover.

Dequantization happens at the use site (``transformer._weight``):
``w_int8.astype(f32) * scale`` then cast to the compute dtype, so the
matmul itself accumulates exactly like the bf16 path — the quantized
forward is a pure function of the int8 bytes + scales, which is what the
integrity sentinel's quantized-graph probe pinning relies on.

Symmetric scheme (no zero-points): ``scale = max(|w|, eps) / 127`` over
the reduce axes, ``q = clip(round(w / scale), -127, 127)``. 127 (not
128) keeps the code symmetric so ``-q`` is always representable.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

# Contracted (input) axes per quantized projection, for STACKED block
# leaves (leading n_layers axis at 0). Reducing over the contracted axes
# gives one scale per output channel — the per-channel symmetric scheme:
#   wqkv (L, d, 3, h, dh) -> scale (L, 1, 3, h, dh)
#   wq   (L, d, h, dh)    -> scale (L, 1, h, dh)
#   wkv  (L, d, 2, g, dh) -> scale (L, 1, 2, g, dh)
#   wo   (L, h, dh, d)    -> scale (L, 1, 1, d)
#   w1   (L, d, [2,] f)   -> scale (L, 1, [2,] f)
#   w2   (L, f, d)        -> scale (L, 1, d)
_REDUCE_AXES: Dict[str, Tuple[int, ...]] = {
    "wqkv": (1,),
    "wq": (1,),
    "wkv": (1,),
    "wo": (1, 2),
    "w1": (1,),
    "w2": (1,),
}

_EPS = 1e-8


def quantize_weight(
    w: jax.Array, axes: Tuple[int, ...]
) -> Tuple[jax.Array, jax.Array]:
    """(int8 codes, fp32 scale) for symmetric per-channel quantization of
    ``w`` reducing over ``axes``. Scale keeps singleton reduce dims so
    ``q.astype(f32) * scale`` broadcasts back to ``w``'s shape."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=axes, keepdims=True)
    # eps floor: an all-zero channel quantizes to zeros with a tiny scale
    # instead of dividing by zero.
    scale = jnp.maximum(amax, _EPS) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_weight(q: jax.Array, scale: jax.Array, dtype: Any) -> jax.Array:
    """Inverse of `quantize_weight` (up to rounding): fp32 multiply, then
    one cast to the compute dtype — the same numerics transformer._weight
    applies at every use site."""
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def quantize_params_for_serving(params: Any, cfg: Any) -> Any:
    """Serving-prep pass: per-channel int8 over the block projections.

    Call AFTER `generate.cast_params_for_inference` (the pass reads any
    float dtype) and BEFORE `generate.shard_params_for_inference` — the
    int8 leaves and their ``{name}_scale`` siblings flow through the
    name-keyed partition rules like any other block leaf.

    Returns a new tree; only ``params['blocks']['attn'|'mlp']`` changes.
    MoE models are rejected loudly (expert einsums are not covered).
    """
    if getattr(cfg, "n_experts", 0):
        raise ValueError(
            "int8 weight quantization does not cover MoE expert matmuls"
        )
    params = dict(params)
    blocks = dict(params["blocks"])
    for sub_name in ("attn", "mlp"):
        sub = dict(blocks[sub_name])
        for name, axes in _REDUCE_AXES.items():
            w = sub.get(name)
            if w is None or not jnp.issubdtype(w.dtype, jnp.floating):
                continue
            q, scale = quantize_weight(w, axes)
            sub[name] = q
            sub[name + "_scale"] = scale
        blocks[sub_name] = sub
    params["blocks"] = blocks
    return params


def is_quantized(params: Any) -> bool:
    """True if `quantize_params_for_serving` has run on this tree."""
    try:
        attn = params["blocks"]["attn"]
    except (KeyError, TypeError):
        return False
    return any(k.endswith("_scale") for k in attn)


def param_bytes(params: Any) -> int:
    """Total bytes across all leaves: what a decode step streams from HBM."""
    return int(
        sum(
            leaf.size * jnp.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(params)
        )
    )
