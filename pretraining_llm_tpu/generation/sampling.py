"""Token sampling: temperature / top-k / top-p / min-p, pure and jittable.

The reference samples with temperature-1 multinomial only
(`/root/reference/src/models/transformer.py:110-113`). That remains the
default; top-k, nucleus (top-p), and min-p sampling are the standard
extensions (min-p keeps tokens with prob >= min_p * max_prob — the
support adapts to the distribution's confidence instead of a fixed mass
or count).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


@jax.named_scope("sample")
def sample_logits(
    logits: jax.Array,
    key: jax.Array,
    *,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
) -> jax.Array:
    """Sample token ids from (B, V) logits. temperature=0 -> greedy."""
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    # Integrity guard (sampling path only — greedy argmax of corrupt
    # logits still lands in-vocab and the golden probes own that case):
    # corrupted state surfaces as NaN/+inf logits, and categorical over
    # them returns an arbitrary IN-RANGE id — silent garbage. Flag such
    # rows before masking (the top-k/top-p/min-p filters introduce
    # legitimate -inf) and return -1 for them: out of vocab range, so the
    # serving engine's reap-time sanity check fails the request loudly
    # instead of streaming it. Fused elementwise+reduce on the existing
    # program — no extra sync, no effect on finite logits.
    bad = jnp.any(jnp.isnan(logits) | (logits == jnp.inf), axis=-1)
    logits = logits / temperature
    if min_p is not None and 0.0 < min_p <= 1.0:
        # Keep tokens whose prob >= min_p * max prob. In logit space:
        # logit >= max_logit + log(min_p) — no softmax materialization.
        cutoff = jnp.max(logits, axis=-1, keepdims=True) + jnp.log(min_p)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    do_top_k = top_k is not None and top_k > 0
    do_top_p = top_p is not None and 0.0 < top_p < 1.0
    if do_top_k:
        # k > V is a no-op filter (the old clamped sort-index agreed);
        # lax.top_k would reject it, so clamp statically.
        top_k = min(top_k, logits.shape[-1])
    if do_top_p:
        # One descending "sort" (lax.top_k over V) serves BOTH filters:
        # the k-th-largest threshold reads straight off it, and masking
        # the sorted copy with the same threshold keeps it exactly the
        # descending sort of the post-top-k logits (monotone masking
        # preserves order and any ties AT the threshold — the old
        # second full jnp.sort, without the second sort).
        sorted_desc = jax.lax.top_k(logits, logits.shape[-1])[0]
        if do_top_k:
            kth = sorted_desc[:, top_k - 1][:, None]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
            sorted_desc = jnp.where(sorted_desc < kth, -jnp.inf, sorted_desc)
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep the smallest prefix with cumulative mass >= top_p (always >= 1 token).
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff_logit = jnp.take_along_axis(sorted_desc, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff_logit, -jnp.inf, logits)
    elif do_top_k:
        # top-k alone never needs the full sort: an O(V·log k) partial
        # top-k finds the k-th largest value (same value-threshold mask
        # as sorting, ties included).
        kth = jax.lax.top_k(logits, top_k)[0][:, -1][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    sampled = jax.random.categorical(key, logits, axis=-1)
    return jnp.where(bad, jnp.int32(-1), sampled.astype(jnp.int32))


def sample_logits_fused(
    logits: jax.Array,
    key: jax.Array,
    *,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    logprobs_k: int = 0,
) -> tuple:
    """`sample_logits` plus the decode-fused host payload.

    The fused decode step ships token ids (and, when ``logprobs_k > 0``,
    the top-k logprobs of the MODEL distribution — raw logits before
    temperature/filtering, the standard logprobs contract) back to the
    host instead of the (B, V) logits array. Token choice is
    `sample_logits` verbatim, so fused-vs-unfused greedy decode is
    bit-identical by construction.

    Returns ``(tokens (B,) int32, logprobs)`` where ``logprobs`` is
    ``None`` when ``logprobs_k == 0`` and otherwise a
    ``(values (B, k) f32, token_ids (B, k) int32)`` pair, values sorted
    descending.
    """
    tokens = sample_logits(
        logits, key, temperature=temperature, top_k=top_k, top_p=top_p,
        min_p=min_p,
    )
    if logprobs_k <= 0:
        return tokens, None
    with jax.named_scope("sample"):
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        vals, ids = jax.lax.top_k(lp, logprobs_k)
        return tokens, (vals, ids.astype(jnp.int32))
