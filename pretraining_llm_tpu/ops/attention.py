"""Attention ops: naive einsum path + implementation dispatch.

The reference computes attention one head at a time in a Python loop, fully
materializing (B, T, T) scores per head with a pre-registered tril mask buffer
(`/root/reference/src/models/attention.py:47-57,95`). TPU-first redesign:

  - All heads batch into single einsums so the MXU sees one large matmul
    (`bqhd,bkhd->bhqk`), not H small ones.
  - The causal mask is index arithmetic fused by XLA — never a materialized
    parameter buffer (the reference wastes ~1 GB on duplicate masks, SURVEY
    §A B10).
  - Softmax runs in fp32 regardless of compute dtype (bf16 exp/sum loses
    accuracy), matmuls accumulate fp32 via preferred_element_type.
  - `impl='flash'` routes to the Pallas blockwise kernel (ops.flash_attention);
    `impl='ring'` to sequence-parallel ring attention (parallel.ring_attention).
    A caller that holds q, k and v as one fused projection's result asks
    ops.flash_attention.flash_takes_qkv and, on a yes, hands that array to
    flash_attention_qkv instead of three slices of it to this dispatch.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def naive_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    kv_mask: Optional[jax.Array] = None,
    causal: bool = True,
    segments: Optional[jax.Array] = None,
    window: int = 0,
) -> jax.Array:
    """Reference einsum attention. q: (B, Tq, H, Dh); k, v: (B, Tk, G, Dh).

    G (KV heads) may divide H (grouped-query attention): the grouped einsum
    attends each group of H/G query heads against its shared KV head without
    materializing repeated K/V — the GQA cache-bandwidth win.

    ``q_positions``/``kv_positions`` (shape (Tq,), (Tk,)) define causality for
    KV-cached decode where the query block sits at an offset; they default to
    aligned ranges. ``kv_mask`` (B, Tk) masks out unwritten cache slots.
    ``segments`` (B, T) int32 document ids (self-attention, Tq == Tk):
    attention never crosses a document boundary (packed-sequence training).
    """
    b, tq, h, dh = q.shape
    tk, g = k.shape[1], k.shape[2]
    scale = 1.0 / (dh**0.5)
    qg = q.reshape(b, tq, g, h // g, dh)
    scores = jnp.einsum(
        "bqgrd,bkgd->bgrqk", qg, k, preferred_element_type=jnp.float32
    ) * scale  # (B, G, H/G, Tq, Tk)
    if causal or window:
        if q_positions is None:
            q_positions = jnp.arange(tq) + (tk - tq)  # aligned suffix by default
        if kv_positions is None:
            kv_positions = jnp.arange(tk)
    if causal:
        causal_mask = q_positions[:, None] >= kv_positions[None, :]  # (Tq, Tk)
        scores = jnp.where(causal_mask[None, None, None, :, :], scores, -jnp.inf)
    if window:
        # Sliding window: a query sees only the last `window` positions —
        # the cached-decode form of Mistral-style attention (old cache
        # slots are masked, not evicted).
        w_ok = (q_positions[:, None] - kv_positions[None, :]) < window
        scores = jnp.where(w_ok[None, None, None, :, :], scores, -jnp.inf)
    if kv_mask is not None:
        # (B, Tk) masks unwritten cache slots uniformly; (B, Tq, Tk)
        # additionally varies by query — the multi-token paged verify's
        # per-row causal frontier (each of the Tq speculative tokens sees
        # a different prefix of its row's pool blocks).
        kv_mask_q = kv_mask if kv_mask.ndim == 3 else kv_mask[:, None, :]
        scores = jnp.where(kv_mask_q[:, None, None, :, :], scores, -jnp.inf)
    if segments is not None:
        if tq != tk:
            raise ValueError("segments requires self-attention (Tq == Tk)")
        seg_ok = segments[:, :, None] == segments[:, None, :]  # (B, Tq, Tk)
        scores = jnp.where(seg_ok[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    if kv_mask is not None:
        # A query slot whose EVERY key is masked (a dead left-pad slot in
        # ragged decode) softmaxes to NaN (0/0). Zero exactly those rows —
        # derived from the MASKS, not from isfinite(), so genuine NaNs from
        # corrupt weights still propagate loudly. Without this, downstream
        # layers' 0-weight attention to the dead slot contributes 0*NaN =
        # NaN, poisoning every real slot in the batch row.
        if causal:
            valid = causal_mask[None, :, :] & kv_mask_q  # (B,Tq,Tk)
        else:
            valid = jnp.broadcast_to(kv_mask_q, (b, tq, tk))
        dead = ~valid.any(axis=-1)  # (B, Tq)
        probs = jnp.where(dead[:, None, None, :, None], 0.0, probs)
    out = jnp.einsum(
        "bgrqk,bkgd->bqgrd", probs.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return out.reshape(b, tq, h, dh).astype(q.dtype)


def multihead_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "naive",
    causal: bool = True,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    kv_mask: Optional[jax.Array] = None,
    block_q: int = 0,
    block_kv: int = 0,
    ring_layout: str = "contiguous",
    segments: Optional[jax.Array] = None,
    window: int = 0,
) -> jax.Array:
    """Dispatch over attention implementations.

    'ring' routes to `parallel.ring_attention` (shard_map over the active
    mesh's 'seq' axis, read from `parallel.sharding.current_mesh()` at trace
    time). Without a seq axis, or for KV-cached decode (kv_mask set), it
    degrades to the dense path — the correct single-shard form.
    ``ring_layout="zigzag"`` asserts the caller already zigzag-permuted the
    sequence dim (models.transformer.loss_fn does this).
    """
    if impl in ("ring", "ulysses"):
        if window:
            raise ValueError(
                "sliding-window attention is not supported by the "
                "ring/ulysses sequence-parallel attention paths"
            )
        if segments is not None:
            # The rotating-KV / all-to-all layouts would need segment ids
            # threaded through their collectives; config validation forbids
            # doc_mask with these impls — this is the backstop.
            raise ValueError(
                "segments (document masking) is not supported by the "
                "ring/ulysses sequence-parallel attention paths"
            )
        from pretraining_llm_tpu.parallel.sharding import current_mesh

        mesh = current_mesh()
        if mesh is not None and mesh.shape.get("seq", 1) > 1 and kv_mask is None:
            if impl == "ring":
                from pretraining_llm_tpu.parallel.ring_attention import ring_attention

                return ring_attention(
                    q, k, v, mesh, causal=causal, layout=ring_layout,
                    block_kv=block_kv or 512,
                )
            from pretraining_llm_tpu.parallel.ulysses import ulysses_attention

            return ulysses_attention(
                q, k, v, mesh, causal=causal, block_q=block_q, block_kv=block_kv
            )
        # No seq axis on the active mesh (or cached decode): the dense path is
        # the correct degenerate form.
        impl = "naive"
    if impl == "naive":
        return naive_attention(
            q,
            k,
            v,
            causal=causal,
            q_positions=q_positions,
            kv_positions=kv_positions,
            kv_mask=kv_mask,
            segments=segments,
            window=window,
        )
    if impl == "flash":
        if q_positions is not None or kv_positions is not None or kv_mask is not None:
            if segments is not None:
                # Loud, like the ring/ulysses backstop: silently dropping
                # the mask here would reintroduce the cross-document leak
                # the feature exists to prevent.
                raise ValueError(
                    "segments (document masking) is not supported on the "
                    "cached-decode attention path"
                )
            # Cached decode shapes are small; the flash kernel targets training.
            return naive_attention(
                q,
                k,
                v,
                causal=causal,
                window=window,
                q_positions=q_positions,
                kv_positions=kv_positions,
                kv_mask=kv_mask,
            )
        from pretraining_llm_tpu.ops.flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal, block_q=block_q, block_kv=block_kv,
            segments=segments, window=window,
        )
    raise ValueError(f"unknown attention impl {impl!r}")
