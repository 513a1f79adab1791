"""Ulysses-style sequence parallelism: all-to-all seq<->head exchange.

The complement to ring attention (SURVEY §2.2): instead of rotating KV shards
around the ring, one `all_to_all` re-shards activations from
sequence-partitioned to head-partitioned, each device runs *full-sequence*
attention for its subset of heads, and a second `all_to_all` swaps back:

    (B, T/n, H,  D)  --all_to_all-->  (B, T, H/n, D)
          attention over the full sequence, H/n heads
    (B, T, H/n, D)  --all_to_all-->  (B, T/n, H,  D)

Two collectives per attention vs ring's n-1 ppermutes; requires n_heads
divisible by the seq-axis size. Inner attention is the dense/flash path, so
on TPU the Pallas kernel runs unchanged under Ulysses.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P


from pretraining_llm_tpu.ops.attention import naive_attention


def _ulysses_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    axis_name: str,
    use_flash: bool,
    block_q: int,
    block_kv: int,
) -> jax.Array:
    """Per-device body. q, k, v: (B, T_local, H, Dh) -> same shape."""

    def seq_to_heads(x):
        # (B, T/n, H, D) -> (B, T, H/n, D): split heads, concat sequence.
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    # GQA: q and k/v exchange independently (H/n vs G/n heads per device);
    # the contiguous head split is group-aligned — device j's H/n query
    # heads cover exactly groups [j*G/n, (j+1)*G/n) — so the grouped inner
    # kernels see whole groups. KV moves G/H the all-to-all bytes of MHA.
    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if use_flash:
        from pretraining_llm_tpu.ops.flash_attention import flash_attention

        out = flash_attention(qh, kh, vh, causal=causal, block_q=block_q, block_kv=block_kv)
    else:
        out = naive_attention(qh, kh, vh, causal=causal)
    return heads_to_seq(out)


def ulysses_supports_grouped(
    mesh: Optional[Mesh],
    n_heads: int,
    n_kv_heads: int,
    *,
    seq_axis: str = "seq",
    head_axis: Optional[str] = "tensor",
) -> bool:
    """Whether grouped (un-expanded) KV can ride the all-to-all exchange.

    True when ulysses won't run (no seq axis — the naive fallback is
    grouped-native) or when the KV heads split evenly over both the head
    (tensor) shards and the seq-axis all-to-all.
    """
    if mesh is None or mesh.shape.get(seq_axis, 1) <= 1:
        return True
    if n_kv_heads == n_heads:
        return True
    tp = mesh.shape.get(head_axis, 1) if head_axis else 1
    n = mesh.shape[seq_axis]
    return n_kv_heads % tp == 0 and (n_kv_heads // tp) % n == 0


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    seq_axis: str = "seq",
    batch_axes: Tuple[str, ...] = ("data", "fsdp"),
    head_axis: Optional[str] = "tensor",
    use_flash: bool = True,
    block_q: int = 0,
    block_kv: int = 0,
) -> jax.Array:
    """Global-view entry: q (B, T, H, Dh), k/v (B, T, G, Dh) with G | H
    (grouped-query attention exchanges only the G KV heads), T sharded over
    seq_axis."""
    n = mesh.shape[seq_axis]
    h, g = q.shape[2], k.shape[2]
    if h % g != 0:
        raise ValueError(f"kv heads ({g}) must divide query heads ({h})")
    tp = mesh.shape[head_axis] if head_axis else 1
    h_local = h // tp
    if h_local % n != 0:
        raise ValueError(
            f"ulysses needs per-device heads ({h_local}) divisible by seq axis size ({n})"
        )
    if g < h and not ulysses_supports_grouped(
        mesh, h, g, seq_axis=seq_axis, head_axis=head_axis
    ):
        raise ValueError(
            f"grouped ulysses needs kv heads ({g}) divisible by "
            f"{head_axis} x {seq_axis} shards; expand K/V to full heads instead"
        )
    spec = P(batch_axes, seq_axis, head_axis, None)
    local = functools.partial(
        _ulysses_local,
        causal=causal,
        axis_name=seq_axis,
        use_flash=use_flash,
        block_q=block_q,
        block_kv=block_kv,
    )
    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)
