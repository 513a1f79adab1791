"""Ring attention: context parallelism over the 'seq' mesh axis.

The reference cannot exceed context 512 — attention materializes full (B,T,T)
scores per head (/root/reference/src/models/attention.py:51-57) and there is
no sequence/context parallelism of any kind (SURVEY §2.2). This module scales
context across chips the TPU way:

  - the sequence dim of q/k/v is sharded over the 'seq' mesh axis
    (`jax.shard_map`);
  - each device keeps its q shard resident and the K/V shards rotate around
    the ring with `jax.lax.ppermute` (ICI neighbor hops), one hop per step;
  - each hop runs *flash-locally*: a blockwise online-softmax scan over KV
    sub-blocks producing unnormalized (o, m, l) partials — never a dense
    (T_local, T_local) fp32 score tensor — and the hop body is
    `jax.checkpoint`ed so autodiff recomputes score blocks instead of
    storing every hop's intermediates;
  - causal hops that contribute nothing are *skipped at runtime* via
    `lax.switch` (mode = none / causal-diagonal / full), not computed and
    masked away;
  - with `layout="zigzag"` the sequence is distributed in balanced
    chunk-pairs: the global sequence splits into 2n chunks and device i owns
    chunks (i, 2n-1-i), so under causal masking every device does the same
    work per hop — a contiguous layout leaves device 0 with one hop of work
    and device n-1 with n (utilization (n+1)/2n). The token permutation is
    applied by the caller (see parallel.zigzag + models.transformer.loss_fn);
    this module only needs the chunk arithmetic.

Memory per device: O(T/n) activations and one in-flight KV shard — 8k+
contexts at the per-chip cost of 8k/n. Compute per hop maps to the MXU via
batched einsums; the ppermute overlaps with the next hop's partial attention
under XLA's async collectives.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30

# Modes for one (q-chunk, kv-chunk) partial-attention call.
_SKIP, _CAUSAL, _FULL = 0, 1, 2


def _empty_stats(b: int, t: int, h: int, d: int):
    """Identity element of the online-softmax merge: (o=0, m=NEG_INF, l=0)."""
    return (
        jnp.zeros((b, t, h, d), jnp.float32),
        jnp.full((b, h, t), NEG_INF, jnp.float32),
        jnp.zeros((b, h, t), jnp.float32),
    )


def _merge_stats(o, m, l, o2, m2, l2):
    """Online-softmax merge of two unnormalized partials.

    o: (B, t, H, D) fp32 unnormalized accumulators; m, l: (B, H, t) fp32.
    The NEG_INF sentinel makes the algebra self-guarding: exp(NEG_INF - x)
    underflows to exactly 0 for any finite x, and exp(0)=1 when both sides
    are still empty.
    """
    m_new = jnp.maximum(m, m2)
    a1 = jnp.exp(m - m_new)
    a2 = jnp.exp(m2 - m_new)
    l_new = l * a1 + l2 * a2
    o_new = o * a1.transpose(0, 2, 1)[..., None] + o2 * a2.transpose(0, 2, 1)[..., None]
    return o_new, m_new, l_new


def _partial_flash(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mode: jax.Array,
    *,
    block_kv: int = 512,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Blockwise partial attention returning unnormalized online-softmax stats.

    q: (B, tq, H, D); k, v: (B, tk, G, D) with G | H — grouped-query
    attention attends each group's H/G query heads against its shared KV head
    directly (never expanding K/V, so the ring's ppermute volume is G/H of
    the MHA cost). ``mode`` is a traced scalar: _SKIP returns empty stats
    without touching the MXU (lax.switch at the call site picks the branch at
    runtime), _CAUSAL masks assuming q and k cover the SAME aligned chunk
    (the only causal case both layouts produce), _FULL attends unmasked.
    Returns (o_unnormalized (B,tq,H,D) fp32, m (B,H,tq) fp32, l (B,H,tq)
    fp32) — stats always in flattened-H layout.
    """
    b, tq, h, d = q.shape
    tk, g = k.shape[1], k.shape[2]
    rep = h // g
    scale = 1.0 / (d**0.5)
    bk = min(block_kv, tk)
    while tk % bk != 0:
        bk //= 2
    nk = tk // bk

    def empty():
        return _empty_stats(b, tq, h, d)

    def attend(causal: bool):
        q_ids = jnp.arange(tq)
        qg = q.reshape(b, tq, g, rep, d)

        def kv_step(carry, inp):
            o, m, l = carry
            j, kb, vb = inp  # kb, vb: (B, bk, G, D)
            s = (
                jnp.einsum(
                    "bqgrd,bkgd->bgrqk", qg, kb, preferred_element_type=jnp.float32
                )
                * scale
            ).reshape(b, h, tq, bk)
            if causal:
                k_pos = j * bk + jnp.arange(bk)
                s = jnp.where((q_ids[:, None] >= k_pos[None, :])[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bgrqk,bkgd->bqgrd",
                p.reshape(b, g, rep, tq, bk).astype(v.dtype),
                vb,
                preferred_element_type=jnp.float32,
            ).reshape(b, tq, h, d)
            o = o * alpha.transpose(0, 2, 1)[..., None] + pv
            return (o, m_new, l), None

        kb = k.reshape(b, nk, bk, g, d).swapaxes(0, 1)
        vb = v.reshape(b, nk, bk, g, d).swapaxes(0, 1)
        (o, m, l), _ = jax.lax.scan(kv_step, empty(), (jnp.arange(nk), kb, vb))
        return o, m, l

    return jax.lax.switch(
        mode, [empty, functools.partial(attend, True), functools.partial(attend, False)]
    )


def _chunk_mode(q_chunk: jax.Array, k_chunk: jax.Array) -> jax.Array:
    """Causal relation of two equal-size chunks by global chunk index."""
    return jnp.where(q_chunk == k_chunk, _CAUSAL, jnp.where(q_chunk > k_chunk, _FULL, _SKIP))


def _ring_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    axis_name: str,
    axis_size: int,
    layout: str,
    block_kv: int,
) -> jax.Array:
    """Per-device body. q, k, v: (B, T_local, H, Dh) shards."""
    my = jax.lax.axis_index(axis_name)
    b, tl, h, d = q.shape
    n = axis_size
    perm = [(i, (i + 1) % n) for i in range(n)]

    if layout == "zigzag" and causal:
        # Device i holds global chunks (i, 2n-1-i), each of size tl//2,
        # concatenated. Every hop costs every device exactly two
        # half-chunk partials -> balanced ring.
        c = tl // 2
        q_halves = (q[:, :c], q[:, c:])

        def hop(carry, r):
            stats0, stats1, kc, vc = carry
            src = (my - r) % n
            q_chunks = (my, 2 * n - 1 - my)
            k_chunks = (src, 2 * n - 1 - src)
            k_halves = (kc[:, :c], kc[:, c:])
            v_halves = (vc[:, :c], vc[:, c:])
            out = []
            for qi, stats in ((0, stats0), (1, stats1)):
                for ki in (0, 1):
                    mode = _chunk_mode(q_chunks[qi], k_chunks[ki])
                    part = _partial_flash(
                        q_halves[qi], k_halves[ki], v_halves[ki], mode, block_kv=block_kv
                    )
                    stats = _merge_stats(*stats, *part)
                out.append(stats)
            kc, vc = jax.lax.ppermute((kc, vc), axis_name, perm)
            return (out[0], out[1], kc, vc), None

        (s0, s1, _, _), _ = jax.lax.scan(
            jax.checkpoint(hop),
            (_empty_stats(b, c, h, d), _empty_stats(b, c, h, d), k, v),
            jnp.arange(n),
        )
        o = jnp.concatenate([s0[0], s1[0]], axis=1)
        l = jnp.concatenate([s0[2], s1[2]], axis=2)
    else:
        def hop(carry, r):
            stats, kc, vc = carry
            src = (my - r) % n
            mode = _chunk_mode(my, src) if causal else jnp.int32(_FULL)
            part = _partial_flash(q, kc, vc, mode, block_kv=block_kv)
            stats = _merge_stats(*stats, *part)
            kc, vc = jax.lax.ppermute((kc, vc), axis_name, perm)
            return (stats, kc, vc), None

        ((o, _, l), _, _), _ = jax.lax.scan(
            jax.checkpoint(hop), (_empty_stats(b, tl, h, d), k, v), jnp.arange(n)
        )

    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (o / safe_l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def ring_supports_grouped(
    mesh: Optional[Mesh],
    n_heads: int,
    n_kv_heads: int,
    *,
    seq_axis: str = "seq",
    head_axis: Optional[str] = "tensor",
) -> bool:
    """Whether grouped (un-expanded) KV can be fed to the ring dispatch.

    True when ring won't actually run (no seq axis — the naive fallback is
    grouped-native anyway) or when every head-axis shard holds whole KV
    groups. Single source of truth for the caller-side guard in
    models.transformer and the trace-time check in ring_attention.
    """
    if mesh is None or mesh.shape.get(seq_axis, 1) <= 1:
        return True
    tp = mesh.shape.get(head_axis, 1) if head_axis else 1
    return n_kv_heads % tp == 0 or n_kv_heads == n_heads


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    seq_axis: str = "seq",
    batch_axes: Tuple[str, ...] = ("data", "fsdp"),
    head_axis: Optional[str] = "tensor",
    layout: str = "contiguous",
    block_kv: int = 512,
) -> jax.Array:
    """Global-view entry: q (B, T, H, Dh), k/v (B, T, G, Dh) with G | H
    (grouped-query attention rotates only the G KV heads around the ring),
    T sharded over seq_axis.

    Nested inside the jitted forward via shard_map; degenerates to a single
    local block (no communication) when the seq axis has size 1. With
    ``layout="zigzag"`` the caller must have permuted the sequence dim with
    `parallel.zigzag.zigzag_perm` (and fed matching position ids to RoPE /
    learned embeddings) — see models.transformer.loss_fn.
    """
    axis_size = mesh.shape[seq_axis]
    if layout == "zigzag" and (q.shape[1] // axis_size) % 2 != 0:
        raise ValueError("zigzag layout needs an even per-device sequence length")
    h, g = q.shape[2], k.shape[2]
    if h % g != 0:
        raise ValueError(f"kv heads ({g}) must divide query heads ({h})")
    if g < h and not ring_supports_grouped(
        mesh, h, g, seq_axis=seq_axis, head_axis=head_axis
    ):
        # Head-sharded q with unshardable grouped KV would misalign groups
        # inside the manual region; the caller must expand K/V first.
        raise ValueError(
            f"grouped ring attention needs kv heads ({g}) divisible by the "
            f"'{head_axis}' mesh axis; expand K/V to full heads instead"
        )
    spec = P(batch_axes, seq_axis, head_axis, None)
    kv_spec = P(batch_axes, seq_axis, head_axis, None)
    local = functools.partial(
        _ring_local,
        causal=causal,
        axis_name=seq_axis,
        axis_size=axis_size,
        layout=layout,
        block_kv=block_kv,
    )
    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec, kv_spec, kv_spec), out_specs=spec, check_vma=False
    )(q, k, v)
