"""Hand-tiled Pallas TPU flash attention (FlashAttention-2 schedule).

Forward and backward kernels with a custom VJP. Design (vs the reference's
fully-materialized (B,H,T,T) scores, /root/reference/src/models/attention.py:51-57):

  - Grid (batch, head, q_blocks, kv_blocks); the kv axis is innermost so the
    fp32 accumulator/stats live in VMEM scratch across kv steps and the output
    block is written once on the last step (standard TPU revisiting pattern).
  - Online softmax: running row-max m and row-sum l; score blocks (bq, bk)
    exist only in VMEM — O(T) memory in sequence length.
  - Causal masking by index arithmetic (broadcasted_iota); fully-masked kv
    blocks skip their matmuls entirely via pl.when (upper-triangle blocks cost
    no FLOPs).
  - QK^T and PV ride the MXU with fp32 accumulation (preferred_element_type);
    inputs stay bf16.
  - **GQA native**: k/v may carry G = n_kv_heads < H heads. The grid's head
    axis indexes QUERY heads; the k/v BlockSpec index maps divide down to the
    shared KV head (h // n_rep) so no repeated K/V ever exists in HBM — the
    bandwidth saving that motivates GQA. The dK/dV kernel grids over KV heads
    and accumulates across the group's n_rep query heads in VMEM scratch.
  - Backward = two kernels (FA2): dQ gridded over q blocks, dK/dV gridded over
    kv blocks, both re-building P from the saved logsumexp; D = rowsum(dO*O)
    is precomputed in plain XLA (the tiled backward below takes it itself).
  - A plain causal call of ONE block a head (T <= 1024 at the default sizes)
    has nothing for the grid to skip: its forward and fused backward walk the
    block in square sub-tiles instead and never form those above the diagonal
    (causal_tiles; the pallas_calls `flash_fwd_tiles` / `flash_bwd_tiles`).
  - Those tiled kernels read q, k, v and write o, dq, dk, dv IN PLACE where
    the head size allows it (heads_in_place): the arrays stay (B, T, H*D) as
    the projections leave them (a free reshape of (B, T, H, D)), a block is a
    128-lane column block of that, and two heads of 64 sit side by side in
    its lanes, told apart by lane masks. No transpose exists on either side
    of such a call, forward, recompute or backward, and the backward takes
    D = rowsum(dO*O) from the blocks it already holds. Every other call folds
    the heads first ((B*H, T, D): _heads_first / _heads_last).
  - Where q, k and v are the three planes of ONE array, a fused projection's
    (B, 3, T, H*D), the same tiled kernels take them out of it themselves
    (pallas_flash_attention_qkv): an operand's block is a column block of
    plane c, the index map choosing c, and the backward writes dq, dk and dv
    into one block of one d(qkv) of that shape. Neither the three slices of
    the projection's result nor the gradient put together again from three
    exist beside such a call.

All kernels run under interpret mode on CPU for unit testing (tests compare
against the naive einsum path).
"""

from __future__ import annotations

import functools
import logging
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger(__name__)

NEG_INF = -1e30  # avoid actual -inf inside kernels (exp/max edge cases)

# Side of the square sub-tiles a lone causal block is walked in (causal_tiles).
# Timed on a v5e at the training cells' calls, T 1024, D 64, bf16, a forward /
# a fused backward call, the kernel's own events in a trace (PR 42):
#   (B*H) 240: block 0.816 / 1.839 ms; tile 128 0.686 / 1.323 (36 of 64
#              sub-tiles); tile 256 0.611 / 1.251 (10 of 16); tile 512
#              0.628 / 1.436 (3 of 4)
#   (B*H) 300: block 1.019 / 2.297 ms; 128 0.857 / 1.652; 256 0.763 / 1.563;
#              512 0.785 / 1.793
# 128 computes the fewest sub-tiles and loses them again to more, smaller
# matmuls and accumulator updates. A multiple of 128 keeps every slice on
# lane-tile boundaries.
CAUSAL_TILE = 256


def _heads_first(x: jax.Array) -> jax.Array:
    """(B, T, H, D) -> (B*H, T, D)"""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _heads_last(x: jax.Array, b: int, h: int) -> jax.Array:
    """(B*H, T, D) -> (B, T, H, D)"""
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _block_sizes(t: int, block_q: int, block_kv: int) -> Tuple[int, int]:
    # Auto default 1024: measured fastest on v5e at T=1024..8192; smaller
    # blocks pay a grid step (~0.35 us) and the online softmax's rescale each.
    # So T <= 1024 stays ONE block a head, and the causal skipping that a
    # grid of smaller blocks would give happens inside it (causal_tiles).
    bq = min(block_q or 1024, t)
    bk = min(block_kv or 1024, t)
    while t % bq:
        bq //= 2
    while t % bk:
        bk //= 2
    return max(bq, 1), max(bk, 1)


def causal_tiles(t: int, bq: int, bk: int, causal: bool, window: int, segments) -> int:
    """How many CAUSAL_TILE-square sub-tiles a side the kernels walk a lone
    causal block in; 0 = the block form (every other call: the grid's
    kernels, untouched). Read from static facts of the call alone: the one
    block of a plain causal call lies on the diagonal, where _run_ok can skip
    nothing and half of every matmul is masked away. Walked in sub-tiles,
    n(n+1)/2 of the n*n are computed and only the n on the diagonal masked."""
    if not causal or window or segments is not None or bq != t or bk != t:
        return 0
    if t % (2 * CAUSAL_TILE):
        return 0
    return t // CAUSAL_TILE


LANES = 128  # a vreg's and an HBM tile's minor dimension


def heads_in_place(d: int, h: int, g: int, n_tiles: int) -> int:
    """How many heads share one column block of the lanes when the tiled
    kernels read q, k, v and write o, dq, dk, dv in place, as (B, T, H*D)
    arrays (what a projection einsum leaves, reshaped for free); 0 = the
    heads are folded first, (B*H, T, D), with a transposing copy of every
    operand and result around the call. Read from the shapes alone, for the
    calls causal_tiles takes (n_tiles > 0; every other call folds):

      - d a multiple of 128: 1. A head is a whole-tile column block of its
        own; grouped heads (g < h) index down to their KV head's block.
      - d = 64, h = g: 2. A block of 128 lanes holds heads 2p and 2p + 1,
        which the kernels tell apart with lane masks (_head_lanes). An odd
        h (GPT-2 XL's 25) leaves the last block's upper half outside the
        array: what is read there is arbitrary (not zero, possibly NaN) and
        is masked off every operand (_kv_inside), what is written there
        falls away.
      - anything else (d = 64 under grouped heads, whose pair would need
        half of a KV block; head sizes that tile no 128 lanes): 0.
    """
    if not n_tiles:
        return 0
    if d % LANES == 0:
        return 1
    if 2 * d == LANES and h == g:
        return 2
    return 0


@functools.lru_cache(maxsize=None)
def _log_form(bh: int, t: int, d: int, bq: int, bk: int, n: int, heads: int,
              one: bool = False) -> None:
    """One INFO line a distinct shape, at trace time: which form it takes,
    where the kernels find the heads (heads_in_place), and whether they find
    q, k and v in one array (pallas_flash_attention_qkv)."""
    if n:
        form = (f"causal tiles of {t // n}, {n * (n + 1) // 2} of {n * n} "
                "sub-tiles computed")
    else:
        form = f"block grid {t // bq} x {t // bk} of ({bq}, {bk})"
    layout = (f"heads in place, {heads} a block of {heads * d} lanes" if heads
              else "heads first")
    if one:
        layout += ", q, k and v from one array"
    logger.info("flash attention (B*H, T, D) = (%d, %d, %d): %s; %s", bh, t, d, form, layout)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _run_ok(i0, j0, bq, bk, causal, window):
    """Block-skip predicate for a (bq, bk) score block at offsets (i0, j0):
    False only when NO (q, k) pair in the block can be valid. Shares a home
    with _mask_ok for the same reason — skip semantics must never diverge
    between the forward and backward kernels."""
    run = jnp.logical_or(not causal, j0 <= i0 + bq - 1)
    if window:
        run = jnp.logical_and(run, j0 + bk - 1 >= i0 - (window - 1))
    return run


def _mask_ok(i0, j0, bq, bk, causal, window, sq_ref, sk_ref):
    """Combined causal/window/segment validity mask for a (bq, bk) score
    block at absolute offsets (i0, j0), or None when nothing masks. ONE
    definition shared by the forward and all three backward kernels — a
    mask tweak must not silently diverge forward from backward."""
    ok = None
    if causal or window:
        q_pos = i0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = j0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        ok = q_pos >= k_pos
    if window:
        w_ok = q_pos - k_pos < window
        ok = w_ok if ok is None else jnp.logical_and(ok, w_ok)
    if sq_ref is not None:
        seg_ok = sq_ref[0] == sk_ref[0]
        ok = seg_ok if ok is None else jnp.logical_and(ok, seg_ok)
    return ok


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, causal, scale, bq, bk, nk, seg, window):
    # `seg` (static) threads document-segment refs: sq (bq, 1) / sk (1, bk)
    # int32 blocks riding the proven trailing-singleton stats layouts; a
    # query may only attend keys of its own document. seg=False traces the
    # exact op sequence the measured kernels compiled — the proven class.
    if seg:
        sq_ref, sk_ref, o_ref, lse_ref, acc, m_scr, l_scr = rest
    else:
        o_ref, lse_ref, acc, m_scr, l_scr = rest
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # Causal: kv block strictly after the q block -> nothing to do.
    # Sliding window additionally skips blocks entirely BELOW the window
    # (every key older than window for every query): O(T*W) compute.
    run = _run_ok(i * bq, j * bk, bq, bk, causal, window)

    @pl.when(run)
    def _compute():
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bk, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        ok = _mask_ok(i * bq, j * bk, bq, bk, causal, window,
                      sq_ref if seg else None, sk_ref if seg else None)
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[:]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (bq, bk) f32
        if seg or window:
            # NEG_INF is finite: a row whose EVERY seen entry is masked
            # keeps m == NEG_INF, making exp(s - m_new) == 1 for masked
            # entries (plain causal never runs such a block; window/seg
            # rows can — early blocks fully below the window, or fully
            # cross-document). Zero p by the combined mask itself, not by
            # exp underflow.
            p = jnp.where(ok, p, 0.0)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[:] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc[:] = acc[:] * alpha + pv

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(safe_l)  # (bq, 1)


def _strip_scores(q, keys, i: int, tile: int, scale: float) -> List[Tuple[slice, jax.Array]]:
    """Scaled scores of causal strip i (the `tile` query rows q, at rows
    [i*tile, (i+1)*tile)) against the keys it can see (`keys(columns)` loads
    them: _kv_inside), as (key columns, scores) pieces: everything left of
    the diagonal in one unmasked piece, then the diagonal sub-tile under
    _mask_ok at its own offsets. Sub-tiles right of the diagonal are never
    formed. Shared by the tiled forward and backward, so the two cannot
    disagree on what a strip sees."""
    r0 = i * tile
    pieces = []
    for cols in ([slice(0, r0)] if i else []) + [slice(r0, r0 + tile)]:
        s = jax.lax.dot_general(
            q, keys(cols), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        pieces.append((cols, s))
    cols, s = pieces[-1]
    ok = _mask_ok(r0, r0, tile, tile, True, 0, None, None)
    pieces[-1] = (cols, jnp.where(ok, s, NEG_INF))
    return pieces


def _head_lanes(shape: Tuple[int, int], j: int, heads: int, inside) -> Optional[jax.Array]:
    """Mask over a (rows, width) value of the lanes that are head j's of the
    `heads` side by side in a block, less those outside the array (`inside`:
    the first lane past it, or None); None where the block is one whole head.
    Zeroing the other head's lanes of q (of dO) makes a contraction over all
    the lanes that head's own: the neighbour's products are exact zeros, and
    the MXU pass is the one a head of 64 half-filled anyway."""
    if heads == 1:
        return None
    d = shape[1] // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mine = jnp.logical_and(lane >= j * d, lane < (j + 1) * d)
    return mine if inside is None else jnp.logical_and(mine, lane < inside)


def _own(mine: Optional[jax.Array], x: jax.Array) -> jax.Array:
    return x if mine is None else jnp.where(mine, x, jnp.zeros_like(x))


def _kv_inside(k_ref, v_ref, scratch, edge: int):
    """Loaders of rows of the block's k and of its v, `keys(cols)` and
    `values(cols)`, and the first lane past the array in this block (None:
    all inside). `edge` (static) is how many lanes of the LAST column block
    lie inside the array where an odd head count leaves it half outside (0:
    none does). What is read past it is arbitrary, and a zeroed lane of q
    times an arbitrary one of k is no zero if that one is a NaN: there k and
    v are copied to scratch with the outside lanes zeroed, once a block, and
    _head_lanes keeps them out of q, dO and O. The head that would sit there
    is computed all the same, from zeros, and its results fall away with the
    lanes they are written to: skipping it under pl.when cost both kernels a
    tenth of their time at 25 heads (PR 50; 3.8% of their work is nobody's)."""
    if not edge:
        return (lambda cols: k_ref[0, cols, :]), (lambda cols: v_ref[0, cols, :]), None
    last = pl.program_id(1) == pl.num_programs(1) - 1
    inside = jnp.where(last, edge, k_ref.shape[2])
    ok = jax.lax.broadcasted_iota(jnp.int32, k_ref.shape[1:], 1) < inside
    ks, vs = scratch
    ks[...] = _own(ok, k_ref[0])
    vs[...] = _own(ok, v_ref[0])
    return (lambda cols: ks[cols, :]), (lambda cols: vs[cols, :]), inside


def _fwd_tiles_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, scale, tile, n, heads, edge):
    """Forward of a lone causal block (causal_tiles), one query strip at a
    time. A strip has every key it can see in VMEM already, so its softmax is
    an ordinary one: no m/l carry, no alpha rescale, no scratch. Arithmetic as
    _fwd_kernel's: bf16 operands, f32 accumulation, p rounded to the value
    dtype once before PV. Every row sees its own diagonal key, so l >= 1.
    A block holds `heads` heads side by side in its lanes (heads_in_place; 1
    when the heads were folded first): each takes its own scores from its own
    lanes of q, p @ v is taken over all the lanes and each head's lanes of it
    selected into o."""
    keys, values, inside = _kv_inside(k_ref, v_ref, scratch, edge)
    for i in range(n):
        rows = slice(i * tile, (i + 1) * tile)
        q = q_ref[0, rows, :]
        o = None
        for j in range(heads):
            mine = _head_lanes(q.shape, j, heads, inside)
            pieces = _strip_scores(_own(mine, q), keys, i, tile, scale)
            m = functools.reduce(
                jnp.maximum, [jnp.max(s, axis=-1, keepdims=True) for _, s in pieces]
            )  # (tile, 1)
            l = acc = None
            for cols, s in pieces:
                p = jnp.exp(s - m)
                v = values(cols)
                p_sum = jnp.sum(p, axis=-1, keepdims=True)
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
                )
                l = p_sum if l is None else l + p_sum
                acc = pv if acc is None else acc + pv
            o = acc / l if o is None else jnp.where(mine, acc / l, o)
            lse_ref[j, rows, :] = m + jnp.log(l)
        o_ref[0, rows, :] = o.astype(o_ref.dtype)


def _tile_spec(in_place: bool, t: int, width: int, n: int, at,
               plane: Optional[int] = None) -> pl.BlockSpec:
    """One (T, width) block of a tiled call's operand or result: block
    `at(*grid ids)` of the n that batch row ids[0] holds. In place the array
    is (B, T, n*width) (less what an odd head count leaves off the last
    block) and the block a column block of its lanes; with the heads folded
    first it is (B*n, T, width) and the block a row of it. `plane` c makes
    the array (B, 3, T, n*width), q, k and v in one, and the block that
    column block of its plane c: the kernel sees the same (1, T, width)."""
    if plane is not None:
        return pl.BlockSpec((1, None, t, width), lambda *ids: (ids[0], plane, 0, at(*ids)))
    if in_place:
        return pl.BlockSpec((1, t, width), lambda *ids: (ids[0], 0, at(*ids)))
    return pl.BlockSpec((1, t, width), lambda *ids: (ids[0] * n + at(*ids), 0, 0))


def _dims(q: jax.Array, h: int, heads: int) -> Tuple[int, int, int]:
    """(B, T, D) of a q handed over in place, (B, T, H*D) or the (B, 3, T, H*D)
    it is a plane of, where `heads` (heads_in_place) says so, and with its
    heads folded first, (B*H, T, D)."""
    if heads:
        return q.shape[0], q.shape[-2], q.shape[-1] // h
    return q.shape[0] // h, q.shape[1], q.shape[2]


def _tiles_layout(t: int, d: int, h: int, g: int, heads: int, one: bool = False):
    """What the forward and the backward of a tiled call share: the heads and
    the lanes of a block, the blocks a batch row's q and its k hold, the
    static `edge` of _kv_inside, _tile_spec left to take (blocks, at, plane),
    and the planes q, k and v are of the array they are handed as (`one`: of
    a fused projection's (B, 3, T, H*D); else each an array of its own)."""
    in_block = max(heads, 1)
    width = d * in_block
    nbq, nbk = pl.cdiv(h, in_block), pl.cdiv(g, in_block)
    edge = (h % in_block) * d
    spec = functools.partial(_tile_spec, bool(heads), t, width)
    return in_block, width, nbq, nbk, edge, spec, (0, 1, 2) if one else (None, None, None)


def _seg_views(segments: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(b, t) int32 document ids -> q-side (b, t, 1) and k-side (b, 1, t)
    views, each blockable with the proven trailing-singleton / single-
    sublane layouts (no in-kernel transpose)."""
    s32 = segments.astype(jnp.int32)
    return s32[:, :, None], s32[:, None, :]


def _fwd(
    q: jax.Array, k: jax.Array, v: jax.Array, h: int, g: int, *,
    causal: bool, block_q: int, block_kv: int, interpret: bool,
    segments: Optional[jax.Array] = None, window: int = 0, heads: int = 0,
    one: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """q (B*H, T, D) and k, v (B*G, T, D) -> o like q and lse (B*H, T, 1);
    with `heads` (heads_in_place) q (B, T, H*D), k, v (B, T, G*D) and lse
    (B*blocks*heads, T, 1), a row a head of each block (an odd head count's
    last row of a batch row is nobody's). With `one` (a tiled call in place,
    h = g) q, k and v are all three the one (B, 3, T, H*D) array whose planes
    they are, and o is (B, T, H*D) as ever."""
    b, t, d = _dims(q, h, heads)
    bh = b * h
    n_rep = h // g
    bq, bk = _block_sizes(t, block_q, block_kv)
    nq, nk = t // bq, t // bk
    scale = 1.0 / (d**0.5)

    n_tiles = causal_tiles(t, bq, bk, causal, window, segments)
    _log_form(bh, t, d, bq, bk, n_tiles, heads, one)
    if n_tiles:
        in_block, width, nbq, nbk, edge, spec, (qp, kp, vp) = _tiles_layout(t, d, h, g, heads, one)
        head = lambda bb, hh: hh
        kv_head = lambda bb, hh: hh // n_rep
        return pl.pallas_call(
            functools.partial(_fwd_tiles_kernel, scale=scale, tile=t // n_tiles, n=n_tiles,
                              heads=in_block, edge=edge),
            grid=(b, nbq),
            in_specs=[spec(nbq, head, qp), spec(nbk, kv_head, kp), spec(nbk, kv_head, vp)],
            out_specs=[
                spec(nbq, head),
                pl.BlockSpec((in_block, t, 1), lambda bb, hh: (bb * nbq + hh, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b,) + q.shape[-2:] if one else q.shape, q.dtype),
                jax.ShapeDtypeStruct((b * nbq * in_block, t, 1), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((t, width), k.dtype)] * (2 if edge else 0),
            interpret=interpret,
            name="flash_fwd_tiles",
        )(q, k, v)

    seg = segments is not None
    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale, bq=bq, bk=bk, nk=nk, seg=seg,
        window=window,
    )
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda bb, hh, i, j: (bb * h + hh, i, 0)),
        # GQA: the group's query heads share one KV head — index division,
        # never a materialized repeat.
        pl.BlockSpec((1, bk, d), lambda bb, hh, i, j: (bb * g + hh // n_rep, j, 0)),
        pl.BlockSpec((1, bk, d), lambda bb, hh, i, j: (bb * g + hh // n_rep, j, 0)),
    ]
    inputs = [q, k, v]
    if seg:
        sq3, sk3 = _seg_views(segments)
        in_specs += [
            pl.BlockSpec((1, bq, 1), lambda bb, hh, i, j: (bb, i, 0)),
            pl.BlockSpec((1, 1, bk), lambda bb, hh, i, j: (bb, 0, j)),
        ]
        inputs += [sq3, sk3]
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bb, hh, i, j: (bb * h + hh, i, 0)),
            # Stats ride in a trailing singleton lane dim: block (bq, 1) on
            # array (t, 1) satisfies Mosaic's (8, 128)-or-full-dim tiling rule
            # without the official kernel's 128-lane broadcast blowup.
            pl.BlockSpec((1, bq, 1), lambda bb, hh, i, j: (bb * h + hh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*inputs)
    return o, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    causal, scale, bq, bk, nk, seg, window
):
    if seg:
        sq_ref, sk_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = _run_ok(i * bq, j * bk, bq, bk, causal, window)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        # All matmuls take bf16 inputs with fp32 accumulation (MXU-native);
        # only the elementwise dS math runs in fp32. Casting do/v up first
        # would silently demote dp to a multi-pass fp32 matmul.
        do = do_ref[0]
        lse = lse_ref[0]  # (bq, 1)
        delta = delta_ref[0]  # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        ok = _mask_ok(i * bq, j * bk, bq, bk, causal, window,
                      sq_ref if seg else None, sk_ref if seg else None)
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse)  # (bq, bk)
        if seg or window:
            # Explicit zero (not exp underflow): lse for a real row is
            # finite, but masked-s NEG_INF is finite too — exp stays ~0
            # there; the guard is for degenerate all-masked rows where
            # lse == NEG_INF would give exp(0) == 1 (see _fwd_kernel).
            p = jnp.where(ok, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    causal, scale, bq, bk, nq, n_inner, seg, window
):
    if seg:
        sq_ref, sk_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    j = pl.program_id(2)  # kv block (outer)
    ri = pl.program_id(3)  # inner: (q head within group) * nq + q block
    i = ri % nq

    @pl.when(ri == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = _run_ok(i * bq, j * bk, bq, bk, causal, window)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        # bf16 matmul inputs, fp32 accumulation (see _bwd_dq_kernel).
        do = do_ref[0]
        lse = lse_ref[0]  # (bq, 1)
        delta = delta_ref[0]  # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        ok = _mask_ok(i * bq, j * bk, bq, bk, causal, window,
                      sq_ref if seg else None, sk_ref if seg else None)
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse)  # (bq, bk)
        if seg or window:
            p = jnp.where(ok, p, 0.0)  # see _bwd_dq_kernel
        # dV += P^T dO
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale  # (bq, bk)
        # dK += dS^T Q
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ri == n_inner - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    causal, scale, n_rep, seg, window
):
    if seg:
        sq_ref, sk_ref, dq_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dq_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    """Single-block backward (t <= one block): dQ, dK, dV in ONE pass.

    The two-kernel FA2 split exists because dQ accumulates over kv blocks
    while dK/dV accumulate over q blocks — with one block each there is
    nothing to accumulate across, so S and P are computed once (5 matmuls vs
    the split's 7) and q/k/v/do are read from HBM once instead of twice.
    GQA: grid is (batch, kv_head, n_rep); dk/dv accumulate the group's query
    heads in scratch across the innermost axis.
    """
    r = pl.program_id(2)  # query head within the kv group
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    # bf16 matmul inputs, fp32 accumulation (see _bwd_dq_kernel).
    do = do_ref[0]
    lse = lse_ref[0]
    delta = delta_ref[0]
    tq, dd = q.shape
    tk = k.shape[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    ok = _mask_ok(0, 0, tq, tk, causal, window,
                  sq_ref if seg else None, sk_ref if seg else None)
    if ok is not None:
        s = jnp.where(ok, s, NEG_INF)
    p = jnp.exp(s - lse)
    if seg or window:
        p = jnp.where(ok, p, 0.0)  # see _bwd_dq_kernel
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta) * scale
    dq_ref[0] = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dq_ref.dtype)
    dv_part = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dk_part = jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(r == 0)
    def _init():
        dk_acc[:] = dk_part
        dv_acc[:] = dv_part

    @pl.when(r != 0)
    def _accum():
        dk_acc[:] += dk_part
        dv_acc[:] += dv_part

    @pl.when(r == n_rep - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_tiles_kernel(
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *scratch, scale, n_rep, tile, n, heads, edge
):
    """_bwd_fused_kernel for a lone causal block (causal_tiles), walked in
    the forward's strips: per strip, s and p from the saved lse, dq written
    to its rows, dk and dv of the keys it sees accumulated in the f32
    scratch. Grid and GQA accumulation over the group are the fused
    kernel's. D = rowsum(dO*O) is taken here, from blocks that are in VMEM
    anyway. With `heads` side by side in a block (see _fwd_tiles_kernel) each
    head's own lanes of q and dO give its s, dp and D; ds @ k is taken over
    all the lanes and the head's selected into dq; p^T @ dO and ds^T @ q of
    a head land in its own lanes of dv and dk by themselves."""
    r = pl.program_id(2)  # query head within the kv group
    keys, values, inside = _kv_inside(k_ref, v_ref, scratch, edge)

    @pl.when(r == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    for i in range(n):
        rows = slice(i * tile, (i + 1) * tile)
        q_all = q_ref[0, rows, :]
        do_all = do_ref[0, rows, :]
        do_o = do_all.astype(jnp.float32) * o_ref[0, rows, :].astype(jnp.float32)
        dq_all = None
        for j in range(heads):
            mine = _head_lanes(q_all.shape, j, heads, inside)
            q, do = _own(mine, q_all), _own(mine, do_all)
            lse = lse_ref[j, rows, :]  # (tile, 1)
            delta = jnp.sum(_own(mine, do_o), axis=-1, keepdims=True)
            dq = None
            for cols, s in _strip_scores(q, keys, i, tile, scale):
                k = keys(cols)
                p = jnp.exp(s - lse)
                # bf16 matmul inputs, fp32 accumulation (see _bwd_dq_kernel).
                dp = jax.lax.dot_general(
                    do, values(cols), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                )
                ds = p * (dp - delta) * scale
                dq_part = jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
                )
                dq = dq_part if dq is None else dq + dq_part
                dv_acc[cols, :] += jax.lax.dot_general(
                    p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
                )
                dk_acc[cols, :] += jax.lax.dot_general(
                    ds.astype(q.dtype), q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
                )
            dq_all = dq if dq_all is None else jnp.where(mine, dq, dq_all)
        dq_ref[0, rows, :] = dq_all.astype(dq_ref.dtype)

    @pl.when(r == n_rep - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_tiles_one_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dqkv_ref, *scratch, **static):
    """_bwd_tiles_kernel writing dq, dk and dv into the three planes of one
    (1, 3, T, width) block of d(qkv): the same body over three views of it."""
    dq_ref, dk_ref, dv_ref = (dqkv_ref.at[:, c] for c in range(3))
    _bwd_tiles_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref, dk_ref, dv_ref,
                      *scratch, **static)


def _bwd_tiles(
    h: int, g: int, heads: int, n_tiles: int, interpret: bool, q, k, v, o, lse, do,
    one: bool = False,
):
    """The fused backward of a call causal_tiles takes, in _fwd's layouts:
    (dq, dk, dv), or with `one` the one d(qkv) of the one array's shape. There
    h = g, so a grid step's dq, dk and dv blocks share their index and one
    output block takes all three: three outputs that XLA stacked after the
    call would be the copy the one array is there to spare."""
    b, t, d = _dims(q, h, heads)
    n_rep = h // g
    in_block, width, nbq, nbk, edge, spec, (qp, kp, vp) = _tiles_layout(t, d, h, g, heads, one)
    head = lambda bb, hh, r: hh * n_rep + r
    kv_head = lambda bb, hh, r: hh
    if one:
        out_specs = pl.BlockSpec((1, 3, t, width), lambda bb, hh, r: (bb, 0, 0, hh))
        out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    else:
        out_specs = [spec(nbq, head), spec(nbk, kv_head), spec(nbk, kv_head)]
        out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)]
    return pl.pallas_call(
        functools.partial(
            _bwd_tiles_one_kernel if one else _bwd_tiles_kernel, scale=1.0 / (d**0.5),
            n_rep=n_rep, tile=t // n_tiles, n=n_tiles, heads=in_block, edge=edge,
        ),
        grid=(b, nbk, n_rep),
        in_specs=[
            spec(nbq, head, qp), spec(nbk, kv_head, kp), spec(nbk, kv_head, vp),  # q, k, v
            spec(nbq, head), spec(nbq, head),  # do, o
            pl.BlockSpec((in_block, t, 1), lambda bb, hh, r: (bb * nbq + head(bb, hh, r), 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((t, width), jnp.float32)] * 2
        + [pltpu.VMEM((t, width), k.dtype)] * (2 if edge else 0),
        interpret=interpret,
        name="flash_bwd_tiles",
    )(q, k, v, do, o, lse)


def _bwd(
    h: int, g: int, causal: bool, block_q: int, block_kv: int, interpret: bool, residuals, grad,
    segments: Optional[jax.Array] = None, window: int = 0, heads: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    q, k, v, o, lse2 = residuals
    lse = lse2[..., None]
    do = grad
    t = q.shape[1]
    bq, bk = _block_sizes(t, block_q, block_kv)
    n_tiles = causal_tiles(t, bq, bk, causal, window, segments)
    if n_tiles:
        return _bwd_tiles(h, g, heads, n_tiles, interpret, q, k, v, o, lse, do)
    bh, t, d = q.shape
    b = bh // h
    n_rep = h // g
    nq, nk = t // bq, t // bk
    scale = 1.0 / (d**0.5)

    seg = segments is not None
    seg_inputs: list = []
    if seg:
        sq3, sk3 = _seg_views(segments)
        seg_inputs = [sq3, sk3]

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True)  # (bh, t, 1)

    if nq == 1 and nk == 1:
        in_specs = [
                pl.BlockSpec((1, t, d), lambda bb, hh, r: (bb * h + hh * n_rep + r, 0, 0)),  # q
                pl.BlockSpec((1, t, d), lambda bb, hh, r: (bb * g + hh, 0, 0)),  # k
                pl.BlockSpec((1, t, d), lambda bb, hh, r: (bb * g + hh, 0, 0)),  # v
                pl.BlockSpec((1, t, d), lambda bb, hh, r: (bb * h + hh * n_rep + r, 0, 0)),  # do
                pl.BlockSpec((1, t, 1), lambda bb, hh, r: (bb * h + hh * n_rep + r, 0, 0)),  # lse
                pl.BlockSpec((1, t, 1), lambda bb, hh, r: (bb * h + hh * n_rep + r, 0, 0)),  # delta
        ]
        if seg:
            in_specs += [
                pl.BlockSpec((1, t, 1), lambda bb, hh, r: (bb, 0, 0)),  # seg q-side
                pl.BlockSpec((1, 1, t), lambda bb, hh, r: (bb, 0, 0)),  # seg k-side
            ]
        dq, dk, dv = pl.pallas_call(
            functools.partial(
                _bwd_fused_kernel, causal=causal, scale=scale, n_rep=n_rep,
                seg=seg, window=window,
            ),
            grid=(b, g, n_rep),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, t, d), lambda bb, hh, r: (bb * h + hh * n_rep + r, 0, 0)),
                pl.BlockSpec((1, t, d), lambda bb, hh, r: (bb * g + hh, 0, 0)),
                pl.BlockSpec((1, t, d), lambda bb, hh, r: (bb * g + hh, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, t, d), q.dtype),
                jax.ShapeDtypeStruct((b * g, t, d), k.dtype),
                jax.ShapeDtypeStruct((b * g, t, d), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((t, d), jnp.float32),
                pltpu.VMEM((t, d), jnp.float32),
            ],
            interpret=interpret,
        )(q, k, v, do, lse, delta, *seg_inputs)
        return dq, dk, dv

    dq_in_specs = [
            pl.BlockSpec((1, bq, d), lambda bb, hh, i, j: (bb * h + hh, i, 0)),  # q
            pl.BlockSpec((1, bk, d), lambda bb, hh, i, j: (bb * g + hh // n_rep, j, 0)),  # k
            pl.BlockSpec((1, bk, d), lambda bb, hh, i, j: (bb * g + hh // n_rep, j, 0)),  # v
            pl.BlockSpec((1, bq, d), lambda bb, hh, i, j: (bb * h + hh, i, 0)),  # do
            pl.BlockSpec((1, bq, 1), lambda bb, hh, i, j: (bb * h + hh, i, 0)),  # lse
            pl.BlockSpec((1, bq, 1), lambda bb, hh, i, j: (bb * h + hh, i, 0)),  # delta
    ]
    if seg:
        dq_in_specs += [
            pl.BlockSpec((1, bq, 1), lambda bb, hh, i, j: (bb, i, 0)),  # seg q-side
            pl.BlockSpec((1, 1, bk), lambda bb, hh, i, j: (bb, 0, j)),  # seg k-side
        ]
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, scale=scale, bq=bq, bk=bk, nk=nk,
            seg=seg, window=window,
        ),
        grid=(b, h, nq, nk),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda bb, hh, i, j: (bb * h + hh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta, *seg_inputs)

    # dK/dV: grid over KV heads; the inner axis walks the group's n_rep query
    # heads x nq q-blocks, accumulating into one (bk, d) scratch per kv block.
    n_inner = n_rep * nq

    def q_row(bb, hh, j, ri):
        return bb * h + hh * n_rep + ri // nq

    dkv_in_specs = [
            pl.BlockSpec((1, bq, d), lambda bb, hh, j, ri: (q_row(bb, hh, j, ri), ri % nq, 0)),  # q
            pl.BlockSpec((1, bk, d), lambda bb, hh, j, ri: (bb * g + hh, j, 0)),  # k
            pl.BlockSpec((1, bk, d), lambda bb, hh, j, ri: (bb * g + hh, j, 0)),  # v
            pl.BlockSpec((1, bq, d), lambda bb, hh, j, ri: (q_row(bb, hh, j, ri), ri % nq, 0)),  # do
            pl.BlockSpec((1, bq, 1), lambda bb, hh, j, ri: (q_row(bb, hh, j, ri), ri % nq, 0)),  # lse
            pl.BlockSpec((1, bq, 1), lambda bb, hh, j, ri: (q_row(bb, hh, j, ri), ri % nq, 0)),  # delta
    ]
    if seg:
        dkv_in_specs += [
            pl.BlockSpec((1, bq, 1), lambda bb, hh, j, ri: (bb, ri % nq, 0)),  # seg q-side
            pl.BlockSpec((1, 1, bk), lambda bb, hh, j, ri: (bb, 0, j)),  # seg k-side
        ]
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, scale=scale, bq=bq, bk=bk, nq=nq,
            n_inner=n_inner, seg=seg, window=window,
        ),
        grid=(b, g, nk, n_inner),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bb, hh, j, ri: (bb * g + hh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bb, hh, j, ri: (bb * g + hh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * g, t, d), k.dtype),
            jax.ShapeDtypeStruct((b * g, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta, *seg_inputs)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper (operands as _fwd takes them), public (B, T, H, D) entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, h, g, causal, block_q, block_kv, interpret, window, heads):
    o, _ = _fwd(q, k, v, h, g, causal=causal, block_q=block_q, block_kv=block_kv,
                interpret=interpret, window=window, heads=heads)
    return o


def _flash_fwd(q, k, v, h, g, causal, block_q, block_kv, interpret, window, heads):
    o, lse = _fwd(q, k, v, h, g, causal=causal, block_q=block_q, block_kv=block_kv,
                  interpret=interpret, window=window, heads=heads)
    # Remat tags: under the 'save_attn_res' policy the VJP
    # residuals themselves are saved, so the backward never re-runs this
    # kernel (plain 'save_attn' only tags the merged output downstream,
    # which cannot reconstruct lse — the fwd kernel reruns there).
    # lse is squeezed to 2-D for the residual: a trailing-singleton (bh, t, 1)
    # buffer saved across the layer scan provokes pathological XLA layout
    # handling (observed as a compile hang with these residuals saved).
    # The residuals are the arrays in the layout the kernels were handed.
    o_res = checkpoint_name(o, "attn_o_res")
    lse2 = checkpoint_name(lse[..., 0], "attn_lse")
    return o, (q, k, v, o_res, lse2)


def _flash_bwd(h, g, causal, block_q, block_kv, interpret, window, heads, residuals, grad):
    return _bwd(h, g, causal, block_q, block_kv, interpret, residuals, grad,
                window=window, heads=heads)


_flash.defvjp(_flash_fwd, _flash_bwd)


# Segment-masked variant: identical kernels with the document-mask refs
# threaded (seg=True). A separate custom_vjp keeps the measured non-segment
# path's trace byte-identical. `segments` is an int32 primal whose
# cotangent space is float0 (non-differentiable by construction).
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_seg(q, k, v, segments, h, g, causal, block_q, block_kv, interpret, window):
    o, _ = _fwd(q, k, v, h, g, causal=causal, block_q=block_q,
                block_kv=block_kv, interpret=interpret, segments=segments,
                window=window)
    return o


def _flash_seg_fwd(q, k, v, segments, h, g, causal, block_q, block_kv, interpret, window):
    o, lse = _fwd(q, k, v, h, g, causal=causal, block_q=block_q,
                  block_kv=block_kv, interpret=interpret, segments=segments,
                  window=window)
    o_res = checkpoint_name(o, "attn_o_res")
    lse2 = checkpoint_name(lse[..., 0], "attn_lse")
    return o, (q, k, v, o_res, lse2, segments)


def _flash_seg_bwd(h, g, causal, block_q, block_kv, interpret, window, residuals, grad):
    *res, segments = residuals
    dq, dk, dv = _bwd(h, g, causal, block_q, block_kv, interpret, tuple(res),
                      grad, segments=segments, window=window)
    dseg = np.zeros(segments.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dseg


_flash_seg.defvjp(_flash_seg_fwd, _flash_seg_bwd)


# One-array variant (pallas_flash_attention_qkv): the VJP is over the fused
# projection's (B, 3, T, H*D) itself, so the residuals hold that one array and
# the cotangent is one d(qkv). Always a plain causal call causal_tiles takes,
# in place (`heads` > 0), with h = g.
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _flash_one(qkv, h, block_q, block_kv, interpret, heads):
    return _flash_one_fwd(qkv, h, block_q, block_kv, interpret, heads)[0]


def _flash_one_fwd(qkv, h, block_q, block_kv, interpret, heads):
    o, lse = _fwd(qkv, qkv, qkv, h, h, causal=True, block_q=block_q, block_kv=block_kv,
                  interpret=interpret, heads=heads, one=True)
    # the tags and the squeeze of lse: see _flash_fwd
    o_res = checkpoint_name(o, "attn_o_res")
    lse2 = checkpoint_name(lse[..., 0], "attn_lse")
    return o, (qkv, o_res, lse2)


def _flash_one_bwd(h, block_q, block_kv, interpret, heads, residuals, grad):
    qkv, o, lse2 = residuals
    t = qkv.shape[2]
    n_tiles = causal_tiles(t, *_block_sizes(t, block_q, block_kv), True, 0, None)
    return (_bwd_tiles(h, h, heads, n_tiles, interpret, qkv, qkv, qkv, o, lse2[..., None],
                       grad, one=True),)


_flash_one.defvjp(_flash_one_fwd, _flash_one_bwd)


def pallas_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 0,
    block_kv: int = 0,
    interpret: Optional[bool] = None,
    segments: Optional[jax.Array] = None,
    window: int = 0,
) -> jax.Array:
    """Flash attention. q: (B, T, H, Dh); k, v: (B, T, G, Dh) with G | H
    (grouped-query attention — G < H never materializes repeated K/V).
    Returns (B, T, H, Dh).

    Where heads_in_place says so (a lone causal block a head at a head size
    of 64 or a multiple of 128: every training step at T <= 1024) the
    kernels are handed the arrays as they are, reshaped (B, T, H*Dh) for
    free, and return o the same way; every other call folds the heads first
    and unfolds o, a transposing copy each. A caller that holds q, k and v as
    one fused projection's result hands that to pallas_flash_attention_qkv
    instead of three slices of it to this.

    ``segments`` (B, T) int32 document ids restricts attention to keys of
    the query's own document (packed-sequence training; composed with the
    causal mask inside the kernel — cross-document pairs never contribute
    to the online softmax or its VJP).

    ``window`` > 0 enables SLIDING-WINDOW attention (Mistral-style): each
    query attends only the last `window` positions. Blocks entirely below
    the window are skipped (pl.when), so compute is O(T*window).

    `interpret=None` auto-selects: compiled on TPU, interpreter elsewhere
    (slow — tests only).
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    b, t, h, d = q.shape
    g = k.shape[2]
    if h % g != 0:
        raise ValueError(f"kv heads ({g}) must divide query heads ({h})")
    if segments is not None and segments.shape != (b, t):
        raise ValueError(
            f"segments must be (batch, seq) = ({b}, {t}), got {segments.shape}"
        )
    bq, bk = _block_sizes(t, block_q, block_kv)
    heads = heads_in_place(d, h, g, causal_tiles(t, bq, bk, causal, window, segments))
    if heads:
        in_place = lambda x: x.reshape(b, t, -1)
        of = _flash(in_place(q), in_place(k), in_place(v), h, g, causal, block_q,
                    block_kv, interpret, int(window), heads)
        return of.reshape(b, t, h, d)
    qf, kf, vf = _heads_first(q), _heads_first(k), _heads_first(v)
    if segments is not None:
        of = _flash_seg(qf, kf, vf, segments.astype(jnp.int32), h, g, causal,
                        block_q, block_kv, interpret, int(window))
    else:
        of = _flash(qf, kf, vf, h, g, causal, block_q, block_kv, interpret,
                    int(window), 0)
    return _heads_last(of, b, h)


def qkv_heads_in_place(t: int, d: int, h: int, block_q: int = 0, block_kv: int = 0) -> int:
    """heads_in_place of plain causal self-attention over h = g heads of d at
    length t: what pallas_flash_attention_qkv needs above 0 to take a call."""
    bq, bk = _block_sizes(t, block_q, block_kv)
    return heads_in_place(d, h, h, causal_tiles(t, bq, bk, True, 0, None))


def pallas_flash_attention_qkv(
    qkv: jax.Array,
    n_heads: int,
    *,
    block_q: int = 0,
    block_kv: int = 0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Plain causal flash attention with q, k and v taken out of one array.
    qkv: (B, 3, T, H*Dh), a fused QKV projection's result with the heads
    merged in the lanes (plane 0 q, 1 k, 2 v; H = n_heads of each). Returns
    (B, T, H, Dh), what pallas_flash_attention(qkv[:, 0], qkv[:, 1],
    qkv[:, 2]) returns bit for bit, and its VJP one d(qkv) of qkv's shape.

    Only for calls the tiled kernels read in place (qkv_heads_in_place > 0: a
    lone causal block a head, T a multiple of 512, heads of 64 or of a
    multiple of 128); a caller asks that first and slices otherwise. The
    kernels are pallas_flash_attention's own: an operand's block is a column
    block of plane c of the one array, and the backward's one output block
    holds dq, dk and dv, so no slice of qkv and no stacking of three
    gradients stands beside the calls.
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    b, planes, t, lanes = qkv.shape
    if planes != 3 or lanes % n_heads:
        raise ValueError(f"qkv must be (B, 3, T, H*Dh) with H = {n_heads}, got {qkv.shape}")
    heads = qkv_heads_in_place(t, lanes // n_heads, n_heads, block_q, block_kv)
    if not heads:
        raise ValueError(
            f"no tiled kernel reads {n_heads} heads of {lanes // n_heads} at T = {t} in place: "
            "slice qkv and call pallas_flash_attention"
        )
    of = _flash_one(qkv, n_heads, block_q, block_kv, interpret, heads)
    return of.reshape(b, t, n_heads, lanes // n_heads)
