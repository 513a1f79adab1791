"""Pallas TPU decode kernel over a latent (MLA) page pool: one read of the pool.

A decode step of latent attention is multi-query attention: H query heads
(``q_lat``, already moved into the latent space by ``mla.absorb``) over ONE
shared key and value a token, the cached latent ``c_kv`` (kv_lora_rank wide),
plus the one rotated key slice ``k_rope`` all heads share. The gather form
(``models/mla.py::_absorbed`` over ``pool[tables]``) reads the row's pages,
writes a gathered copy, and reads that copy for the scores and again for
``p . latents``. This kernel reads each live page of both pools straight
through the block table, once, and keeps scores, online softmax and the
weighted sum in VMEM; no gathered copy exists.

A row brings T queries (static; 1 for a decode step, ``k + 1`` for the verify
and the draft of a speculative round): query i stands at slot ``seq + i`` and
sees slots ``0 .. seq + i``. Their T * H query rows are one left operand of the
score matmuls and of ``p . latents``, all scored against each page group as it
is copied, so a row's latents cross HBM once for all of them; what differs
between a row's queries is a column of last visible slots in the mask.

Why not the shape of ``ops/pallas_paged.py`` (one 64-token page a grid step):
at 32 rows x 129 pages x 6 layers that is 24.8 k grid steps a decode step of
72 KB each, and the fixed cost of a grid step alone would eat the gain
(ISSUE 28). Here:

  - Grid ``(rows,)``; inside a row a ``fori_loop`` over its LIVE groups of
    ``PAGES_PER_STEP`` pages (``jax.experimental.pallas.ops.tpu.paged_attention``
    with ``inline_seq_dim``, the pattern). Dead groups cost nothing, not even a
    grid step.
  - Both pools stay in HBM (``memory_space=ANY``); block table and
    ``seq_lens`` are scalar prefetch. A group's pages are async copies on one
    semaphore into one of two VMEM buffers; group g + 1 (or the next row's
    first group) is in flight while group g is computed.
  - **Folded pages.** A page holds ``fold`` consecutive slots side by side in
    each of its rows (``models/mla.py::page_fold``: the latent pool is
    (n_blocks, block / fold, fold * c), the rope pool (n_blocks, block / fold,
    fold * r), the same bytes as (block, c) and (block, r) row-major). With
    fold * r a multiple of 128 lanes both pools keep the TPU's natural tiled
    layout, a page of either is one contiguous DMA, and the slots whose number
    is ``a`` modulo ``fold`` are a lane-aligned slice of the buffer. The rope
    scores of all ``fold`` classes come from one small matmul against a
    block-diagonal copy of the rope queries; scores, softmax and ``p . latents``
    then run class by class over the same running maximum and sum. The order of
    slots inside a class is the buffer's, the mask is computed for that order,
    and a softmax does not care.
  - Masking, the finite NEG_INF and the safe division are those of
    ``ops/pallas_paged.py``: slot ``seq + i`` (query i's own token, written
    this step) is visible to it, everything past it is not (a later query's
    token, or what a rejected draft left above the frontier), and a query with
    no visible slot gives zeros. The live groups are those up to the last
    query's slot. A dead page inside the last live group is copied like a live
    one (the table's dead tail names a real block, block 0 by convention) and
    masked; any block of the pool holds finite values.

Forward only (decode never differentiates).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite: exp/max edge cases (same constant as pallas_paged)

# Pages a step of the in-row loop copies and computes: 16 x 64 tokens x 576 x 2 B
# = 1.2 MB a buffer at the serving cell's widths (timed at 4, 8, 16 and 32 on
# the chip at one query a row, PERF.md section 6, PR 28; at 8, 16 and 32 at two
# queries, PR 36: 16 for every query count).
PAGES_PER_STEP = 16


def _latent_kernel(
    tbl_ref,  # (B, nbp) int32 scalar prefetch (SMEM), nbp a multiple of P
    seq_ref,  # (B,) int32 scalar prefetch (SMEM)
    q_ref,  # (1, T * H, C) queries in the latent space, query i's heads at rows i*H..i*H+H
    qr_ref,  # (1, fold * T * H, fold * R) rope queries, block-diagonal over the classes
    lat_ref,  # (n_blocks, rows, fold * C), left in HBM
    rope_ref,  # (n_blocks, rows, fold * R), left in HBM
    o_ref,  # (1, T * H, C)
    lbuf,  # VMEM (2, P, rows, fold * C): two page groups of latents
    rbuf,  # VMEM (2, P, rows, fold * R): and of rope slices
    sem,  # DMA semaphores (2,), one a buffer
    slot_ref,  # SMEM (1,) int32: the buffer this row's first group is in
    *,
    nb: int,
    pages: int,
    queries: int,
    scale: float,
):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    th, c = q_ref.shape[1], q_ref.shape[2]  # a row's T * H query rows
    rows, fold = lat_ref.shape[1], lat_ref.shape[2] // c
    bs = rows * fold
    span = pages * bs  # slots a group covers
    cols = pages * rows  # and of each class
    n_groups_max = tbl_ref.shape[1] // pages

    def copies(row, group, slot):
        out = []
        for i in range(pages):
            page = tbl_ref[row, group * pages + i]
            out.append(pltpu.make_async_copy(lat_ref.at[page], lbuf.at[slot, i], sem.at[slot]))
            out.append(pltpu.make_async_copy(rope_ref.at[page], rbuf.at[slot, i], sem.at[slot]))
        return out

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        for cp in copies(0, 0, 0):
            cp.start()

    slot0 = slot_ref[0]
    # groups holding a slot some query sees: slots 0..seq + T - 1, at least one, at most all
    live = jnp.clip((seq_ref[b] + (queries - 1 + span)) // span, 1, n_groups_max)
    # the last slot query i sees: seq + i (its own token), or the row's last
    # slot past the capacity (that token went to the scratch block, as in the
    # gather form); a scalar for one query a row, a column over the query rows
    own = seq_ref[b]
    if queries > 1:
        own = own + jax.lax.broadcasted_iota(jnp.int32, (th, 1), 0) // (th // queries)
    last = jnp.minimum(own, nb * bs - 1)
    q = q_ref[0]
    q_rope = qr_ref[0]
    # slot of column j of class 0, within a group: page j // rows, row j % rows
    col = jax.lax.broadcasted_iota(jnp.int32, (th, cols), 1)
    slot_of_col = (col // rows) * bs + (col % rows) * fold

    def body(g, carry):
        m_prev, l_prev, acc = carry
        slot = (slot0 + g) % 2

        @pl.when(g + 1 < live)
        def _next_group():
            for cp in copies(b, g + 1, 1 - slot):
                cp.start()

        @pl.when(jnp.logical_and(g + 1 == live, b + 1 < n_rows))
        def _next_row():
            for cp in copies(b + 1, 0, 1 - slot):
                cp.start()

        for cp in copies(b, g, slot):
            cp.wait()
        ropes = rbuf[slot].reshape(cols, rbuf.shape[-1])
        # (fold * T * H, cols): rows a*T*H..(a+1)*T*H are class a's rope scores
        s_rope = jax.lax.dot_general(
            q_rope, ropes, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        classes = []
        m_new = m_prev
        for a in range(fold):
            k = lbuf[slot, :, :, pl.ds(a * c, c)].reshape(cols, c)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )  # (T * H, cols)
            s = (s + s_rope[a * th : (a + 1) * th]) * scale
            valid = g * span + slot_of_col + a <= last
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
            classes.append((k, s, valid))
        alpha = jnp.exp(m_prev - m_new)
        l_new, acc = l_prev * alpha, acc * alpha
        for k, s, valid in classes:
            # a fully masked row keeps m == NEG_INF and exp(s - m) == 1: zero by the mask
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc + jax.lax.dot_general(
                p.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
        return m_new, l_new, acc

    init = (
        jnp.full((th, 1), NEG_INF, jnp.float32),
        jnp.zeros((th, 1), jnp.float32),
        jnp.zeros((th, c), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(0, live, body, init)
    slot_ref[0] = (slot0 + live) % 2
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "pages", "interpret"))
def _latent_call(q_lat, q_rope, latent_pool, rope_pool, tables, seq_lens, scale, pages, interpret):
    b, t, h, c = q_lat.shape
    r = q_rope.shape[-1]
    _, rows, fc = latent_pool.shape
    fold = fc // c
    nb = tables.shape[1]
    th = t * h
    # a row's T * H query rows are one left operand: query i's heads at rows i*H..i*H+H
    q_lat, q_rope = q_lat.reshape(b, th, c), q_rope.reshape(b, th, r)
    # a whole number of page groups; the new tail names the scratch block 0, as a dead tail does
    tables = jnp.pad(tables.astype(jnp.int32), ((0, 0), (0, -nb % pages)))
    # class a's queries against lanes a*r..a*r+r of a folded rope row, zeros elsewhere
    q_fold = jnp.einsum("bhr,ac->bahcr", q_rope, jnp.eye(fold, dtype=q_rope.dtype))
    q_fold = q_fold.reshape(b, fold * th, fold * r)
    kernel = functools.partial(_latent_kernel, nb=nb, pages=pages, queries=t, scale=scale)
    row_block = lambda shape: pl.BlockSpec((1,) + shape, lambda bb, tbl, seq: (bb, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            row_block((th, c)),
            row_block((fold * th, fold * r)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=row_block((th, c)),
        scratch_shapes=[
            pltpu.VMEM((2, pages, rows, fc), latent_pool.dtype),
            pltpu.VMEM((2, pages, rows, fold * r), rope_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, th, c), q_lat.dtype),
        # rows run in order: a row's last step starts the next row's first copy
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables, seq_lens.astype(jnp.int32), q_lat, q_fold, latent_pool, rope_pool)
    return out.reshape(b, t, h, c)


def latent_decode_attention(
    q_lat: jax.Array,  # (B, T, H, C) queries moved into the latent space, T a few (static)
    q_rope: jax.Array,  # (B, T, H, R) their rotated slices
    latent_pool: jax.Array,  # (n_blocks, block / fold, fold * C)
    rope_pool: jax.Array,  # (n_blocks, block / fold, fold * R)
    block_tables: jax.Array,  # (B, max_blocks) int32, 0-padded tails
    seq_lens: jax.Array,  # (B,) int32: slot seq_len + i holds query i's token
    *,
    scale: float,
    pages_per_step: int = PAGES_PER_STEP,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """softmax(([q_lat | q_rope] . [latents | ropes]) * scale) . latents, query
    i of a row over the row's slots 0..seq_len + i (every slot, and not its own
    token's, past the row's capacity), read from the two pools through the
    block table once for all T queries: (B, T, H, C) in ``q_lat``'s dtype. What
    lies past a query's last slot is never read into its result.
    ``interpret=None``: compiled on TPU, the interpreter elsewhere (tests)."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if q_lat.ndim != 4:
        raise ValueError(f"queries {q_lat.shape} are not (rows, queries a row, heads, latent width)")
    b, t, h, c = q_lat.shape
    r = q_rope.shape[-1]
    fold = latent_pool.shape[2] // c
    if (
        q_rope.shape != (b, t, h, r)
        or latent_pool.shape[2] != fold * c
        or rope_pool.shape != latent_pool.shape[:2] + (fold * r,)
        or not q_lat.dtype == q_rope.dtype == latent_pool.dtype == rope_pool.dtype
    ):
        raise ValueError(
            f"queries {q_lat.shape} {q_lat.dtype} and {q_rope.shape} {q_rope.dtype} do not match "
            f"the pools' latents {latent_pool.shape} {latent_pool.dtype} and ropes "
            f"{rope_pool.shape} {rope_pool.dtype} (pages of block / fold rows, fold slots a row)"
        )
    if block_tables.shape[0] != b or seq_lens.shape != (b,):
        raise ValueError(
            f"tables {block_tables.shape} / seq_lens {seq_lens.shape} do not match batch {b}"
        )
    return _latent_call(
        q_lat, q_rope, latent_pool, rope_pool, block_tables, seq_lens,
        float(scale), int(pages_per_step), bool(interpret),
    )
