"""Pallas TPU paged-attention decode kernels (gather-free block tables).

The XLA paged-decode path (models/transformer.py, the gather form) attends
over ``pool[tables]``: every slot the block table names, ``max_blocks x
block_size`` of them a row, whatever the row holds and whether the row is in
use, and masks. On a v5e XLA keeps the gathered copy in VMEM while it fits
(one HBM pass over every tabled slot) and writes it to HBM and reads it back
when it does not (three): what costs is the slots, and past some table width
the copies too (PERF.md section 6, PR 30). The kernels here read pool pages
through the block table instead, and only pages that hold a visible slot --
vLLM's PagedAttention memory model (SURVEY section 2.2).

Two forms behind one entry point, ``paged_decode_attention``:

**One query a row** (the serving decode step), ``_decode_kernel``, after
``ops/pallas_latent.py``:

  - Grid ``(rows,)``; both pools stay in HBM (``memory_space=ANY``), block
    table and ``seq_lens`` are scalar prefetch. Inside a row a ``fori_loop``
    runs over its LIVE groups of ``PAGES_PER_STEP`` pages: each group's pages
    are async copies into one of two VMEM buffers while the previous group is
    computed, and a row's last step starts the next row's first group. A dead
    group is never entered, and inside a group a page that holds no visible
    slot is neither copied nor computed: a row costs its live pages, an idle
    row (tables on the scratch block, ``seq`` 0) one page. A group whose pages
    are all live is computed as one block, a partly live one page by page.
  - A page of either pool is one contiguous copy of ``block_size x G x Dh``
    elements. GQA stays native and nothing is strided: (slot, kv head) is ONE
    flat key axis of ``block_size * G`` rows a page, all H query heads are
    scored against it, and the ``G - 1`` foreign kv heads of a query head are
    masked together with the length mask. The tile loads are those a per-head
    slice would need, the MXU has room at H rows, and ``p . V`` over the same
    flat axis is then right as it stands.
  - Online softmax and the accumulator are float32 loop carries; the output is
    written once a row. ``window=`` keeps its meaning: pages wholly below the
    window are not copied.

**Several queries a row** (the speculative verify; ``q`` of 4 dims; also one
query a row over a pool whose pages are no copy of their own, see
``pages_copy_in_place``), ``_paged_kernel``: grid (batch, max_blocks), one
page a grid step through BlockSpec index maps, accumulator and softmax
statistics in VMEM scratch across the block steps (the revisiting schedule of
ops/pallas_flash.py). Dead
table entries are 0 = the scratch block: consecutive identical block indices
elide their DMA and ``pl.when`` skips their compute. One page a grid step is
12-16 k grid steps a decode step at serving sizes, which is why the
single-query form does not take this shape.

Both: mask, finite NEG_INF and safe division are the same (slot ``seq + t``
holds query t's own token and is visible; a row with no visible slot gives
zeros); any block of the pool holds finite values. Forward only (decode never
differentiates).

The model takes the single-query form by itself on a TPU
(``models/transformer.py::paged_attention_form``); ``cfg.paged_attention_impl
== "kernel"`` forces the Pallas forms everywhere (interpreted off the TPU).
int8 pools go through ``ops/pallas_ragged.py``, which fuses the dequant.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite: exp/max edge cases (same constant as pallas_flash)

# Pages a step of the single-query form's in-row loop copies and, where all of
# them are live, computes as one block: 4 x 128 KB a pool at the serving cells'
# widths (64 slots x 8 kv heads x 128, bf16). Timed on the chip at both cells'
# row lengths at 2, 4, 8 and 16 (PERF.md section 6, PR 30).
PAGES_PER_STEP = 4
_GROUP_BYTES = 8 << 20  # VMEM for the two pools' two page groups; wider pages, fewer a step
_FOREIGN = 1 << 30  # the "slot" of another kv head's key: past every length


def pages_copy_in_place(kv_heads: int, head_dim: int) -> bool:
    """Whether the single-query form can take a (n_blocks, block, kv_heads,
    head_dim) pool as it lies: a page is a copy of its own only where the head
    width fills whole 128-lane tiles (Mosaic refuses narrower slices), and the
    flat (block * kv_heads, head_dim) view is the same bytes only where the kv
    heads fill or divide the TPU's 8-sublane tile (XLA copies the whole pool
    into another layout otherwise). At-size compiles for a described v5e,
    PERF.md section 6, PR 30."""
    return head_dim % 128 == 0 and (kv_heads % 8 == 0 or 8 % kv_heads == 0)


def _pages_a_step(pool: jax.Array, wanted: int) -> int:
    """``wanted`` pages a step, or as many as keep two groups of both pools
    inside ``_GROUP_BYTES`` of VMEM."""
    page_bytes = math.prod(pool.shape[1:]) * pool.dtype.itemsize
    return max(1, min(wanted, _GROUP_BYTES // (4 * page_bytes)))


def _decode_kernel(
    tbl_ref,  # (B, nb) int32 scalar prefetch (SMEM)
    seq_ref,  # (B,) int32 scalar prefetch (SMEM)
    q_ref,  # (1, H, Dh)
    k_ref,  # (n_blocks, bs * G, Dh), left in HBM: row s * G + g is slot s of kv head g
    v_ref,  # (n_blocks, bs * G, Dh), left in HBM
    o_ref,  # (1, H, Dh)
    kbuf,  # VMEM (2, P, bs * G, Dh): two page groups of keys
    vbuf,  # VMEM (2, P, bs * G, Dh): and of values
    sem,  # DMA semaphores (2,), one a buffer
    slot_ref,  # SMEM (1,) int32: the buffer this row's first group is in
    *,
    bs: int,
    g: int,
    pages: int,
    scale: float,
    window: int,
):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    nb = tbl_ref.shape[1]
    h, d = q_ref.shape[1], q_ref.shape[2]
    n_rep = h // g
    keys = bs * g  # rows of the flat key axis a page

    def live_pages(row):
        """(first page holding a visible slot, how many do): slots
        (seq - window, seq], or 0..seq without a window; a row at or past its
        capacity wrote its token to the scratch block and sees its last slot."""
        last = jnp.minimum(seq_ref[row], nb * bs - 1)
        if not window:
            return 0, last // bs + 1
        first = jnp.clip(seq_ref[row] - window + 1, 0, last) // bs
        return first, last // bs - first + 1

    def each_live_copy(row, group, slot, act):
        first, n = live_pages(row)
        for i in range(pages):

            @pl.when(group * pages + i < n)
            def _page():
                page = tbl_ref[row, first + group * pages + i]
                act(pltpu.make_async_copy(k_ref.at[page], kbuf.at[slot, i], sem.at[slot]))
                act(pltpu.make_async_copy(v_ref.at[page], vbuf.at[slot, i], sem.at[slot]))

    start = lambda cp: cp.start()
    wait = lambda cp: cp.wait()

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        # what a partly live group leaves of the buffer is multiplied by zero
        # weights: it has to be finite from the first step on
        vbuf[...] = jnp.zeros_like(vbuf)
        each_live_copy(0, 0, 0, start)

    slot0 = slot_ref[0]
    seq = seq_ref[b]
    first, n = live_pages(b)
    n_groups = (n + pages - 1) // pages  # at least one
    q = q_ref[0]

    def slots_of_cols(width):
        """Column j of ``width`` pages side by side: slot (j // G) of kv head
        j % G, pages ``bs`` slots apart; a query head sees its own kv head."""
        row = jax.lax.broadcasted_iota(jnp.int32, (h, width * keys), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (h, width * keys), 1)
        return jnp.where(col % g == row // n_rep, col // g, _FOREIGN)

    def attend(carry, k, v, upto, slot_of_col):
        """One online-softmax step over the keys ``k`` (columns, Dh), whose
        column j is visible while ``slot_of_col[j] <= upto``."""
        m_prev, l_prev, acc = carry
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (H, columns)
        valid = slot_of_col <= upto
        if window:
            valid = jnp.logical_and(valid, slot_of_col > upto - window)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a fully masked row keeps m == NEG_INF and exp(s - m) == 1: zero by the mask
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc * alpha + pv

    of_a_page, of_a_group = slots_of_cols(1), slots_of_cols(pages)

    def group_body(gi, carry):
        slot = (slot0 + gi) % 2

        @pl.when(gi + 1 < n_groups)
        def _next_group():
            each_live_copy(b, gi + 1, 1 - slot, start)

        @pl.when(jnp.logical_and(gi + 1 == n_groups, b + 1 < n_rows))
        def _next_row():
            each_live_copy(b + 1, 0, 1 - slot, start)

        each_live_copy(b, gi, slot, wait)
        here = jnp.minimum(n - gi * pages, pages)  # live pages of this group
        upto = seq - (first + gi * pages) * bs  # the last visible slot, counted from the group's first

        def whole_group(carry):
            # every page live: one block of straight-line code, its matmul
            # tiles in flight together (a loop over pages costs a fifth more)
            k = kbuf[slot].reshape(pages * keys, d)
            v = vbuf[slot].reshape(pages * keys, d)
            return attend(carry, k, v, upto, of_a_group)

        def live_pages_only(carry):
            page = lambda i, carry: attend(carry, kbuf[slot, i], vbuf[slot, i], upto - i * bs, of_a_page)
            return jax.lax.fori_loop(0, here, page, carry)

        return jax.lax.cond(here == pages, whole_group, live_pages_only, carry)

    init = (
        jnp.full((h, 1), NEG_INF, jnp.float32),
        jnp.zeros((h, 1), jnp.float32),
        jnp.zeros((h, d), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(0, n_groups, group_body, init)
    slot_ref[0] = (slot0 + n_groups) % 2
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "pages", "interpret"))
def _decode_call(q, k_pool, v_pool, block_tables, seq_lens, window, pages, interpret):
    b, h, d = q.shape
    n_blocks, bs, g, _ = k_pool.shape
    kernel = functools.partial(
        _decode_kernel, bs=bs, g=g, pages=pages, scale=1.0 / (d**0.5), window=window
    )
    row_block = pl.BlockSpec((1, h, d), lambda bb, tbl, seq: (bb, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[row_block, pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row_block,
        scratch_shapes=[
            pltpu.VMEM((2, pages, bs * g, d), k_pool.dtype),
            pltpu.VMEM((2, pages, bs * g, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    # (slot, kv head) as one key axis: the same bytes in the same order
    flat = lambda pool: pool.reshape(n_blocks, bs * g, d)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        # rows run in order: a row's last step starts the next row's first copy
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), q, flat(k_pool), flat(v_pool))


def _paged_kernel(
    tbl_ref,  # (B, nb) int32 scalar-prefetch (SMEM)
    seq_ref,  # (B,) int32 scalar-prefetch (SMEM)
    q_ref,  # (1, H*T, Dh) — heads-major fold, query t at row h*T + t
    k_ref,  # (1, bs, G, Dh) — the page tbl[b, j]
    v_ref,  # (1, bs, G, Dh)
    o_ref,  # (1, H*T, Dh)
    acc,  # VMEM (H*T, Dh) f32
    m_scr,  # VMEM (H*T, 1) f32
    l_scr,  # VMEM (H*T, 1) f32
    *,
    bs: int,
    nb: int,
    g: int,
    n_rep: int,
    t: int,
    scale: float,
    window: int,
):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    seq = seq_ref[b]
    # Block liveness: any linear slot in [j*bs, j*bs+bs) visible to any
    # of the T queries — query t's frontier is seq + t (slot seq + t
    # holds its just-written token: inclusive, exactly the gather path's
    # per-query mask). Sliding window kills blocks entirely below the
    # OLDEST query's window.
    run = j * bs <= seq + (t - 1)
    if window:
        run = jnp.logical_and(run, j * bs + bs - 1 > seq - window)

    @pl.when(run)
    def _compute():
        rows = n_rep * t
        # Per-row frontier: row r within a group is query (r % t) of head
        # (r // t) — the heads-major fold keeps each GQA group's rows
        # contiguous so the static slice below works, at the price of
        # this tiny modulo iota.
        t_of_row = jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0) % t
        lin = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        valid = lin <= seq + t_of_row  # (n_rep*T, bs)
        if window:
            valid = jnp.logical_and(valid, lin > seq + t_of_row - window)
        q = q_ref[0]  # (H*T, Dh)
        k = k_ref[0]  # (bs, G, Dh)
        v = v_ref[0]
        for grp in range(g):
            sl = slice(grp * rows, (grp + 1) * rows)
            qg = q[sl]  # (n_rep*T, Dh)
            kg = k[:, grp]  # (bs, Dh)
            vg = v[:, grp]
            s = jax.lax.dot_general(
                qg, kg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (n_rep*T, bs)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[sl]  # (n_rep*T, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            # A fully-masked row keeps m == NEG_INF -> exp(s-m)=1 for
            # masked entries; zero by the mask itself (flash kernel
            # discipline).
            p = jnp.where(valid, p, 0.0)
            l_scr[sl] = l_scr[sl] * alpha + jnp.sum(
                p, axis=-1, keepdims=True
            )
            m_scr[sl] = m_new
            pv = jax.lax.dot_general(
                p.astype(vg.dtype), vg, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc[sl] = acc[sl] * alpha + pv

    @pl.when(j == nb - 1)
    def _finalize():
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / safe_l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("t", "window", "interpret"))
def _paged_call(q, k_pool, v_pool, block_tables, seq_lens, t, window,
                interpret):
    b, ht, d = q.shape  # ht == H * T, heads-major fold
    n_blocks, bs, g, _ = k_pool.shape
    nb = block_tables.shape[1]
    n_rep = ht // (g * t)
    kernel = functools.partial(
        _paged_kernel, bs=bs, nb=nb, g=g, n_rep=n_rep, t=t,
        scale=1.0 / (d**0.5), window=window,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, ht, d), lambda bb, j, tbl, seq: (bb, 0, 0)),
            pl.BlockSpec(
                (1, bs, g, d),
                lambda bb, j, tbl, seq: (tbl[bb, j], 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, bs, g, d),
                lambda bb, j, tbl, seq: (tbl[bb, j], 0, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, ht, d), lambda bb, j, tbl, seq: (bb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((ht, d), jnp.float32),
            pltpu.VMEM((ht, 1), jnp.float32),
            pltpu.VMEM((ht, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, ht, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q, k_pool, v_pool)


def paged_decode_attention(
    q: jax.Array,  # (B, H, Dh) or (B, T, H, Dh) — T queries per row
    k_pool: jax.Array,  # (n_blocks, block_size, G, Dh)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32, 0-padded tails
    seq_lens: jax.Array,  # (B,) int32 — slot seq_len + t holds query t's K/V
    *,
    window: int = 0,
    pages_per_step: int = PAGES_PER_STEP,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Paged decode attention straight off the block pool.

    (B, H, Dh) is the serving decode step (one query per row): each row's
    live pages are copied from the pools in place, ``pages_per_step`` a step
    of an in-row loop (where ``pages_copy_in_place``; the form below at one
    query otherwise). A 4-dim (B, T, H, Dh) q is the multi-token form (the
    speculative verify), one page a grid step: query t sits at logical slot
    seq + t and sees slots <= seq + t — exactly the gather path's per-query
    frontier masks. Returns q's shape. Numerics match the gather path to
    accumulation-order tolerance; what is saved is every slot of the table
    that holds nothing visible. `interpret=None` auto-selects: compiled on
    TPU, interpreter elsewhere (tests).
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    multi = q.ndim == 4
    if multi:
        b, t, h, d = q.shape
        # Heads-major fold (H*T rows, query t of head h at row h*T + t):
        # keeps each GQA group's rows CONTIGUOUS so the kernel's static
        # group slices work; the transpose is B*T*H*D elements (tiny at
        # decode shapes).
        qf = q.transpose(0, 2, 1, 3).reshape(b, h * t, d)
    else:
        b, h, d = q.shape
        t = 1
        qf = q
    g = k_pool.shape[2]
    if h % g != 0:
        raise ValueError(f"kv heads ({g}) must divide query heads ({h})")
    if k_pool.shape != v_pool.shape:
        raise ValueError(f"k/v pool mismatch: {k_pool.shape} vs {v_pool.shape}")
    if block_tables.shape[0] != b or seq_lens.shape != (b,):
        raise ValueError(
            f"tables {block_tables.shape} / seq_lens {seq_lens.shape} do not "
            f"match batch {b}"
        )
    if not multi and pages_copy_in_place(g, d):
        return _decode_call(
            q, k_pool, v_pool, block_tables, seq_lens, int(window),
            _pages_a_step(k_pool, int(pages_per_step)), bool(interpret),
        )
    out = _paged_call(
        qf, k_pool, v_pool, block_tables, seq_lens, t, int(window),
        bool(interpret),
    )
    if multi:
        return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    return out
