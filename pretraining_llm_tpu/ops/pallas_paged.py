"""Pallas TPU paged-attention decode kernel (gather-free block tables).

The XLA paged-decode path (models/transformer.py, the gather form) attends
over ``pool[tables]``: every slot the block table names, ``max_blocks x
block_size`` of them a row, whatever the row holds and whether the row is in
use, and masks. On a v5e XLA keeps the gathered copy in VMEM while it fits
(one HBM pass over every tabled slot) and writes it to HBM and reads it back
when it does not (three): what costs is the slots, and past some table width
the copies too (PERF.md section 6, PR 30). The kernel here reads pool pages
through the block table instead, and only pages that hold a visible slot --
vLLM's PagedAttention memory model (SURVEY section 2.2).

One form, ``paged_decode_attention``: one query a row (the serving decode
step) over a pool whose pages are copies of their own
(``pages_copy_in_place``; a pool of 30 heads is built with 32,
``pool_kv_heads``), ``_decode_kernel``, after ``ops/pallas_latent.py``:

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

Mask, finite NEG_INF and safe division are the gather form's (slot ``seq``
holds the query's own token and is visible; a row with no visible slot gives
zeros); any block of the pool holds finite values. Forward only (decode never
differentiates).

The model takes the kernel by itself where the input allows it
(``models/transformer.py::paged_attention_form``); several queries a row, int8
pools, narrow or odd heads and a serving mesh keep the gather form.
``gather_attention`` below is that form as a plain function: the reference
the kernel's tests and ``scripts/chip_kernels.py`` compare against.
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


def pool_kv_heads(kv_heads: int, head_dim: int) -> int:
    """The head axis an unquantized per-head pool is built with: ``kv_heads``,
    or the next multiple of 8 where more than 8 heads of whole 128-lane tiles
    neither fill nor divide the 8-sublane tile (30 -> 32: plain multi-head
    attention at an odd head count), so that its pages are copies of their own
    all the same (``pages_copy_in_place``). The heads past ``kv_heads`` are
    padding: written as zeros with every token, masked in the kernel with the
    foreign kv heads, cut off after the gather, never read as data. Up to 8
    heads and narrow heads keep their count (a pad would multiply the pool, or
    buy nothing: the kernel does not take them either way)."""
    if head_dim % 128 == 0 and kv_heads > 8 and kv_heads % 8:
        return -(-kv_heads // 8) * 8
    return kv_heads


def pad_kv_heads(x: jax.Array, heads: int) -> jax.Array:
    """(..., G, Dh) -> (..., heads, Dh): zeros in the pool's padding heads
    (``pool_kv_heads``); ``x`` itself where the pool has none."""
    g = x.shape[-2]
    return x if g == heads else jnp.pad(x, ((0, 0),) * (x.ndim - 2) + ((0, heads - g), (0, 0)))


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
    g: int,  # the pool's head axis: the real kv heads first, then its padding heads
    n_rep: int,  # query heads a real kv head
    pages: int,
    scale: float,
    window: int,
):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    nb = tbl_ref.shape[1]
    h, d = q_ref.shape[1], q_ref.shape[2]
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
        j % G, pages ``bs`` slots apart; a query head sees its own kv head (a
        padding head, past the last query head's, is no one's)."""
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


@functools.partial(jax.jit, static_argnames=("window", "pages", "interpret", "n_rep"))
def _decode_call(q, k_pool, v_pool, block_tables, seq_lens, window, pages, interpret, n_rep=None):
    b, h, d = q.shape
    n_blocks, bs, g, _ = k_pool.shape
    kernel = functools.partial(
        _decode_kernel, bs=bs, g=g, n_rep=n_rep or h // g, pages=pages, scale=1.0 / (d**0.5), window=window
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


def paged_decode_attention(
    q: jax.Array,  # (B, H, Dh): one query a row
    k_pool: jax.Array,  # (n_blocks, block_size, G, Dh)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32, 0-padded tails
    seq_lens: jax.Array,  # (B,) int32 — slot seq_len holds the query's own K/V
    *,
    window: int = 0,
    pages_per_step: int = PAGES_PER_STEP,
    interpret: Optional[bool] = None,
    kv_heads: Optional[int] = None,
) -> jax.Array:
    """Paged decode attention straight off the block pool.

    ``kv_heads``: the pool's real kv heads, the first of its head axis, where
    the rest is padding (``pool_kv_heads``); None = the whole axis.

    The serving decode step, one query a row: each row's live pages are
    copied from the pools in place, ``pages_per_step`` a step of an in-row
    loop. Returns q's shape. Numerics match the gather form
    (``gather_attention``) to accumulation-order tolerance; what is saved is
    every slot of the table that holds nothing visible. Several queries a
    row and a pool whose pages are no copy of their own
    (``pages_copy_in_place``) are the gather form's and raise here.
    `interpret=None` auto-selects: compiled on TPU, interpreter elsewhere
    (tests).
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if q.ndim != 3:
        raise ValueError(
            f"the kernel takes one query a row, q of (B, H, Dh), got {q.shape}: "
            "several queries a row take the gather form"
        )
    b, h, d = q.shape
    g = k_pool.shape[2]
    real = g if kv_heads is None else int(kv_heads)
    if h % real != 0 or real > g:
        raise ValueError(f"kv heads ({real} of the pool's {g}) must divide query heads ({h})")
    if k_pool.shape != v_pool.shape:
        raise ValueError(f"k/v pool mismatch: {k_pool.shape} vs {v_pool.shape}")
    if block_tables.shape[0] != b or seq_lens.shape != (b,):
        raise ValueError(
            f"tables {block_tables.shape} / seq_lens {seq_lens.shape} do not "
            f"match batch {b}"
        )
    if not pages_copy_in_place(g, d):
        raise ValueError(
            f"a page of {g} kv heads of {d} is no copy of its own (pages_copy_in_place: "
            "heads of whole 128-lane tiles, kv heads that fill or divide 8): "
            "such a pool takes the gather form"
        )
    return _decode_call(
        q, k_pool, v_pool, block_tables, seq_lens, int(window),
        _pages_a_step(k_pool, int(pages_per_step)), bool(interpret), h // real,
    )


def gather_attention(
    q: jax.Array,  # (B, T, H, Dh)
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    q_lens: jax.Array,
    *,
    window: int = 0,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """The reference of paged attention in plain XLA: materialize
    ``pool[tables]`` and run the per-query masked softmax in float32 -- the
    model's gather branch math with a validity term for per-row query counts.
    Query t of row b sits at slot ``seq_lens[b] + t`` and sees slots up to its
    own (inside ``window``, if any); pad queries (``t >= q_lens[b]``) return
    zeros, as a row with no visible slot does in the kernel.
    ``k_scale``/``v_scale`` are an int8 pool's scale pages: dequantized after
    the gather."""
    b, t, h, d = q.shape
    g = k_pool.shape[2]
    n_rep = h // g
    bs = k_pool.shape[1]
    kv_len = block_tables.shape[1] * bs
    ck = k_pool[block_tables].reshape(b, kv_len, g, d)
    cv = v_pool[block_tables].reshape(b, kv_len, g, d)
    if k_scale is not None:
        cks = k_scale[block_tables].reshape(b, kv_len, g, 1)
        cvs = v_scale[block_tables].reshape(b, kv_len, g, 1)
        ck = ck.astype(jnp.float32) * (
            cks.astype(jnp.float32) * (1.0 / 127.0)
        )
        cv = cv.astype(jnp.float32) * (
            cvs.astype(jnp.float32) * (1.0 / 127.0)
        )
    if n_rep > 1:
        ck = jnp.repeat(ck, n_rep, axis=2)
        cv = jnp.repeat(cv, n_rep, axis=2)
    lin = jnp.arange(kv_len)
    pos = seq_lens[:, None] + jnp.arange(t)[None, :]  # (B, T)
    mask = lin[None, None, :] <= pos[:, :, None]  # (B, T, kv_len)
    if window:
        mask = mask & (lin[None, None, :] > pos[:, :, None] - window)
    qvalid = jnp.arange(t)[None, :] < q_lens[:, None]  # (B, T)
    mask = mask & qvalid[:, :, None]
    s = jnp.einsum(
        "bthd,bkhd->bthk", q.astype(jnp.float32), ck.astype(jnp.float32)
    ) / (d**0.5)
    s = jnp.where(mask[:, :, None, :], s, NEG_INF)
    # Pad queries are fully masked: a plain softmax would spread 1/kv_len
    # everywhere; zero them like the kernel's safe-l division does.
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(mask[:, :, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bthk,bkhd->bthd", p, cv.astype(jnp.float32))
    return out.astype(q.dtype)
