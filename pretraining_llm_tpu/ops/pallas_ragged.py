"""Ragged paged-attention Pallas kernel: heterogeneous query counts per row.

`ops/pallas_paged.py` serves a batch where every row carries the SAME
number of query tokens (1 at decode, k+1 at the speculative verify).
Chunked prefill breaks that symmetry: one launch now mixes decode rows
(q_len 1..spec_depth) with prefill-chunk rows (q_len up to
`serving.prefill_chunk_tokens`), each row's queries starting at its own
committed offset `seq_lens[b]`. This kernel is the uniform kernel
generalized by ONE extra scalar-prefetch operand, `q_lens (B,)`:

  - Block liveness becomes per-row: page j is fetched/computed only when
    ``j*bs <= seq + (q_len - 1)`` — a decode row (q_len 1) stops at its
    frontier page while a chunk row in the same launch scans up to its
    chunk end. Dead table entries stay 0 (the reserved scratch block), so
    consecutive identical indices elide their DMA exactly as in
    pallas_paged.py.
  - The causal mask gains a query-validity term: query t of row b is
    real only when ``t < q_lens[b]``; pad queries (the static T bound
    minus the row's true count) are fully masked and finalize to zeros
    via the safe-l division — they cost VPU lanes, never HBM traffic
    beyond the row's live pages.
  - Online-softmax f32 accumulators in VMEM and the GQA-native shared
    K/V blocks are inherited unchanged (heads-major fold keeps each
    group's rows contiguous for the static group slices).

Two speed layers sit on top of the correctness core (both off by
default, both pinned against `ragged_gather_attention`):

  - **KV-split work partitioning** (``kv_splits > 1``, FA2 /
    flash-decoding style): a third grid dimension splits each row's page
    range into ``kv_splits`` partitions walked by parallel grid lanes.
    Each partition runs the same online softmax into its own VMEM
    scratch and flushes *unnormalized* partials — (acc, m, l) — to HBM;
    a small XLA combine then merges partitions with the standard
    log-sum-exp weights ``w_p = exp(m_p - max_p m_p)`` and finalizes.
    One 8k-context row no longer serializes a whole launch while decode
    rows idle. ``kv_splits=None`` auto-tunes the partition count from
    (max_pages, B) — enough lanes to fill the core grid, never slicing
    below ~2 pages per partition.
  - **AMLA rescaling** (``amla=True``): the online softmax runs in base
    2 with an *integer-quantized* running max (``m = ceil(max(s·log2e))``),
    so the per-page correction ``alpha = 2^(m_prev - m_new)`` has an
    integer exponent and the acc/l rescale becomes an ADD to the f32
    exponent field (bitcast + integer add, guarded against underflow and
    zero) instead of a vector multiply — MUL-by-ADD. On int8 pools the
    dequant scales are absorbed into the same restructure: K's scale
    multiplies the (rows, bs) score columns after the dot and V's scale
    multiplies the probability columns before the PV dot, so the
    quantized path stops paying a (bs, Dh) elementwise dequant multiply
    per page.

`ragged_gather_attention` below is the XLA fallback: the same
pool-gather + per-query masked softmax the model's gather branch runs,
extended with the q_len validity mask. CPU tier-1 tests pin the kernel
against it (interpret mode) across the split/AMLA grid, and
chunked-vs-monolithic bit-identity on CPU rides the model's gather
branch, which ignores q_lens entirely — pad-query outputs are computed
and discarded there, so real-query numerics are untouched by
construction.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite: exp/max edge cases (same constant as pallas_paged)
LOG2E = 1.4426950408889634  # log2(e): converts nat-domain scores to base 2


def _exp2_mul_add(x: jax.Array, k: jax.Array) -> jax.Array:
    """``x * 2^k`` for integer ``k <= 0`` as an exponent-field ADD.

    The AMLA trick: because the running max is integer-quantized, the
    online-softmax correction is a power of two, and multiplying an f32
    by 2^k is an integer add of ``k << 23`` to its bit pattern — one VPU
    integer add per element instead of a float multiply. Guards:
    ``exp_field == 0`` (zeros/subnormals stay zero) and
    ``exp_field + k <= 0`` (underflow flushes to zero instead of
    borrowing into the sign bit). ``k`` must already be clamped to
    ``[-126, 0]``.
    """
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    exp_field = jnp.bitwise_and(jnp.right_shift(bits, 23), 0xFF)
    shifted = bits + jnp.left_shift(k, 23)
    ok = jnp.logical_and(exp_field > 0, exp_field + k > 0)
    return jnp.where(
        ok, jax.lax.bitcast_convert_type(shifted, jnp.float32), 0.0
    )


def _attend_page(
    j,  # dynamic page index within the row's table
    seq,
    qlen,
    q_ref,
    k_ref,
    v_ref,
    ks_ref,
    vs_ref,
    acc,
    m_scr,
    l_scr,
    *,
    bs: int,
    g: int,
    n_rep: int,
    t: int,
    scale: float,
    window: int,
    quantized: bool,
    amla: bool,
):
    """One page's online-softmax update, shared by both kernels.

    Classic form: nat-domain scores, float-multiply rescale, elementwise
    int8 dequant of the K/V page. AMLA form: base-2 scores with an
    integer-quantized running max, exponent-add rescale, and the int8
    scales absorbed as column multiplies on the score/probability
    matrices (never touching the (bs, Dh) page elementwise).
    """
    rows = n_rep * t
    # Row r within a group is query (r % t) of head (r // t); the
    # heads-major fold keeps each GQA group's rows contiguous so the
    # static slice below works.
    t_of_row = jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0) % t
    lin = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
    # Causal frontier per query PLUS query validity: queries at or
    # past the row's true count are padding (fully masked; finalize
    # zeros them via safe_l).
    valid = jnp.logical_and(lin <= seq + t_of_row, t_of_row < qlen)
    if window:
        valid = jnp.logical_and(valid, lin > seq + t_of_row - window)
    q = q_ref[0]  # (H*T, Dh)
    k = k_ref[0]  # (bs, G, Dh)
    v = v_ref[0]
    if quantized:
        ks = ks_ref[0]  # (bs, G, 1)
        vs = vs_ref[0]
    for grp in range(g):
        sl = slice(grp * rows, (grp + 1) * rows)
        qg = q[sl]  # (n_rep*T, Dh)
        kg = k[:, grp]  # (bs, Dh)
        vg = v[:, grp]
        if quantized and not amla:
            # Fused page dequant — the transformer._kv_dequantize
            # numerics (int8 * fp32-upcast scale / 127), done HERE so
            # only int8 bytes + scale pages cross HBM. The s/pv dots
            # below then run in f32 either way (bf16 accumulation
            # semantics are preserved by preferred_element_type=f32).
            kg = kg.astype(jnp.float32) * (
                ks[:, grp].astype(jnp.float32) * (1.0 / 127.0)
            )
            vg = vg.astype(jnp.float32) * (
                vs[:, grp].astype(jnp.float32) * (1.0 / 127.0)
            )
        if amla:
            s = jax.lax.dot_general(
                qg, kg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * (scale * LOG2E)  # (n_rep*T, bs), base-2 domain
            if quantized:
                # Absorbed K dequant: one (1, bs) column multiply on the
                # score matrix replaces the (bs, Dh) elementwise page
                # dequant (dot-then-scale == scale-then-dot).
                s = s * (
                    ks[:, grp].astype(jnp.float32).reshape(1, bs)
                    * (1.0 / 127.0)
                )
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[sl]  # (n_rep*T, 1)
            # Integer-quantized running max: ceil makes m_prev - m_new an
            # integer <= 0, so alpha = 2^delta is a pure exponent add.
            m_new = jnp.maximum(
                m_prev, jnp.ceil(jnp.max(s, axis=-1, keepdims=True))
            )
            delta = jnp.clip(m_prev - m_new, -126.0, 0.0).astype(jnp.int32)
            p = jnp.exp2(s - m_new)
            p = jnp.where(valid, p, 0.0)
            l_scr[sl] = _exp2_mul_add(l_scr[sl], delta) + jnp.sum(
                p, axis=-1, keepdims=True
            )
            m_scr[sl] = m_new
            if quantized:
                # Absorbed V dequant: scale the probability columns
                # ((rows, bs)) instead of the V page ((bs, Dh)).
                pv_p = p * (
                    vs[:, grp].astype(jnp.float32).reshape(1, bs)
                    * (1.0 / 127.0)
                )
                vg = vg.astype(jnp.float32)
            else:
                pv_p = p
            pv = jax.lax.dot_general(
                pv_p.astype(vg.dtype), vg, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc[sl] = _exp2_mul_add(acc[sl], delta) + pv
        else:
            s = jax.lax.dot_general(
                qg, kg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (n_rep*T, bs)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[sl]  # (n_rep*T, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            # Fully-masked rows keep m == NEG_INF -> exp(s-m)=1 on masked
            # entries; zeroed by the mask itself (flash kernel discipline).
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


def _ragged_kernel(
    tbl_ref,  # (B, nb) int32 scalar-prefetch (SMEM)
    seq_ref,  # (B,) int32 scalar-prefetch (SMEM)
    qlen_ref,  # (B,) int32 scalar-prefetch (SMEM) — true queries per row
    q_ref,  # (1, H*T, Dh) — heads-major fold, query t at row h*T + t
    k_ref,  # (1, bs, G, Dh) — the page tbl[b, j]
    v_ref,  # (1, bs, G, Dh)
    *rest,  # quantized: ks_ref, vs_ref (1, bs, G, 1) scale pages, then
    #         o_ref + the three VMEM scratch refs; exact: o_ref + scratch
    bs: int,
    nb: int,
    g: int,
    n_rep: int,
    t: int,
    scale: float,
    window: int,
    quantized: bool = False,
    amla: bool = False,
):
    if quantized:
        ks_ref, vs_ref, o_ref, acc, m_scr, l_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc, m_scr, l_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    seq = seq_ref[b]
    qlen = qlen_ref[b]
    # Per-row block liveness: the LAST real query of this row sits at
    # slot seq + qlen - 1; pages past it are dead for this row even when
    # another row in the launch reaches further (the uniform kernel's
    # static (t-1) bound made every row pay the longest row's scan).
    # qlen == 0 rows (pure padding) run no block at all.
    run = j * bs <= seq + (qlen - 1)
    if window:
        run = jnp.logical_and(run, j * bs + bs - 1 > seq - window)

    @pl.when(run)
    def _compute():
        _attend_page(
            j, seq, qlen, q_ref, k_ref, v_ref, ks_ref, vs_ref,
            acc, m_scr, l_scr, bs=bs, g=g, n_rep=n_rep, t=t, scale=scale,
            window=window, quantized=quantized, amla=amla,
        )

    @pl.when(j == nb - 1)
    def _finalize():
        l = l_scr[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / safe_l).astype(o_ref.dtype)


def _ragged_split_kernel(
    tbl_ref,
    seq_ref,
    qlen_ref,
    q_ref,
    k_ref,
    v_ref,
    *rest,  # quantized: ks_ref, vs_ref, then acc/m/l partial outputs +
    #         the three VMEM scratch refs; exact: partials + scratch
    bs: int,
    nb: int,
    nb_split: int,  # pages per partition (ceil(nb / kv_splits))
    g: int,
    n_rep: int,
    t: int,
    scale: float,
    window: int,
    quantized: bool = False,
    amla: bool = False,
):
    """KV-split variant: grid (B, kv_splits, nb_split); partition p of
    row b walks pages [p*nb_split, (p+1)*nb_split) ∩ [0, nb) and flushes
    UNNORMALIZED partials (acc, m, l) for the XLA log-sum-exp combine in
    `_ragged_call`. Same page math as `_ragged_kernel` via
    `_attend_page`."""
    if quantized:
        ks_ref, vs_ref, oa_ref, om_ref, ol_ref, acc, m_scr, l_scr = rest
    else:
        ks_ref = vs_ref = None
        oa_ref, om_ref, ol_ref, acc, m_scr, l_scr = rest
    b = pl.program_id(0)
    part = pl.program_id(1)
    jj = pl.program_id(2)
    j = part * nb_split + jj

    @pl.when(jj == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    seq = seq_ref[b]
    qlen = qlen_ref[b]
    # Per-row liveness as in the single-pass kernel, PLUS the partition
    # bound: the last partition's tail blocks past nb are dead (their
    # index map clamps to the last table entry, so the repeated index
    # elides the DMA).
    run = jnp.logical_and(j < nb, j * bs <= seq + (qlen - 1))
    if window:
        run = jnp.logical_and(run, j * bs + bs - 1 > seq - window)

    @pl.when(run)
    def _compute():
        _attend_page(
            j, seq, qlen, q_ref, k_ref, v_ref, ks_ref, vs_ref,
            acc, m_scr, l_scr, bs=bs, g=g, n_rep=n_rep, t=t, scale=scale,
            window=window, quantized=quantized, amla=amla,
        )

    @pl.when(jj == nb_split - 1)
    def _flush():
        # Partials, not normalized output: empty partitions flush
        # (acc=0, m=NEG_INF, l=0) and drop out of the combine naturally.
        oa_ref[0, 0] = acc[:]
        om_ref[0, 0] = m_scr[:]
        ol_ref[0, 0] = l_scr[:]


def _auto_kv_splits(nb: int, b: int) -> int:
    """Partition-count heuristic (TPU ragged-paged-attention style).

    The (B, splits) product is the parallel grid surface; target ~8
    lanes (fills a TPU core's sequencer comfortably without shredding
    page locality), never slice a row below 2 pages per partition, and
    a batch that already fills the grid gets no splits at all.
    """
    target = max(1, 8 // max(b, 1))
    p = 1
    while p * 2 <= target and nb // (p * 2) >= 2:
        p *= 2
    return p


@functools.partial(
    jax.jit,
    static_argnames=("t", "window", "interpret", "kv_splits", "amla"),
)
def _ragged_call(q, k_pool, v_pool, block_tables, seq_lens, q_lens, t,
                 window, interpret, kv_splits=1, amla=False,
                 k_scale=None, v_scale=None):
    b, ht, d = q.shape  # ht == H * T, heads-major fold
    n_blocks, bs, g, _ = k_pool.shape
    nb = block_tables.shape[1]
    n_rep = ht // (g * t)
    quantized = k_scale is not None
    tables = block_tables.astype(jnp.int32)
    prefetch = (tables, seq_lens.astype(jnp.int32), q_lens.astype(jnp.int32))
    operands = [q, k_pool, v_pool]
    if quantized:
        operands += [k_scale, v_scale]
    scratch = [
        pltpu.VMEM((ht, d), jnp.float32),
        pltpu.VMEM((ht, 1), jnp.float32),
        pltpu.VMEM((ht, 1), jnp.float32),
    ]

    def _params(dims):
        # dimension_semantics lets Mosaic parallelize the batch/partition
        # dims.
        return pltpu.CompilerParams(dimension_semantics=dims)

    if kv_splits <= 1:
        kernel = functools.partial(
            _ragged_kernel, bs=bs, nb=nb, g=g, n_rep=n_rep, t=t,
            scale=1.0 / (d**0.5), window=window, quantized=quantized,
            amla=amla,
        )
        page_spec = pl.BlockSpec(
            (1, bs, g, d),
            lambda bb, j, tbl, seq, ql: (tbl[bb, j], 0, 0, 0),
        )
        in_specs = [
            pl.BlockSpec(
                (1, ht, d), lambda bb, j, tbl, seq, ql: (bb, 0, 0)
            ),
            page_spec,
            page_spec,
        ]
        if quantized:
            # Scale pages ride the SAME block-table index map as their
            # K/V pages — a dead table entry elides all four DMAs
            # together.
            scale_spec = pl.BlockSpec(
                (1, bs, g, 1),
                lambda bb, j, tbl, seq, ql: (tbl[bb, j], 0, 0, 0),
            )
            in_specs += [scale_spec, scale_spec]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, ht, d), lambda bb, j, tbl, seq, ql: (bb, 0, 0)
            ),
            scratch_shapes=scratch,
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, ht, d), q.dtype),
            compiler_params=_params(("parallel", "arbitrary")),
            interpret=interpret,
        )(*prefetch, *operands)

    # --- KV-split path: partials per partition + XLA combine ---------
    splits = kv_splits
    nb_split = -(-nb // splits)  # ceil: last partition may run short
    kernel = functools.partial(
        _ragged_split_kernel, bs=bs, nb=nb, nb_split=nb_split, g=g,
        n_rep=n_rep, t=t, scale=1.0 / (d**0.5), window=window,
        quantized=quantized, amla=amla,
    )

    def _page_idx(bb, part, jj, tbl, seq, ql):
        # Clamp the tail of the last partition back to a real table
        # entry: repeated indices elide the DMA, and liveness (j < nb)
        # keeps the compute off.
        j = part * nb_split + jj
        return (tbl[bb, jnp.minimum(j, nb - 1)], 0, 0, 0)

    page_spec = pl.BlockSpec((1, bs, g, d), _page_idx)
    in_specs = [
        pl.BlockSpec(
            (1, ht, d), lambda bb, part, jj, tbl, seq, ql: (bb, 0, 0)
        ),
        page_spec,
        page_spec,
    ]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, bs, g, 1), _page_idx),
            pl.BlockSpec((1, bs, g, 1), _page_idx),
        ]
    part_map = lambda bb, part, jj, tbl, seq, ql: (bb, part, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, splits, nb_split),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, ht, d), part_map),
            pl.BlockSpec((1, 1, ht, 1), part_map),
            pl.BlockSpec((1, 1, ht, 1), part_map),
        ],
        scratch_shapes=scratch,
    )
    acc_p, m_p, l_p = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, splits, ht, d), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, ht, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, ht, 1), jnp.float32),
        ],
        compiler_params=_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, *operands)
    # Log-sum-exp combine across partitions. Empty partitions carry
    # (acc=0, m=NEG_INF, l=0): against a live sibling their weight
    # underflows to 0; an all-empty row keeps w=1 but l_tot=0, so the
    # safe-l division returns the pad-query zeros contract.
    m_tot = jnp.max(m_p, axis=1, keepdims=True)  # (b, 1, ht, 1)
    w = jnp.exp2(m_p - m_tot) if amla else jnp.exp(m_p - m_tot)
    l_tot = jnp.sum(l_p * w, axis=1)  # (b, ht, 1)
    acc_tot = jnp.sum(acc_p * w, axis=1)  # (b, ht, d)
    safe_l = jnp.where(l_tot == 0.0, 1.0, l_tot)
    return (acc_tot / safe_l).astype(q.dtype)


def ragged_paged_attention(
    q: jax.Array,  # (B, T, H, Dh) — T is the batch's MAX query count
    k_pool: jax.Array,  # (n_blocks, block_size, G, Dh)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32, 0-padded tails
    seq_lens: jax.Array,  # (B,) int32 — row b's committed offset
    q_lens: jax.Array,  # (B,) int32 — row b's TRUE query count, <= T
    *,
    window: int = 0,
    interpret: Optional[bool] = None,
    k_scale: Optional[jax.Array] = None,  # (n_blocks, block_size, G, 1)
    v_scale: Optional[jax.Array] = None,
    kv_splits: Optional[int] = None,  # None = auto heuristic; 1 = off
    amla: bool = False,
) -> jax.Array:
    """Ragged paged attention straight off the block pool.

    ``k_scale``/``v_scale`` (both or neither) mark int8 pools: K/V pages
    hold int8 codes and the scale pools hold each (slot, head)'s amax
    scale (fp32 or bf16); the kernel dequantizes inside its page loop
    (transformer._kv_dequantize numerics, fp32 math), so quantized
    serving never materializes a dequantized pool copy.

    One launch serves rows with heterogeneous query counts: row b's
    query t sits at logical slot ``seq_lens[b] + t`` and sees slots
    ``<= seq_lens[b] + t`` (its own just-written K/V inclusive —
    identical to the gather path's per-query frontier), but only
    queries ``t < q_lens[b]`` are real; the rest are padding whose
    outputs come back as zeros and must be discarded by the caller.
    A decode row rides with q_len 1, a prefill chunk with its chunk
    length — the mixed batch costs each row only ITS OWN live pages
    (per-row DMA elision), not the longest row's scan.

    ``kv_splits`` partitions every row's page range across that many
    parallel grid lanes (FA2 work partitioning; partials merged by a
    log-sum-exp combine). ``None`` auto-tunes from (max_pages, B);
    ``1`` keeps the single-pass kernel. ``amla=True`` switches the
    online softmax to the exp2 MUL-by-ADD rescale (int8 scales absorbed
    into the same restructure). Both default to the single-pass classic
    form — bit-compatible with the pre-split kernel.

    Invariant (caller-enforced, unchecked under jit): 0 <= q_lens <= T
    and seq_lens + q_lens <= max_blocks * block_size. Returns q's
    shape. `interpret=None` auto-selects: compiled on TPU, interpreter
    elsewhere (tests).
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if q.ndim != 4:
        raise ValueError(
            f"ragged attention takes (B, T, H, Dh) queries, got {q.shape} "
            f"(single-token decode belongs to paged_decode_attention)"
        )
    b, t, h, d = q.shape
    # Heads-major fold (H*T rows, query t of head h at row h*T + t):
    # keeps each GQA group's rows CONTIGUOUS for the kernel's static
    # group slices — same fold as the uniform multi-token kernel.
    qf = q.transpose(0, 2, 1, 3).reshape(b, h * t, d)
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
    if q_lens.shape != (b,):
        raise ValueError(f"q_lens {q_lens.shape} does not match batch {b}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    if k_scale is not None:
        want = k_pool.shape[:-1] + (1,)
        if k_scale.shape != want or v_scale.shape != want:
            raise ValueError(
                f"scale pools must be {want}, got {k_scale.shape} / "
                f"{v_scale.shape}"
            )
    nb = block_tables.shape[1]
    if kv_splits is None:
        kv_splits = _auto_kv_splits(nb, b)
    kv_splits = int(kv_splits)
    if kv_splits < 1:
        raise ValueError(f"kv_splits must be >= 1 (or None for auto), "
                         f"got {kv_splits}")
    kv_splits = min(kv_splits, nb)
    out = _ragged_call(
        qf, k_pool, v_pool, block_tables, seq_lens, q_lens, t, int(window),
        bool(interpret), kv_splits=kv_splits, amla=bool(amla),
        k_scale=k_scale, v_scale=v_scale,
    )
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def ragged_gather_attention(
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
    """XLA gather fallback: materialize ``pool[tables]`` and run the
    per-query masked softmax — the model's gather branch math with the
    ragged validity term added. ONE source of truth for what the kernel
    must compute; tier-1 CPU tests pin the kernel (interpret mode)
    against this across the kv_splits × amla grid. Pad queries
    (t >= q_lens[b]) return zeros, matching the kernel's safe-l
    finalize. ``k_scale``/``v_scale`` mirror `ragged_paged_attention`:
    int8 pools dequantized after the gather."""
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
