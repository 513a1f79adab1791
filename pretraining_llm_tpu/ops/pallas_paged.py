"""Pallas TPU paged-attention decode kernel (gather-free block tables).

The XLA paged-decode path (models/transformer.py paged branch) assembles
each row's logical KV sequence with a `pool[tables]` gather before a
masked einsum — three full passes over the row's KV bytes per layer step
(read pool, write gathered copy, read it again in attention; measured on
the latent pool, where `ops/pallas_latent.py` now makes the one pass with
several pages a step: PERF.md section 6, PR 28). This kernel
reads the pool blocks DIRECTLY: the block table is a scalar-prefetch
operand, and the K/V BlockSpec index maps use it to DMA exactly the
row's pages into VMEM — vLLM's PagedAttention memory model expressed as
Pallas index maps instead of CUDA pointer chasing (SURVEY §2.2; the
reference has no serving/paged path at all,
/root/reference/src/models/transformer.py:96-114).

Design:
  - Grid (batch, max_blocks), block axis innermost; fp32 accumulator and
    online-softmax stats (m, l) live in VMEM scratch across block steps,
    the output block written once on the last step — the same revisiting
    schedule as ops/pallas_flash.py.
  - Dead table entries (beyond a row's pages) are 0 = the reserved
    scratch block: consecutive identical block indices elide their DMA
    in the Pallas pipeline, so a row's dead tail costs one block fetch,
    and its compute is skipped entirely via pl.when.
  - GQA native: a static Python loop over the G KV heads computes each
    group's (n_rep, block_size) score panel from the SHARED (bs, Dh) key
    block — no repeated K/V in HBM or VMEM, matching the flash kernel's
    index-division discipline.
  - Forward only: decode never differentiates, so there is no VJP and
    no saved stats output.

Used by the model when ``cfg.paged_attention_impl == "kernel"`` (int8
pools keep the gather path — quantized blocks need their scale pages
dequantized first, which the gather already fuses).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite: exp/max edge cases (same constant as pallas_flash)


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
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Paged decode attention straight off the block pool.

    (B, H, Dh) is the serving decode step (one query per row); a 4-dim
    (B, T, H, Dh) q is the multi-token form (the speculative verify):
    query t sits at logical slot seq + t and sees slots <= seq + t —
    exactly the gather path's per-query frontier masks. Returns q's
    shape. Numerics match the gather path to accumulation-order
    tolerance; the HBM win is structural — the row's KV bytes are read
    ONCE, no gathered copy is ever written. `interpret=None`
    auto-selects: compiled on TPU, interpreter elsewhere (tests).
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
    out = _paged_call(
        qf, k_pool, v_pool, block_tables, seq_lens, t, int(window),
        bool(interpret),
    )
    if multi:
        return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
    return out
