"""Pallas fused cross-entropy head: hidden @ W -> per-token loss, no logits.

STATUS: EXPERIMENT, not a product path (VERDICT r4 weak #4). Interpret-mode
correct and fully tested, but on a v5e under an earlier installation
(2026-07/08) this kernel class hung the chip three times across two remat
configs (multi-hour backend wedges — the Mosaic-level cause is not isolated; the grid/accumulator
pattern matches the proven flash kernels, so the trigger is suspected in
the V-innermost revisiting schedule's DMA pattern at 50304-wide vocab
tiles), and everywhere it DID complete it measured slower than the
chunked/dense XLA heads (29.9-31.5% vs 40+% MFU at 124M — the CE-scatter
fix moved the bottleneck out of the head entirely). It stays out of
chip_smoke.py and of every benchmark cell until a bounded reproduction
exists. The product CE heads are models.transformer's chunked and
dense implementations.

The CE head is the single largest matmul in GPT-2-class models (~24% of
step FLOPs at 124M: D=768 x V=50304) and the naive form is HBM-bound — the
(S, V) fp32 logits round-trip to HBM between the matmul, the logsumexp and
the backward. The chunked head (models.transformer._chunked_ce) bounds the
materialization to 1/n_chunks; this kernel eliminates it:

  - forward: grid (S tiles x V tiles), V innermost. Each step computes one
    logits tile `h_tile @ W_tile` in VMEM (bf16 MXU matmul, fp32
    accumulation) and folds it into running (max, sumexp) stats plus the
    label's logit — FlashAttention-style online softmax over the vocab dim.
    Per-token loss = lse - label_logit. Nothing of size V ever leaves VMEM.
  - backward: two kernels (same split as the flash dQ/dKV pair, and for the
    same reason — each gradient accumulates over a DIFFERENT grid dim, and
    scratch accumulators are only safe across the innermost one). Both
    recompute their logits tiles from (hidden, W), form
    p~ = g * (softmax - onehot), and contract: dH = p~ @ W^T (vocab dim
    inner), dW = H^T @ p~ (token dim inner).
  - custom VJP residuals: (hidden, W, labels, lse) — O(S + D*V), no logits.

Reference cost being removed: the reference computes full (B*T, V) logits
and hands them to F.cross_entropy (/root/reference/src/models/transformer.py:73-77).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Same halve-until-divides tiling rule as the flash kernels — one source.
from pretraining_llm_tpu.ops.flash_attention import _pick_block as _pick


def _tiles(s: int, v: int, block_s: int, block_v: int):
    bs = _pick(s, block_s, 256)
    v_pad = -(-v // 128) * 128
    bv = _pick(v_pad, block_v, 1024)
    return bs, bv, v_pad, s // bs, v_pad // bv


def _logits_tile(h, w, j, bv, v):
    """(bs, bv) fp32 logits tile with the padded vocab tail masked."""
    logits = jax.lax.dot_general(
        h, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    v_pos = j * bv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    return jnp.where(v_pos < v, logits, NEG_INF), v_pos


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    h_ref, w_ref, label_ref, loss_ref, lse_ref, m_ref, l_ref, gold_ref, *, bv, nv, v
):
    j = pl.program_id(1)
    logits, v_pos = _logits_tile(h_ref[...], w_ref[...], j, bv, v)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        gold_ref[...] = jnp.zeros_like(gold_ref)

    m_prev = m_ref[...]  # (bs, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(
        jnp.exp(logits - m_new), axis=1, keepdims=True
    )
    m_ref[...] = m_new
    hit = (v_pos == label_ref[...]).astype(jnp.float32)  # one-hot in-tile
    gold_ref[...] = gold_ref[...] + jnp.sum(logits * hit, axis=1, keepdims=True)

    @pl.when(j == nv - 1)
    def _finish():
        lse = m_ref[...] + jnp.log(l_ref[...])
        lse_ref[...] = lse
        loss_ref[...] = lse - gold_ref[...]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _scaled_p(h_ref, w_ref, label_ref, lse_ref, g_ref, j, bv, v):
    """p~ = g * (softmax - onehot) for one tile, fp32 (bs, bv)."""
    logits, v_pos = _logits_tile(h_ref[...], w_ref[...], j, bv, v)
    p = jnp.exp(logits - lse_ref[...])
    p = p - (v_pos == label_ref[...]).astype(jnp.float32)
    return p * g_ref[...]


def _bwd_dh_kernel(
    h_ref, w_ref, label_ref, lse_ref, g_ref, dh_ref, acc_ref, *, bv, nv, v
):
    """grid (S, V), V inner: dH tile accumulates across the vocab tiles."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p16 = _scaled_p(h_ref, w_ref, label_ref, lse_ref, g_ref, j, bv, v).astype(
        w_ref.dtype
    )
    # This contraction runs OVER the vocab tile — zero W's padded tail
    # columns explicitly (p is 0 there, but 0 * uninitialized can be NaN).
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, w_ref.shape, 1)
    w = jnp.where(col < v, w_ref[...], jnp.zeros_like(w_ref))
    acc_ref[...] += jax.lax.dot_general(
        p16, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(j == nv - 1)
    def _finish():
        dh_ref[...] = acc_ref[...]


def _bwd_dw_kernel(
    h_ref, w_ref, label_ref, lse_ref, g_ref, dw_ref, acc_ref, *, bv, ns, v
):
    """grid (V, S), S inner: dW tile accumulates across the token tiles."""
    j = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p16 = _scaled_p(h_ref, w_ref, label_ref, lse_ref, g_ref, j, bv, v).astype(
        h_ref.dtype
    )
    acc_ref[...] += jax.lax.dot_general(
        h_ref[...], p16, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(i == ns - 1)
    def _finish():
        dw_ref[...] = acc_ref[...]


# ---------------------------------------------------------------------------
# custom VJP plumbing
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ce(h, w, labels, block_s, block_v, interpret):
    loss, _ = _ce_fwd(h, w, labels, block_s, block_v, interpret)
    return loss


def _ce_fwd(h, w, labels, block_s, block_v, interpret):
    s, d = h.shape
    v = w.shape[1]
    bs, bv, v_pad, ns, nv = _tiles(s, v, block_s, block_v)
    labels2 = labels.astype(jnp.int32).reshape(s, 1)

    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bv=bv, nv=nv, v=v),
        grid=(ns, nv),
        in_specs=[
            pl.BlockSpec((bs, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            pl.BlockSpec((bs, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bs, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bs, 1), lambda i, j: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bs, 1), jnp.float32),
            pltpu.VMEM((bs, 1), jnp.float32),
            pltpu.VMEM((bs, 1), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(h, w, labels2)
    return loss[:, 0], (h, w, labels2, lse)


def _ce_bwd(block_s, block_v, interpret, residuals, g):
    h, w, labels2, lse = residuals
    g2 = g.reshape(-1, 1).astype(jnp.float32)
    s, d = h.shape
    v = w.shape[1]
    bs, bv, v_pad, ns, nv = _tiles(s, v, block_s, block_v)

    in_specs_sv = [
        pl.BlockSpec((bs, d), lambda i, j: (i, 0)),
        pl.BlockSpec((d, bv), lambda i, j: (0, j)),
        pl.BlockSpec((bs, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((bs, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((bs, 1), lambda i, j: (i, 0)),
    ]
    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, bv=bv, nv=nv, v=v),
        grid=(ns, nv),
        in_specs=in_specs_sv,
        out_specs=pl.BlockSpec((bs, d), lambda i, j: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bs, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((s, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(h, w, labels2, lse, g2)

    in_specs_vs = [
        pl.BlockSpec((bs, d), lambda j, i: (i, 0)),
        pl.BlockSpec((d, bv), lambda j, i: (0, j)),
        pl.BlockSpec((bs, 1), lambda j, i: (i, 0)),
        pl.BlockSpec((bs, 1), lambda j, i: (i, 0)),
        pl.BlockSpec((bs, 1), lambda j, i: (i, 0)),
    ]
    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, bv=bv, ns=ns, v=v),
        grid=(nv, ns),
        in_specs=in_specs_vs,
        out_specs=pl.BlockSpec((d, bv), lambda j, i: (0, j)),
        scratch_shapes=[pltpu.VMEM((d, bv), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((d, v_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(h, w, labels2, lse, g2)
    return dh.astype(h.dtype), dw[:, :v].astype(w.dtype), None


_ce.defvjp(_ce_fwd, _ce_bwd)


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------


def fused_cross_entropy(
    hidden: jax.Array,
    w: jax.Array,
    labels: jax.Array,
    *,
    bias: Optional[jax.Array] = None,
    block_s: int = 0,
    block_v: int = 0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Per-token CE loss of a tied/untied LM head without materializing logits.

    hidden: (S, D); w: (D, V); labels: (S,) int. Returns (S,) fp32 losses
    (= lse - label_logit). ``bias`` is unsupported (the kernel targets the
    framework's default biasless/tied head; the chunked-CE fallback handles
    bias) — passing one raises.

    `interpret=None` auto-selects: compiled on TPU, interpreter elsewhere
    (slow — tests only).
    """
    if bias is not None:
        raise ValueError("fused CE kernel does not support an lm_head bias")
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return _ce(hidden, w, labels, block_s, block_v, interpret)
