"""Flash attention: blockwise online-softmax, O(T) memory.

Eliminates the reference's fully-materialized (B, H, T, T) score tensor
(`/root/reference/src/models/attention.py:51-57`) — the exact memory wall that
caps its context at 512. Two tiers:

  - `blockwise_attention` (this module, always available): FlashAttention-2
    schedule expressed in pure JAX — `lax.scan` over KV blocks with running
    (max, sum) renormalization, `jax.checkpoint` on the inner step so autodiff
    recomputes score blocks instead of storing them. XLA maps the per-block
    einsums onto the MXU; this is the correctness baseline and the fallback on
    CPU.
  - `ops.pallas_flash` (TPU): the hand-tiled Pallas kernel with fused masking
    and VMEM-resident blocks, selected automatically on TPU backends.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp


def _pick_block(t: int, requested: int, default: int) -> int:
    if requested > 0:
        block = requested
    else:
        block = default
    block = min(block, t)
    while t % block != 0:  # shapes in this framework are powers of two; be safe
        block //= 2
        if block == 0:
            return t
    return max(block, 1)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 0,
    block_kv: int = 0,
    q_offset: Any = 0,
    k_offset: int = 0,
    segments: Any = None,
    window: int = 0,
) -> jax.Array:
    """Online-softmax attention. q: (B, Tq, H, Dh), k/v: (B, Tk, G, Dh)
    with G | H -> (B, Tq, H, Dh). Tq and Tk may differ.

    GQA-NATIVE: each group of H/G query heads attends its shared KV head
    through grouped einsums — K/V are never expanded to H heads (the
    cache-bandwidth win GQA exists for). G == H reduces to plain MHA.

    ``q_offset`` (python int or traced scalar) places the query block at
    positions [q_offset, q_offset+Tq) against keys at [0, Tk) for the
    causal mask — the rectangular form chunked prefill needs (each chunk
    attends the already-written cache prefix; keys above the frontier are
    causally excluded, so no explicit length mask is required).

    ``segments`` (B, T) int32 document ids (self-attention only, Tq == Tk):
    queries attend only keys of their own document — packed-sequence
    training without cross-document attention.

    ``window`` > 0: sliding-window attention (each query sees the last
    `window` positions only). ``k_offset`` places the KEYS at positions
    [k_offset, k_offset+Tk) — chunked windowed prefill passes a trimmed
    cache view whose below-window prefix was sliced off.
    """
    b, tq_len, h, dh = q.shape
    tk_len, g = k.shape[1], k.shape[2]
    r = h // g  # query heads per KV group
    bq = _pick_block(tq_len, block_q, 512)
    bk = _pick_block(tk_len, block_kv, 512)
    nq, nk = tq_len // bq, tk_len // bk
    scale = 1.0 / (dh**0.5)

    qb = q.reshape(b, nq, bq, g, r, dh)
    kb = k.reshape(b, nk, bk, g, dh)
    vb = v.reshape(b, nk, bk, g, dh)
    has_seg = segments is not None
    if has_seg:
        if tq_len != tk_len:
            raise ValueError("segments requires self-attention (Tq == Tk)")
        seg32 = segments.astype(jnp.int32)
        sqb = seg32.reshape(b, nq, bq)
        skb = seg32.reshape(b, nk, bk)

    q_ids = jnp.arange(bq)
    k_ids = jnp.arange(bk)

    @jax.checkpoint
    def kv_step(carry, inputs):
        o, m, l, qi, q_block, sq_block = carry
        kj, k_block, v_block, sk_block = inputs
        s = (
            jnp.einsum(
                "bqgrd,bkgd->bgrqk", q_block, k_block,
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # (B, G, R, bq, bk) fp32
        if causal or window:
            q_pos = q_offset + qi * bq + q_ids  # (bq,)
            k_pos = k_offset + kj * bk + k_ids  # (bk,)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
        if window:
            w_ok = (q_pos[:, None] - k_pos[None, :]) < window
            s = jnp.where(w_ok[None, None, None], s, -jnp.inf)
        if has_seg:
            # True -inf: the existing isfinite() guards zero p/alpha for
            # fully cross-document blocks.
            seg_ok = sq_block[:, :, None] == sk_block[:, None, :]  # (B,bq,bk)
            s = jnp.where(seg_ok[:, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))  # (B, G, R, bq)
        # exp(-inf - -inf) guard: rows of a fully-masked block keep m = -inf
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.exp(m - m_new)
        alpha = jnp.where(jnp.isfinite(m) | jnp.isfinite(m_new), alpha, 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bgrqk,bkgd->bqgrd", p.astype(v_block.dtype), v_block,
            preferred_element_type=jnp.float32,
        )
        o = o * alpha.transpose(0, 3, 1, 2)[..., None] + pv
        return (o, m_new, l, qi, q_block, sq_block), None

    def q_block_fn(qi, q_block, sq_block):
        o0 = jnp.zeros((b, bq, g, r, dh), jnp.float32)
        m0 = jnp.full((b, g, r, bq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, g, r, bq), jnp.float32)
        sk_scan = skb.swapaxes(0, 1) if has_seg else jnp.zeros((nk, b, 1), jnp.int32)
        (o, m, l, _, _, _), _ = jax.lax.scan(
            kv_step, (o0, m0, l0, qi, q_block, sq_block),
            (jnp.arange(nk), kb.swapaxes(0, 1), vb.swapaxes(0, 1), sk_scan)
        )
        return o / l.transpose(0, 3, 1, 2)[..., None]

    sq_map = sqb.swapaxes(0, 1) if has_seg else jnp.zeros((nq, b, 1), jnp.int32)
    out = jax.lax.map(
        lambda args: q_block_fn(*args), (jnp.arange(nq), qb.swapaxes(0, 1), sq_map)
    )
    # out: (nq, B, bq, G, R, Dh) -> (B, Tq, H, Dh)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, tq_len, h, dh).astype(q.dtype)


def _pallas_available() -> bool:
    return jax.default_backend() == "tpu"


_BATCH_AXES = ("data", "fsdp")


def _tensor_shards(mesh, batch: int, h: int, g: int, batch_axes) -> int:
    """Over how many 'tensor' shards an attention call's heads split when the
    call (`batch` rows, h query and g KV heads) is expressible per shard of
    `mesh`; 0 when it is not (head counts not divisible by the tensor axis,
    an indivisible batch; seq/pipe-sharded activations belong to the
    ring/ulysses/pipeline paths)."""
    if any(mesh.shape.get(ax, 1) > 1 for ax in ("seq", "pipe")):
        return 0
    batch_shards = 1
    for ax in batch_axes:
        batch_shards *= mesh.shape.get(ax, 1)
    if batch % batch_shards != 0:
        return 0  # small/partial batch: let the caller's fallback handle it
    tp = mesh.shape.get("tensor", 1)
    if tp > 1 and (h % tp != 0 or g % tp != 0):
        return 0
    return tp


def shard_mapped_kernel(kernel, q, k, v, mesh, *, batch_axes=_BATCH_AXES,
                        segments=None):
    """Run an attention kernel per-shard under a batch/head-sharded mesh.

    GSPMD cannot partition a pallas_call — traced directly on sharded
    operands it would REPLICATE the kernel, all-gathering the global batch
    onto every device. This wraps it in a shard_map over the batch axes
    (+ 'tensor' on the head dim when the head counts divide).

    Returns None when the layout isn't expressible per-shard
    (_tensor_shards) — caller falls back.
    """
    from jax.sharding import PartitionSpec as P

    tp = _tensor_shards(mesh, q.shape[0], q.shape[2], k.shape[2], batch_axes)
    if not tp:
        return None
    spec = P(batch_axes, None, "tensor" if tp > 1 else None, None)
    if segments is not None:
        seg_spec = P(batch_axes, None)
        return jax.shard_map(
            lambda q_, k_, v_, s_: kernel(q_, k_, v_, segments=s_),
            mesh=mesh, in_specs=(spec, spec, spec, seg_spec), out_specs=spec,
            check_vma=False,
        )(q, k, v, segments)
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def _kernel_reach(mesh) -> str:
    """How a Pallas attention kernel is reached under the active mesh:
    'direct' (no mesh, or every nontrivial axis manual), 'shard_map' (no
    manual axis) or 'partial' (a partial-manual region: not at all).

    Manual-region classification (ADVICE r2): the direct kernel
    call is only correct when EVERY nontrivial mesh axis is manual
    (ulysses' all-to-all body — operands are per-device local
    arrays). In a PARTIAL-manual region (the pipeline: manual over
    'pipe' only) activations are still auto-sharded over
    data/fsdp, so a direct pallas_call would be replicated by
    GSPMD, all-gathering the global batch — and a nested shard_map
    over the auto axes is not expressible either; the blockwise
    fallback serves there (GSPMD partitions plain JAX ops)."""
    if mesh is None or all(s == 1 for s in mesh.shape.values()):
        return "direct"
    abstract_mesh = jax.sharding.get_abstract_mesh()
    manual_axes = {
        name
        for name, kind in zip(
            abstract_mesh.axis_names, abstract_mesh.axis_types
        )
        if kind == jax.sharding.AxisType.Manual
    }
    nontrivial = {name for name, size in mesh.shape.items() if size > 1}
    if nontrivial <= manual_axes:
        return "direct"  # fully manual region
    return "partial" if manual_axes else "shard_map"


def _qkv_shards(shape, n_heads: int, block_q: int, block_kv: int):
    """How flash_attention_qkv runs the tiled Pallas kernels on a fused
    projection's result of `shape` (B, 3, T, H*Dh): (None, 1) for a direct
    call, (mesh, tensor shards) for one a shard_map wraps; None where it has
    no kernel for the call and the caller slices q, k and v out for
    flash_attention."""
    from pretraining_llm_tpu.ops.pallas_flash import qkv_heads_in_place
    from pretraining_llm_tpu.parallel.sharding import current_mesh

    if not _pallas_available() or len(shape) != 4 or shape[1] != 3 or shape[3] % n_heads:
        return None
    if not qkv_heads_in_place(shape[2], shape[3] // n_heads, n_heads, block_q, block_kv):
        return None
    mesh = current_mesh()
    reach = _kernel_reach(mesh)
    if reach == "direct":
        return None, 1
    tp = _tensor_shards(mesh, shape[0], n_heads, n_heads, _BATCH_AXES) if reach == "shard_map" else 0
    return (mesh, tp) if tp else None


def flash_takes_qkv(shape, n_heads: int, *, block_q: int = 0, block_kv: int = 0) -> bool:
    """Whether flash_attention_qkv takes a fused QKV projection's result of
    `shape` (B, 3, T, H*Dh) as it is: on a TPU, a plain causal call the tiled
    kernels read in place (pallas_flash.qkv_heads_in_place), under a mesh
    case in which flash_attention reaches the Pallas kernel. Read from the
    call and the mesh; nothing selects it."""
    return _qkv_shards(shape, n_heads, block_q, block_kv) is not None


def flash_attention_qkv(
    qkv: jax.Array, n_heads: int, *, block_q: int = 0, block_kv: int = 0
) -> jax.Array:
    """Plain causal flash attention over the q, k and v that are the planes of
    one array, (B, 3, T, H*Dh) -> (B, T, H, Dh): flash_attention of the three
    slices, without the slices and with one d(qkv) on the way back. For calls
    flash_takes_qkv says yes to; the mesh cases are flash_attention's."""
    from jax.sharding import PartitionSpec as P

    from pretraining_llm_tpu.ops.pallas_flash import pallas_flash_attention_qkv

    shards = _qkv_shards(qkv.shape, n_heads, block_q, block_kv)
    if shards is None:
        raise ValueError(f"no Pallas kernel takes q, k and v from one array of {qkv.shape} here")
    mesh, tp = shards
    kernel = functools.partial(
        pallas_flash_attention_qkv, n_heads=n_heads // tp, block_q=block_q, block_kv=block_kv
    )
    if mesh is None:
        return kernel(qkv)
    # o crosses the shard_map's edge with its heads merged, (B, T, H*Dh), and
    # is split into heads outside: with (B, T, 25, 64) at the edge the compiler
    # relaid the recomputed o T minor-most for attn.out, a copy a layer a step
    # and a slower dot (1.7% of the four-chip cell's step on the chip, PR 55).
    b, _, t, lanes = qkv.shape
    head_ax = "tensor" if tp > 1 else None
    merged = jax.shard_map(
        lambda x: kernel(x).reshape(x.shape[0], t, -1), mesh=mesh,
        in_specs=(P(_BATCH_AXES, None, None, head_ax),),
        out_specs=P(_BATCH_AXES, None, head_ax), check_vma=False,
    )(qkv)
    return merged.reshape(b, t, n_heads, lanes // n_heads)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 0,
    block_kv: int = 0,
    segments: Any = None,
    window: int = 0,
) -> jax.Array:
    """Memory-efficient attention; Pallas kernel on TPU, blockwise JAX elsewhere.

    q: (B, T, H, D); k, v: (B, T, G, D) with G | H. The Pallas kernel handles
    GQA natively (query groups index shared KV blocks); the blockwise
    fallback is GQA-native too (grouped einsums, K/V never expanded).

    ``segments`` (B, T) int32 document ids: packed-sequence training —
    attention (and its VJP) never crosses a document boundary. Threaded
    into whichever tier serves the call.
    """
    if q.shape[2] % k.shape[2] != 0:
        # Same fail-fast the Pallas path gives; without it the CPU fallback
        # dies in an unrelated reshape.
        raise ValueError(
            f"kv heads ({k.shape[2]}) must divide query heads ({q.shape[2]})"
        )

    if _pallas_available():
        from pretraining_llm_tpu.ops.pallas_flash import pallas_flash_attention
        from pretraining_llm_tpu.parallel.sharding import current_mesh

        kernel = functools.partial(
            pallas_flash_attention, causal=causal, block_q=block_q,
            block_kv=block_kv, window=window,
        )
        mesh = current_mesh()
        reach = _kernel_reach(mesh)
        if reach == "direct":
            return kernel(q, k, v, segments=segments)
        if reach == "shard_map":
            out = shard_mapped_kernel(kernel, q, k, v, mesh, segments=segments)
            if out is not None:
                return out
        # Partial-manual region, or unexpressible per-shard layout
        # (seq/pipe-sharded activations, indivisible batch or heads):
        # blockwise fallback below. Loud (VERDICT r2 #9) — the user
        # configured the Pallas kernel and is getting the slower JAX
        # path; fires once per trace (warnings dedupe).
        import warnings

        why = (
            "inside a partial-manual shard_map region (e.g. the "
            "pipeline's pipe-only region)"
            if reach == "partial"
            else "the mesh/shape layout is not expressible per-shard "
            "(seq/pipe-sharded activations, or batch/head counts not "
            "divisible by the mesh axes)"
        )
        warnings.warn(
            f"flash attention falling back to blockwise JAX (no Pallas "
            f"kernel): {why}.",
            stacklevel=2,
        )
    # blockwise_attention is GQA-native (grouped einsums) — no K/V expansion.
    return blockwise_attention(
        q, k, v, causal=causal,
        block_q=block_q, block_kv=block_kv, segments=segments, window=window,
    )
