"""Latent (MLA) attention: low-rank queries, one latent a token for all heads.

Per token and layer the cache holds ``cfg.latent_dim`` values: the
RMS-normalised compressed KV ``c_kv`` (kv_lora_rank) and the one rotated key
slice ``k_rope`` (qk_rope_head_dim) that all heads share. They are two fields
of the cache, ``latent`` and ``rope``, because of how the TPU lays arrays out:
one (n_blocks, block, 576) pool gets a device layout with the block index
minor-most (576 is no multiple of 128 lanes), and every decode step then
copies the whole pool into the scatter's layout and back, 0.74 ms each way
a layer at 4,097 blocks (PERF.md section 6, PR 27). In the page pool both
fields are **folded** (``page_fold``): ``fold`` consecutive slots lie side by
side in one row of a page, (n_blocks, block / fold, fold * 512) and
(n_blocks, block / fold, fold * 64), the same bytes as (block, 512) and
(block, 64) row-major, so that a rope row fills whole 128-lane tiles. Both
keep their natural layouts, a page of either is one contiguous piece of HBM
that a kernel can copy by itself, and the gather form's ``pool[tables]``
reshapes to (rows, slots, width) as before. Two forms of one function:

- **expanded** (training forward, prefill): keys and values of every head are
  expanded from the latent, ``[k_nope | v] = c_kv W_kvb``, and ordinary causal
  attention runs on ``[q_nope | q_rope] . [k_nope | k_rope]``;
- **absorbed** (decode against a cache or a page pool): ``W_kvb``'s key half
  moves into the query, ``q~ = q_nope W_kvb^K``, scores and the weighted sum
  run in the latent space against the cached latents, and the value half maps
  the result back, ``o = (softmax . c_kv) W_kvb^V``. Nothing per head is ever
  read from the cache. Over a page pool the middle of it has two forms and the
  input picks one (``decode_form``): a few queries a row on a TPU (the decode
  step's one, the ``k + 1`` of a speculative round's verify and draft) read the
  latent pool once for all of them, in place, through ``ops/pallas_latent.py``;
  anything else (a prefill chunk, every other backend) gathers ``pool[tables]``
  first and reads the copy twice (about five passes over the row's latents;
  PERF.md section 6, PR 28), which is also the tests' oracle.

The softmax scale is ``cfg.softmax_scale`` (YaRN's mscale squared included).
Scopes: the low-rank projections in ``attn.qkv``, ``mla.absorb`` around both
absorbed matmuls, ``attn.kv_write`` / ``attn.paged_gather`` / ``attn.core`` /
``attn.out`` as in the per-head path.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from pretraining_llm_tpu.config import ModelConfig
from pretraining_llm_tpu.models import layers

Params = Dict[str, Any]


def init_attn_params(cfg: ModelConfig, key: jax.Array, resid_std: float, dtype: Any) -> Params:
    d, h = cfg.d_model, cfg.n_heads
    r, c = cfg.q_lora_rank, cfg.kv_lora_rank
    ks = jax.random.split(key, 5)

    def normal(k, shape, s=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    attn: Params = {
        "wkv_a": normal(ks[2], (d, cfg.latent_dim)),
        "kv_norm": layers.init_norm("rmsnorm", c, dtype),
        # (c, H, nope + v): each head's key half, then its value half
        "wkv_b": normal(ks[3], (c, h, cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": normal(ks[4], (h, cfg.v_head_dim, d), resid_std),
    }
    if r:
        attn["wq_a"] = normal(ks[0], (d, r))
        attn["q_norm"] = layers.init_norm("rmsnorm", r, dtype)
        attn["wq_b"] = normal(ks[1], (r, h, cfg.head_dim))
    else:
        attn["wq"] = normal(ks[0], (d, h, cfg.head_dim))
    if cfg.attn_output_gate:
        attn["wgate"] = normal(jax.random.fold_in(key, 5), (d, h))
    return attn


_w = layers.weight


def _project(attn: Params, h: jax.Array, cfg: ModelConfig, cdt: Any):
    """(q (B,T,H,nope+rope), c_kv (B,T,c) normalised, k_r (B,T,rope)) before RoPE."""
    f32 = jnp.float32
    with jax.named_scope("attn.qkv"):
        hc = h.astype(cdt)
        if "wq_a" in attn:
            cq = jnp.einsum("btd,dr->btr", hc, _w(attn, "wq_a", cdt), preferred_element_type=f32)
            cq = layers.rmsnorm(attn["q_norm"], cq.astype(cdt), cfg.norm_eps)
            q = jnp.einsum("btr,rhn->bthn", cq, _w(attn, "wq_b", cdt), preferred_element_type=f32)
        else:
            q = jnp.einsum("btd,dhn->bthn", hc, _w(attn, "wq", cdt), preferred_element_type=f32)
        kv = jnp.einsum(
            "btd,dc->btc", hc, _w(attn, "wkv_a", cdt), preferred_element_type=f32
        ).astype(cdt)
        c_kv = layers.rmsnorm(attn["kv_norm"], kv[..., : cfg.kv_lora_rank], cfg.norm_eps)
        return q.astype(cdt), c_kv, kv[..., cfg.kv_lora_rank :]


def _expanded(attn: Params, q, c_kv, k_rope, cfg: ModelConfig, cdt: Any, impl: str) -> jax.Array:
    """Causal self-attention over this call's own tokens, keys and values
    expanded per head: (B, T, H, v_head_dim)."""
    from pretraining_llm_tpu.ops.attention import multihead_attention

    b, t, h, dh = q.shape
    nope, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    with jax.named_scope("attn.qkv"):
        kvx = jnp.einsum(
            "btc,chn->bthn", c_kv, _w(attn, "wkv_b", cdt), preferred_element_type=jnp.float32
        ).astype(cdt)
        k = jnp.concatenate(
            [kvx[..., :nope], jnp.broadcast_to(k_rope[:, :, None, :], (b, t, h, dh - nope))], axis=-1
        )
        v = kvx[..., nope:]
        # The attention ops scale by 1/sqrt(width) and want one width for q, k
        # and v: fold the model's scale into q and zero-pad to a common width
        # (a multiple of 128 lanes for the flash kernel); zero columns change
        # neither the scores nor the kept columns of the output.
        width = -(-max(dh, dv) // 128) * 128 if impl == "flash" else max(dh, dv)
        q = (q.astype(jnp.float32) * (cfg.softmax_scale * width ** 0.5)).astype(cdt)
        pad = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, width - a.shape[-1])))
        q, k, v = pad(q), pad(k), pad(v)
    with jax.named_scope("attn.core"):
        out = multihead_attention(
            q, k, v, impl=impl, block_q=cfg.flash_block_q, block_kv=cfg.flash_block_kv,
        )
    return out[..., :dv]


# The most queries a row that take the in-place kernel: their T * H query rows
# are one left operand of its matmuls and its softmax carry lives in VMEM whole
# (128 rows at 32 heads; compiled at that size in tests/test_pallas_latent.py).
KERNEL_QUERIES = 4


def decode_form(t: int, backend: Optional[str] = None) -> str:
    """The form attention over a latent page pool takes for ``t`` queries a
    row: ``"latent_kernel"`` (``ops/pallas_latent.py``: the pool read once for
    all of them, in place) for up to ``KERNEL_QUERIES`` where Mosaic compiles
    (the decode step's one, the ``spec_k + 1`` of a speculative round),
    ``"gather"`` (``pool[tables]``, then ``_absorbed``) for more (the chunk
    lane's hundreds) and for every other backend. Read from the input, never
    from an option; the engine reports the form of its decode program in
    ``pool_info()``."""
    backend = backend or jax.default_backend()
    return "latent_kernel" if t <= KERNEL_QUERIES and backend == "tpu" else "gather"


def _dot_dtype(cdt: Any) -> Any:
    # The CPU backend has no batched-bf16 DotThunk (see models/moe.py): the
    # absorbed dots are batched over heads or rows, so they run in float32 there.
    return jnp.float32 if jax.default_backend() == "cpu" else cdt


def _absorb(attn: Params, q, core, cfg: ModelConfig, cdt: Any) -> jax.Array:
    """``W_kvb``'s key half into the query, ``core(q_lat (B,T,H,c), q_rope
    (B,T,H,rope)) -> (B,T,H,c)`` in the latent space (under ``attn.core``),
    its value half out of the result: (B, T, H, v_head_dim)."""
    nope = cfg.qk_nope_head_dim
    f32 = jnp.float32
    ddt = _dot_dtype(cdt)
    wkv_b = _w(attn, "wkv_b", cdt).astype(ddt)
    q = q.astype(ddt)
    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum(
            "bthn,chn->bthc", q[..., :nope], wkv_b[..., :nope], preferred_element_type=f32
        ).astype(ddt)
    with jax.named_scope("attn.core"):
        o_lat = core(q_lat, q[..., nope:]).astype(ddt)
    with jax.named_scope("mla.absorb"):
        return jnp.einsum(
            "bthc,chn->bthn", o_lat, wkv_b[..., nope:], preferred_element_type=f32
        ).astype(cdt)


def _absorbed(attn: Params, q, latents, ropes, mask, cfg: ModelConfig, cdt: Any) -> jax.Array:
    """q (B,T,H,nope+rope) against cached ``c_kv`` (B,K,kv_lora_rank) and
    ``k_rope`` (B,K,rope) under ``mask`` (B,T,K): (B, T, H, v_head_dim)."""
    f32 = jnp.float32
    ddt = _dot_dtype(cdt)
    latents, ropes = latents.astype(ddt), ropes.astype(ddt)

    def core(q_lat, q_rope):
        s = jnp.einsum("bthc,bkc->bhtk", q_lat, latents, preferred_element_type=f32)
        s = s + jnp.einsum("bthr,bkr->bhtk", q_rope, ropes, preferred_element_type=f32)
        s = jnp.where(mask[:, None], s * cfg.softmax_scale, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        # a row with no visible slot (a dead left-pad query) gives zeros, not NaN
        p = jnp.where(jnp.any(mask, axis=-1)[:, None, :, None], p, 0.0)
        return jnp.einsum("bhtk,bkc->bthc", p.astype(ddt), latents, preferred_element_type=f32)

    return _absorb(attn, q, core, cfg, cdt)


def page_fold(block_size: int, rope_dim: int) -> int:
    """Slots that lie side by side in one row of a pool page: as many as make
    a rope row a whole number of 128-lane tiles (2 at a rope width of 64), or
    the largest power of two under that which divides the block (toy blocks)."""
    fold = 128 // math.gcd(128, rope_dim)
    while block_size % fold:
        fold //= 2
    return fold


# The most tokens a row that ``_write_slots`` writes as so many single-token
# writes of whole page rows (a speculative round's verify writes two); longer
# calls (prefill chunks) take one scatter of windows.
ROW_WRITE_TOKENS = 4


def _write_slots(pool: jax.Array, blk_ids, slots, values, fold: int) -> jax.Array:
    """``values`` (B, T, w) into slots (B, T) of blocks (B, T) of a folded pool
    (n_blocks, block / fold, fold * w): slot s is lanes (s % fold) * w .. + w of
    row s // fold."""
    w = values.shape[-1]
    values = values.astype(pool.dtype)
    if values.shape[1] == 1:
        # One token a row of the batch: no two of them share a row of a page
        # (requests own their blocks; idle rows share the scratch block), so
        # read each row, put the token in its lanes and write the row back. A
        # scatter of whole rows keeps its natural form on the TPU; windows at
        # lane offsets become B sequential dynamic-update-slices a pool, 0.30
        # ms a layer where this takes 0.05 (PERF.md section 6, PR 28).
        blk, row = blk_ids[:, 0], slots[:, 0] // fold
        mine = (jnp.arange(fold * w) // w)[None, :] == (slots % fold)
        return pool.at[blk, row].set(jnp.where(mine, jnp.tile(values[:, 0], (1, fold)), pool[blk, row]))
    if values.shape[1] <= ROW_WRITE_TOKENS:
        # a few tokens a row (the verify of a speculative round): one such write
        # a token, in order, since a row's next token may share its page row
        for i in range(values.shape[1]):
            pool = _write_slots(pool, blk_ids[:, i : i + 1], slots[:, i : i + 1], values[:, i : i + 1], fold)
        return pool
    idx = jnp.stack([blk_ids, slots // fold, (slots % fold) * w], axis=-1)
    dims = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(2,), inserted_window_dims=(0, 1), scatter_dims_to_operand_dims=(0, 1, 2)
    )
    return jax.lax.scatter(pool, idx, values, dims, mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def _absorbed_in_place(attn: Params, q, pool, rpool, tables, seq, cfg: ModelConfig, cdt: Any):
    """A few queries a row over the page pool without a gathered copy: q
    (B,T,H,nope+rope) against the pages ``tables`` names, slots 0..``seq + i``
    of a row visible to its query i."""
    from pretraining_llm_tpu.ops.pallas_latent import latent_decode_attention

    def core(q_lat, q_rope):
        return latent_decode_attention(
            q_lat.astype(pool.dtype), q_rope.astype(pool.dtype), pool, rpool,
            tables, seq, scale=cfg.softmax_scale,
        )

    return _absorb(attn, q, core, cfg, cdt)


def attention_block(
    blk: Params, x: jax.Array, cfg: ModelConfig, rope: Tuple[jax.Array, jax.Array],
    positions: jax.Array, kv: Optional[Params], cache_index: Optional[jax.Array],
    pad_offsets: Optional[jax.Array] = None, paged: Any = None, residual: bool = True,
) -> Tuple[jax.Array, Optional[Params]]:
    """The latent counterpart of ``transformer._attention_block``: same
    arguments, same cache discipline. ``kv`` is ``{'latent': (B, Tmax, c),
    'rope': (B, Tmax, r)}`` (contiguous) or ``{'latent_pool': (n_blocks, block
    / fold, fold * c), 'rope_pool': (n_blocks, block / fold, fold * r)}`` (paged,
    ``page_fold``)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    attn = blk["attn"]
    with jax.named_scope("blk.norm"):
        h = layers.apply_norm(cfg.norm, blk["ln1"], x, cfg.norm_eps)
    q, c_kv, k_r = _project(attn, h, cfg, cdt)
    b, t = q.shape[:2]
    nope = cfg.qk_nope_head_dim
    if paged is not None:
        rope_pos = paged.seq_lens[:, None] + jnp.arange(t, dtype=paged.seq_lens.dtype)[None, :]
    elif pad_offsets is not None:
        rope_pos = jnp.clip(positions[None, :] - pad_offsets[:, None], 0)
    else:
        rope_pos = positions
    with jax.named_scope("attn.rope"):
        cos, sin = rope
        q = jnp.concatenate(
            [q[..., :nope], layers.apply_rope(q[..., nope:], cos, sin, rope_pos)], axis=-1
        )
        k_rope = layers.apply_rope(k_r[:, :, None, :], cos, sin, rope_pos)[:, :, 0]
    rdim = k_rope.shape[-1]

    new_kv: Optional[Params] = None
    if kv is not None and "latent_pool" in kv:
        if paged is None:
            raise ValueError("a paged kv pool requires forward(..., paged=PagedInfo)")
        pool, rpool = kv["latent_pool"], kv["rope_pool"]
        fold = pool.shape[2] // c_kv.shape[-1]
        block_size = pool.shape[1] * fold
        tables, seq = paged.block_tables, paged.seq_lens
        capacity = tables.shape[1] * block_size
        with jax.named_scope("attn.kv_write"):
            # the write discipline of the per-head pool: slot seq + i, past
            # the row's capacity into the scratch block 0
            pos = seq[:, None] + jnp.arange(t, dtype=seq.dtype)[None, :]
            in_range = pos < capacity
            pos_c = jnp.minimum(pos, capacity - 1)
            blk_ids = jnp.where(in_range, tables[jnp.arange(b)[:, None], pos_c // block_size], 0)
            slots = jnp.where(in_range, pos_c % block_size, 0)
            pool = _write_slots(pool, blk_ids, slots, c_kv, fold)
            rpool = _write_slots(rpool, blk_ids, slots, k_rope, fold)
            new_kv = {"latent_pool": pool, "rope_pool": rpool}
        if decode_form(t) == "latent_kernel":
            out = _absorbed_in_place(attn, q, pool, rpool, tables, seq, cfg, cdt)
        else:
            with jax.named_scope("attn.paged_gather"):
                kv_len = tables.shape[1] * block_size
                cached = pool[tables].reshape(b, kv_len, c_kv.shape[-1]).astype(cdt)
                cached_r = rpool[tables].reshape(b, kv_len, rdim).astype(cdt)
            mask = jnp.arange(kv_len)[None, None, :] <= pos[:, :, None]
            out = _absorbed(attn, q, cached, cached_r, mask, cfg, cdt)
    elif kv is not None:
        with jax.named_scope("attn.kv_write"):
            write = lambda buf, val: jax.lax.dynamic_update_slice_in_dim(
                buf, val.astype(buf.dtype), cache_index, axis=1
            )
            buf, rbuf = write(kv["latent"], c_kv), write(kv["rope"], k_rope)
            new_kv = {"latent": buf, "rope": rbuf}
        # Provably at offset zero: a concrete 0, or a call that fills the whole
        # cache (the engine's prefill stages into a cache of exactly its bucket
        # and passes its zero as a traced scalar; t tokens fit t slots at 0 only).
        prefill_at_zero = cache_index is None or buf.shape[1] == t or (
            not isinstance(cache_index, jax.core.Tracer) and int(cache_index) == 0
        )
        if t > 1 and prefill_at_zero and pad_offsets is None:
            # PREFILL: causal self-attention over this call's own tokens, in
            # the expanded form (the flash kernel on TPU when configured).
            out = _expanded(attn, q, c_kv, k_rope, cfg, cdt, cfg.attention_impl)
        else:
            slots = jnp.arange(buf.shape[1])
            mask = slots[None, None, :] <= (cache_index + jnp.arange(t))[None, :, None]
            if pad_offsets is not None:
                mask = mask & (slots[None, None, :] >= pad_offsets[:, None, None])
            mask = jnp.broadcast_to(mask, (b, t, buf.shape[1]))
            out = _absorbed(attn, q, buf.astype(cdt), rbuf.astype(cdt), mask, cfg, cdt)
    else:
        out = _expanded(attn, q, c_kv, k_rope, cfg, cdt, cfg.attention_impl)

    out = checkpoint_name(out, "attn_out")
    with jax.named_scope("attn.out"):
        if "wgate" in attn:
            # one sigmoid gate a head, from the sublayer's input
            gate = jnp.einsum(
                "btd,dh->bth", h.astype(cdt), _w(attn, "wgate", cdt), preferred_element_type=jnp.float32
            )
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(gate)[..., None]).astype(cdt)
        out = jnp.einsum(
            "bthn,hnd->btd", out, _w(attn, "wo", cdt), preferred_element_type=jnp.float32
        ).astype(cdt)
        return (x + out.astype(x.dtype) if residual else out.astype(x.dtype)), new_kv
