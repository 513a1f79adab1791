"""Latent (MLA) attention: low-rank queries, one latent a token for all heads.

Per token and layer the cache holds ``cfg.latent_dim`` values: the
RMS-normalised compressed KV ``c_kv`` (kv_lora_rank) and the one rotated key
slice ``k_rope`` (qk_rope_head_dim) that all heads share. They are two fields
of the cache, ``latent`` and ``rope``, because of how the TPU lays arrays out:
one (n_blocks, block, 576) pool gets a device layout with the block index
minor-most (576 is no multiple of 128 lanes), and every decode step then
copies the whole pool into the scatter's layout and back, 0.74 ms each way
a layer at 4,097 blocks (PERF.md section 6, PR 27). A (n_blocks, block, 512)
pool and a (n_blocks, block * 64) one keep their natural layouts. Two
forms of one function:

- **expanded** (training forward, prefill): keys and values of every head are
  expanded from the latent, ``[k_nope | v] = c_kv W_kvb``, and ordinary causal
  attention runs on ``[q_nope | q_rope] . [k_nope | k_rope]``;
- **absorbed** (decode against a cache or a page pool): ``W_kvb``'s key half
  moves into the query, ``q~ = q_nope W_kvb^K``, scores and the weighted sum
  run in the latent space against the cached latents, and the value half maps
  the result back, ``o = (softmax . c_kv) W_kvb^V``. Nothing per head is ever
  read from the cache.

The softmax scale is ``cfg.softmax_scale`` (YaRN's mscale squared included).
Scopes: the low-rank projections in ``attn.qkv``, ``mla.absorb`` around both
absorbed matmuls, ``attn.kv_write`` / ``attn.paged_gather`` / ``attn.core`` /
``attn.out`` as in the per-head path.
"""

from __future__ import annotations

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
    q = checkpoint_name(q, "qkv")
    k = checkpoint_name(k, "qkv")
    v = checkpoint_name(v, "qkv")
    with jax.named_scope("attn.core"):
        out = multihead_attention(
            q, k, v, impl=impl, block_q=cfg.flash_block_q, block_kv=cfg.flash_block_kv,
        )
    return out[..., :dv]


def _absorbed(attn: Params, q, latents, ropes, mask, cfg: ModelConfig, cdt: Any) -> jax.Array:
    """q (B,T,H,nope+rope) against cached ``c_kv`` (B,K,kv_lora_rank) and
    ``k_rope`` (B,K,rope) under ``mask`` (B,T,K): (B, T, H, v_head_dim)."""
    nope = cfg.qk_nope_head_dim
    f32 = jnp.float32
    # The CPU backend has no batched-bf16 DotThunk (see models/moe.py): these
    # four dots are batched over heads or rows, so they run in float32 there.
    ddt = f32 if jax.default_backend() == "cpu" else cdt
    wkv_b = _w(attn, "wkv_b", cdt).astype(ddt)
    q, latents, ropes = q.astype(ddt), latents.astype(ddt), ropes.astype(ddt)
    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum(
            "bthn,chn->bthc", q[..., :nope], wkv_b[..., :nope], preferred_element_type=f32
        ).astype(ddt)
    with jax.named_scope("attn.core"):
        s = jnp.einsum("bthc,bkc->bhtk", q_lat, latents, preferred_element_type=f32)
        s = s + jnp.einsum("bthr,bkr->bhtk", q[..., nope:], ropes, preferred_element_type=f32)
        s = jnp.where(mask[:, None], s * cfg.softmax_scale, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        # a row with no visible slot (a dead left-pad query) gives zeros, not NaN
        p = jnp.where(jnp.any(mask, axis=-1)[:, None, :, None], p, 0.0)
        o_lat = jnp.einsum(
            "bhtk,bkc->bthc", p.astype(ddt), latents, preferred_element_type=f32
        ).astype(ddt)
    with jax.named_scope("mla.absorb"):
        return jnp.einsum(
            "bthc,chn->bthn", o_lat, wkv_b[..., nope:], preferred_element_type=f32
        ).astype(cdt)


def attention_block(
    blk: Params, x: jax.Array, cfg: ModelConfig, rope: Tuple[jax.Array, jax.Array],
    positions: jax.Array, kv: Optional[Params], cache_index: Optional[jax.Array],
    pad_offsets: Optional[jax.Array] = None, paged: Any = None, residual: bool = True,
) -> Tuple[jax.Array, Optional[Params]]:
    """The latent counterpart of ``transformer._attention_block``: same
    arguments, same cache discipline. ``kv`` is ``{'latent': (B, Tmax, c),
    'rope': (B, Tmax, r)}`` (contiguous) or ``{'latent_pool': (n_blocks, block,
    c), 'rope_pool': (n_blocks, block * r)}`` (paged)."""
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
        block_size = pool.shape[1]
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
            pool = pool.at[blk_ids, slots].set(c_kv.astype(pool.dtype))
            rpool = rpool.at[
                blk_ids[..., None], slots[..., None] * rdim + jnp.arange(rdim, dtype=slots.dtype)
            ].set(k_rope.astype(rpool.dtype))
            new_kv = {"latent_pool": pool, "rope_pool": rpool}
        with jax.named_scope("attn.paged_gather"):
            kv_len = tables.shape[1] * block_size
            cached = pool[tables].reshape(b, kv_len, pool.shape[-1]).astype(cdt)
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
        out = jnp.einsum(
            "bthn,hnd->btd", out, _w(attn, "wo", cdt), preferred_element_type=jnp.float32
        ).astype(cdt)
        return (x + out.astype(x.dtype) if residual else out.astype(x.dtype)), new_kv
