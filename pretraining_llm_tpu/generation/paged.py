"""Paged KV cache: block pool + tables for continuous-batching serving.

The contiguous cache (`models.transformer.make_kv_cache`) sizes every row
for the worst case and fixes the batch at compile time — fine for offline
generation, wasteful for serving, where requests of wildly different
lengths come and go. The paged layout decouples memory from batch rows:

  - K/V live in a shared POOL of fixed-size blocks
    ((L, n_blocks, block_size, G, Dh), `make_paged_kv_pool`);
  - each live request owns an ordered list of pool block ids — a row of
    the int32 ``block_tables`` — plus its logical length in ``seq_lens``;
  - the decode program (`paged_decode_step`) is compiled ONCE for the
    engine's (max_batch, max_blocks) shape: admission, growth, and
    eviction only edit int32 tables host-side.

This is vLLM's PagedAttention memory model re-expressed for XLA: block
tables are gather/scatter indices into statically-shaped pools, not
pointers (the CUDA kernel's pointer-chasing would defeat XLA tiling).
Attention reads ride one `pool[tables]` gather per layer — the same HBM
bytes the dense ragged-decode path reads for an equal total length.

The reference has no serving path at all (generate is batch-1, fixed
count: /root/reference/src/models/transformer.py:96-114); this module +
`generation.serving` are beyond-reference capability.
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from pretraining_llm_tpu.config import ModelConfig
from pretraining_llm_tpu.generation.sampling import (
    sample_logits,
    sample_logits_fused,
)
from pretraining_llm_tpu.models import mtp, transformer
from pretraining_llm_tpu.models.transformer import PagedInfo
from pretraining_llm_tpu.ops import pallas_moe
from pretraining_llm_tpu.ops.pallas_paged import pad_kv_heads

# Pool-key names <- their contiguous-cache counterparts (prefill writes a
# dense per-request cache, then scatters its pages into the pools).
_POOL_OF_DENSE = {
    "k": "k_pool",
    "v": "v_pool",
    "k_scale": "k_scale_pool",
    "v_scale": "v_scale_pool",
    "latent": "latent_pool",
    "rope": "rope_pool",
}


def _laid_out(params: Any, cfg: ModelConfig, mesh: Any) -> Any:
    """A tree handed in from outside the engine, in the serving layout the
    engine's own tree has (``transformer.serving_layout``: the same stored
    tree gives the very same arrays); under a serving mesh as it came."""
    return transformer.serving_layout(params, cfg) if mesh is None else params


def pool_block_size(pools: transformer.KVCache, cfg: ModelConfig) -> int:
    """Tokens a page of ``pools`` holds (per-head or latent)."""
    # the first layer that has pages (a hybrid stack's recurrent layers keep state slots)
    fields = next(f for f in pools["layers"] if f and "state_pool" not in f)
    if "k_pool" in fields:
        return int(fields["k_pool"].shape[1])
    # a latent page is folded (models/mla.py::page_fold): rows x (slots a row x width)
    rows, lanes = fields["latent_pool"].shape[-2:]
    return int(rows * lanes // cfg.kv_lora_rank)


def required_blocks(n_tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``n_tokens`` cache slots."""
    return -(-n_tokens // block_size)


def prefill_bucket(cfg: ModelConfig, n_rows: int, max_pages: int, block_size: int) -> Tuple[int, int]:
    """(rows, pages) of the batched prefill program that takes ``n_rows``
    prompts of at most ``max_pages`` pages: both bucketed to powers of two so
    the jit cache stays at O(log(max_batch) * log(max_pages)) program variants,
    the pages no further than the model's context (its whole pages), which
    every prompt the engine accepts fits."""
    pages = min(1 << (max_pages - 1).bit_length(), cfg.context_length // block_size)
    return 1 << (n_rows - 1).bit_length(), max(max_pages, pages)


def window_first_block(seq_len: int, window: int, block_size: int) -> int:
    """The first page a window layer still needs of a row with ``seq_len``
    tokens cached: its next query, at position ``seq_len``, sees the positions
    above ``seq_len - window``. Every page before it lies wholly behind the
    window, for this query and every later one."""
    return max(0, seq_len - window + 1) // block_size


def window_layers(cfg: ModelConfig) -> Tuple[int, ...]:
    """The layers that keep the second cache lifetime (a pool of their own,
    pages given back behind the window); empty unless the stack mixes window
    and full attention layers."""
    if not cfg.two_lifetimes:
        return ()
    return tuple(i for i, kind in enumerate(cfg.attn_kinds) if kind == "window")


def state_slots(pools: transformer.KVCache) -> int:
    """State slots a hybrid stack's pools hold, the scratch slot (the last) not
    counted; 0 for a model that keeps pages alone."""
    state = next((f for f in pools["layers"] if "state_pool" in f), None)
    return 0 if state is None else state["state_pool"].shape[0] - 1


def check_paged_bounds(block_tables, seq_lens, block_size: int) -> None:
    """Host-side guard for the PagedInfo capacity invariant: a decode step
    WRITES slot seq_len, so seq_len == max_blocks*block_size would clamp
    the page index onto the row's LAST table entry and silently overwrite
    a live block (jit gathers clamp, they don't raise). Call before
    dispatching paged_decode_step whenever you build tables yourself."""
    import numpy as np

    tables = np.asarray(block_tables)
    seq = np.asarray(seq_lens)
    cap = tables.shape[-1] * block_size
    if (seq >= cap).any() or (seq < 0).any():
        bad = np.nonzero((seq >= cap) | (seq < 0))[0].tolist()
        raise ValueError(
            f"paged rows {bad} violate 0 <= seq_len < capacity={cap}: a "
            f"step would overwrite a live block (seq_lens={seq[bad]})"
        )


class BlockAllocator:
    """Host-side free-list over pool block ids. Block 0 is reserved as the
    idle-row scratch target (see make_paged_kv_pool) and never handed out.
    """

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError("need n_blocks >= 2 (block 0 is reserved)")
        self.n_blocks = n_blocks
        # LIFO free list: recently-freed blocks are reused first, keeping
        # the hot working set of pool pages small.
        self._free: List[int] = list(range(n_blocks - 1, 0, -1))
        self._live: set = set()

    @property
    def available(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n block ids, or None if the pool cannot cover them (all-or-
        nothing: a partial grant would deadlock admission)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        self._live.update(ids)
        return ids

    def alloc_upto(self, n: int) -> List[int]:
        """Up to ``n`` block ids — possibly fewer, possibly empty. The
        opportunistic multi-window page-horizon path: the pipelined serving
        scheduler pre-grows rows toward ``window * pipeline_depth`` write
        slots from the free list only, so a page flush never has to land
        between an already-dispatched window and its reap. Grants beyond a
        row's true need are speculative; callers roll them back with
        ``free()`` (release, preemption, or the reclaim pass)."""
        if n < 0:
            raise ValueError(f"alloc_upto({n})")
        ids = [self._free.pop() for _ in range(min(n, len(self._free)))]
        self._live.update(ids)
        return ids

    def free(self, ids: Sequence[int]) -> None:
        for i in ids:
            if i not in self._live:
                raise ValueError(f"double free / foreign block id {i}")
            self._live.discard(i)
            self._free.append(i)


def _scatter_staged_pages(
    pools: transformer.KVCache,
    dense_cache: transformer.KVCache,
    flat_ids: jax.Array,  # (n_rows * n_pages,) int32 pool block ids
    n_chunks: int,  # n_rows * n_pages (static)
    slots: Optional[jax.Array] = None,  # (n_rows,) int32 state slots
    window_ids: Optional[jax.Array] = None,  # (n_rows * n_pages,) int32, the window layers' pool
    in_window_pool: Tuple[int, ...] = (),  # the layers that scatter at window_ids (window_layers)
) -> transformer.KVCache:
    """ONE definition of the staged-cache -> pool page scatter, shared by
    the single-prompt and batched admission prefills. The staged cache is
    STACKED ((L, N, n_pages*bs, ...) fields, make_kv_cache(stacked=True)), or
    per layer for a hybrid stack, whose layers keep unlike caches; each layer
    of each field is cut into ``n_chunks`` pages and scattered into that
    layer's pool at ``flat_ids`` (pad pages point at the reserved scratch
    block 0 — duplicate indices there are benign). A recurrent layer's staged state
    and conv tail go whole into slot ``slots[row]`` of its state pools (pad
    rows: the scratch slot), written with the pages. The layers
    ``in_window_pool`` (two cache lifetimes) scatter at ``window_ids`` into
    their own pool: a page that lies wholly behind the window already has id 0
    there and lands on the scratch block like a pad page."""
    # per layer only where the layers keep unlike caches (_staging_cache)
    per_layer = "layers" in dense_cache and any("state_pool" in lp for lp in pools["layers"])
    field = (lambda layer, key: dense_cache["layers"][layer][key]) if per_layer else (
        lambda layer, key: dense_cache[key][layer])
    names = set().union(*dense_cache["layers"]) if per_layer else set(dense_cache)
    staged = [(d, p) for d, p in _POOL_OF_DENSE.items() if d in names]
    if not staged:
        # A per-layer staging cache would otherwise silently prefill NOTHING.
        raise ValueError(
            f"no cache fields matched the pool mapping; staging cache "
            f"keys = {sorted(dense_cache)} (need make_kv_cache(stacked=True))"
        )

    def _layer(layer, layer_pool):
        out = dict(layer_pool)
        if not layer_pool:  # a layer with no mixer staged nothing and keeps nothing
            return out
        if "state_pool" in layer_pool:
            if slots is None:
                raise ValueError("a state-slot model's prefill names each row's slot")
            for name in ("state", "conv"):
                pool = layer_pool[name + "_pool"]
                out[name + "_pool"] = pool.at[slots].set(field(layer, name).astype(pool.dtype))
            return out
        for dense_key, pool_key in staged:
            pool = layer_pool[pool_key]
            # a page is whatever one block of this pool holds: (bs, G, Dh) per
            # head; (bs / fold, fold * c) of latents and (bs / fold, fold * r) of
            # rotated key slices, the same values row-major as (bs, c) and (bs, r)
            staged_field = field(layer, dense_key)
            if pool.ndim == 4:  # per head: zeros in the pool's padding heads, where it has any
                staged_field = pad_kv_heads(staged_field, pool.shape[-2])
            pages = staged_field.reshape((n_chunks,) + pool.shape[1:])
            ids = window_ids if layer in in_window_pool else flat_ids
            out[pool_key] = pool.at[ids].set(pages.astype(pool.dtype))
        return out

    with jax.named_scope("attn.kv_write"):
        return {**pools, "layers": tuple(_layer(i, lp) for i, lp in enumerate(pools["layers"]))}


def _staging_cache(cfg: ModelConfig, n_rows: int, p_bucket: int) -> transformer.KVCache:
    """The dense cache a prefill forward fills for ``_scatter_staged_pages``:
    stacked (the rolled depth scan), per layer for a hybrid stack."""
    return transformer.make_kv_cache(cfg, n_rows, p_bucket, stacked=not cfg.hybrid)


# A prefill computes the f32 logits of every position and keeps the last real
# one of each row. Past this many bytes of logits (a long prompt under a large
# vocabulary: 8,192 positions of 131,072 are 4.3 GB a row) the head runs on the
# last positions' hidden states alone; the same numbers, computed for fewer rows.
_ALL_POSITION_LOGITS_BYTES = 2 << 30


def _prefill_last_logits(
    params: Any, prompts: jax.Array, last_idx: jax.Array, cfg: ModelConfig,
    cache: transformer.KVCache,
) -> Tuple[jax.Array, transformer.KVCache]:
    """Causal forward over (N, P) padded prompts into ``cache`` -> (logits
    (N, V) f32 at each row's ``last_idx``, the filled cache)."""
    n_rows, p_bucket = prompts.shape
    # a recurrent layer leaves its state as of each row's last real token
    lengths = last_idx + 1 if cfg.hybrid else None
    # (a state-slot model's pools leave the least room beside the weights: its
    # head always runs on the last positions alone)
    if not cfg.hybrid and 4 * n_rows * p_bucket * cfg.vocab_size <= _ALL_POSITION_LOGITS_BYTES:
        logits, cache = transformer.forward(
            params, prompts, cfg, kv_cache=cache, cache_index=jnp.int32(0),
            lengths=lengths,
        )
        last = jnp.take_along_axis(
            logits,
            jnp.broadcast_to(last_idx[:, None, None], (n_rows, 1, logits.shape[-1])),
            axis=1,
        )[:, 0]
        return last, cache
    hidden, cache = transformer.forward(
        params, prompts, cfg, kv_cache=cache, cache_index=jnp.int32(0),
        return_pre_logits=True, lengths=lengths,
    )
    return transformer.lm_head(params, _rows_at(hidden, last_idx), cfg)[:, 0], cache


def _rows_at(hidden: jax.Array, idx: jax.Array) -> jax.Array:
    """(N, 1, D): row ``idx[i]`` of each sequence of ``hidden`` (N, T, D)."""
    n, _, d = hidden.shape
    return jnp.take_along_axis(hidden, jnp.broadcast_to(idx[:, None, None], (n, 1, d)), axis=1)


@functools.partial(jax.jit, static_argnames=("n_pages", "in_window_pool"), donate_argnums=(0,))
def _scatter_pages(
    pools: transformer.KVCache,
    dense_cache: transformer.KVCache,
    block_ids: jax.Array,  # (n_pages,) int32
    n_pages: int,
    slot: Optional[jax.Array] = None,  # () int32
    window_ids: Optional[jax.Array] = None,  # (n_pages,) int32
    in_window_pool: Tuple[int, ...] = (),
) -> transformer.KVCache:
    """Scatter a (L, 1, n_pages*bs, ...) dense prefill cache into the pools
    at ``block_ids``. Donated pools: the update is in-place on device. (The
    batch-1 form of ``_scatter_staged_pages``.) A state-slot model's prompt
    goes into ``slot``; with none named, into the slot under the pools' own
    cursor, which then moves on: the n-th prompt so prefilled lands in slot n
    (mod the batch rows), the row a caller that builds its tables in prefill
    order decodes it at."""
    if "state_cursor" not in pools:
        return _scatter_staged_pages(
            pools, dense_cache, block_ids, n_pages, window_ids=window_ids,
            in_window_pool=in_window_pool,
        )
    cursor = pools["state_cursor"]
    if slot is None:
        slot, cursor = cursor % state_slots(pools), cursor + 1
    pools = _scatter_staged_pages(pools, dense_cache, block_ids, n_pages, slot[None])
    return {**pools, "state_cursor": cursor}


@functools.partial(jax.jit, static_argnames=("cfg", "p_bucket", "mesh"))
def _prefill_dense(
    params: Any,
    prompt: jax.Array,  # (1, p_bucket) int32, zero-padded
    prompt_len: jax.Array,  # () int32 — true length, traced
    cfg: ModelConfig,
    p_bucket: int,
    mesh: Any = None,
) -> Tuple[jax.Array, transformer.KVCache]:
    """One causal forward over the padded prompt into a fresh dense cache
    sized exactly p_bucket. Returns (last real token's logits (V,), cache).

    Pad slots >= prompt_len hold garbage K/V, but in the paged layout the
    decode mask only exposes linear index j once j <= seq_len — and the
    decode write to slot seq_len lands BEFORE the mask exposes it, exactly
    the dense-prefill overwrite discipline (`generate._generate_jit`).
    """
    from pretraining_llm_tpu.parallel.sharding import activation_mesh

    with activation_mesh(mesh):
        # One forward fills the staging cache and _scatter_pages consumes it
        # field by field ((L, 1, pages*bs, ...) -> pool pages): stacked.
        cache = _staging_cache(cfg, 1, p_bucket)
        if cfg.hybrid or 4 * p_bucket * cfg.vocab_size > _ALL_POSITION_LOGITS_BYTES:
            last, cache = _prefill_last_logits(
                params, prompt, (prompt_len - 1).astype(jnp.int32)[None], cfg, cache
            )
            return last[0], cache
        logits, cache = transformer.forward(
            params, prompt, cfg, kv_cache=cache, cache_index=jnp.int32(0)
        )
        idx = jnp.broadcast_to(
            (prompt_len - 1).astype(jnp.int32), (1, 1, logits.shape[-1])
        )
        last = jnp.take_along_axis(logits, idx, axis=1)[0, 0]
        return last, cache


def prefill_into_pool(
    params: Any,
    cfg: ModelConfig,
    pools: transformer.KVCache,
    prompt_ids: Sequence[int],
    block_ids: Sequence[int],
    *,
    mesh: Any = None,
    slot: Optional[int] = None,
    window_block_ids: Optional[Sequence[int]] = None,
) -> Tuple[jax.Array, transformer.KVCache]:
    """Prefill one prompt and write its pages into the pool.

    ``window_block_ids`` (two cache lifetimes): as many ids as ``block_ids``,
    the window layers' pages in their own pool, 0 for every page that lies
    wholly behind the window (``window_first_block``).

    ``block_ids`` must be exactly ceil(len(prompt)/block_size) pages
    (allocator output). Returns (last-token logits (V,) fp32, updated
    pools). Compiles once per page count, not per prompt length. ``slot``: the
    state slot of a state-slot model's row (``_scatter_pages`` for none).
    """
    block_size = pool_block_size(pools, cfg)
    p = len(prompt_ids)
    if p == 0:
        raise ValueError("empty prompt")
    n_pages = required_blocks(p, block_size)
    if n_pages != len(block_ids):
        raise ValueError(
            f"prompt of {p} tokens needs exactly {n_pages} pages; got "
            f"{len(block_ids)} block ids"
        )
    p_bucket = n_pages * block_size
    prompt = jnp.zeros((1, p_bucket), jnp.int32)
    prompt = prompt.at[0, :p].set(jnp.asarray(prompt_ids, jnp.int32))
    last, dense = _prefill_dense(
        _laid_out(params, cfg, mesh), prompt, jnp.int32(p), cfg, p_bucket, mesh
    )
    in_window_pool = window_layers(cfg)
    if bool(in_window_pool) != (window_block_ids is not None) or (
        in_window_pool and len(window_block_ids) != n_pages
    ):
        raise ValueError(
            "a stack with two cache lifetimes, and no other, names window_block_ids, "
            "one id a page of block_ids"
        )
    pools = _scatter_pages(
        pools, dense, jnp.asarray(block_ids, jnp.int32), n_pages,
        None if slot is None else jnp.int32(slot),
        None if window_block_ids is None else jnp.asarray(window_block_ids, jnp.int32),
        in_window_pool,
    )
    return last, pools


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "p_bucket", "n_pages", "temperature", "top_k", "top_p",
        "min_p", "mesh", "with_draft",
    ),
    donate_argnums=(1,),
)
def _prefill_scatter_sample(
    params: Any,
    pools: transformer.KVCache,
    prompts: jax.Array,  # (N, p_bucket) int32, zero-padded rows
    prompt_lens: jax.Array,  # (N,) int32 — true lengths (>= 1)
    block_ids: jax.Array,  # (N, n_pages) int32 — 0 (scratch) for pad pages
    key: jax.Array,
    cfg: ModelConfig,
    p_bucket: int,
    n_pages: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    mesh: Any = None,
    slots: Optional[jax.Array] = None,  # (N,) int32 state slots (pad rows: the scratch slot)
    with_draft: bool = False,
    window_ids: Optional[jax.Array] = None,  # (N, n_pages) int32: the window layers' pages, 0 behind the window
) -> Tuple[jax.Array, transformer.KVCache]:
    """Batched admission in ONE device program: causal prefill over N
    padded prompts -> scatter every row's pages into the pools -> sample
    each row's first token. The per-request admission path paid one
    prefill program + one scatter + one host-synced sample PER request —
    N arrivals in a scheduling window cost N serialized dispatch round
    trips, the dominant term in the 8x serving/decode gap measured on an
    earlier installation (2026-08). Here
    N admissions are one dispatch and at most one sync (the engine defers
    even that in pipelined mode).

    Pad pages (rows shorter than the bucket) scatter to the reserved
    scratch block 0; duplicate scatter indices there are benign by the
    pool's scratch discipline. Pad ROWS (N rounded up to a bucket) carry
    all-zero tables and garbage tokens the caller slices away.

    ``with_draft`` (a model with an MTP module, self-drafting): the module is
    prefilled too, over the stack's hidden state of every prompt position and
    the token that followed it (the prompt shifted by one, the token just
    sampled behind its end), into its own layer of the same staged pages; the
    first value is then (N, 2): each row's first token and its first draft,
    the argmax of the module's logits at the prompt's last position.
    """
    from pretraining_llm_tpu.parallel.sharding import activation_mesh

    n_rows = prompts.shape[0]
    with activation_mesh(mesh):
        # One forward fills the staging cache; the scatter consumes
        # (L, N, pages*bs, ...) fields: stacked.
        cache = _staging_cache(cfg, n_rows, p_bucket)
        idx = jnp.clip(prompt_lens - 1, 0, p_bucket - 1).astype(jnp.int32)
        if with_draft:
            # the head on the last positions only, for the stack and the module
            # alike: the module reads every position's hidden state anyway
            hidden, cache = transformer.forward(
                params, prompts, cfg, kv_cache=cache, cache_index=jnp.int32(0),
                return_pre_logits=True,
            )
            last = transformer.lm_head(params, _rows_at(hidden, idx), cfg)[:, 0]
        else:
            last, cache = _prefill_last_logits(params, prompts, idx, cfg, cache)
        toks = sample_logits(
            last, key, temperature=temperature, top_k=top_k, top_p=top_p,
            min_p=min_p,
        ).astype(jnp.int32)
        if with_draft:
            following = jnp.roll(prompts, -1, axis=1).at[jnp.arange(n_rows), idx].set(toks)
            m_hidden, cache, _ = mtp.mtp_forward(
                params, hidden, following, cfg, kv_cache=cache, cache_index=jnp.int32(0),
                return_pre_logits=True,
            )
            with jax.named_scope("mtp.head"):
                m_last = transformer.lm_head(params, _rows_at(m_hidden, idx), cfg)[:, 0]
                toks = jnp.stack([toks, jnp.argmax(m_last, axis=-1).astype(jnp.int32)], axis=1)

        pools = _scatter_staged_pages(
            pools, cache, block_ids.reshape(-1), n_rows * n_pages, slots,
            None if window_ids is None else window_ids.reshape(-1), window_layers(cfg),
        )
        return toks, pools


def prefill_into_pool_batched(
    params: Any,
    cfg: ModelConfig,
    pools: transformer.KVCache,
    prompts: Sequence[Sequence[int]],
    rows_block_ids: Sequence[Sequence[int]],
    key: jax.Array,
    *,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    mesh: Any = None,
    slots: Optional[Sequence[int]] = None,
    with_draft: bool = False,
    rows_window_ids: Optional[Sequence[Sequence[int]]] = None,
) -> Tuple[jax.Array, transformer.KVCache]:
    """Prefill N prompts and write all their pages into the pool in one
    device program; returns (first sampled token per prompt — a DEVICE
    (N,) int32 array, no host sync — and the updated pools).

    ``rows_block_ids[i]`` must be exactly ceil(len(prompts[i])/block_size)
    pages. Rows and pages are bucketed (``prefill_bucket``). ``slots[i]`` is
    prompt i's state slot (state-slot models). ``with_draft``: the model's MTP
    module is prefilled too and the first value is (N, 2), first token and
    first draft (``_prefill_scatter_sample``). ``rows_window_ids[i]`` (two
    cache lifetimes): as many ids as ``rows_block_ids[i]``, the window layers'
    pages in their own pool, 0 for each page wholly behind the window.
    """
    block_size = pool_block_size(pools, cfg)
    n = len(prompts)
    if n == 0:
        raise ValueError("no prompts")
    pages = []
    for i, (p, ids) in enumerate(zip(prompts, rows_block_ids)):
        if len(p) == 0:
            raise ValueError("empty prompt")
        np_i = required_blocks(len(p), block_size)
        if np_i != len(ids):
            raise ValueError(
                f"prompt {i} of {len(p)} tokens needs exactly {np_i} pages; "
                f"got {len(ids)} block ids"
            )
        pages.append(np_i)
    import numpy as np

    if bool(window_layers(cfg)) != (rows_window_ids is not None):
        raise ValueError("a stack with two cache lifetimes, and no other, names rows_window_ids")
    bucket_rows, bucket_pages = prefill_bucket(cfg, n, max(pages), block_size)
    p_bucket = bucket_pages * block_size
    prompt_arr = np.zeros((bucket_rows, p_bucket), np.int32)
    lens = np.ones((bucket_rows,), np.int32)
    ids_arr = np.zeros((bucket_rows, bucket_pages), np.int32)
    for i, (p, ids) in enumerate(zip(prompts, rows_block_ids)):
        prompt_arr[i, : len(p)] = p
        lens[i] = len(p)
        ids_arr[i, : len(ids)] = ids
    window_arr = None
    if rows_window_ids is not None:
        window_arr = np.zeros((bucket_rows, bucket_pages), np.int32)
        for i, (ids, own) in enumerate(zip(rows_block_ids, rows_window_ids)):
            if len(own) != len(ids):
                raise ValueError(f"prompt {i}: {len(ids)} pages but {len(own)} window ids")
            window_arr[i, : len(own)] = own
        window_arr = jnp.asarray(window_arr)
    toks, pools = _prefill_scatter_sample(
        _laid_out(params, cfg, mesh), pools, jnp.asarray(prompt_arr), jnp.asarray(lens),
        jnp.asarray(ids_arr), key, cfg, p_bucket, bucket_pages,
        temperature, top_k, top_p, min_p, mesh, _slot_array(pools, slots, bucket_rows),
        with_draft, window_arr,
    )
    return toks[:n], pools


def _slot_array(pools: transformer.KVCache, slots: Optional[Sequence[int]], rows: int):
    """(rows,) int32 state slots for a prefill program's rows, the pad rows on
    the scratch slot; None for a model without state slots."""
    import numpy as np

    scratch = state_slots(pools)
    if not scratch:
        return None
    if slots is None:
        raise ValueError("a state-slot model's prefill names each row's slot")
    out = np.full((rows,), scratch, np.int32)
    out[: len(slots)] = slots
    return jnp.asarray(out)


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "t_bucket", "temperature", "top_k", "top_p", "min_p", "mesh",
    ),
    donate_argnums=(1,),
)
def _suffix_prefill_sample(
    params: Any,
    pools: transformer.KVCache,
    suffix: jax.Array,  # (N, t_bucket) int32, zero-padded rows
    suffix_lens: jax.Array,  # (N,) int32 — true suffix lengths (>= 1)
    block_tables: jax.Array,  # (N, max_blocks) int32 — shared + private ids
    cached_lens: jax.Array,  # (N,) int32 — resident prefix length per row
    key: jax.Array,
    cfg: ModelConfig,
    t_bucket: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    mesh: Any = None,
    slots: Optional[jax.Array] = None,  # (N,) int32 state slots (pad rows: the scratch slot)
) -> Tuple[jax.Array, transformer.KVCache]:
    """Prefix-cache hit admission: ONE multi-token paged forward over each
    row's uncached suffix. Token j of row i writes its K/V at slot
    cached_lens[i] + j through the row's table (landing only in the row's
    PRIVATE suffix blocks — the hit cap guarantees cached_len is block-
    aligned and strictly below the prompt), while attention gathers the
    shared prefix pages read-only (the model's paged tq>1 branch masks
    lin <= pos per query). The first output token samples from the last
    real suffix position.

    Pad tokens (rows shorter than the bucket) write slots >= the prompt
    length — private pages above the frontier, overwritten by decode
    before the mask ever exposes them, or scratch-redirected past the
    table (the established slot-reuse discipline). Pad ROWS carry all-
    zero tables and cached_len 0, so every write scatters to the reserved
    scratch block 0.
    """
    from pretraining_llm_tpu.parallel.sharding import activation_mesh

    n_rows = suffix.shape[0]
    with activation_mesh(mesh):
        # q_lens rides along for the state-slot layers (models/recurrent.py:
        # a pad query leaves the row's state alone); attention ignores it, so
        # its outputs are bit-identical with or without it.
        logits, pools = transformer.forward(
            params, suffix, cfg, kv_cache=pools,
            paged=PagedInfo(block_tables, cached_lens, q_lens=suffix_lens, slots=slots),
        )
        idx = jnp.clip(suffix_lens - 1, 0, t_bucket - 1).astype(jnp.int32)
        last = jnp.take_along_axis(
            logits,
            jnp.broadcast_to(idx[:, None, None], (n_rows, 1, logits.shape[-1])),
            axis=1,
        )[:, 0]
        toks = sample_logits(
            last, key, temperature=temperature, top_k=top_k, top_p=top_p,
            min_p=min_p,
        ).astype(jnp.int32)
        return toks, pools


def prefill_suffix_into_pool_batched(
    params: Any,
    cfg: ModelConfig,
    pools: transformer.KVCache,
    suffixes: Sequence[Sequence[int]],
    tables_rows: Any,  # (N, max_blocks) int array — engine table rows
    cached_lens: Sequence[int],
    key: jax.Array,
    *,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    mesh: Any = None,
    t_bucket: Optional[int] = None,
    slots: Optional[Sequence[int]] = None,
) -> Tuple[jax.Array, transformer.KVCache]:
    """Prefill ONLY the uncached suffixes of N prefix-cache-hit prompts in
    one device program; returns (first sampled token per row — a DEVICE
    (N,) int32 array, no host sync — and the updated pools).

    ``tables_rows[i]`` is row i's full block-table row (shared prefix
    blocks followed by private suffix blocks, zero-padded);
    ``cached_lens[i]`` its block-aligned resident prefix length. Rows and
    suffix lengths bucket to powers of two, mirroring
    ``prefill_into_pool_batched``'s jit-cache discipline.

    ``t_bucket`` pins the token-axis shape instead (chunked prefill: the
    engine feeds fixed-size chunks, so EVERY group — full chunks and the
    final tail alike — compiles ONE program per row bucket, where pow2
    length bucketing would recompile per novel prompt-length residue;
    see ServingEngine._dispatch_prefill_chunks).
    """
    import numpy as np

    n = len(suffixes)
    if n == 0:
        raise ValueError("no suffixes")
    if len(cached_lens) != n:
        raise ValueError(f"{n} suffixes but {len(cached_lens)} cached_lens")
    for i, s in enumerate(suffixes):
        if len(s) == 0:
            # The hit cap ((p-1)//bs blocks) makes this unreachable from
            # the engine; guard it for direct callers.
            raise ValueError(f"suffix {i} is empty (hit must be capped)")
    tables_np = np.asarray(tables_rows, np.int32)
    if tables_np.ndim != 2 or tables_np.shape[0] != n:
        raise ValueError(
            f"tables_rows must be (n={n}, max_blocks); got {tables_np.shape}"
        )
    max_t = max(len(s) for s in suffixes)
    bucket_rows = 1 << (n - 1).bit_length()
    if t_bucket is None:
        t_bucket = 1 << (max_t - 1).bit_length()
    elif t_bucket < max_t:
        raise ValueError(
            f"t_bucket={t_bucket} cannot hold a {max_t}-token suffix"
        )
    suf_arr = np.zeros((bucket_rows, t_bucket), np.int32)
    lens = np.ones((bucket_rows,), np.int32)
    tab_arr = np.zeros((bucket_rows, tables_np.shape[1]), np.int32)
    cl_arr = np.zeros((bucket_rows,), np.int32)
    for i, s in enumerate(suffixes):
        suf_arr[i, : len(s)] = s
        lens[i] = len(s)
        tab_arr[i] = tables_np[i]
        cl_arr[i] = int(cached_lens[i])
    toks, pools = _suffix_prefill_sample(
        params, pools, jnp.asarray(suf_arr), jnp.asarray(lens),
        jnp.asarray(tab_arr), jnp.asarray(cl_arr), key, cfg, t_bucket,
        temperature, top_k, top_p, min_p, mesh, _slot_array(pools, slots, bucket_rows),
    )
    return toks[:n], pools


def _forward_sample_one(
    params, pools, tokens, block_tables, seq_lens, key, cfg,
    temperature, top_k, top_p, min_p, mesh=None, logprobs_k=0,
    with_moe_counts=False, window_tables=None,
):
    """The single decode step both jitted entry points trace: forward one
    token per row through the paged cache, sample the next. Kept as ONE
    definition so the sps=1 and windowed paths can never diverge.

    Returns ``(next_token (B,), logprobs, pools)`` — ``logprobs`` is
    ``None`` unless ``logprobs_k > 0``, in which case it is the
    ``(values (B, k), ids (B, k))`` top-k log-softmax of the raw logits
    (the decode-fused host payload; see `sample_logits_fused`).
    ``with_moe_counts`` (dropless expert models) appends the step's tokens
    per expert, (expert layers, E) int32."""
    from pretraining_llm_tpu.parallel.sharding import activation_mesh

    with activation_mesh(mesh):
        if with_moe_counts:
            logits, pools, counts = transformer.forward(
                params, tokens[:, None], cfg, kv_cache=pools,
                paged=PagedInfo(block_tables, seq_lens, window_tables=window_tables),
                return_moe_counts=True,
            )
        else:
            logits, pools = transformer.forward(
                params,
                tokens[:, None],
                cfg,
                kv_cache=pools,
                paged=PagedInfo(block_tables, seq_lens, window_tables=window_tables),
            )
        nxt, lp = sample_logits_fused(
            logits[:, 0], key, temperature=temperature, top_k=top_k,
            top_p=top_p, min_p=min_p, logprobs_k=logprobs_k,
        )
        if with_moe_counts:
            return nxt.astype(jnp.int32), lp, pools, counts
        return nxt.astype(jnp.int32), lp, pools


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "temperature", "top_k", "top_p", "min_p", "mesh"),
    donate_argnums=(1,),
)
def paged_decode_step(
    params: Any,
    pools: transformer.KVCache,
    tokens: jax.Array,  # (B,) int32 — each row's previously sampled token
    block_tables: jax.Array,  # (B, max_blocks) int32
    seq_lens: jax.Array,  # (B,) int32
    key: jax.Array,
    cfg: ModelConfig,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    mesh: Any = None,
    window_tables: Optional[jax.Array] = None,  # (B, max_blocks) int32: PagedInfo.window_tables
) -> Tuple[jax.Array, transformer.KVCache]:
    """One lockstep decode step for every batch row (active or idle).

    Writes each row's token at its slot seq_len, attends over its blocks,
    samples the next token. Idle rows (table row all zeros, seq_len 0)
    scribble on the reserved scratch block and their sampled token is
    ignored by the engine. Donated pools: in-place scatter, no copy.
    (Kept as its own jit rather than paged_decode_steps(n=1): the raw
    ``key`` preserves the existing sps=1 sampling stream, where the scan
    would consume split(key, 1)[0].)
    """
    nxt, _, pools = _forward_sample_one(
        params, pools, tokens, block_tables, seq_lens, key, cfg,
        temperature, top_k, top_p, min_p, mesh, window_tables=window_tables,
    )
    return nxt, pools


def _accept_reject(
    drafts: jax.Array,  # (B, k) int32 proposals
    q_dists: Optional[jax.Array],  # (B, k, V) the draft's distributions; None: each proposal was its argmax
    t_logits: jax.Array,  # (B, k+1, V) the target's logits after the seed token and after each proposal
    key: jax.Array,
    temperature: float,
) -> Tuple[jax.Array, jax.Array]:
    """ONE accept/reject rule for both draft sources (a separate draft model:
    ``paged_spec_round``; the model's own MTP module: ``paged_mtp_round``),
    vectorized over rows -> (emit (B, k+1), n_emit (B,)): row b's output is
    ``emit[b, :n_emit[b]]``, the accepted proposals and then the target's
    correction or bonus token.

    Greedy: a proposal is accepted iff it is the target's argmax, and the
    closing token is the target's argmax at the first rejected position, so
    the output equals target-only greedy decoding whatever was proposed.
    Sampling: Leviathan's rule, accept ``d`` with probability
    ``min(1, p(d) / q(d))`` and resample a rejection from ``norm(max(p - q,
    0))``; a proposal that was the draft's argmax is a point mass (``q(d)`` =
    1: accepted with probability ``p(d)``, resampled from ``p`` without ``d``),
    so the output is distributed as target-only sampling either way."""
    from pretraining_llm_tpu.generation.speculative import _probs

    b, k = drafts.shape
    v = t_logits.shape[-1]
    rows = jnp.arange(b)
    with jax.named_scope("spec.accept"):
        if temperature == 0.0:
            best = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)  # (B, k+1)
            accepts = best[:, :k] == drafts
            n_acc = jnp.sum(jnp.cumprod(accepts.astype(jnp.int32), axis=1), axis=1).astype(jnp.int32)
            final = best[rows, n_acc]
        else:
            p_dists = jax.vmap(jax.vmap(lambda l: _probs(l, temperature)))(t_logits)  # (B, k+1, V)
            if q_dists is None:
                q_dists = jax.nn.one_hot(drafts, v, dtype=jnp.float32)
            _, sub_u, sub_r = jax.random.split(key, 3)
            cols = jnp.arange(k)[None, :]
            p_at = p_dists[rows[:, None], cols, drafts]  # (B, k)
            q_at = q_dists[rows[:, None], cols, drafts]
            u = jax.random.uniform(sub_u, (b, k))
            accepts = u < jnp.minimum(1.0, p_at / jnp.maximum(q_at, 1e-30))
            n_acc = jnp.sum(jnp.cumprod(accepts.astype(jnp.int32), axis=1), axis=1).astype(jnp.int32)
            # the bonus position: residual against q = 0 is p itself
            q_pad = jnp.concatenate([q_dists, jnp.zeros((b, 1, v), jnp.float32)], axis=1)
            resid = jnp.maximum(p_dists[rows, n_acc] - q_pad[rows, n_acc], 0.0)
            resid = resid / jnp.maximum(jnp.sum(resid, axis=-1, keepdims=True), 1e-30)
            final = jax.random.categorical(sub_r, jnp.log(resid + 1e-30)).astype(jnp.int32)
        emit = jnp.concatenate([drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)  # (B, k+1)
        return emit.at[rows, n_acc].set(final), n_acc + 1


@functools.partial(
    jax.jit,
    static_argnames=("cfg_t", "cfg_d", "k", "temperature", "mesh"),
    donate_argnums=(1, 2),
)
def paged_spec_round(
    params_t: Any,
    t_pools: transformer.KVCache,
    d_pools: transformer.KVCache,
    params_d: Any,
    tokens: jax.Array,  # (B,) int32 — each row's newest accepted token
    block_tables: jax.Array,  # (B, max_blocks) int32 — SHARED by both pools
    seq_lens: jax.Array,  # (B,) int32
    key: jax.Array,
    cfg_t: ModelConfig,
    cfg_d: ModelConfig,
    k: int,
    temperature: float = 0.0,
    mesh: Any = None,
) -> Tuple[jax.Array, jax.Array, transformer.KVCache, transformer.KVCache]:
    """One speculative round for every batch row over the paged pools:
    k single-token DRAFT steps propose, then the target VERIFIES all k in
    one (k+1)-token multi-token paged forward (models/transformer.py's
    tq>1 paged branch). Returns (emit (B, k+1), n_emit (B,), t_pools,
    d_pools): row b's valid output is emit[b, :n_emit[b]], between 1 and
    k+1 tokens (the accepted prefix + the target's correction/bonus).

    Both pools share ONE block table and frontier: page p of a request
    holds target K/V in the target pool and draft K/V in the draft pool
    (the allocator hands out ids once — the draft cache needs no second
    bookkeeping). Rejected slots hold garbage above the new frontier and
    are overwritten by the next round's writes, the same slot-reuse
    discipline as the contiguous speculative path
    (generation/speculative.py).

    Greedy (temperature=0) output equals target-only paged decoding row
    for row; sampling uses the Leviathan accept/reject rule vectorized
    over rows.
    """
    from pretraining_llm_tpu.generation.speculative import _probs
    from pretraining_llm_tpu.parallel.sharding import activation_mesh

    with activation_mesh(mesh):
        # --- draft: k proposal steps (no extra write-only step needed —
        # paged writes land at seq+j each step, and the verify below
        # covers the same slots in the draft's NEXT round implicitly
        # because slot reuse overwrites garbage).
        def draft_step(carry, j):
            d_pools, tok, key = carry
            key, sub = jax.random.split(key)
            logits, d_pools = transformer.forward(
                params_d, tok[:, None], cfg_d, kv_cache=d_pools,
                paged=transformer.PagedInfo(block_tables, seq_lens + j),
            )
            q_dist = jax.vmap(lambda l: _probs(l, temperature))(
                logits[:, 0]
            )  # (B, V)
            if temperature == 0.0:
                nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            else:
                nxt = jax.random.categorical(
                    sub, logits[:, 0].astype(jnp.float32) / temperature
                ).astype(jnp.int32)
            return (d_pools, nxt, key), (nxt, q_dist)

        (d_pools, d_last, key), (drafts, q_dists) = jax.lax.scan(
            draft_step, (d_pools, tokens, key), jnp.arange(k)
        )
        drafts = drafts.T  # (B, k)
        q_dists = jnp.moveaxis(q_dists, 0, 1)  # (B, k, V)

        # Write-only parking step (same as the contiguous path): the k-th
        # proposal's K/V must reach slot seq+k, or an all-accept round
        # leaves the next round's draft attending a stale slot — output
        # stays correct either way (acceptance always verifies against
        # the target), but the draft's hit rate would silently degrade.
        _, d_pools = transformer.forward(
            params_d, d_last[:, None], cfg_d, kv_cache=d_pools,
            paged=transformer.PagedInfo(block_tables, seq_lens + k),
        )

        # --- target: verify last + k drafts in ONE multi-token forward
        seq_tokens = jnp.concatenate(
            [tokens[:, None], drafts], axis=1
        )  # (B, k+1)
        t_logits, t_pools = transformer.forward(
            params_t, seq_tokens, cfg_t, kv_cache=t_pools,
            paged=transformer.PagedInfo(block_tables, seq_lens),
        )  # (B, k+1, V)
        emit, n_emit = _accept_reject(drafts, q_dists, t_logits, key, temperature)
        return emit, n_emit, t_pools, d_pools


def _mtp_verify(params, pools, seq_tokens, block_tables, seq_lens, cfg):
    """The verify half of a self-drafting round: the stack over ``seq_tokens``
    (B, 2) at positions ``seq_lens``, ``seq_lens + 1`` through the pool ->
    (logits (B, 2, V), pools, the hidden state that feeds the module (B, 2, D),
    tokens per expert of the expert layers or None)."""
    out = transformer.forward(
        params, seq_tokens, cfg, kv_cache=pools, paged=PagedInfo(block_tables, seq_lens),
        return_hidden=True, return_moe_counts=cfg.moe_dropless,
    )
    return out[0], out[1], out[2]["final_hidden"], out[3] if cfg.moe_dropless else None


def _routing_counters(cfg: ModelConfig, counts: jax.Array, pairs: int) -> Any:
    """The routing counters of ``counts`` (steps, expert layers, E) tokens to
    each held expert, every step ``pairs`` sorted (token, choice) rows a layer:
    tokens an expert, experts touched a layer and the visits those groups take
    in the expert kernel (``pallas_moe.group_visits`` at the width the layer
    gives these rows: weight reads, equal to the touched where every group fits
    one visit), each summed over the steps."""
    visits, _, _ = pallas_moe.group_visits(counts, pallas_moe.windows(pairs, cfg.n_experts))
    return {
        "expert_tokens": jnp.sum(counts, axis=0),
        "experts_touched": jnp.sum((counts > 0).astype(jnp.int32), axis=(0, 2)),
        "expert_visits": jnp.sum(visits, axis=(0, 2)),
    }


def _round_counters(cfg: ModelConfig, counts: Any, m_counts: Any, pairs: int) -> Any:
    """A round's routing counters, the stack's expert layers and then the
    module's block, as ``paged_decode_steps`` gives a window's; None for a
    model without dropless experts."""
    if not cfg.moe_dropless:
        return None
    return _routing_counters(cfg, jnp.concatenate([counts, m_counts[None]], axis=0)[None], pairs)


@functools.partial(
    jax.jit, static_argnames=("cfg", "temperature", "mesh"), donate_argnums=(1,)
)
def paged_mtp_round(
    params: Any,
    pools: transformer.KVCache,
    tokens: jax.Array,  # (B,) int32: each row's newest committed token, x_s
    drafts: jax.Array,  # (B,) int32: each row's pending draft of x_{s+1}
    block_tables: jax.Array,  # (B, max_blocks) int32
    seq_lens: jax.Array,  # (B,) int32: s, tokens before it are cached in every layer
    key: jax.Array,
    cfg: ModelConfig,
    temperature: float = 0.0,
    mesh: Any = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, Any, transformer.KVCache]:
    """One self-drafting speculative round for every batch row: the model's
    own multi-token-prediction module is the draft (``models/mtp.py``), its
    cache one more layer of the same pool under the same block tables.

    Verify, then draft (the module needs the stack's hidden state of the
    positions just verified and the token that followed each):

    - verify: the stack over ``[x_s, d]`` at positions ``s, s + 1`` in one
      two-query forward through the pool; ``_accept_reject`` (the rule
      ``paged_spec_round`` uses) emits ``[y]`` if ``d`` is rejected, ``[d, y']``
      if accepted;
    - draft: the module over ``(h_s, emit[0])`` and ``(h_{s+1}, emit[1])`` at
      the same positions; the next draft is the argmax of its logits at the
      last accepted position (a point-mass proposal, whatever the
      temperature). After a rejection the second position holds garbage in
      both caches, above the new frontier ``s + 1`` and overwritten by the
      next round: ``paged_spec_round``'s slot-reuse discipline.

    Returns (emit (B, 2), n_emit (B,), next draft (B,), routing counters or
    None, pools). The module's cache covers the positions the stack's does
    after every round, and no shape depends on what was accepted. Greedy
    output equals target-only paged decoding row for row.

    One device program, so that a round is one dispatch; its two halves carry
    the device scopes ``spec.verify`` and ``mtp.draft`` (``spec.accept``
    between them), and the engine spans the dispatch as ``serving.spec_round``."""
    from pretraining_llm_tpu.parallel.sharding import activation_mesh

    b = tokens.shape[0]
    with activation_mesh(mesh):
        with jax.named_scope("spec.verify"):
            t_logits, pools, hidden, counts = _mtp_verify(
                params, pools, jnp.stack([tokens, drafts], axis=1), block_tables, seq_lens, cfg
            )
        emit, n_emit = _accept_reject(drafts[:, None], None, t_logits, key, temperature)
        with jax.named_scope("mtp.draft"):
            m_hidden, pools, m_counts = mtp.mtp_forward(
                params, hidden, emit, cfg, kv_cache=pools,
                paged=PagedInfo(block_tables, seq_lens), return_pre_logits=True,
            )
            with jax.named_scope("mtp.head"):
                m_logits = transformer.lm_head(params, m_hidden[jnp.arange(b), n_emit - 1][:, None], cfg)
                nxt = jnp.argmax(m_logits[:, 0], axis=-1).astype(jnp.int32)
        return emit, n_emit, nxt, _round_counters(cfg, counts, m_counts, 2 * b * cfg.experts_per_token), pools


def paged_mtp_logits(
    params: Any,
    pools: transformer.KVCache,
    seq_tokens: jax.Array,  # (B, 2) int32: the tokens at positions s, s + 1
    following: jax.Array,  # (B, 2) int32: the tokens at s + 1, s + 2
    block_tables: jax.Array,
    seq_lens: jax.Array,
    cfg: ModelConfig,
    mesh: Any = None,
) -> Tuple[jax.Array, jax.Array, transformer.KVCache]:
    """The round's two forwards without its decisions, for a caller that
    forces the tokens: the stack's logits (B, 2, V) over ``seq_tokens`` and
    the module's (B, 2, V) over the stack's own hidden states and
    ``following``, both through the pool (what ``paged_decode_logits`` is to
    the decode step). ``pools`` is donated."""
    return _paged_mtp_logits(
        _laid_out(params, cfg, mesh), pools, seq_tokens, following, block_tables, seq_lens, cfg, mesh
    )


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"), donate_argnums=(1,))
def _paged_mtp_logits(params, pools, seq_tokens, following, block_tables, seq_lens, cfg, mesh=None):
    from pretraining_llm_tpu.parallel.sharding import activation_mesh

    with activation_mesh(mesh):
        t_logits, pools, hidden, _ = _mtp_verify(params, pools, seq_tokens, block_tables, seq_lens, cfg)
        m_logits, pools, _ = mtp.mtp_forward(
            params, hidden, following, cfg, kv_cache=pools, paged=PagedInfo(block_tables, seq_lens)
        )
        return t_logits, m_logits, pools


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "n_steps", "temperature", "top_k", "top_p",
                     "min_p", "mesh"),
    donate_argnums=(1,),
)
def paged_decode_steps(
    params: Any,
    pools: transformer.KVCache,
    tokens: jax.Array,  # (B,) int32
    block_tables: jax.Array,  # (B, max_blocks) int32
    seq_lens: jax.Array,  # (B,) int32
    key: jax.Array,
    cfg: ModelConfig,
    n_steps: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    mesh: Any = None,
    window_tables: Optional[jax.Array] = None,  # (B, max_blocks) int32: PagedInfo.window_tables
) -> Tuple[jax.Array, transformer.KVCache]:
    """``n_steps`` lockstep decode steps in ONE device program.

    Multi-step scheduling: per-step host dispatch dominates a serving
    engine whose host-to-device link is slow (an earlier installation
    paid ~ms per call), so the scheduler runs a fixed window of steps per
    dispatch and
    reaps/admits only at window boundaries. Rows that finish mid-window
    keep decoding into their own (pre-allocated, then freed) pages and
    the host discards the surplus tokens; rows that pass their table
    capacity redirect writes to the scratch block (see the overshoot
    guard in the model's paged branch). The scheduler must pre-allocate
    pages covering seq_len + n_steps writes per surviving row
    (ServingEngine._ensure_write_pages horizon).

    Returns ((B, n_steps) sampled tokens in order, updated pools). For a
    dropless expert model the first value is a pair: the tokens, and the
    window's routing counters ``{"expert_tokens": (expert layers, E) tokens
    routed to each expert over the window, "experts_touched": (expert layers,)
    experts that got a token, "expert_visits": (expert layers,) visits of the
    expert kernel those groups take, both summed over the steps}`` — read back
    with the tokens, in the same transfer.
    """
    counted = cfg.moe_dropless

    def one(carry, sub):
        pools, tok, seq = carry
        if counted:
            nxt, _, pools, counts = _forward_sample_one(
                params, pools, tok, block_tables, seq, sub, cfg,
                temperature, top_k, top_p, min_p, mesh, with_moe_counts=True,
                window_tables=window_tables,
            )
            return (pools, nxt, seq + 1), (nxt, counts)
        nxt, _, pools = _forward_sample_one(
            params, pools, tok, block_tables, seq, sub, cfg,
            temperature, top_k, top_p, min_p, mesh, window_tables=window_tables,
        )
        return (pools, nxt, seq + 1), nxt

    subs = jax.random.split(key, n_steps)
    (pools, _, _), toks = jax.lax.scan(
        one, (pools, tokens, seq_lens), subs
    )
    if counted:
        toks, counts = toks  # counts: (n_steps, expert layers, E)
        return (toks.T, _routing_counters(cfg, counts, tokens.shape[0] * cfg.experts_per_token)), pools
    return toks.T, pools  # (B, n_steps)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "temperature", "top_k", "top_p", "min_p",
                     "mesh", "logprobs_k"),
    donate_argnums=(1,),
)
def paged_decode_step_lp(
    params: Any,
    pools: transformer.KVCache,
    tokens: jax.Array,  # (B,) int32
    block_tables: jax.Array,  # (B, max_blocks) int32
    seq_lens: jax.Array,  # (B,) int32
    key: jax.Array,
    cfg: ModelConfig,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    mesh: Any = None,
    logprobs_k: int = 1,
    window_tables: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, transformer.KVCache]:
    """`paged_decode_step` plus the top-k logprob payload (raw ``key``,
    preserving the sps=1 sampling stream exactly like its twin).
    Returns ``(tokens (B,), lp_values (B, k), lp_ids (B, k), pools)``."""
    nxt, lp, pools = _forward_sample_one(
        params, pools, tokens, block_tables, seq_lens, key, cfg,
        temperature, top_k, top_p, min_p, mesh, logprobs_k=logprobs_k,
        window_tables=window_tables,
    )
    return nxt, lp[0], lp[1], pools


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "n_steps", "temperature", "top_k", "top_p",
                     "min_p", "mesh", "logprobs_k"),
    donate_argnums=(1,),
)
def paged_decode_steps_lp(
    params: Any,
    pools: transformer.KVCache,
    tokens: jax.Array,  # (B,) int32
    block_tables: jax.Array,  # (B, max_blocks) int32
    seq_lens: jax.Array,  # (B,) int32
    key: jax.Array,
    cfg: ModelConfig,
    n_steps: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    mesh: Any = None,
    logprobs_k: int = 1,
    window_tables: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, transformer.KVCache]:
    """`paged_decode_steps` with the top-k logprob payload.

    Same scan, same key stream (split(key, n_steps)), same token
    numerics — the ONLY addition is the per-step (values, ids) top-k
    log-softmax of each step's raw logits, computed inside the same
    device program so the host still receives token ids + a (B, n, k)
    sliver instead of (B, n, V) logits.

    Returns ``(tokens (B, n_steps), lp_values (B, n_steps, k) f32,
    lp_ids (B, n_steps, k) int32, pools)``.
    """

    def one(carry, sub):
        pools, tok, seq = carry
        nxt, lp, pools = _forward_sample_one(
            params, pools, tok, block_tables, seq, sub, cfg,
            temperature, top_k, top_p, min_p, mesh,
            logprobs_k=logprobs_k, window_tables=window_tables,
        )
        return (pools, nxt, seq + 1), (nxt, lp[0], lp[1])

    subs = jax.random.split(key, n_steps)
    (pools, _, _), (toks, lp_vals, lp_ids) = jax.lax.scan(
        one, (pools, tokens, seq_lens), subs
    )
    return (
        toks.T,  # (B, n_steps)
        lp_vals.transpose(1, 0, 2),  # (B, n_steps, k)
        lp_ids.transpose(1, 0, 2),
        pools,
    )


def paged_decode_logits(
    params: Any,
    pools: transformer.KVCache,
    tokens: jax.Array,  # (B,) int32
    block_tables: jax.Array,  # (B, max_blocks) int32
    seq_lens: jax.Array,  # (B,) int32
    cfg: ModelConfig,
    mesh: Any = None,
    window_tables: Optional[jax.Array] = None,
) -> Tuple[jax.Array, transformer.KVCache]:
    """UNFUSED decode forward: one step, raw (B, V) last-position logits.

    The measurement/fallback lane for decode-fused sampling: forward
    only, with token selection left to a SEPARATE `sample_tokens`
    dispatch — exactly the extra device→host logits round-trip the fused
    path (`paged_decode_step[s]` / `_lp`) eliminates. The serving engine
    keeps this lane wired (``fused_sampling=False``) so fused-vs-unfused
    greedy bit-identity stays testable and the transfer win stays
    benchable. ``pools`` is donated.
    """
    return _paged_decode_logits(
        _laid_out(params, cfg, mesh), pools, tokens, block_tables, seq_lens, cfg, mesh, window_tables
    )


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"), donate_argnums=(1,))
def _paged_decode_logits(params, pools, tokens, block_tables, seq_lens, cfg, mesh=None, window_tables=None):
    from pretraining_llm_tpu.parallel.sharding import activation_mesh

    with activation_mesh(mesh):
        logits, pools = transformer.forward(
            params,
            tokens[:, None],
            cfg,
            kv_cache=pools,
            paged=PagedInfo(block_tables, seq_lens, window_tables=window_tables),
        )
    return logits[:, 0].astype(jnp.float32), pools


@functools.partial(
    jax.jit,
    static_argnames=("temperature", "top_k", "top_p", "min_p"),
)
def sample_tokens(
    logits: jax.Array,  # (B, V) f32
    key: jax.Array,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
) -> jax.Array:
    """The unfused lane's second dispatch: `sample_logits` as its own
    jitted program over host-visible logits. Same math as the fused
    in-program sampling (JAX PRNG is jit-boundary invariant), so fused
    vs unfused token streams are bit-identical given identical logits."""
    return sample_logits(
        logits, key, temperature=temperature, top_k=top_k, top_p=top_p,
        min_p=min_p,
    )
