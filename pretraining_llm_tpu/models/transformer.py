"""Decoder-only transformer: pure-functional init/forward over a param pytree.

Capability parity with `/root/reference/src/models/transformer.py` (forward with
optional targets -> (logits, loss); SURVEY §2.5 architecture spec) — redesigned
TPU-first instead of translated:

  - Blocks are *stacked* (leading n_layers dim on every block param) and the
    depth loop is a `jax.lax.scan`, so XLA traces/compiles one block regardless
    of depth (the reference Python-loops 64 modules: transformer.py:68-69).
  - One fused QKV projection per block feeding all heads at once (the
    reference runs 16 separate per-head Linears in a Python loop:
    attention.py:95) — the MXU wants one big matmul.
  - Causal masking is index arithmetic inside the attention op, not the
    reference's ~1 GB of per-head registered tril buffers (attention.py:33).
  - fp32 master params, bf16 compute, fp32 softmax/logits/loss: TPU-native
    mixed precision with no GradScaler (the reference's scaler is vestigial
    for bf16, SURVEY §A B8).
  - `reference_parity` shape (no output projection, untied biased lm_head,
    ReLU, learned positions) is reachable via ModelConfig flags — see the
    `reference-3b` preset.

The same forward serves training (kv_cache=None) and KV-cached decode
(kv_cache + cache_index given): a cache is per-layer leaves walked by a
trace-time loop, or one stacked array scanned with the blocks (make_kv_cache).
"""

from __future__ import annotations

import contextlib
import functools
import math
import weakref
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P


from pretraining_llm_tpu.config import ModelConfig
from pretraining_llm_tpu.models import hyper, layers, mla, moe, recurrent
from pretraining_llm_tpu.ops import remat
from pretraining_llm_tpu.ops.attention import multihead_attention
from pretraining_llm_tpu.ops.flash_attention import flash_attention_qkv, flash_takes_qkv
from pretraining_llm_tpu.ops.pallas_paged import (
    pad_kv_heads, pages_copy_in_place, paged_decode_attention, pool_kv_heads,
)
from pretraining_llm_tpu.parallel.sharding import constrain, current_mesh

Params = Dict[str, Any]
KVCache = Dict[str, jax.Array]  # {'k','v'}: (L, B, Tmax, kv_heads, Dh)

# The ``jax.named_scope`` names below (``attn.core``, ``mlp``, ...) and each
# op's source line are read back from profiler traces. JAX's persistent
# compilation cache leaves such metadata out of its key unless told otherwise,
# and then hands out an executable compiled from an earlier tree, which carries
# that tree's scopes and lines (or none): keep the metadata in the key.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


class PagedInfo(NamedTuple):
    """Batch-level paged-decode state, shared by every layer.

    The per-layer block POOLS ride the kv_cache scan carry exactly like the
    contiguous cache (see make_paged_kv_pool); the int32 routing state here
    is what the serving engine mutates host-side between steps — admission,
    growth, and eviction never change a device-array shape, so the decode
    program compiles once and serves forever (vLLM's PagedAttention idea
    re-expressed for XLA's static-shape model: block tables are gather/
    scatter indices, not pointers).

    INVARIANT (caller-enforced, unchecked under jit): every row's
    seq_lens < max_blocks * block_size — a decode step WRITES slot
    seq_lens, so at capacity the page index would clamp onto the row's
    last table entry and silently overwrite a live block. Schedulers must
    bound-check host-side before dispatch (ServingEngine does; drive
    `generation.paged.check_paged_bounds` if you build tables yourself).
    """

    block_tables: jax.Array  # (B, max_blocks) int32 — pool block ids per row
    seq_lens: jax.Array  # (B,) int32 — tokens already in the cache per row
    # Ragged multi-token calls (chunked prefill): row b's TRUE query count
    # (<= T); queries past it are padding whose outputs the caller
    # discards. None = uniform (every row carries all T queries — decode
    # steps and the speculative verify). A state-slot layer reads it
    # (models/recurrent.py: a pad query leaves the row's state alone);
    # attention computes pad queries and lets the caller discard them, so
    # outputs for REAL queries are bit-identical whether or not it is passed.
    q_lens: Optional[jax.Array] = None  # (B,) int32 or None
    # State-slot models (models/recurrent.py): the slot of the state pools each row
    # reads and writes. None = row b's own slot b, left alone while the row's
    # table names no page (the decode step); a prefill program's rows are not
    # the engine's, so it passes them (pad rows: the scratch slot, the last).
    slots: Optional[jax.Array] = None  # (B,) int32 or None
    # Two cache lifetimes (cfg.two_lifetimes): the window layers' table into
    # their own pool, as wide as block_tables and indexed the same way (entry
    # j is the page of slots j * block_size ...), naming LIVE pages only: a
    # page wholly behind the window has gone back to its pool and its entry
    # is 0 (the scratch block), which the window mask never exposes. None =
    # every layer reads block_tables.
    window_tables: Optional[jax.Array] = None  # (B, max_blocks) int32 or None


def paged_attention_form(
    cfg: ModelConfig,
    tq: int,
    quantized: bool,
    backend: Optional[str] = None,
    mesh: Any = None,
) -> str:
    """The form attention over a per-head page pool takes for ``tq`` queries
    a row: ``"kernel"`` (``ops/pallas_paged.py``: the row's live pages read in
    place) or ``"gather"`` (``pool[tables]`` and a masked einsum over every
    slot the table names). It is read from the input as ``mla.decode_form``
    reads a latent pool's: the single-token decode step over an unquantized
    pool that no serving mesh shards and whose pages are copies of their own
    takes the kernel where Mosaic compiles, everything else (several queries a
    row, int8 pools, a sharded pool, narrow or odd heads, every other backend)
    the gather form. The engine reports the decode step's form in
    ``pool_info()``."""
    if (
        tq == 1
        and not quantized
        and mesh is None
        and (backend or jax.default_backend()) == "tpu"
        # the head axis the pool was built with (make_paged_kv_pool)
        and pages_copy_in_place(pool_kv_heads(cfg.kv_heads, cfg.head_dim), cfg.head_dim)
    ):
        return "kernel"
    return "gather"


def _lm_head_weights(params: Params, cfg: ModelConfig):
    """(w_out (D, V), bias (V,)|None) — single source of truth for the output
    head, shared by forward (sampling logits) and loss_fn (chunked CE)."""
    if cfg.tie_embeddings:
        return params["tok_embed"]["embedding"].T, None
    head = params["lm_head"]
    return head["kernel"], head.get("bias")


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Initialize the parameter pytree (fp32 masters by default).

    GPT-2 style init: N(0, 0.02) everywhere, residual-output projections
    (wo, w2) scaled by 1/sqrt(2*n_layers), zeros for biases.

    This is the STORED layout: what checkpoints hold, the importers write and
    training, ``generate.py`` and a serving mesh read. A serving engine on one
    device reads a dense SwiGLU's ``w1`` from a copy it lays out once when it
    is built (``serving_layout``); nothing stored changes for it.
    """
    dtype = jnp.dtype(cfg.param_dtype)
    d, h, dh, f, v, t, nl = (
        cfg.d_model,
        cfg.n_heads,
        cfg.head_dim,
        cfg.d_ff,
        cfg.vocab_size,
        cfg.context_length,
        cfg.n_layers,
    )
    std = 0.02
    resid_std = std / (2 * nl) ** 0.5
    k_tok, k_pos, k_head, k_blocks = jax.random.split(key, 4)

    def normal(k: jax.Array, shape: Tuple[int, ...], s: float = std) -> jax.Array:
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    g = cfg.kv_heads

    def init_block(k: jax.Array, dense_ffn: bool = False, mixer: str = "attn", ffn: bool = True) -> Params:
        """One layer: its mixer under ``ln1`` and its FFN under ``ln2``. A layer
        of one sublayer (``mixer="none"`` or ``ffn=False``: the table's
        ``"none"``) has that sublayer and its one norm alone."""
        ks = jax.random.split(k, 5)
        attn: Params = {}
        if mixer == "none":
            pass
        elif mixer != "attn":
            attn: Params = recurrent.MIXERS[mixer].init_params(cfg, ks[0], resid_std, dtype)
        elif cfg.kv_lora_rank:
            attn: Params = mla.init_attn_params(cfg, ks[0], resid_std, dtype)
        elif g == h:
            attn: Params = {"wqkv": normal(ks[0], (d, 3, h, dh))}
            if cfg.qkv_bias:
                attn["bqkv"] = jnp.zeros((3, h, dh), dtype)
        else:
            # GQA: separate q and (smaller) fused kv projections.
            attn = {
                "wq": normal(ks[0], (d, h, dh)),
                "wkv": normal(ks[4], (d, 2, g, dh)),
            }
            if cfg.qkv_bias:
                attn["bq"] = jnp.zeros((h, dh), dtype)
                attn["bkv"] = jnp.zeros((2, g, dh), dtype)
        if cfg.use_output_proj and not cfg.kv_lora_rank and mixer == "attn":
            attn["wo"] = normal(ks[1], (h, dh, d), resid_std)
            attn["bo"] = jnp.zeros((d,), dtype)
        if not cfg.kv_lora_rank and mixer == "attn":
            if cfg.qk_norm or cfg.qk_norm_whole:
                # a head's width, or all heads' (qk_norm_whole)
                attn["q_norm"] = layers.init_norm("rmsnorm", dh * (h if cfg.qk_norm_whole else 1), dtype)
                attn["k_norm"] = layers.init_norm("rmsnorm", dh * (g if cfg.qk_norm_whole else 1), dtype)
            if cfg.attn_output_gate:
                attn["wg"] = normal(jax.random.fold_in(k, 13), (d, h, dh))
        if not ffn:
            mlp: Params = {}
        elif cfg.moe_dropless and not dense_ffn:
            mlp: Params = moe.init_dropless_params(cfg, ks[2], resid_std, dtype)
        elif cfg.n_experts and not dense_ffn:
            mlp: Params = moe.init_moe_params(cfg, ks[2], resid_std, dtype)
        elif cfg.activation == "swiglu":
            mlp: Params = {"w1": normal(ks[2], (d, 2, f)), "w2": normal(ks[3], (f, d), resid_std)}
            if cfg.mlp_bias:
                mlp["b1"] = jnp.zeros((2, f), dtype)
                mlp["b2"] = jnp.zeros((d,), dtype)
        else:
            mlp = {"w1": normal(ks[2], (d, f)), "w2": normal(ks[3], (f, d), resid_std)}
            if cfg.mlp_bias:
                mlp["b1"] = jnp.zeros((f,), dtype)
                mlp["b2"] = jnp.zeros((d,), dtype)
        block = {}
        if mixer != "none":
            block.update(ln1=layers.init_norm(cfg.norm, d, dtype), attn=attn)
        if ffn:
            block.update(ln2=layers.init_norm(cfg.norm, d, dtype), mlp=mlp)
        if cfg.sandwich_norm:
            block["ln1_post"] = layers.init_norm(cfg.norm, d, dtype)
            block["ln2_post"] = layers.init_norm(cfg.norm, d, dtype)
        if cfg.hc_mult > 1:
            k_hc = jax.random.split(jax.random.fold_in(k, 7))
            block["hc_attn"] = hyper.init_hc_params(cfg, k_hc[0], dtype)
            block["hc_mlp"] = hyper.init_hc_params(cfg, k_hc[1], dtype)
        return block

    # vmap over per-layer keys -> every block param gets a leading (n_layers,) dim
    layer_keys = jax.random.split(k_blocks, nl)
    params: Params = {
        "tok_embed": {"embedding": normal(k_tok, (v, d))},
        "final_norm": layers.init_norm(cfg.norm, d, dtype),
    }
    if cfg.hybrid:
        # a hybrid stack: one stack a kind of layer (stack_key), whatever the
        # order the kinds come in (layer_groups)
        kinds = cfg.layer_kinds
        for kind in sorted(set(kinds)):
            of_kind = jnp.asarray([i for i, k in enumerate(kinds) if k == kind])
            params[stack_key(cfg, *kind)] = jax.vmap(lambda k, _kind=kind: init_block(
                k, dense_ffn=_kind[1] == "dense", mixer=_kind[0], ffn=_kind[1] != "none"
            ))(layer_keys[of_kind])
    else:
        params["blocks"] = jax.vmap(init_block)(layer_keys[cfg.n_dense_layers:])
        if cfg.n_dense_layers:
            # the leading dense layers, a group of their own ahead of "blocks"
            params["dense_blocks"] = jax.vmap(lambda k: init_block(k, dense_ffn=True))(
                layer_keys[: cfg.n_dense_layers]
            )
    if cfg.pos_embed == "learned":
        params["pos_embed"] = {"embedding": normal(k_pos, (t, d))}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": normal(k_head, (d, v))}
        if cfg.lm_head_bias:
            params["lm_head"]["bias"] = jnp.zeros((v,), dtype)
    if cfg.mtp_depth:
        # The multi-token-prediction module (models/mtp.py): one block of the
        # stack's last kind, never scanned with the stack (layer_groups does
        # not see it); embedding and head are the stack's.
        k_mtp = jax.random.split(jax.random.fold_in(key, 11))
        params["mtp"] = {
            "enorm": layers.init_norm(cfg.norm, d, dtype),
            "hnorm": layers.init_norm(cfg.norm, d, dtype),
            "eh_proj": normal(k_mtp[0], (2 * d, d)),
            "block": init_block(k_mtp[1]),
            "final_norm": layers.init_norm(cfg.norm, d, dtype),
        }
    return params


def stack_key(cfg: ModelConfig, mixer: str, ffn: str) -> str:
    """Where the layers of one kind are stacked in the parameter tree:
    "blocks", or "dense_blocks" for an expert model's leading dense layers; a
    hybrid stack's attention layers in "attn_blocks" and "attn_dense_blocks"
    (its recurrent layers keep the plain names), and the layers with no mixer
    of a table of single sublayers in "ffn_blocks" and "ffn_dense_blocks"
    (there "blocks" are the recurrent mixers alone, "attn_blocks" the
    attention layers alone)."""
    key = "dense_blocks" if ffn == "dense" and cfg.n_experts else "blocks"
    if mixer == "none":
        return "ffn_" + key
    return "attn_" + key if cfg.hybrid and mixer == "attn" else key


def layer_groups(params: Params, cfg: ModelConfig):
    """The stack as runs of like layers, each one scan of ``forward``: [(the
    layers it holds, the stacked blocks of their kind, the run's first layer's
    place in that stack)]. A homogeneous model is one run of all of "blocks",
    an expert model with leading dense layers two; a hybrid stack alternates
    between its kinds' stacks, a stack of window and full attention layers
    between its attention kinds inside "blocks" (``cfg.layer_runs``)."""
    if not (cfg.hybrid or cfg.two_lifetimes):
        k = cfg.n_dense_layers if "dense_blocks" in params else 0
        groups = [(range(k, cfg.n_layers), params["blocks"], 0)]
        if k:
            groups.insert(0, (range(k), params["dense_blocks"], 0))
        return groups
    kinds, seen, groups = cfg.layer_kinds, {}, []
    for a, b in cfg.layer_runs:
        key = stack_key(cfg, *kinds[a])
        groups.append((range(a, b), params[key], seen.get(key, 0)))
        seen[key] = seen.get(key, 0) + b - a
    return groups


# ---------------------------------------------------------------------------
# The serving layout of a dense SwiGLU's w1
# ---------------------------------------------------------------------------

# id of a stored w1 -> (weak reference to it, its gate and up halves): the same
# stored leaf gives the same halves, and they go when the leaf does.
_SERVING_COPIES: Dict[int, Tuple[Any, Tuple[jax.Array, jax.Array]]] = {}


@jax.jit
def _gate_and_up(w1: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(L, d, 2, f) -> two (L, d, f)."""
    return w1[:, :, 0], w1[:, :, 1]


def _serving_halves(w1: Any) -> Optional[Tuple[jax.Array, jax.Array]]:
    """The halves of a stored, stacked SwiGLU ``w1`` (L, d, 2, f), made once a
    leaf; None where the rule leaves it as it is: a GELU's (L, d, f), int8
    codes, an array a mesh shards, anything that is not an array on a device."""
    if (
        not isinstance(w1, jax.Array)
        or isinstance(w1, jax.core.Tracer)
        or w1.ndim != 4
        or not jnp.issubdtype(w1.dtype, jnp.floating)
        or not isinstance(w1.sharding, jax.sharding.SingleDeviceSharding)
    ):
        return None
    key = id(w1)
    held = _SERVING_COPIES.get(key)
    if held is None or held[0]() is not w1:
        # (the table bound now: a leaf may die after the module's names have gone)
        forget = lambda _, copies=_SERVING_COPIES: copies.pop(key, None)
        held = (weakref.ref(w1, forget), _gate_and_up(w1))
        _SERVING_COPIES[key] = held
    return held[1]


def serving_layout(params: Params, cfg: ModelConfig) -> Params:
    """``params`` with the dense SwiGLU FFNs of a per-head model's stacks (its
    attention layers' and, in a hybrid stack, its recurrent layers' alike)
    laid out for the serving programs: ``mlp.w1`` (L, d, 2, f) becomes
    ``mlp.w1_gate`` and ``mlp.w1_up``, (L, d, f) each. The TPU tiles an array's
    last two axes; stored, those are (2, f), the matmul wants the contracted
    ``d`` there, and XLA copies the whole stack into another tiling in every
    program that reads it (12 ms of a 30 ms decode step on an 18-layer
    Mistral, PERF.md section 6, PR 45). A weight whose last two axes are
    (contracted, output), as w2 is stored, is read in place, and ``_dense_mlp``
    runs two such matmuls where it finds the halves.

    The stored layout (``init_params``, checkpoints, the importers) is what it
    was: this is a copy made once, one jitted program a leaf, for the engine
    and for the paged entry points that take a tree from outside it. Every
    other leaf is shared with ``params``. The halves are remembered weakly by
    the identity of the stored leaf, so the same stored tree gives the same
    arrays again (the engine and a caller that still holds the stored tree
    meet one copy) and they are freed with it. A latent-attention model's
    stacks, a GELU's w1, expert stacks, int8 leaves, a tree a mesh shards and a
    tree already in this form pass through untouched; with nothing to lay out
    the result is ``params`` itself."""
    if cfg.kv_lora_rank:
        return params
    out = params
    stacks = {
        next(k for k, v in params.items() if v is stack) for _, stack, _ in layer_groups(params, cfg)
    }
    for key in sorted(stacks):
        mlp = params[key].get("mlp", {})  # a stack of mixers alone has none
        halves = _serving_halves(mlp.get("w1"))
        if halves is not None:
            mlp = {k: v for k, v in mlp.items() if k != "w1"}
            mlp["w1_gate"], mlp["w1_up"] = halves
            out = {**out, key: {**params[key], "mlp": mlp}}
    return out


# ---------------------------------------------------------------------------
# Block forward
# ---------------------------------------------------------------------------


_weight = layers.weight


def _attention_block(
    blk: Params,
    x: jax.Array,
    cfg: ModelConfig,
    rope: Optional[Tuple[jax.Array, jax.Array]],
    positions: jax.Array,
    kv: Optional[Params],
    cache_index: Optional[jax.Array],
    zigzag: bool = False,
    pad_offsets: Optional[jax.Array] = None,
    segments: Optional[jax.Array] = None,
    paged: Optional[PagedInfo] = None,
    residual: bool = True,
    kind: Optional[str] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    """Pre-LN attention sub-block: x + attn(ln1(x)). Returns (x, new_kv).
    ``residual=False`` returns attn(ln1(x)) alone: hyper-connections and the
    sandwich norms write it into the residual themselves.

    ``kind`` ("window" | "full"; None = the one kind ``cfg.sliding_window``
    implies) is the layer's attention kind in a stack that mixes them
    (``cfg.attn_kinds``): a window layer masks to the last
    ``cfg.sliding_window`` positions and reads ``paged.window_tables``, a full
    layer sees every earlier position through ``paged.block_tables`` and, with
    ``cfg.rope_full_layers`` off, rotates nothing.

    ``pad_offsets`` (B,) enables RAGGED cached decode: row i is left-padded
    by pad_offsets[i] slots, so its token at cache slot s has logical
    position s - pad_offsets[i]. Slot indices drive causality (equivalent
    to logical causality under a shared left-pad layout), RoPE uses the
    per-row logical positions, and the kv mask excludes each row's dead
    pad slots.
    """
    if cfg.kv_lora_rank:
        if zigzag or segments is not None:
            raise ValueError("latent attention has no ring layout and no document mask")
        return mla.attention_block(
            blk, x, cfg, rope, positions, kv, cache_index, pad_offsets, paged, residual
        )
    kind = kind or ("window" if cfg.sliding_window else "full")
    window = cfg.sliding_window if kind == "window" else 0
    if kind == "full" and not cfg.rope_full_layers:
        rope = None
    # A mixed stack's device trace tells its kinds of layer apart by this outer
    # scope: window from full layers, attention from recurrent ones.
    with jax.named_scope(f"attn.{kind}") if cfg.attn_kinds or cfg.hybrid else contextlib.nullcontext():
        return _attention_core(
            blk, x, cfg, rope, positions, kv, cache_index, zigzag, pad_offsets, segments,
            paged, residual, window,
        )


def _qkv_stays_whole(
    cfg: ModelConfig, attn: Params, kv: Any, rope: Any, segments: Any, window: int,
    shape: Tuple[int, ...],
) -> bool:
    """Whether the fused projection's result, `shape` (B, 3, T, 1, H*Dh), goes
    to the flash kernels as one array. Read from the call, never set: no cache
    is written (training, evaluation), nothing stands between the projection
    and the attention (no rotation for this layer, no q/k norm, no multiplier
    on q), the call is plain causal attention by the flash implementation (no
    window, no document mask), and flash_takes_qkv finds tiled kernels that
    read these heads in place (T, head size, block sizes) and a mesh case
    that reaches them, on a TPU."""
    if kv is not None or rope is not None or segments is not None or window:
        return False
    if cfg.attention_multiplier or cfg.qk_norm_whole or "q_norm" in attn:
        return False
    return cfg.attention_impl == "flash" and flash_takes_qkv(
        shape[:3] + shape[4:], shape[4] // cfg.head_dim,
        block_q=cfg.flash_block_q, block_kv=cfg.flash_block_kv,
    )


def _attention_core(
    blk: Params,
    x: jax.Array,
    cfg: ModelConfig,
    rope: Optional[Tuple[jax.Array, jax.Array]],
    positions: jax.Array,
    kv: Optional[Params],
    cache_index: Optional[jax.Array],
    zigzag: bool,
    pad_offsets: Optional[jax.Array],
    segments: Optional[jax.Array],
    paged: Optional[PagedInfo],
    residual: bool,
    window: int,
) -> Tuple[jax.Array, Optional[Params]]:
    """Per-head attention of ``_attention_block`` with the layer's ``window``
    (0 = full) and ``rope`` (None = no position encoding) settled."""
    cdt = jnp.dtype(cfg.compute_dtype)
    h = layers.norm_in(cfg, blk["ln1"], x)
    # With no cache to write (training, evaluation) the projections see ONE
    # head of H*Dh lanes: the same dots, whose results end in (1, H*Dh) and
    # not (H, Dh), split into heads again after. The TPU compiler lays a dot's
    # result out by the shape the dot gives it. One that ends in (H, 64) it
    # keeps with T minor-most (64 is half a lane tile) and then copies,
    # transposing, into and out of every flash call; one that ends in H*64
    # lanes stays as the flash kernels read it (ops/pallas_flash.py::
    # heads_in_place) and no copy stands beside the call (PR 50, the
    # optimised HLO of both training cells). The programs that write a cache
    # are what they were.
    # Where the fused projection's result can go to those kernels as it is,
    # (B, 3, T, H*Dh), it does (qkv_whole; _qkv_stays_whole has the rule): they
    # take q, k and v out of its planes themselves and hand back one d(qkv),
    # and neither three slice copies a forward pass nor the gradient put
    # together again from three stand beside the calls (PR 55). Every other
    # call slices, as ever.
    as_lanes = kv is None
    qkv_whole: Optional[jax.Array] = None

    def lanes(a: jax.Array) -> jax.Array:
        """(..., H, Dh) -> (..., 1, H*Dh) where the heads stay merged."""
        return a.reshape(a.shape[:-2] + (1, -1)) if as_lanes else a

    with jax.named_scope("attn.qkv"):
        if "wqkv" in blk["attn"]:
            qkv = jnp.einsum(
                "btd,dchn->bcthn", h.astype(cdt), lanes(_weight(blk["attn"], "wqkv", cdt)),
                preferred_element_type=jnp.float32,
            ).astype(cdt)
            if "bqkv" in blk["attn"]:
                bqkv = lanes(blk["attn"]["bqkv"].astype(cdt))  # (3, H, Dh)
                qkv = qkv + bqkv[None, :, None, :, :]
            if _qkv_stays_whole(cfg, blk["attn"], kv, rope, segments, window, qkv.shape):
                qkv_whole = qkv.reshape(qkv.shape[:3] + (-1,))
                q = k = v = None  # the kernels' to take out of qkv_whole
            else:
                q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        else:
            # GQA: H query heads, kv_heads <= H key/value heads.
            q = jnp.einsum(
                "btd,dhn->bthn", h.astype(cdt), lanes(_weight(blk["attn"], "wq", cdt)),
                preferred_element_type=jnp.float32,
            ).astype(cdt)
            kvp = jnp.einsum(
                "btd,dcgn->bctgn", h.astype(cdt), lanes(_weight(blk["attn"], "wkv", cdt)),
                preferred_element_type=jnp.float32,
            ).astype(cdt)
            if "bq" in blk["attn"]:
                bq = lanes(blk["attn"]["bq"].astype(cdt))  # (H, Dh)
                bkv = lanes(blk["attn"]["bkv"].astype(cdt))  # (2, G, Dh)
                q = q + bq[None, None]
                kvp = kvp + bkv[None, :, None]
            k, v = kvp[:, 0], kvp[:, 1]
        if as_lanes and qkv_whole is None:
            q, k, v = (a.reshape(a.shape[:2] + (-1, cfg.head_dim)) for a in (q, k, v))

    if cfg.attention_multiplier:
        # Scores times attention_multiplier where every attention form below
        # scales by 1/sqrt(head_dim): the ratio of the two goes onto the queries.
        with jax.named_scope("attn.qkv"):
            q = (q.astype(jnp.float32) * (cfg.attention_multiplier * cfg.head_dim ** 0.5)).astype(cdt)

    if cfg.qk_norm_whole:
        with jax.named_scope("attn.qk_norm"):
            # over all heads' channels at once, before the heads are cut
            whole = lambda p, a: layers.rmsnorm(
                p, a.reshape(a.shape[:2] + (-1,)), cfg.norm_eps).reshape(a.shape)
            q, k = whole(blk["attn"]["q_norm"], q), whole(blk["attn"]["k_norm"], k)
    elif "q_norm" in blk["attn"]:
        with jax.named_scope("attn.qk_norm"):
            q = layers.rmsnorm(blk["attn"]["q_norm"], q, cfg.norm_eps)
            k = layers.rmsnorm(blk["attn"]["k_norm"], k, cfg.norm_eps)

    if rope is not None:
        cos, sin = rope
        if paged is not None:
            # Paged decode: row i's j-th query token sits at logical
            # position seq_lens[i] + j (linear index within its own block
            # list; j > 0 only in the speculative verify).
            rope_pos = paged.seq_lens[:, None] + jnp.arange(
                k.shape[1], dtype=paged.seq_lens.dtype
            )[None, :]
        elif pad_offsets is not None:
            # Per-row logical positions: slot - left-pad offset. Pad slots
            # clip to 0; their K/V is masked out of every real attention.
            rope_pos = jnp.clip(positions[None, :] - pad_offsets[:, None], 0)
        else:
            rope_pos = positions
        with jax.named_scope("attn.rope"):
            q = layers.apply_rope(q, cos, sin, rope_pos)
            k = layers.apply_rope(k, cos, sin, rope_pos)

    # GQA: every attention path attends H query heads against G KV heads
    # directly when the layout allows it (no K/V expansion — the cache/HBM
    # bandwidth win; ring/ulysses additionally move G/H the KV bytes through
    # their collectives). Ring needs whole groups per tensor shard, ulysses
    # needs the KV heads to split over tensor x seq shards (see the
    # *_supports_grouped predicates); KV is repeated up front otherwise
    # (training-time only).
    n_rep = cfg.n_heads // cfg.kv_heads

    def rep(a: jax.Array) -> jax.Array:
        return jnp.repeat(a, n_rep, axis=2) if n_rep > 1 else a

    new_kv: Optional[Params] = None
    if kv is not None and "k_pool" in kv:
        # PAGED decode (serving): the cache is a POOL of fixed-size blocks
        # (n_blocks, block_size, G, Dh); each batch row owns an ordered list
        # of pool block ids (paged.block_tables) and a logical length
        # (paged.seq_lens). One step = scatter this token's K/V into the
        # row's slot seq_len, then attend over the row's gathered blocks
        # masked to <= seq_len. All shapes are static — the serving engine
        # admits/evicts requests by editing int32 tables host-side, never
        # recompiling. (The reference has no serving path at all; its
        # generate is batch-1 fixed-count, transformer.py:96-114.)
        if paged is None:
            raise ValueError(
                "a paged kv pool requires forward(..., paged=PagedInfo)"
            )
        bsz = q.shape[0]
        tq = k.shape[1]
        block_size = kv["k_pool"].shape[1]
        tables, seq = paged.block_tables, paged.seq_lens
        if window and cfg.two_lifetimes:
            if paged.window_tables is None:
                raise ValueError(
                    "a stack with two cache lifetimes reads its window layers' pool "
                    "through PagedInfo.window_tables"
                )
            tables = paged.window_tables  # the window layers' own pool and live pages
        # Token i of this call writes logical slot seq + i. tq == 1 is the
        # serving decode step; tq > 1 is the speculative-decoding paged
        # VERIFY (k+1 draft tokens through the target in one program —
        # prompts still enter via generation.paged.prefill_into_pool).
        # Multi-step scheduling overshoot guard: inside a fixed-length
        # decode window a row can pass its capacity (it gets reaped right
        # after); redirect such writes to the reserved scratch block
        # instead of letting the page index clamp onto the row's LAST
        # block and corrupt a live slot. Single-step schedulers never hit
        # this (check_paged_bounds), multi-step ones hit it by design.
        capacity = tables.shape[1] * block_size
        with jax.named_scope("attn.kv_write"):
            pos = seq[:, None] + jnp.arange(tq, dtype=seq.dtype)[None, :]  # (B,T)
            in_range = pos < capacity
            pos_c = jnp.minimum(pos, capacity - 1)
            blk_ids = jnp.where(
                in_range, tables[jnp.arange(bsz)[:, None], pos_c // block_size], 0
            )  # (B, T)
            slots = jnp.where(in_range, pos_c % block_size, 0)  # (B, T)
        quantized = "k_scale_pool" in kv

        def scatter(pool, val):
            # One (B, T)-indexed scatter per pool: rows own disjoint
            # blocks and a row's T slots are distinct, so indices collide
            # only on the reserved scratch block (idle rows, overshoot
            # redirects) — whose content is never unmasked. A pool with
            # padding heads (pool_kv_heads) gets whole rows: zeros there.
            return pool.at[blk_ids, slots].set(pad_kv_heads(val, pool.shape[-2]).astype(pool.dtype))

        with jax.named_scope("attn.kv_write"):
            if quantized:
                k_q, k_sc = _kv_quantize(k)
                v_q, v_sc = _kv_quantize(v)
                new_kv = {
                    "k_pool": scatter(kv["k_pool"], k_q),
                    "v_pool": scatter(kv["v_pool"], v_q),
                    "k_scale_pool": scatter(kv["k_scale_pool"], k_sc),
                    "v_scale_pool": scatter(kv["v_scale_pool"], v_sc),
                }
            else:
                new_kv = {
                    "k_pool": scatter(kv["k_pool"], k),
                    "v_pool": scatter(kv["v_pool"], v),
                }

        form = paged_attention_form(cfg, tq, quantized, mesh=current_mesh())
        if form == "kernel":
            # Gather-free: the Pallas kernel copies each row's LIVE pages
            # straight from the pool through the block table
            # (ops/pallas_paged.py), several a step of an in-row loop; the
            # slots of the table that hold nothing are never read.
            with jax.named_scope("attn.core"):
                out = paged_decode_attention(
                    q[:, 0].astype(cdt),
                    new_kv["k_pool"].astype(cdt),
                    new_kv["v_pool"].astype(cdt),
                    tables, seq, window=window, kv_heads=cfg.kv_heads,
                )
            out = out[:, None]
        else:
            max_blocks = tables.shape[1]
            kv_len = max_blocks * block_size

            def gather(pool):
                # (B, max_blocks, block_size, ...) -> (B, kv_len, ...): each
                # row's logical KV sequence, assembled from its pool blocks
                # (without the pool's padding heads, where it has any).
                rows = pool[tables].reshape((bsz, kv_len) + pool.shape[2:])
                return rows if pool.shape[2] == cfg.kv_heads else rows[:, :, : cfg.kv_heads]

            with jax.named_scope("attn.paged_gather"):
                if quantized:
                    ck = _kv_dequantize(
                        gather(new_kv["k_pool"]), gather(new_kv["k_scale_pool"]), cdt
                    )
                    cv = _kv_dequantize(
                        gather(new_kv["v_pool"]), gather(new_kv["v_scale_pool"]), cdt
                    )
                else:
                    ck = gather(new_kv["k_pool"]).astype(cdt)
                    cv = gather(new_kv["v_pool"]).astype(cdt)
            with jax.named_scope("attn.core"):
                lin = jnp.arange(kv_len)
                # Causality is the length mask, per query token: token i (at
                # logical slot seq+i) sees slots <= seq+i — its own just-
                # written K/V and everything before it. Unallocated table tail
                # entries point at arbitrary blocks but sit at linear indices
                # beyond the frontier — always masked.
                kv_mask = lin[None, None, :] <= pos[:, :, None]  # (B, T, kv_len)
                if window:
                    kv_mask = kv_mask & (
                        lin[None, None, :] > pos[:, :, None] - window
                    )
                out = multihead_attention(
                    q, ck, cv, impl="naive", causal=False, kv_mask=kv_mask
                )
    elif kv is not None:
        # Decode: write this step's K/V into the cache at cache_index, attend
        # over the whole (masked) cache. The cache is a per-layer dict
        # {'k','v'} (+ {'k_scale','v_scale'} when kv_cache_dtype='int8').
        tq = k.shape[1]
        quantized = "k_scale" in kv

        def write(buf, val):
            return jax.lax.dynamic_update_slice_in_dim(
                buf, val.astype(buf.dtype), cache_index, axis=1
            )

        with jax.named_scope("attn.kv_write"):
            if quantized:
                k_q, k_sc = _kv_quantize(k)
                v_q, v_sc = _kv_quantize(v)
                new_kv = {
                    "k": write(kv["k"], k_q),
                    "v": write(kv["v"], v_q),
                    "k_scale": write(kv["k_scale"], k_sc),
                    "v_scale": write(kv["v_scale"], v_sc),
                }
            else:
                new_kv = {"k": write(kv["k"], k), "v": write(kv["v"], v)}
        tmax = new_kv["k"].shape[1]
        # The flash-prefill shortcut is only valid when the write offset is
        # PROVABLY zero at trace time (a concrete 0, as the generate prefill
        # passes). A traced or nonzero offset — chunked prefill continuing
        # at index>0 — must attend the cached prefix too, so it keeps the
        # masked-einsum path; the contract is enforced here, not advisory.
        prefill_at_zero = cache_index is None or (
            not isinstance(cache_index, jax.core.Tracer) and int(cache_index) == 0
        )
        if (
            tq > 1
            and prefill_at_zero
            and pad_offsets is None  # ragged rows need the per-row kv mask
            and cfg.attention_impl in ("flash", "ring", "ulysses")
        ):
            # PREFILL (kv_cache set, Tq>1, cache_index==0): attending over
            # the written cache prefix [0, Tq) is exactly causal
            # self-attention over this block's local q/k/v, so it routes
            # through the flash kernel — O(block) memory instead of
            # materialized (Tq, Tmax) masked scores against the whole
            # cache, which re-acquired the O(T^2) wall at 8k prompts
            # (VERDICT r2 next #6). Single-token decode steps keep the
            # masked einsum below (per-step shapes are tiny). Ring/ulysses
            # are training-time layouts; their decode prefill uses flash
            # (the dispatch inside falls back safely under exotic meshes).
            with jax.named_scope("attn.core"):
                out = multihead_attention(
                    q, k, v, impl="flash",
                    block_q=cfg.flash_block_q, block_kv=cfg.flash_block_kv,
                    window=window,
                )
        elif (
            tq > 1
            and pad_offsets is None
            and cfg.attention_impl in ("flash", "ring", "ulysses")
        ):
            # CHUNKED prefill (traced or nonzero offset): rectangular
            # blockwise attention of this chunk's queries (positions
            # [cache_index, cache_index+tq)) against the cache —
            # O(block) transient memory instead of materialized
            # (Tq, Tmax) masked scores, GQA-native (grouped cache, never
            # expanded). No explicit length mask needed: slots at/above
            # the write frontier sit at positions > every query position,
            # so causality alone excludes them, and slots below hold the
            # valid prefix written by earlier chunks.
            from pretraining_llm_tpu.ops.flash_attention import blockwise_attention

            kv_view = new_kv
            k_lo = 0
            if not isinstance(cache_index, jax.core.Tracer):
                # Concrete offset (host-side chunk loops): slice off the
                # key blocks that lie entirely beyond the frontier before
                # dequant/attention — they would contribute only masked
                # scores (~2x the needed FLOPs on a mid-cache chunk).
                # Round up to the configured KV tile so the slice never
                # shrinks the block _pick_block would choose. With a
                # sliding window, ALSO slice off the below-window prefix
                # (tile-aligned down) — otherwise chunked windowed prefill
                # pays O(T^2) scanning keys that are entirely masked;
                # k_offset keeps the sliced keys' positions absolute.
                tile = cfg.flash_block_kv or 512
                hi = min(tmax, -(-(int(cache_index) + tq) // tile) * tile)
                if window:
                    k_lo = max(
                        0,
                        (int(cache_index) - window + 1)
                        // tile * tile,
                    )
                kv_view = {
                    name: buf[:, k_lo:hi] for name, buf in new_kv.items()
                }
            with jax.named_scope("attn.core"):
                ck, cv = _materialize_cache(kv_view, quantized, cdt)
                out = blockwise_attention(
                    q, ck, cv, causal=True,
                    block_q=cfg.flash_block_q, block_kv=cfg.flash_block_kv,
                    q_offset=cache_index, k_offset=k_lo,
                    window=window,
                )
        else:
            with jax.named_scope("attn.core"):
                kv_positions = jnp.arange(tmax)
                kv_mask = (kv_positions < cache_index + tq)[None, :]
                if pad_offsets is not None:
                    # Ragged rows: slots below each row's left-pad offset are
                    # dead (never written with real tokens) — mask them out.
                    kv_mask = kv_mask & (kv_positions[None, :] >= pad_offsets[:, None])
                cache_k, cache_v = _materialize_cache(new_kv, quantized, cdt)
                out = multihead_attention(
                    q,
                    cache_k,
                    cache_v,
                    impl="naive",
                    q_positions=positions,
                    kv_positions=kv_positions,
                    kv_mask=kv_mask,
                    window=window,
                )
    else:
        grouped_ok = cfg.attention_impl in ("naive", "flash")
        if cfg.attention_impl == "ring":
            from pretraining_llm_tpu.parallel.ring_attention import ring_supports_grouped

            grouped_ok = ring_supports_grouped(
                current_mesh(), cfg.n_heads, cfg.kv_heads
            )
        elif cfg.attention_impl == "ulysses":
            from pretraining_llm_tpu.parallel.ulysses import ulysses_supports_grouped

            grouped_ok = ulysses_supports_grouped(
                current_mesh(), cfg.n_heads, cfg.kv_heads
            )
        with jax.named_scope("attn.core"):
            if qkv_whole is not None:
                out = flash_attention_qkv(
                    qkv_whole, qkv_whole.shape[-1] // cfg.head_dim,
                    block_q=cfg.flash_block_q, block_kv=cfg.flash_block_kv,
                )
            else:
                out = multihead_attention(
                    q,
                    k if grouped_ok else rep(k),
                    v if grouped_ok else rep(v),
                    impl=cfg.attention_impl,
                    block_q=cfg.flash_block_q,
                    block_kv=cfg.flash_block_kv,
                    ring_layout="zigzag" if zigzag else "contiguous",
                    segments=segments,
                    window=window,
                )

    # Tag for the 'save_attn' remat policy: keep the (cheap-to-store,
    # expensive-to-recompute) attention output, recompute everything else.
    out = checkpoint_name(out, "attn_out")

    if "wg" in blk["attn"]:
        with jax.named_scope("attn.gate"):
            gate = jnp.einsum(
                "btd,dhn->bthn", h.astype(cdt), _weight(blk["attn"], "wg", cdt),
                preferred_element_type=jnp.float32,
            )
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(cdt)

    with jax.named_scope("attn.out"):
        if cfg.use_output_proj:
            wo = _weight(blk["attn"], "wo", cdt)  # (H, Dh, d)
            if as_lanes:
                wo = wo.reshape((1, -1) + wo.shape[2:])
            out = jnp.einsum(
                "bthn,hnd->btd", lanes(out), wo, preferred_element_type=jnp.float32,
            ).astype(cdt) + blk["attn"]["bo"].astype(cdt)
        else:
            # Reference shape (attention.py:95): concat heads is the output.
            b, t = out.shape[:2]
            out = out.reshape(b, t, cfg.n_heads * cfg.head_dim)
    out = layers.norm_out(cfg, blk["ln1"], out)
    return layers.join_residual(x, out, cfg.residual_multiplier, residual), new_kv


def _dense_mlp(mlp: Params, h: jax.Array, cfg: ModelConfig, limit: Any = None) -> jax.Array:
    """The dense FFN on normed input: w2 . act(w1 . h), in compute dtype.
    ``limit`` clamps a SwiGLU (``moe.swiglu``)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    if "w1_gate" in mlp:
        # the serving layout (serving_layout): w1's halves, a matmul each
        gate, up = (
            jnp.einsum(
                "btd,df->btf", h, _weight(mlp, name, cdt), preferred_element_type=jnp.float32
            ).astype(cdt)
            for name in ("w1_gate", "w1_up")
        )
        if "b1" in mlp:
            gate, up = gate + mlp["b1"][0].astype(cdt), up + mlp["b1"][1].astype(cdt)
        hidden = moe.swiglu(gate, up, limit)
    elif cfg.activation == "swiglu":
        gates = jnp.einsum(
            "btd,dcf->bctf", h, _weight(mlp, "w1", cdt), preferred_element_type=jnp.float32
        ).astype(cdt)
        if "b1" in mlp:
            gates = gates + mlp["b1"].astype(cdt)[None, :, None, :]
        hidden = moe.swiglu(gates[:, 0], gates[:, 1], limit)
    else:
        hidden = jnp.einsum(
            "btd,df->btf", h, _weight(mlp, "w1", cdt), preferred_element_type=jnp.float32
        ).astype(cdt)
        if "b1" in mlp:
            hidden = hidden + mlp["b1"].astype(cdt)
        hidden = layers.activation_fn(cfg.activation, hidden)
    out = jnp.einsum(
        "btf,fd->btd", hidden, _weight(mlp, "w2", cdt), preferred_element_type=jnp.float32
    ).astype(cdt)
    if "b2" in mlp:
        out = out + mlp["b2"].astype(cdt)
    return out


def _mlp_block(
    blk: Params, x: jax.Array, cfg: ModelConfig, decode: bool = False,
    residual: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Pre-LN MLP sub-block: x + mlp(ln2(x)). Returns (x, router aux loss);
    for a dropless expert layer the second value is the tokens routed to each
    expert, (E,) int32, instead."""
    cdt = jnp.dtype(cfg.compute_dtype)
    h = layers.norm_in(cfg, blk["ln2"], x).astype(cdt)
    mlp = blk["mlp"]
    with jax.named_scope("mlp"):
        if "router" in mlp and cfg.moe_dropless:
            out, aux = moe.moe_mlp_dropless(
                mlp, h, cfg,
                lambda shared, hh: _dense_mlp(shared, hh, cfg, mlp.get("shared_limit")),
            )
        elif "router" in mlp:
            out, aux = moe.moe_mlp(mlp, h, cfg, decode=decode)
        else:
            out, aux = _dense_mlp(mlp, h, cfg), jnp.zeros((), jnp.float32)
    out = layers.norm_out(cfg, blk["ln2"], out)
    return layers.join_residual(x, out, cfg.residual_multiplier, residual), aux


def _block(
    blk: Params,
    x: jax.Array,
    cfg: ModelConfig,
    rope: Optional[Tuple[jax.Array, jax.Array]],
    positions: jax.Array,
    kv: Optional[Params],
    cache_index: Optional[jax.Array],
    zigzag: bool = False,
    pad_offsets: Optional[jax.Array] = None,
    segments: Optional[jax.Array] = None,
    paged: Optional[PagedInfo] = None,
    lengths: Optional[jax.Array] = None,
    attn_kind: Optional[str] = None,
    mixer: str = "attn",
) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """One decoder layer. ``mixer`` is the layer's entry in the layer table
    (``cfg.layer_kinds``): "attn", a recurrent mixer of ``recurrent.MIXERS``, or
    "none". A layer of one sublayer is what ``blk`` holds: an FFN alone (no
    "attn"; its cache entry, empty, goes through as it came) or a mixer alone
    (no "mlp"), each under its one norm, x + f(N(x))."""
    if mixer == "none":
        x, aux = _mlp_block(blk, x, cfg, decode=kv is not None and x.shape[1] == 1)
        return x, kv, aux
    no_ffn = "mlp" not in blk  # then the layer's other sublayer is all of it: no router loss, no counts
    if mixer != "attn":
        if zigzag or segments is not None:
            raise ValueError("a recurrent layer has no ring layout and no document mask")
        x, new_kv = recurrent.mixer_block(mixer, blk, x, cfg, kv, pad_offsets, paged, lengths)
        if no_ffn:
            return x, new_kv, jnp.zeros((), jnp.float32)
        x, aux = _mlp_block(blk, x, cfg, decode=kv is not None and x.shape[1] == 1)
        return x, new_kv, aux
    if cfg.hc_mult > 1:
        # x is the token's (B, T, n, d) residual streams: each sublayer reads
        # one vector from them and writes its output back (models/hyper.py).
        coef = hyper.coefficients(blk["hc_attn"], x, cfg)
        y, new_kv = _attention_block(
            blk, hyper.read(coef, x), cfg, rope, positions, kv, cache_index, zigzag,
            pad_offsets, segments=segments, paged=paged, residual=False,
        )
        x = hyper.write(coef, x, y)
        coef = hyper.coefficients(blk["hc_mlp"], x, cfg)
        y, aux = _mlp_block(
            blk, hyper.read(coef, x), cfg, decode=kv is not None and x.shape[1] == 1,
            residual=False,
        )
        return hyper.write(coef, x, y), new_kv, aux
    if "ln1_post" in blk:
        # Sandwich norms: each sublayer's output is normed before it joins the residual.
        decode = kv is not None and x.shape[1] == 1
        y, new_kv = _attention_block(
            blk, x, cfg, rope, positions, kv, cache_index, zigzag, pad_offsets,
            segments=segments, paged=paged, residual=False, kind=attn_kind,
        )
        with jax.named_scope("blk.norm"):
            x = x + layers.apply_norm(cfg.norm, blk["ln1_post"], y, cfg.norm_eps)
        y, aux = _mlp_block(blk, x, cfg, decode=decode, residual=False)
        with jax.named_scope("blk.norm"):
            x = x + layers.apply_norm(cfg.norm, blk["ln2_post"], y, cfg.norm_eps)
        return x, new_kv, aux
    x, new_kv = _attention_block(
        blk, x, cfg, rope, positions, kv, cache_index, zigzag, pad_offsets,
        segments=segments, paged=paged, kind=attn_kind,
    )
    x = constrain(
        x, ("data", "fsdp"), "seq" if cfg.sequence_parallel else None, None
    )
    if no_ffn:
        return x, new_kv, jnp.zeros((), jnp.float32)
    # Uncapacitated MoE routing only for single-token decode steps: prefill
    # processes whole prompts, where capacity = token count would rebuild the
    # O(S^2) dispatch the grouped path exists to avoid.
    x, aux = _mlp_block(blk, x, cfg, decode=kv is not None and x.shape[1] == 1)
    x = constrain(
        x, ("data", "fsdp"), "seq" if cfg.sequence_parallel else None, None
    )
    return x, new_kv, aux


# ---------------------------------------------------------------------------
# Full forward
# ---------------------------------------------------------------------------


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array] = None,
    kv_cache: Optional[KVCache] = None,
    cache_index: Optional[jax.Array] = None,
    return_hidden: bool = False,
    return_aux: bool = False,
    return_pre_logits: bool = False,
    zigzag: bool = False,
    blocks_baked: bool = False,
    pad_offsets: Optional[jax.Array] = None,
    paged: Optional[PagedInfo] = None,
    return_moe_counts: bool = False,
    lengths: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Compute logits. tokens: (B, T) int32 -> logits (B, T, V) fp32.

    ``return_moe_counts=True`` (dropless expert models) additionally returns
    the tokens this call routed to each expert, per expert layer:
    (n_layers - n_dense_layers, E) int32.

    ``paged`` + a pool-layout ``kv_cache`` (make_paged_kv_pool) selects
    PAGED single-token decode for continuous-batching serving: block
    tables route each row's reads/writes through a shared block pool (see
    PagedInfo / generation.serving.ServingEngine).

    Training/eval: kv_cache=None. Decode: pass a stacked cache
    {'k','v'}: (L, B, Tmax, kv_heads, Dh) — plus {'k_scale','v_scale'}
    when ``kv_cache_dtype='int8'`` — and the integer write offset
    ``cache_index``; the updated cache is returned. Cached calls with T>1
    and a provably-zero ``cache_index`` (a concrete 0, as the generate
    prefill passes) take the flash-prefill shortcut under
    ``attention_impl != 'naive'``; a traced or nonzero offset (CHUNKED
    prefill) routes through rectangular blockwise attention against the
    cache — O(block) transient memory at any offset. impl='naive' keeps
    the masked einsum everywhere.

    ``return_hidden=True`` additionally returns intermediate activations
    {'block_outputs': (L, B, T, D), 'final_hidden': (B, T, D)} — the
    feature-extraction hook replacing the reference's bespoke
    ``forward_embedding`` methods (transformer.py:80-94, SURVEY §A Q3).

    ``return_aux=True`` additionally returns the summed MoE router
    load-balance loss (zero for dense models).

    ``zigzag=True`` declares that the caller permuted the sequence dim with
    `parallel.zigzag.zigzag_perm` (and passed the matching ``positions``);
    ring attention then uses the balanced zigzag chunk layout. loss_fn
    manages this automatically — set it manually only if you permute inputs
    yourself.

    ``blocks_baked=True`` declares that ``params['blocks']`` is stored in the
    interleaved-pipeline rank-major layout (parallel.pipeline
    .interleave_layout, baked by train_step.shard_train_state) — only valid
    when the pipelined path is active, and required for correctness with a
    baked state.

    ``pad_offsets`` (B,) int32 enables RAGGED cached decode (decode-only;
    requires ``kv_cache``): each row is left-padded by pad_offsets[i] dead
    slots, so a batch of different-length prompts decodes in lockstep —
    `generation.generate(..., prompt_lengths=...)` builds this layout. Row
    i's token at cache slot s has logical position s - pad_offsets[i]
    (RoPE / learned positions use logical; causality + cache writes use
    slots; the kv mask hides each row's pad slots).

    ``lengths`` (B,) int32: each row's true token count in a right-padded
    multi-token call (a bucketed prefill). Attention needs none (causality
    keeps the padding out of every real position); a recurrent layer
    (models/recurrent.py) does, to leave its state as of the last real token.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    b, t = tokens.shape
    if pad_offsets is not None and kv_cache is None:
        raise ValueError(
            "pad_offsets (ragged left-padded rows) is a cached-decode "
            "layout; training/eval calls must not pass it"
        )
    if paged is not None:
        if not _is_pool_cache(kv_cache):
            raise ValueError(
                "paged=PagedInfo requires a pool-layout kv_cache "
                "(make_paged_kv_pool)"
            )
        # t == 1: serving decode; small t > 1: speculative paged verify.
        # PROMPTS still enter via generation.paged.prefill_into_pool —
        # the in-forward path scatters tokens one slot past the frontier.
        if pad_offsets is not None:
            raise ValueError(
                "pad_offsets is the contiguous ragged layout; paged rows "
                "are ragged natively via seq_lens"
            )
    elif _is_pool_cache(kv_cache):
        raise ValueError(
            "a pool-layout kv_cache requires forward(..., paged=PagedInfo)"
        )
    if positions is None:
        start = cache_index if cache_index is not None else 0
        positions = start + jnp.arange(t)

    # Packed-document masking: derive per-token document ids from the
    # separator token IN-MODEL (no data-pipeline change — the uint16 token
    # stream already contains the per-document EOT appended at preprocess
    # time). Token i belongs to document #(separators strictly before i),
    # so the separator itself is the LAST token of its document; attention
    # never crosses a boundary. Training/eval only — generation of a
    # packed stream is meaningless, and validation forbids the combination.
    segments = None
    if cfg.doc_mask_token >= 0:
        if kv_cache is not None:
            raise ValueError(
                "doc_mask_token is a training/eval feature; cached decode "
                "must run with doc masking disabled"
            )
        is_sep = (tokens == cfg.doc_mask_token).astype(jnp.int32)
        segments = jnp.cumsum(is_sep, axis=1) - is_sep  # exclusive cumsum

    # Replicate the (vocab x fsdp)-sharded table explicitly before the
    # lookup: the gather's output sharding then propagates from the
    # batch-sharded token indices. Left implicit, XLA propagates the TABLE's
    # sharding onto the (B, T, D) output and then cannot reach the
    # batch-sharded constraint efficiently — the "[SPMD] involuntary full
    # rematerialization" replicate-then-reshard of the activations seen in
    # the multichip dryrun (XLA all-gathers the table either way).
    with jax.named_scope("embed"):
        emb_table = constrain(params["tok_embed"]["embedding"], None, None)
        x = emb_table[tokens].astype(cdt)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.embed_scale, cdt)
        if cfg.pos_embed == "learned":
            pos_table = constrain(params["pos_embed"]["embedding"], None, None)
            if paged is not None:
                # Each row's query tokens sit at their own logical positions
                # (seq + i); clip keeps overshoot rows (scratch-redirected
                # garbage by contract) inside the table.
                ppos = jnp.clip(
                    paged.seq_lens[:, None]
                    + jnp.arange(t, dtype=paged.seq_lens.dtype)[None, :],
                    0, cfg.context_length - 1,
                )
                x = x + pos_table[ppos].astype(cdt)
            elif pad_offsets is not None:
                logical = jnp.clip(positions[None, :] - pad_offsets[:, None], 0)
                x = x + pos_table[logical].astype(cdt)  # (B, T, D) per-row gather
            else:
                x = x + pos_table[positions].astype(cdt)[None]
    rope = None
    if cfg.pos_embed == "rope":
        rope = layers.rope_table(
            cfg.context_length, cfg.qk_rope_head_dim or cfg.head_dim, cfg.rope_theta, cfg.rope_yarn
        )
    x = constrain(x, ("data", "fsdp"), "seq" if cfg.sequence_parallel else None, None)
    if cfg.hc_mult > 1:
        x = hyper.copy_in(x, cfg)

    def in_stack(blk, experts, layer, limits=None):
        """``blk`` with its group's expert stack and its own place in it (see
        moe.moe_mlp_dropless): the stack is closed over, never sliced.
        ``limits``: the layer's two SwiGLU clamps (routed, shared), if its
        group has any."""
        if experts is None:
            return blk
        mlp = {**blk["mlp"], "experts": experts, "expert_layer": layer}
        if limits is not None:
            mlp.update(expert_limit=limits[0], shared_limit=limits[1])
        return {**blk, "mlp": mlp}

    def clamps_of(layers_of):
        """(n, 2) float32 clamps of a group's layers, or None if all are off."""
        both = [
            [lim[i] if lim else 0.0 for i in layers_of]
            for lim in (cfg.moe_swiglu_limits, cfg.moe_shared_swiglu_limits)
        ]
        return jnp.asarray(both, jnp.float32).T if any(map(any, both)) else None

    def scan_body(carry, layer_inputs, kind=None, mixer="attn"):
        x, aux_sum = carry
        if kv_cache is None:
            blk = layer_inputs
            x, _, aux = _block(
                blk, x, cfg, rope, positions, None, None, zigzag,
                segments=segments, lengths=lengths, attn_kind=kind, mixer=mixer,
            )
            if aux.ndim:  # a dropless layer's tokens per expert ride the outputs
                return (x, aux_sum), ((x if return_hidden else None), aux)
            return (x, aux_sum + aux), (x if return_hidden else None)
        blk, cache_layer = layer_inputs
        x, new_kv, aux = _block(
            blk, x, cfg, rope, positions, cache_layer, cache_index,
            pad_offsets=pad_offsets, paged=paged, lengths=lengths, attn_kind=kind, mixer=mixer,
        )
        if aux.ndim:
            return (x, aux_sum), (new_kv, aux)
        return (x, aux_sum + aux), new_kv

    body = remat.checkpoint_wrap(scan_body, cfg.remat)

    def without_experts(blocks):
        """(blocks less a dropless group's expert stack, that stack or None)."""
        if not (cfg.moe_dropless and "experts" in blocks.get("mlp", {})):
            return blocks, None
        mlp = {k: v for k, v in blocks["mlp"].items() if k != "experts"}
        return {**blocks, "mlp": mlp}, blocks["mlp"]["experts"]

    mesh = current_mesh()
    use_pipeline = (
        kv_cache is None
        and cfg.pipeline_stages > 1
        and mesh is not None
        and mesh.shape.get("pipe", 1) > 1
    )

    # The stack as groups of like layers, each one scan (layer_groups). A
    # homogeneous model is one group and traces exactly as it did before
    # groups existed. Each group: (the layers it holds, its stacked blocks).
    groups = layer_groups(params, cfg)
    counts_of: list = []  # each expert group's tokens per expert, (its layers, E)

    def scan_group(layers_of, stack, first, x, aux, cache=None):
        """One group's depth scan -> (x, aux, per-layer outputs). ``stack``
        holds every layer of the group's kind, the group's from ``first`` on."""
        blocks, experts = without_experts(stack)
        n = len(layers_of)
        # a mixed stack's runs are each of one attention kind and one mixer (cfg.layer_runs)
        kind = cfg.attn_kinds[layers_of[0]] if cfg.attn_kinds else None
        mixer = cfg.layer_kinds[layers_of[0]][0]
        step = body if kind is None and mixer == "attn" else remat.checkpoint_wrap(
            functools.partial(scan_body, kind=kind, mixer=mixer), cfg.remat)
        part = n != jax.tree.leaves(blocks)[0].shape[0]
        if part and cache is not None and experts is None:
            # A run that is part of its stack, in a serving program: the scan
            # walks the run's places in the stack and takes each layer out of
            # it there, as a scan over the whole stack takes its own. A slice
            # of the stack handed to the scan is a copy of the run's weights
            # (1.3 GB for three 7B-wide layers, beside pools that leave 2 GB).
            # Training keeps the slice: a gradient through an index would add
            # into a whole stack's worth of zeros a layer.
            def at_place(carry, inputs):
                place, cache_layer = inputs
                blk = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, place, 0, keepdims=False), blocks)
                return scan_body(carry, (blk, cache_layer), kind, mixer)

            (x, aux), out = jax.lax.scan(at_place, (x, aux), (jnp.arange(first, first + n, dtype=jnp.int32), cache))
            return x, aux, out
        if part:
            blocks = jax.tree.map(lambda a: a[first : first + n], blocks)
        xs = blocks if cache is None else (blocks, cache)
        if experts is not None:
            clamps = clamps_of(layers_of)

            # the expert stack is closed over; each layer gets its index in it
            def with_experts(carry, inputs):
                inputs, layer = inputs
                layer, limits = layer if clamps is not None else (layer, None)
                if cache is None:
                    return scan_body(carry, in_stack(inputs, experts, layer, limits), kind, mixer)
                return scan_body(carry, (in_stack(inputs[0], experts, layer, limits), inputs[1]), kind, mixer)

            idx = jnp.arange(first, first + n, dtype=jnp.int32)
            xs = (xs, idx if clamps is None else (idx, clamps))
            step = remat.checkpoint_wrap(with_experts, cfg.remat)
        (x, aux), out = jax.lax.scan(step, (x, aux), xs)
        if cfg.moe_dropless and "router" in blocks.get("mlp", {}):
            out, counts = out  # an expert group yields (outputs, tokens per expert)
            counts_of.append(counts)
        return x, aux, out

    def concat_groups(outs):
        return outs[0] if len(outs) == 1 else jax.tree.map(
            lambda *a: jnp.concatenate(a, axis=0), *outs
        )

    block_outputs = None
    aux0 = jnp.zeros((), jnp.float32)
    if blocks_baked and not use_pipeline:
        raise ValueError(
            "blocks_baked=True but the pipelined path is inactive (no pipe "
            "mesh installed, or pipeline_stages<=1): a rank-major baked "
            "layer stack would be scanned in the wrong depth order. "
            "De-interleave with parallel.pipeline.deinterleave_layout first."
        )
    if use_pipeline:
        if return_hidden:
            raise ValueError("return_hidden is not supported with pipeline parallelism")
        if len(groups) > 1:
            raise ValueError("pipeline parallelism takes a homogeneous layer stack")
        from pretraining_llm_tpu.parallel import pipeline

        def pipe_block(blk, h):
            h, _, aux = _block(blk, h, cfg, rope, positions, None, None, zigzag)
            return h, aux

        x, aux_total = pipeline.pipeline_apply(
            params["blocks"], x, mesh, pipe_block,
            n_micro=cfg.pipeline_microbatches, remat=cfg.remat,
            interleave=cfg.pipeline_interleave, baked=blocks_baked,
        )
        new_cache = None
    elif kv_cache is None:
        aux_total, outs = aux0, []
        for layers_of, stack, first in groups:
            x, aux_total, out = scan_group(layers_of, stack, first, x, aux_total)
            outs.append(out)
        block_outputs = concat_groups(outs) if return_hidden else None
        new_cache = None
    elif "layers" in kv_cache:
        # Per-layer cache (and every page pool): a trace-time python loop
        # over layers, each layer's cache leaves updated by ONE
        # dynamic-update-slice directly on the caller's carry, which XLA can
        # alias; a stacked cache riding the depth scan is a fresh (L, ...)
        # buffer every step. Layer weights are static slices of the stacked
        # block params and fold into their consumers.
        if t > cfg.decode_loop_max_tokens:
            # A long call (prefill): the layer loop would scale the program
            # and its compile time by n_layers. Stack, run the rolled scan
            # once, unstack: two cache copies a call. Short multi-token calls
            # (speculative verify rounds) repeat every few tokens and keep
            # the in-place loop below.
            aux_total, new_layers = aux0, []
            for layers_of, stack, first in groups:
                lyrs = [kv_cache["layers"][i] for i in layers_of]
                stacked_cache = {
                    name: jnp.stack([lyr[name] for lyr in lyrs]) for name in lyrs[0]
                }
                x, aux_total, new_stacked = scan_group(
                    layers_of, stack, first, x, aux_total, stacked_cache)
                new_layers += [
                    {name: buf[i] for name, buf in new_stacked.items()}
                    for i in range(len(lyrs))
                ]
        else:
            aux_total = aux0
            new_layers, counts = [], []
            for layers_of, stack, first in groups:
                blocks, experts = without_experts(stack)
                clamps = clamps_of(layers_of) if experts is not None else None
                for i, layer in enumerate(layers_of):
                    blk = jax.tree.map(
                        lambda a, _l=first + i: jax.lax.index_in_dim(
                            a, _l, 0, keepdims=False
                        ),
                        blocks,
                    )
                    blk = in_stack(blk, experts, first + i, None if clamps is None else clamps[i])
                    x, new_kv, aux = _block(
                        blk, x, cfg, rope, positions, kv_cache["layers"][layer],
                        cache_index, pad_offsets=pad_offsets, paged=paged, lengths=lengths,
                        attn_kind=cfg.attn_kinds[layer] if cfg.attn_kinds else None,
                        mixer=cfg.layer_kinds[layer][0],
                    )
                    if aux.ndim:
                        counts.append(aux)
                    else:
                        aux_total = aux_total + aux
                    new_layers.append(new_kv)
            if counts:
                counts_of.append(jnp.stack(counts))
        # layers past the stack's (the MTP module's cache: models/mtp.py) stay as they are
        new_cache = {**kv_cache, "layers": tuple(new_layers) + tuple(kv_cache["layers"][cfg.n_layers:])}
    else:
        # Stacked dense cache (make_kv_cache(..., stacked=True)): the layers
        # ride the depth scan. For a caller that makes the cache, runs one
        # forward and hands the result on (prefill staging).
        aux_total, outs = aux0, []
        n_cached = jax.tree.leaves(kv_cache)[0].shape[0]
        for layers_of, stack, first in groups:
            cache = kv_cache if len(groups) == 1 and n_cached == cfg.n_layers else jax.tree.map(
                lambda a: a[layers_of.start : layers_of.stop], kv_cache
            )
            x, aux_total, out = scan_group(layers_of, stack, first, x, aux_total, cache)
            outs.append(out)
        if n_cached > cfg.n_layers:  # the MTP module's layer stays as it is
            outs.append(jax.tree.map(lambda a: a[cfg.n_layers :], kv_cache))
        new_cache = concat_groups(outs)

    if cfg.hc_mult > 1:
        x = hyper.sum_out(x)
    with jax.named_scope("final_norm"):
        x = layers.apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
        if cfg.logits_scaling != 1.0:
            # logits / logits_scaling, taken on the hidden state: the head is
            # linear (no bias with it), and every head downstream (lm_head, the
            # chunked CE, the engine's last-position head) then needs no copy
            x = x * jnp.asarray(1.0 / cfg.logits_scaling, x.dtype)
    if return_pre_logits:
        # Loss path: the chunked-CE head computes logits itself (see
        # _chunked_ce); hand back the final-norm hidden states.
        logits = x
    else:
        logits = lm_head(params, x, cfg)
    extras: Tuple[Any, ...] = ()
    if return_hidden:
        extras += ({"block_outputs": block_outputs, "final_hidden": x},)
    if return_aux:
        extras += (aux_total,)
    if return_moe_counts:
        extras += (jnp.concatenate(counts_of) if len(counts_of) > 1 else (counts_of or [None])[0],)
    if extras:
        return (logits, new_cache) + extras
    return logits, new_cache


def lm_head(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """f32 logits (B, T, V) of final-norm hidden states (B, T, D)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    w_out, head_bias = _lm_head_weights(params, cfg)
    with jax.named_scope("lm_head"):
        logits = jnp.einsum(
            "btd,dv->btv", x.astype(cdt), w_out.astype(cdt), preferred_element_type=jnp.float32
        )
        if head_bias is not None:
            logits = logits + head_bias.astype(jnp.float32)
        return logits


# The chunk rule's two numbers: bytes of f32 logits a chunk may hold, and the
# fewest tokens worth a chunk. Module-level so a test reaches several chunks
# at a toy size.
_CE_CHUNK_BYTES = 512 * 1024 * 1024
_CE_MIN_CHUNK_TOKENS = 512


def _ce_n_chunks(s: int, vocab_size: int) -> int:
    """Chunks for ``s`` tokens of one device's f32 logits."""
    # Chunk only when the fp32 logits buffer is big enough to matter (XLA
    # already fuses the small-head case well — measured neutral-to-slower to
    # chunk at GPT-2 batch sizes). Target <= ~512 MB per chunk.
    logits_bytes = s * vocab_size * 4
    want = max(1, -(-logits_bytes // _CE_CHUNK_BYTES))
    n_chunks = 1
    if want > 1:
        # Any divisor of S with chunk >= 512 keeps the memory bound; prefer
        # the smallest chunk count >= want, else the largest available (an
        # awkward S loses granularity, not the whole saving).
        divisors = [c for c in range(2, s // _CE_MIN_CHUNK_TOKENS + 1) if s % c == 0]
        at_least = [c for c in divisors if c >= want]
        if at_least:
            n_chunks = min(at_least)
        elif divisors:
            n_chunks = max(divisors)
        if n_chunks < want:
            import warnings

            warnings.warn(
                f"chunked CE head: batch*seq={s} has no divisor >= {want} with "
                f"chunk >= {_CE_MIN_CHUNK_TOKENS}; using {n_chunks} chunks — logits memory "
                f"{logits_bytes / n_chunks / 2**20:.0f} MB/chunk exceeds the "
                f"{_CE_CHUNK_BYTES / 2**20:.0f} MB target. Prefer power-of-two batch*context products.",
                stacklevel=3,
            )
    return n_chunks


def _chunked_ce(
    hidden: jax.Array,
    w_out: jax.Array,
    bias: Optional[jax.Array],
    targets: jax.Array,
    cfg: ModelConfig,
    z: float = 0.0,
) -> jax.Array:
    """Mean cross-entropy head dispatcher (chunked | dense).

    chunked (default): no full (B*T, V) logits buffer. The fp32 logits for
    GPT-2-sized vocabs dwarf every other activation (B=12, T=1024,
    V=50304 -> 2.5 GB); computing them whole, saving them for backward, and
    re-reading them is pure HBM traffic. Instead scan over token chunks:
    each chunk's logits live only transiently, and the backward recomputes
    them chunk-by-chunk (one extra small matmul per chunk for a ~3x cut in
    head memory traffic). Under a mesh whose batch axes (data, fsdp) hold
    more than one device, the chunks are cut from each device's OWN tokens
    and the scans run per device (see _lse_saved_ce): left to the
    partitioner, the batch sharding lands on the chunk axis the scan walks,
    and every chunk's full-vocabulary f32 logits are all-reduced over the
    d-sharded head, forward and backward (18% of gpt2-xl's fsdp=4 step).
    dense: the OPPOSITE trade — deliberately materializes and SAVES the
    compute-dtype (S, V) logits so backward recomputes nothing (see
    _dense_lse_ce); head memory is S*V*2 bytes.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    b, t, d = hidden.shape
    s = b * t
    if cfg.ce_impl == "dense":
        # ZERO-recompute head: the backward of the chunked path re-runs the
        # (S, V) logits matmul (2*S*d*V FLOPs), while this path SAVES
        # compute-dtype logits (+ the f32 lse) and backward is just softmax +
        # the two unavoidable grad matmuls, at S*V*2 bytes of saved
        # residual and without the chunk scan's serialization. Numerics: backward's
        # softmax is exp(bf16-rounded logits - lse) vs the chunked path's
        # freshly recomputed f32-accum logits; grads agree to bf16 rounding
        # (tested) — the forward LOSS value is computed from f32-accum
        # logits either way and matches exactly.
        return _dense_lse_ce(
            hidden.reshape(s, d), w_out, bias, targets.reshape(s), cdt, z=z
        ) / s
    # The devices the batch is spread over each chunk their own tokens; a
    # batch they do not divide stays with the partitioner.
    mesh = current_mesh()
    batch_axes = tuple(
        ax for ax in ("data", "fsdp") if mesh is not None and mesh.shape.get(ax, 1) > 1
    )
    shards = math.prod(mesh.shape[ax] for ax in batch_axes)
    if b % shards:
        batch_axes, shards = (), 1
    n_chunks = _ce_n_chunks(s // shards, cfg.vocab_size)
    xs = hidden.reshape(shards * n_chunks, s // (shards * n_chunks), d)
    ts_ = targets.reshape(xs.shape[:2])
    return _lse_saved_ce(xs, w_out, bias, ts_, cdt, z=z, mesh=mesh, batch_axes=batch_axes) / s


def _subtract_onehot(p: jax.Array, targets: jax.Array) -> jax.Array:
    """softmax-grad core: p - onehot(targets), WITHOUT a scatter.

    The obvious ``p.at[arange, t].add(-1)`` lowers to a TPU scatter, which
    linearizes the whole (S, V) fp32 block to scatter layout and back —
    profiled at ~8% of the entire gpt2-124m train step (the top two
    data-formatting ops in the 2026-08-01 hlo_stats capture, ~15 ms/step
    of pure relayout at b16). The iota-compare-subtract form fuses into
    the same elementwise pass that builds p: zero extra memory traffic.

    Contract: targets must lie in [0, vocab_size). The scatter form wrapped
    negative indices (``.at[t].add`` subtracts at column V+t); this form is a
    NO-OP for out-of-range ids, so the two differ if an ignore-index
    convention is ever added — route ignored positions through a loss MASK
    (as loss_fn's docmask path does), never a sentinel target id.
    """
    if __debug__ and not isinstance(targets, jax.core.Tracer):
        assert int(targets.min()) >= 0 and int(targets.max()) < p.shape[1], (
            "_subtract_onehot: targets outside [0, vocab) — use a loss mask, "
            "not a sentinel id"
        )
    cols = jax.lax.broadcasted_iota(jnp.int32, p.shape, dimension=1)
    return p - (cols == targets[:, None]).astype(p.dtype)


def _head_logits32(xc, wc, bias, cdt):
    """The ONE definition of head logits for both custom-VJP CE heads:
    compute-dtype operands, f32 accumulation, f32 bias add. The chunked and
    dense backward paths must stay numerically in lockstep — any change to
    this formula applies to both."""
    with jax.named_scope("lm_head"):
        logits = jnp.einsum(
            "sd,dv->sv", xc.astype(cdt), wc, preferred_element_type=jnp.float32
        )
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        return logits


def _lse_saved_ce(xs, w_out, bias, ts_, cdt, z=0.0, mesh=None, batch_axes=()):
    """Sum of per-token CE over chunked logits, custom VJP.

    vs `lax.scan(jax.checkpoint(chunk))`: the checkpointed backward re-runs
    the whole forward per chunk — logits matmul, then max + exp + sum for
    logsumexp, then ANOTHER exp for its VJP — four elementwise passes over
    the (S, V) block that exists only to rebuild what one saved (S,) vector
    already knows. Saving lse (4 bytes/token) lets the backward form
    softmax = exp(logits - lse) in ONE pass after the (unavoidable) logits
    matmul recompute. Matmul count and the fp32 dW scan carry are identical
    to the autodiff version — this strictly removes VPU reduction passes.

    Gradients match the checkpointed path to float-associativity: dlogits
    stays fp32 into the dX/dW matmuls exactly as autodiff would keep it.

    ``batch_axes`` (the mesh's data/fsdp axes of extent > 1, else empty):
    the leading dim of xs/ts_ is then sharded over them, each device's
    chunks contiguous, and both scans run inside a shard_map that is manual
    over those axes only (tensor/seq/pipe stay with the partitioner). The
    compute-dtype head weight enters replicated, so it is gathered over
    fsdp once a pass, and the devices' f32 partial dW (and db) are summed
    once after the backward scan; the partitioner then keeps each device's
    slice of the sum. A scan carry in global view cannot hold an unreduced
    partial sum, hence the manual region. With no batch axes this is the
    plain single-device code.
    """
    rows, whole = P(batch_axes), P()

    def per_device(f, in_specs, out_specs):
        if not batch_axes:
            return f
        return jax.shard_map(
            f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names=set(batch_axes), check_vma=False,
        )

    def logits_of(xc, wc, bias):
        return _head_logits32(xc, wc, bias, cdt)

    @jax.custom_vjp
    def ce(xs, w_out, bias):
        return _fwd(xs, w_out, bias)[0]

    def _fwd(xs, w_out, bias):
        def scan_chunks(xs, ts_, wc, bias):
            def chunk(carry, inp):
                xc, tc = inp
                logits = logits_of(xc, wc, bias)
                lse = jax.nn.logsumexp(logits, axis=-1)
                label_logit = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
                total = jnp.sum(lse - label_logit)
                if z:
                    # z-loss (PaLM/ST-MoE): z * lse^2 keeps softmax logits from
                    # drifting (lse ~ 0 means calibrated normalizers; also
                    # guards bf16 logit overflow at scale).
                    total = total + z * jnp.sum(jnp.square(lse))
                return carry + total, lse

            total, lses = jax.lax.scan(chunk, jnp.zeros((), jnp.float32), (xs, ts_))
            if batch_axes:
                total = jax.lax.psum(total, batch_axes)
            return total, lses

        total, lses = per_device(
            scan_chunks, (rows, rows, whole, whole), (whole, rows)
        )(xs, ts_, w_out.astype(cdt), bias)
        return total, (xs, w_out, bias, lses)

    def _bwd(res, g):
        xs, w_out, bias, lses = res

        def scan_chunks(xs, ts_, lses, wc, bias, g):
            dw0 = jnp.zeros(wc.shape, jnp.float32)
            db0 = None if bias is None else jnp.zeros(bias.shape, jnp.float32)

            def chunk(carry, inp):
                dw_acc, db_acc = carry
                xc, tc, lse = inp
                logits = logits_of(xc, wc, bias)
                p = jnp.exp(logits - lse[:, None])  # softmax, one pass
                if z:
                    # d(lse^2)/dlogits = 2*lse*softmax -> fold into p's scale.
                    p = p * (1.0 + 2.0 * z * lse[:, None])
                dlogits = _subtract_onehot(p, tc) * g  # fp32
                dx = jnp.einsum(
                    "sv,dv->sd", dlogits, wc, preferred_element_type=jnp.float32
                )
                dw_acc = dw_acc + jnp.einsum(
                    "sd,sv->dv", xc.astype(cdt), dlogits,
                    preferred_element_type=jnp.float32,
                )
                if db_acc is not None:
                    db_acc = db_acc + jnp.sum(dlogits, axis=0)
                return (dw_acc, db_acc), dx.astype(xs.dtype)

            (dw, db), dxs = jax.lax.scan(chunk, (dw0, db0), (xs, ts_, lses))
            if batch_axes:  # the devices' partial sums meet once, after the scan
                dw, db = jax.lax.psum((dw, db), batch_axes)
            return dxs, dw, db

        dxs, dw, db = per_device(
            scan_chunks, (rows, rows, rows, whole, whole, whole), (rows, whole, whole)
        )(xs, ts_, lses, w_out.astype(cdt), bias, g)
        return (
            dxs,
            dw.astype(w_out.dtype),
            None if bias is None else db.astype(bias.dtype),
        )

    ce.defvjp(_fwd, _bwd)
    return ce(xs, w_out, bias)


def _dense_lse_ce(x, w_out, bias, ts_, cdt, z=0.0):
    """Sum of per-token CE with SAVED logits — no backward recompute.

    Custom VJP saving (compute-dtype logits, f32 lse): forward computes the
    (S, V) logits once with f32 accumulation (loss value identical to the
    chunked path), backward rebuilds softmax in one elementwise pass from
    the saved block and goes straight to the dX/dW matmuls. The matmul the
    chunked backward re-runs simply never happens again.
    """
    @jax.custom_vjp
    def ce(x, w_out, bias):
        return _fwd(x, w_out, bias)[0]

    def _fwd(x, w_out, bias):
        logits = _head_logits32(x, w_out.astype(cdt), bias, cdt)
        lse = jax.nn.logsumexp(logits, axis=-1)
        label_logit = jnp.take_along_axis(logits, ts_[:, None], axis=-1)[:, 0]
        total = jnp.sum(lse - label_logit)
        if z:
            total = total + z * jnp.sum(jnp.square(lse))  # see _lse_saved_ce
        # Save in compute dtype: halves the residual vs f32 at bf16-rounding
        # cost in backward only (the fp32 loss above is already computed).
        return total, (x, w_out, bias, logits.astype(cdt), lse)

    def _bwd(res, g):
        x, w_out, bias, logits_c, lse = res
        p = jnp.exp(logits_c.astype(jnp.float32) - lse[:, None])
        if z:
            p = p * (1.0 + 2.0 * z * lse[:, None])  # see _lse_saved_ce
        dlogits = _subtract_onehot(p, ts_) * g  # fp32
        dx = jnp.einsum(
            "sv,dv->sd", dlogits, w_out.astype(cdt),
            preferred_element_type=jnp.float32,
        )
        dw = jnp.einsum(
            "sd,sv->dv", x.astype(cdt), dlogits,
            preferred_element_type=jnp.float32,
        )
        db = None if bias is None else jnp.sum(dlogits, axis=0)
        return (
            dx.astype(x.dtype),
            dw.astype(w_out.dtype),
            None if bias is None else db.astype(bias.dtype),
        )

    ce.defvjp(_fwd, _bwd)
    return ce(x, w_out, bias)


def loss_fn(
    params: Params,
    tokens: jax.Array,
    targets: jax.Array,
    cfg: ModelConfig,
    *,
    include_aux: bool = True,
    blocks_baked: bool = False,
) -> jax.Array:
    """Mean next-token cross-entropy in fp32 (reference: transformer.py:73-77).

    Computed via the chunked head (see _chunked_ce) — numerically identical
    to logsumexp over full logits, but O(1/n_chunks) head memory. For MoE
    models the Switch-style router load-balance loss is added with weight
    ``cfg.router_aux_coef`` when ``include_aux`` (training objective); eval
    passes include_aux=False so reported val_loss stays pure cross-entropy,
    comparable across dense and MoE models.

    With zigzag ring attention active (attention_impl='ring',
    ring_layout='zigzag', a seq>1 mesh), tokens/targets/positions are
    permuted here into the balanced chunk-pair layout — mean CE is
    permutation invariant, so the loss value is identical to the dense
    computation (tested) while causal ring work balances across devices.
    """
    positions = None
    zigzag = False
    if cfg.attention_impl == "ring" and cfg.ring_layout == "zigzag":
        mesh = current_mesh()
        n_seq = mesh.shape.get("seq", 1) if mesh is not None else 1
        if n_seq > 1:
            if tokens.shape[1] % (2 * n_seq) == 0:
                from pretraining_llm_tpu.parallel.zigzag import zigzag_perm

                perm = zigzag_perm(tokens.shape[1], n_seq)
                # Re-pin the batch/seq sharding after the permutation: the
                # gather's output sharding is otherwise ambiguous to XLA's
                # propagation, which falls back to replicate-then-reshard on
                # the embedding lookup downstream ("[SPMD] involuntary full
                # rematerialization" warnings). Constrained here, the zigzag
                # shuffle is one explicit (B, T) int32 collective permute and
                # the embedding gather stays shard-local.
                tokens = constrain(tokens[:, perm], ("data", "fsdp"), "seq")
                targets = constrain(targets[:, perm], ("data", "fsdp"), "seq")
                positions = jnp.asarray(perm)
                zigzag = True
            else:
                import warnings

                warnings.warn(
                    f"ring_layout='zigzag' configured but seq_len="
                    f"{tokens.shape[1]} is not divisible by 2*seq_axis="
                    f"{2 * n_seq}; falling back to the imbalanced contiguous "
                    "ring layout (utilization ~(n+1)/2n).",
                    stacklevel=2,
                )
    hidden, _, aux = forward(
        params, tokens, cfg, positions=positions, zigzag=zigzag,
        return_aux=True, return_pre_logits=True, blocks_baked=blocks_baked,
    )
    w_out, bias = _lm_head_weights(params, cfg)
    # z-loss is part of the TRAINING objective only — include_aux=False
    # (eval) keeps reported val_loss pure cross-entropy, exactly like the
    # MoE router aux term.
    with jax.named_scope("loss.ce"):
        loss = _chunked_ce(
            hidden, w_out, bias, targets, cfg,
            z=cfg.z_loss_coef if include_aux else 0.0,
        )
    if cfg.n_experts and include_aux:
        loss = loss + cfg.router_aux_coef * aux
    return loss


def _is_pool_cache(kv_cache: Optional[KVCache]) -> bool:
    """True for a page pool (make_paged_kv_pool), false for a dense cache."""
    layers_ = (kv_cache or {}).get("layers")
    # the first layer that keeps a cache at all (a layer with no mixer keeps none)
    first = next((lyr for lyr in layers_ or () if lyr), {})
    return any(name in first for name in ("k_pool", "latent_pool", "state_pool"))


def _unstack_fields(
    cfg: ModelConfig, fields: Dict[str, Tuple[Tuple[int, ...], Any]],
    state_fields: Optional[Dict[str, Tuple[Tuple[int, ...], Any]]] = None,
    window_fields: Optional[Dict[str, Tuple[Tuple[int, ...], Any]]] = None,
) -> KVCache:
    """{'layers': per-layer dicts of fresh zero arrays} from {name:
    (stacked_shape, dtype)} specs — allocated per layer DIRECTLY (never
    materializing the stacked array first: pools are sized toward HBM
    capacity, and a transient 2x would OOM engines that otherwise fit).
    Each layer gets its own buffers (sharing one zeros across carry
    leaves would alias donated updates). A recurrent layer of a hybrid stack keeps
    ``state_fields`` ({name: (shape, dtype)}, no layer dimension) instead, a
    window layer of a stack with two cache lifetimes ``window_fields`` (specs
    like ``fields``, its own pool's size)."""
    window = tuple(window_fields is not None and k == "window" for k in cfg.layer_attn_kinds)
    return {
        "layers": tuple(
            {} if mixer == "none" else  # an FFN alone keeps nothing between calls
            {name: jnp.zeros(shape, dt) for name, (shape, dt) in state_fields.items()}
            if mixer != "attn" else
            {name: jnp.zeros(shape[1:], dt)
             for name, (shape, dt) in (window_fields if own else fields).items()}
            # the stack's layers, then the MTP module's block (an attention layer)
            for (mixer, _), own in zip(
                cfg.layer_kinds + (("attn", ""),) * cfg.mtp_depth, window + (False,) * cfg.mtp_depth)
        )
    }


def make_kv_cache(
    cfg: ModelConfig, batch_size: int, max_length: int, dtype: Any = None,
    *, stacked: bool = False,
) -> KVCache:
    """Dense decode cache: {'layers': (per-layer dicts of (B, T, G, Dh)
    fields,)}, each leaf updated in place on the caller's token-scan carry —
    the container for a decode loop. ``stacked=True`` gives {(L, B, T, G, Dh)}
    fields that ride the depth scan instead: for a caller that fills the
    cache with one forward and hands it on (prefill staging), where there is
    no carry to alias and the rolled scan keeps the program O(1) in depth."""
    if max_length > cfg.context_length:
        # Position tables (learned or RoPE) are sized by context_length; JAX
        # gather would silently clamp out-of-range positions — fail fast here.
        raise ValueError(
            f"kv cache max_length={max_length} exceeds context_length={cfg.context_length}"
        )
    # GQA caches only kv_heads heads — the memory win that motivates GQA.
    shape = (cfg.n_cache_layers, batch_size, max_length, cfg.kv_heads, cfg.head_dim)
    if cfg.kv_lora_rank:
        # Latent attention caches one latent and one rotated key slice a
        # token, shared by all heads (two fields: see models/mla.py).
        dt = jnp.dtype(dtype or cfg.compute_dtype)
        lead = (cfg.n_cache_layers, batch_size, max_length)
        fields = {"latent": (lead + (cfg.kv_lora_rank,), dt), "rope": (lead + (cfg.qk_rope_head_dim,), dt)}
    elif cfg.kv_cache_dtype == "int8":
        if dtype is not None:
            # An explicit element dtype contradicts the quantized layout;
            # dropping it silently would hand back an int8 cache to a
            # caller that asked for an exact fp baseline.
            raise ValueError(
                f"make_kv_cache(dtype={dtype!r}) conflicts with "
                "kv_cache_dtype='int8'; use kv_cache_dtype='compute' for an "
                "exact cache"
            )
        # Per-(token, head) symmetric int8: values + an fp32 amax scale.
        # Persistent cache bytes per element: 1 + 4/Dh vs 2 (bf16) — ~1.9x
        # smaller at Dh=64; the transient dequant is per-layer, per-step.
        sshape = shape[:-1] + (1,)
        fields = {
            "k": (shape, jnp.int8),
            "v": (shape, jnp.int8),
            "k_scale": (sshape, jnp.float32),
            "v_scale": (sshape, jnp.float32),
        }
    else:
        dtype = jnp.dtype(dtype or cfg.compute_dtype)
        fields = {"k": (shape, dtype), "v": (shape, dtype)}
    if stacked:
        if cfg.hybrid:
            raise ValueError("a hybrid stack's layers keep unlike caches: no stacked form")
        return {name: jnp.zeros(s, dt) for name, (s, dt) in fields.items()}
    return _unstack_fields(cfg, fields, recurrent.state_shapes(cfg, batch_size))


def make_paged_kv_pool(
    cfg: ModelConfig, n_blocks: int, block_size: int, dtype: Any = None,
    *, scale_dtype: Any = None, state_slots: int = 0, window_blocks: int = 0,
) -> KVCache:
    """Block POOL layout for paged serving decode (see PagedInfo).

    A stack of window and full attention layers (``cfg.two_lifetimes``) keeps
    two cache lifetimes: its full layers get ``n_blocks`` pages each, its
    window layers ``window_blocks`` each, a pool of their own under a table
    and an allocator of their own (``PagedInfo.window_tables``), block 0 the
    scratch there too. Every other model gives every layer ``n_blocks``.

    One pool a layer, {'layers': (per-layer dicts,)}: {'k_pool','v_pool'}:
    (n_blocks, block_size, kv_heads, Dh) (the head axis rounded up where
    ``ops/pallas_paged.py::pool_kv_heads`` says so: 30 heads of 128 are stored
    as 32, so that the decode kernel reads the pages in place), plus scale pools when
    ``kv_cache_dtype='int8'``. A latent (MLA) model pools ``latent_dim``
    values a token, the same for every head:
    {'latent_pool': (n_blocks, block_size / fold, fold * kv_lora_rank),
    'rope_pool': (n_blocks, block_size / fold, fold * qk_rope_head_dim)}
    (two fields, ``fold`` slots side by side in a row of a page, so that both
    keep the TPU's natural layout: see models/mla.py).
    Block 0 is reserved by convention as the idle-row scratch target (the
    serving engine parks inactive batch rows on it); allocators hand out
    ids from 1. A model with a multi-token-prediction module (``mtp_depth``)
    gets one more layer of the same pages, index ``n_layers``, under the same
    block tables: the module's block's own cache (models/mtp.py).

    A hybrid stack (``cfg.hybrid``) gives pages to its attention layers only,
    per-head or latent; each recurrent layer keeps {'state_pool': (state_slots
    + 1, ...) float32, 'conv_pool': (state_slots + 1, kernel - 1, channels)},
    the shapes its mixer names (``recurrent.state_shapes``): a slot a batch
    row (the engine's ``max_batch``), a row's slot its index, and the last slot
    the scratch that a prefill's pad rows write (as block 0 is for pages).

    ``scale_dtype`` (int8 pools only) picks the per-(slot, head) scale
    element type: fp32 by default (historical layout, bit-compatible with
    the dense int8 cache), bfloat16 for the ``serving.quantize=int8-kv``
    mode — per-slot bytes drop from Dh+4 to Dh+2, so an int8-kv pool
    holds 2*Dh/(Dh+2) ≈ 1.94x (Dh=64) the blocks of a bf16 pool at equal
    HBM budget (fp32 scales stall at 1.88x, under the 1.9x capacity
    target). The quantize scatter casts the fp32 amax to bf16 at write
    and every dequant upcasts back to fp32, so page bytes stay a pure
    function of the token's hidden state (the bit-identity contract).
    """
    if n_blocks < 2:
        raise ValueError("need n_blocks >= 2 (block 0 is the idle scratch)")
    if block_size % 8:
        # TPU sublane granularity; also keeps page gathers tile-aligned.
        raise ValueError(f"block_size must be a multiple of 8, got {block_size}")
    # an unquantized per-head pool's head axis may carry padding (pool_kv_heads)
    heads = cfg.kv_heads if cfg.kv_cache_dtype == "int8" else pool_kv_heads(cfg.kv_heads, cfg.head_dim)
    shape = (cfg.n_cache_layers, n_blocks, block_size, heads, cfg.head_dim)
    if cfg.kv_lora_rank:
        if scale_dtype is not None:
            raise ValueError("a latent pool has no int8 pages yet (ROADMAP)")
        dt = jnp.dtype(dtype or cfg.compute_dtype)
        fold = mla.page_fold(block_size, cfg.qk_rope_head_dim)
        page = (cfg.n_cache_layers, n_blocks, block_size // fold)
        fields = {
            "latent_pool": (page + (fold * cfg.kv_lora_rank,), dt),
            "rope_pool": (page + (fold * cfg.qk_rope_head_dim,), dt),
        }
    elif cfg.kv_cache_dtype == "int8":
        if dtype is not None:
            raise ValueError(
                f"make_paged_kv_pool(dtype={dtype!r}) conflicts with "
                "kv_cache_dtype='int8'"
            )
        sdt = jnp.dtype(scale_dtype or jnp.float32)
        if sdt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
            raise ValueError(
                f"int8 pool scale_dtype must be float32 or bfloat16, got {sdt}"
            )
        sshape = shape[:-1] + (1,)
        fields = {
            "k_pool": (shape, jnp.int8),
            "v_pool": (shape, jnp.int8),
            "k_scale_pool": (sshape, sdt),
            "v_scale_pool": (sshape, sdt),
        }
    else:
        if scale_dtype is not None:
            raise ValueError(
                f"make_paged_kv_pool(scale_dtype={scale_dtype!r}) needs "
                "kv_cache_dtype='int8' (exact pools carry no scale pages)"
            )
        dtype = jnp.dtype(dtype or cfg.compute_dtype)
        fields = {"k_pool": (shape, dtype), "v_pool": (shape, dtype)}
    state_fields = None
    if cfg.hybrid:
        if state_slots < 1:
            raise ValueError("a hybrid stack's state pools need state_slots (the batch rows)")
        state_fields = {
            name + "_pool": spec for name, spec in recurrent.state_shapes(cfg, state_slots + 1).items()
        }
    # Per-layer pools update in place on the serving window's token-scan
    # carry (see make_kv_cache).
    window_fields = None
    if cfg.two_lifetimes:
        if window_blocks < 2:
            raise ValueError(
                "a stack with two cache lifetimes needs window_blocks >= 2, its window "
                "layers' own pool (block 0 is the idle scratch)"
            )
        window_fields = {
            name: ((shape[0], window_blocks) + shape[2:], dt) for name, (shape, dt) in fields.items()
        }
    elif window_blocks:
        raise ValueError("window_blocks is for a stack of window and full attention layers")
    pools = _unstack_fields(cfg, fields, state_fields, window_fields)
    if state_fields:
        # where generation.paged.prefill_into_pool puts a prompt it is given no slot for
        pools["state_cursor"] = jnp.zeros((), jnp.int32)
    return pools


def _kv_quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 per-(token, head) over the channel dim."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1, keepdims=True), 1e-8)
    q = jnp.round(x32 / scale * 127.0).astype(jnp.int8)
    return q, scale


def _kv_dequantize(q: jax.Array, scale: jax.Array, dtype: Any) -> jax.Array:
    # Scale upcast FIRST: bf16 scale pools (int8-kv serving) must multiply
    # in fp32 like the historical fp32 scales do — JAX weak typing would
    # otherwise compute `scale * (1/127)` in bf16. Bit-wise a no-op for
    # fp32 scales.
    scale32 = scale.astype(jnp.float32)
    return (q.astype(jnp.float32) * (scale32 * (1.0 / 127.0))).astype(dtype)


def _materialize_cache(kv: Params, quantized: bool, dtype: Any):
    """(k, v) in compute dtype from a (possibly int8-quantized, possibly
    sliced) cache view — the single dequant point for every cached-attention
    read path."""
    if quantized:
        return (
            _kv_dequantize(kv["k"], kv["k_scale"], dtype),
            _kv_dequantize(kv["v"], kv["v_scale"], dtype),
        )
    return kv["k"].astype(dtype), kv["v"].astype(dtype)
