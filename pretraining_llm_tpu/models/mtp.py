"""The multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437 section 2.2).

One module beside the stack (``cfg.mtp_depth == 1``, ``params["mtp"]``). For
position ``p`` it takes the stack's output hidden state ``h_p`` and the token
that followed, ``x_{p+1}``:

    h'_p = W_eh . [RMSNorm_e(emb(x_{p+1})) ; RMSNorm_h(h_p)]        (2D -> D)

runs one whole decoder block of the stack's own kind over ``h'`` (the same
``transformer._block``: latent or per-head attention with a cache layer of its
own, dense FFN or the dropless experts with the shared one), a final norm of
its own, and the stack's head; the embedding is the stack's too. Its logits at
``p`` predict token ``p + 2``: the serving engine takes their argmax as the
draft of the next speculative round (``generation/paged.py::paged_mtp_round``).

``h_p`` is the stack's output *after* its final norm, as ``forward`` hands it
back (``return_hidden`` -> ``final_hidden``, or ``return_pre_logits``), which
is what the public serving implementations pass; the embedding half comes
first in the concatenation. Neither is settled by the sources' ``config.json``
(benchmark/configs/joyai-llm-flash.json lists both under ``assumed``).

The block's cache is layer ``cfg.n_layers`` of the same cache or page pool the
stack uses (``make_kv_cache`` / ``make_paged_kv_pool`` give it one), under the
same positions, cache index or block tables: a caller keeps it covering the
positions the stack's cache covers.

Scopes: ``mtp.embed_proj`` (lookup, both norms, concatenation, projection),
``mtp.block`` around the block (its ``attn.*``, ``mla.absorb`` and ``moe.*``
nest inside, so a reader can tell module from stack), ``mtp.head``.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from pretraining_llm_tpu.config import ModelConfig
from pretraining_llm_tpu.models import layers, transformer
from pretraining_llm_tpu.models.transformer import KVCache, PagedInfo, Params


def _module_layer(kv_cache: KVCache, cfg: ModelConfig) -> Params:
    """The module's layer of a cache or pool: per layer, or a stacked cache's slice."""
    if "layers" in kv_cache:
        return kv_cache["layers"][cfg.n_layers]
    return {name: a[cfg.n_layers] for name, a in kv_cache.items()}


def _with_module_layer(kv_cache: KVCache, cfg: ModelConfig, new: Params) -> KVCache:
    if "layers" in kv_cache:
        lyrs = kv_cache["layers"]
        return {**kv_cache, "layers": lyrs[: cfg.n_layers] + (new,) + lyrs[cfg.n_layers + 1 :]}
    return {name: a.at[cfg.n_layers].set(new[name]) for name, a in kv_cache.items()}


def mtp_forward(
    params: Params,
    hidden: jax.Array,  # (B, T, D): the stack's output hidden state of positions p
    next_tokens: jax.Array,  # (B, T) int32: the token at p + 1 of each
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array] = None,
    kv_cache: Optional[KVCache] = None,
    cache_index: Optional[jax.Array] = None,
    paged: Optional[PagedInfo] = None,
    return_pre_logits: bool = False,
) -> Tuple[jax.Array, Optional[KVCache], Any]:
    """The module over ``T`` positions a row -> (logits (B, T, V) float32 that
    predict token p + 2, the cache with the module's layer updated, the
    block's tokens per expert (E,) int32 or a zero for a dense block).

    Cache arguments as ``transformer.forward``'s: none (the whole sequence at
    once), a dense ``kv_cache`` + ``cache_index``, or a page pool + ``paged``
    (positions ``seq_lens + i``, written through the row's block table).
    ``return_pre_logits`` hands back the module's normed hidden state instead
    of logits, for a caller that runs the head on some positions only."""
    if not cfg.mtp_depth:
        raise ValueError("the model has no multi-token-prediction module (mtp_depth)")
    m = params["mtp"]
    cdt = jnp.dtype(cfg.compute_dtype)
    t = next_tokens.shape[1]
    if positions is None:
        positions = (cache_index if cache_index is not None else 0) + jnp.arange(t)
    with jax.named_scope("mtp.embed_proj"):
        emb = params["tok_embed"]["embedding"][next_tokens].astype(cdt)
        both = jnp.concatenate(
            [
                layers.apply_norm(cfg.norm, m["enorm"], emb, cfg.norm_eps).astype(cdt),
                layers.apply_norm(cfg.norm, m["hnorm"], hidden.astype(cdt), cfg.norm_eps).astype(cdt),
            ],
            axis=-1,
        )
        x = jnp.einsum(
            "btc,cd->btd", both, layers.weight(m, "eh_proj", cdt), preferred_element_type=jnp.float32
        ).astype(cdt)
    rope = layers.rope_table(
        cfg.context_length, cfg.qk_rope_head_dim or cfg.head_dim, cfg.rope_theta, cfg.rope_yarn
    )
    kv = None if kv_cache is None else _module_layer(kv_cache, cfg)
    with jax.named_scope("mtp.block"):
        x, new_kv, aux = transformer._block(
            m["block"], x, cfg, rope, positions, kv, cache_index, paged=paged
        )
    if kv_cache is not None:
        kv_cache = _with_module_layer(kv_cache, cfg, new_kv)
    with jax.named_scope("mtp.head"):
        x = layers.apply_norm(cfg.norm, m["final_norm"], x, cfg.norm_eps)
        out = x if return_pre_logits else transformer.lm_head(params, x, cfg)
    return out, kv_cache, aux
