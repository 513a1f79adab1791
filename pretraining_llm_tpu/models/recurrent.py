"""The cache discipline of a recurrent mixer: a layer whose cache is a
fixed-size state a row (``models/kda.py``, ``models/mamba.py``,
``models/gdn.py``), where an
attention layer's is pages.

A mixer is a module with ``init_params``, ``state_shapes`` ({"state": float32,
"conv": compute dtype} for so many rows), ``step_form`` and ``mix`` (the
sublayer's input, state and conv tail in; output, new state and new tail out,
under a validity mask), and ``SCOPE``, the prefix of its device scopes.
``MIXERS`` names them as the layer table does (``ModelConfig.layer_kinds``);
whoever asks "does this model keep state slots, and of what shape" asks here
and the table, never a mixer by name.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from pretraining_llm_tpu.config import ModelConfig
from pretraining_llm_tpu.models import gdn, kda, layers, mamba

Params = Dict[str, Any]

MIXERS = {"kda": kda, "mamba": mamba, "gdn": gdn}


def state_shapes(cfg: ModelConfig, rows: int) -> Optional[Dict[str, Tuple[Tuple[int, ...], Any]]]:
    """What ``rows`` rows keep in each recurrent layer of ``cfg``'s stack,
    {"state", "conv"}: (shape, dtype); None for a stack of attention layers."""
    return MIXERS[cfg.state_mixer].state_shapes(cfg, rows) if cfg.hybrid else None


def step_form(cfg: ModelConfig, rows: int, mesh: Any = None) -> Optional[str]:
    """The form one token of the recurrence takes over a state pool of ``rows``
    slots (the mixer's own ``step_form``, read from the pool's shape and
    dtype); None without recurrent layers."""
    if not cfg.hybrid:
        return None
    shape, dtype = state_shapes(cfg, rows)["state"]
    return MIXERS[cfg.state_mixer].step_form(jax.ShapeDtypeStruct(shape, dtype), mesh=mesh)


def mixer_block(
    mixer: str, blk: Params, x: jax.Array, cfg: ModelConfig, kv: Optional[Params],
    pad_offsets: Optional[jax.Array] = None, paged: Any = None,
    lengths: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    """The recurrent counterpart of ``transformer._attention_block``:
    x + mix(ln1(x)), or x + ln1(mix(x)) where the norm stands on the output
    (``cfg.norm_placement``), and the layer's new cache. ``kv`` is None (training
    forward: a fresh state, nothing kept), ``{"state": (B,...), "conv":
    (B,kernel-1,C)}`` (a contiguous cache: the call starts from it) or
    ``{"state_pool", "conv_pool"}`` (serving: slot ``paged.slots[b]`` of the
    pools, or row b's own slot; a row whose table names no page is dead and
    leaves its slot alone). ``lengths`` (B,) are the rows' true token counts in
    a right-padded call, ``pad_offsets`` (B,) their left padding in a ragged
    one."""
    mod = MIXERS[mixer]
    b, t, _ = x.shape
    h = layers.norm_in(cfg, blk["ln1"], x)
    joined = lambda y: layers.join_residual(
        x, layers.norm_out(cfg, blk["ln1"], y), cfg.residual_multiplier)

    pos = jnp.arange(t)[None, :]
    valid = ends = None
    if paged is not None and paged.q_lens is not None and t > 1:
        lengths = paged.q_lens
    if lengths is not None and t > 1:
        valid, ends = pos < lengths[:, None], lengths.astype(jnp.int32)
    elif pad_offsets is not None and t > 1:
        valid = pos >= pad_offsets[:, None]  # a decode step's token is real in every row
    if kv is None:
        shapes = mod.state_shapes(cfg, b)
        state, tail = (jnp.zeros(*shapes[name]) for name in ("state", "conv"))
        y, _, _ = mod.mix(blk["attn"], h, cfg, state, tail, valid, ends)
        return joined(y), None
    if "state_pool" not in kv:
        y, state, tail = mod.mix(blk["attn"], h, cfg, kv["state"], kv["conv"], valid, ends)
        return joined(y), {"state": state, "conv": tail}
    if paged is None:
        raise ValueError("a state pool requires forward(..., paged=PagedInfo)")
    spool, cpool = kv["state_pool"], kv["conv_pool"]
    if paged.slots is None:
        # A row's slot is its index, so the recurrence runs over the pools as
        # they lie, every slot a row (the scratch slot a row of zeros): no
        # gather, no copy back. A dead row (its table names no page) is all
        # padding: no decay, no input and a tail that ends before its first
        # token leave its slot as it was, by the arithmetic and not by a select
        # over the states.
        n = spool.shape[0]
        live = jnp.pad(paged.block_tables[:, 0] != 0, (0, n - b))
        valid = live[:, None] if valid is None else jnp.pad(valid, ((0, n - b), (0, 0))) & live[:, None]
        ends = jnp.where(live, t if ends is None else jnp.pad(ends, (0, n - b)), 0).astype(jnp.int32)
        y, spool, cpool = mod.mix(
            blk["attn"], jnp.pad(h, ((0, n - b), (0, 0), (0, 0))), cfg, spool, cpool, valid, ends)
        y = y[:b]
    else:
        scope = f"{mod.SCOPE}.chunk" if t > 1 else f"{mod.SCOPE}.step"
        with jax.named_scope(scope):
            # a row that holds nothing yet (the first chunk of a prompt) starts from
            # a fresh state, whatever its slot's last owner left there
            fresh = (paged.seq_lens == 0)[:, None, None]
            state = jnp.where(fresh[..., None], 0.0, spool[paged.slots])
            tail = jnp.where(fresh, 0, cpool[paged.slots])
        y, new_state, new_tail = mod.mix(blk["attn"], h, cfg, state, tail, valid, ends)
        with jax.named_scope(scope):
            spool = spool.at[paged.slots].set(new_state)
            cpool = cpool.at[paged.slots].set(new_tail)
    return joined(y), {"state_pool": spool, "conv_pool": cpool}
