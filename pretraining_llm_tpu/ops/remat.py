"""Rematerialization policies: one name -> jax.checkpoint wrapper mapping.

Single source of truth for what each `ModelConfig.remat` value saves, shared
by the scanned-layer path (models.transformer.forward) and the pipelined path
(parallel.pipeline.pipeline_apply) so the same config string always means the
same backward-pass schedule.

Policies (cheapest memory -> cheapest recompute):
  - "full":          save nothing; backward re-runs the whole block (the
                     training cells' setting).
  - "dots_saveable": save every matmul output.
  - "save_attn":     save only the merged attention output ("attn_out" tag);
                     backward re-runs QKV projection + the flash forward.
  - "save_attn_res": save the flash kernel's OUTPUT residuals ("attn_o_res",
                     "attn_lse") instead: the attention VJP starts from its
                     saved (o, lse), so the flash forward never reruns, while
                     the QKV projection still recomputes. Same memory class
                     as save_attn (+lse, 4 bytes/token/head). No cell selects
                     it yet (ROADMAP S5(a)).
  - "none":          no checkpointing (autodiff saves everything it needs).
"""

from __future__ import annotations

from typing import Callable

import jax

# Tag names referenced by checkpoint_name() calls in models/transformer.py,
# models/mla.py and ops/pallas_flash.py. Keep these lists in sync with the
# tag sites — a policy naming a tag that no longer exists silently saves
# nothing for it.
_SAVE_ATTN = ("attn_out",)
_SAVE_ATTN_RES = ("attn_o_res", "attn_lse")


def checkpoint_wrap(fn: Callable, remat: str) -> Callable:
    """Wrap a per-layer body with the checkpoint policy named by ``remat``."""
    if remat == "none":
        return fn
    if remat == "full":
        return jax.checkpoint(fn)
    if remat == "dots_saveable":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)
    if remat == "save_attn":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(*_SAVE_ATTN)
        )
    if remat == "save_attn_res":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.save_only_these_names(*_SAVE_ATTN_RES),
        )
    raise ValueError(f"unknown remat policy {remat!r}")
