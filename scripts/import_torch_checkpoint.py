#!/usr/bin/env python
"""Import a reference (PyTorch) checkpoint into this framework.

A user switching from the reference brings checkpoints shaped like
`torch.save({'model_state_dict': ..., 'optimizer_state_dict': ...})`
(reference: scripts/train_transformer.py:104-109) for its exact architecture
(SURVEY §2.5: per-head biasless K/Q/V Linears, no attention output
projection, ReLU MLP with biases, learned positions, untied biased lm_head).
This tool maps those weights onto this framework's stacked functional pytree
(fused wqkv, scanned blocks) under the matching `reference_parity`-style
ModelConfig, and writes a framework checkpoint directory that
`scripts/generate_text.py --model_path <out_dir>` and `scripts/train.py`
(resume) load directly.

Mapping (reference state_dict key -> params leaf):
  token_embed.weight    (V, D)  -> tok_embed.embedding
  position_embed.weight (T, D)  -> pos_embed.embedding
  attn_blocks.{i}.ln1.{weight,bias}            -> blocks.ln1.{scale,bias}[i]
  attn_blocks.{i}.attn.heads.{h}.{query,key,value}.weight (dh, D)
        -> blocks.attn.wqkv[i, :, {0,1,2}, h, :] (transposed to (D, dh))
  attn_blocks.{i}.ln2.{weight,bias}            -> blocks.ln2.{scale,bias}[i]
  attn_blocks.{i}.mlp.hidden.{weight,bias}     -> blocks.mlp.{w1,b1}[i] (w T)
  attn_blocks.{i}.mlp.proj.{weight,bias}       -> blocks.mlp.{w2,b2}[i] (w T)
  layer_norm.{weight,bias}                     -> final_norm.{scale,bias}
  lm_head.{weight,bias}        (V, D) / (V,)   -> lm_head.{kernel (D,V), bias}
  *.tril / pos_idxs buffers                    -> dropped (mask buffers, B10)

The leaves above are the framework's stored layout, unchanged by serving: a
serving engine makes its own layout of a dense SwiGLU's w1 when it is built
(`models/transformer.py::serving_layout`); a GELU/ReLU w1 (D, F) as written
here is read in place.

Usage:
  python scripts/import_torch_checkpoint.py ckpt.pt --out_dir imported_ckpt
  python scripts/generate_text.py --model_path imported_ckpt --input_text "..."
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import re
import sys
from typing import Any, Dict

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pretraining_llm_tpu.utils.compile_cache import use_compile_cache

use_compile_cache()


def _strip_prefixes(sd: Dict[str, Any]) -> Dict[str, Any]:
    """Drop DDP ('module.') and torch.compile ('_orig_mod.') wrappers, in
    any nesting order (compile-of-DDP gives '_orig_mod.module.*')."""
    out = {}
    for k, v in sd.items():
        changed = True
        while changed:
            changed = False
            for pre in ("module.", "_orig_mod."):
                if k.startswith(pre):
                    k = k[len(pre):]
                    changed = True
        out[k] = v
    return out


def import_state_dict(sd: Dict[str, np.ndarray]):
    """(reference state_dict of numpy arrays) -> (ModelConfig, params).

    Every key must be consumed — leftover keys mean the checkpoint's
    architecture deviates from the reference spec and a silent import would
    drop trained weights; that is an error, not a warning.
    """
    from pretraining_llm_tpu.config import ModelConfig

    sd = {k: np.asarray(v, np.float32) for k, v in sd.items()}
    unused = set(sd)

    def take(key: str) -> np.ndarray:
        unused.discard(key)
        return sd[key]

    vocab_size, d_model = take("token_embed.weight").shape
    context_length = take("position_embed.weight").shape[0]
    n_layers = 1 + max(
        int(m.group(1))
        for k in sd
        if (m := re.match(r"attn_blocks\.(\d+)\.", k))
    )
    n_heads = 1 + max(
        int(m.group(1))
        for k in sd
        if (m := re.match(r"attn_blocks\.0\.attn\.heads\.(\d+)\.", k))
    )
    dh = sd["attn_blocks.0.attn.heads.0.key.weight"].shape[0]
    d_ff = sd["attn_blocks.0.mlp.hidden.weight"].shape[0]
    cfg = ModelConfig(
        vocab_size=vocab_size,
        context_length=context_length,
        d_model=d_model,
        n_heads=n_heads,
        d_head=dh,
        n_layers=n_layers,
        mlp_ratio=d_ff / d_model,
        activation="relu",
        norm="layernorm",
        pos_embed="learned",
        use_output_proj=False,
        tie_embeddings=False,
        lm_head_bias=True,
        qkv_bias=False,
        mlp_bias=True,
    )

    def stack(fmt: str, transform=lambda a: a):
        return np.stack([transform(take(fmt.format(i=i))) for i in range(n_layers)])

    # Fused QKV: slot order (q, k, v) matches _attention_block's unpacking.
    wqkv = np.zeros((n_layers, d_model, 3, n_heads, dh), np.float32)
    for i in range(n_layers):
        for h in range(n_heads):
            for c, name in enumerate(("query", "key", "value")):
                w = take(f"attn_blocks.{i}.attn.heads.{h}.{name}.weight")  # (dh, D)
                wqkv[i, :, c, h, :] = w.T

    params = {
        "tok_embed": {"embedding": sd["token_embed.weight"]},
        "pos_embed": {"embedding": sd["position_embed.weight"]},
        "blocks": {
            "ln1": {
                "scale": stack("attn_blocks.{i}.ln1.weight"),
                "bias": stack("attn_blocks.{i}.ln1.bias"),
            },
            "attn": {"wqkv": wqkv},
            "ln2": {
                "scale": stack("attn_blocks.{i}.ln2.weight"),
                "bias": stack("attn_blocks.{i}.ln2.bias"),
            },
            "mlp": {
                "w1": stack("attn_blocks.{i}.mlp.hidden.weight", lambda a: a.T),
                "b1": stack("attn_blocks.{i}.mlp.hidden.bias"),
                "w2": stack("attn_blocks.{i}.mlp.proj.weight", lambda a: a.T),
                "b2": stack("attn_blocks.{i}.mlp.proj.bias"),
            },
        },
        "final_norm": {
            "scale": take("layer_norm.weight"),
            "bias": take("layer_norm.bias"),
        },
        "lm_head": {
            "kernel": take("lm_head.weight").T,
            "bias": take("lm_head.bias"),
        },
    }
    if unused:
        raise ValueError(
            "checkpoint has weights this importer does not map (architecture "
            f"deviates from the reference spec): {sorted(unused)[:8]}"
        )
    return cfg, params


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("checkpoint", help="reference .pt file (torch.save format)")
    ap.add_argument("--out_dir", required=True)
    ap.add_argument(
        "--tokenizer", default="gpt2",
        help="tokenizer name recorded for generate_text (reference uses gpt2/r50k)",
    )
    args = ap.parse_args()

    import torch

    raw = torch.load(args.checkpoint, map_location="cpu", weights_only=True)
    sd = raw.get("model_state_dict", raw)  # reference schema or a bare state_dict
    sd = _strip_prefixes({k: v.numpy() for k, v in sd.items() if hasattr(v, "numpy")})
    sd = {k: v for k, v in sd.items() if not k.endswith((".tril", "pos_idxs"))}

    cfg, params = import_state_dict(sd)

    import jax

    from pretraining_llm_tpu.config import Config, DataConfig
    from pretraining_llm_tpu.training import checkpoint as ckpt

    full_cfg = Config(
        model=cfg,
        data=DataConfig(tokenizer_name=args.tokenizer),
        name="imported-reference",
    )
    params = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    path = ckpt.save_checkpoint(
        args.out_dir, 0, {"params": params},
        extra={"step": 0, "config": dataclasses.asdict(full_cfg),
               "preset": full_cfg.name, "source": os.path.abspath(args.checkpoint)},
    )
    n = sum(int(np.prod(np.shape(l))) for l in jax.tree.leaves(params))
    print(f"imported {n/1e6:.1f}M params ({cfg.n_layers}L d{cfg.d_model} "
          f"h{cfg.n_heads} ctx{cfg.context_length} V{cfg.vocab_size}) -> {path}")


if __name__ == "__main__":
    main()
