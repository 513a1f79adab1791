#!/usr/bin/env python
"""Import a Hugging Face GPT-2 checkpoint into this framework.

Beyond the reference's own checkpoint schema (import_torch_checkpoint.py):
users migrating from the HF ecosystem bring `GPT2LMHeadModel` weights
(config.json + model weights in a local directory). This tool maps them onto
this framework's stacked functional pytree and writes a framework checkpoint
directory that `scripts/generate_text.py --model_path <out_dir>`,
`scripts/evaluate.py`, and `scripts/train.py` (resume/fine-tune) load
directly.

Architecture facts relied on (and asserted): GPT-2 is pre-LN with learned
absolute positions, fused Conv1D qkv (weights stored (in, out) — exactly
this framework's orientation, no transposes), gelu_new activation (== this
framework's tanh-approximate "gelu"), LayerNorm eps 1e-5, tied lm_head with
no bias.

Mapping (HF state_dict key -> params leaf):
  transformer.wte.weight (V, D)          -> tok_embed.embedding (tied head)
  transformer.wpe.weight (T, D)          -> pos_embed.embedding
  transformer.h.{i}.ln_1.{weight,bias}   -> blocks.ln1.{scale,bias}[i]
  transformer.h.{i}.attn.c_attn.weight (D, 3D) -> blocks.attn.wqkv[i]
                                            reshaped (D, 3, H, Dh)
  transformer.h.{i}.attn.c_attn.bias (3D,)     -> blocks.attn.bqkv[i] (3, H, Dh)
  transformer.h.{i}.attn.c_proj.weight (D, D)  -> blocks.attn.wo[i] (H, Dh, D)
  transformer.h.{i}.attn.c_proj.bias (D,)      -> blocks.attn.bo[i]
  transformer.h.{i}.mlp.c_fc.{weight,bias}     -> blocks.mlp.{w1,b1}[i]
  transformer.h.{i}.mlp.c_proj.{weight,bias}   -> blocks.mlp.{w2,b2}[i]
  transformer.ln_f.{weight,bias}         -> final_norm.{scale,bias}
  lm_head.weight                         -> dropped (tied to wte)
  *.attn.bias / *.attn.masked_bias       -> dropped (causal-mask buffers; this
                                            framework masks by index arithmetic)

The leaves above are the framework's stored layout, unchanged by serving: a
serving engine makes its own layout of a dense SwiGLU's w1 when it is built
(`models/transformer.py::serving_layout`); a GELU/ReLU w1 (D, F) as written
here is read in place.

Usage:
  python scripts/import_hf_checkpoint.py /path/to/hf_gpt2_dir --out_dir imported
  python scripts/generate_text.py --model_path imported --input_text "..."
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import re
import sys
from typing import Dict, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pretraining_llm_tpu.utils.compile_cache import use_compile_cache

use_compile_cache()

_DROP_SUFFIXES = (".attn.bias", ".attn.masked_bias")


def check_hf_config(hf_cfg) -> float:
    """Reject GPT-2-family configs whose NUMERICS deviate from what the
    mapped weights will run under here (state-dict shapes alone cannot
    catch these). Returns the layer-norm epsilon to carry over."""
    problems = []
    if getattr(hf_cfg, "activation_function", "gelu_new") != "gelu_new":
        problems.append(
            f"activation_function={hf_cfg.activation_function!r} (only "
            "gelu_new == this framework's tanh-approx gelu is supported)"
        )
    if getattr(hf_cfg, "scale_attn_by_inverse_layer_idx", False):
        problems.append("scale_attn_by_inverse_layer_idx=True")
    if getattr(hf_cfg, "reorder_and_upcast_attn", False):
        problems.append("reorder_and_upcast_attn=True")
    if problems:
        raise ValueError(
            "HF config numerics differ from this framework's forward; a "
            f"silent import would corrupt outputs: {problems}"
        )
    return float(getattr(hf_cfg, "layer_norm_epsilon", 1e-5))


def import_hf_model(model):
    """(GPT2LMHeadModel) -> (ModelConfig, params), config-validated."""
    norm_eps = check_hf_config(model.config)
    sd = {k: v.detach().cpu().numpy() for k, v in model.state_dict().items()}
    return import_hf_state_dict(sd, int(model.config.n_head), norm_eps=norm_eps)


def import_hf_state_dict(sd: Dict[str, np.ndarray], n_heads: int,
                         norm_eps: float = 1e-5):
    """(HF GPT2LMHeadModel state_dict as numpy, n_head) -> (ModelConfig, params).

    Every key must be consumed — leftovers mean the checkpoint is not the
    GPT-2 architecture this importer maps, and silently dropping trained
    weights would corrupt the import.
    """
    from pretraining_llm_tpu.config import ModelConfig

    sd = {
        k[len("transformer."):] if k.startswith("transformer.") else k: v
        for k, v in sd.items()
        if not k.endswith(_DROP_SUFFIXES)
    }
    # lm_head.weight is tied storage of wte — assert, then drop.
    if "lm_head.weight" in sd:
        if not np.array_equal(sd["lm_head.weight"], sd["wte.weight"]):
            raise ValueError(
                "lm_head.weight is not tied to wte.weight; untied GPT-2 "
                "variants are not supported by this importer"
            )
        del sd["lm_head.weight"]
    sd = {k: np.asarray(v, np.float32) for k, v in sd.items()}
    unused = set(sd)

    def take(key: str) -> np.ndarray:
        unused.discard(key)
        return sd[key]

    vocab_size, d_model = take("wte.weight").shape
    context_length = take("wpe.weight").shape[0]
    n_layers = 1 + max(
        int(m.group(1)) for k in sd if (m := re.match(r"h\.(\d+)\.", k))
    )
    if d_model % n_heads:
        raise ValueError(f"n_heads={n_heads} does not divide d_model={d_model}")
    dh = d_model // n_heads
    d_ff = sd["h.0.mlp.c_fc.weight"].shape[1]

    cfg = ModelConfig(
        vocab_size=vocab_size,
        context_length=context_length,
        d_model=d_model,
        n_heads=n_heads,
        n_layers=n_layers,
        # +0.5 so int(mlp_ratio * d_model) reconstructs d_ff EXACTLY —
        # the bare ratio truncates one low for some integer pairs
        # (e.g. int((220/49)*49) == 219).
        mlp_ratio=(d_ff + 0.5) / d_model,
        activation="gelu",  # == HF gelu_new (tanh approximation)
        norm="layernorm",
        pos_embed="learned",
        use_output_proj=True,
        tie_embeddings=True,
        lm_head_bias=False,
        qkv_bias=True,
        mlp_bias=True,
        norm_eps=norm_eps,
    )
    assert cfg.d_ff == d_ff, (cfg.d_ff, d_ff)

    def stack(fmt: str, transform=lambda a: a):
        return np.stack(
            [transform(take(fmt.format(i=i))) for i in range(n_layers)]
        )

    params = {
        "tok_embed": {"embedding": sd["wte.weight"]},
        "pos_embed": {"embedding": sd["wpe.weight"]},
        "blocks": {
            "ln1": {
                "scale": stack("h.{i}.ln_1.weight"),
                "bias": stack("h.{i}.ln_1.bias"),
            },
            "attn": {
                # Conv1D stores (in, out): (D, 3D) -> (D, 3, H, Dh) directly.
                "wqkv": stack(
                    "h.{i}.attn.c_attn.weight",
                    lambda a: a.reshape(d_model, 3, n_heads, dh),
                ),
                "bqkv": stack(
                    "h.{i}.attn.c_attn.bias",
                    lambda a: a.reshape(3, n_heads, dh),
                ),
                "wo": stack(
                    "h.{i}.attn.c_proj.weight",
                    lambda a: a.reshape(n_heads, dh, d_model),
                ),
                "bo": stack("h.{i}.attn.c_proj.bias"),
            },
            "ln2": {
                "scale": stack("h.{i}.ln_2.weight"),
                "bias": stack("h.{i}.ln_2.bias"),
            },
            "mlp": {
                "w1": stack("h.{i}.mlp.c_fc.weight"),
                "b1": stack("h.{i}.mlp.c_fc.bias"),
                "w2": stack("h.{i}.mlp.c_proj.weight"),
                "b2": stack("h.{i}.mlp.c_proj.bias"),
            },
        },
        "final_norm": {
            "scale": take("ln_f.weight"),
            "bias": take("ln_f.bias"),
        },
    }
    if unused:
        raise ValueError(
            "checkpoint has weights this importer does not map (not the "
            f"GPT-2 architecture): {sorted(unused)[:8]}"
        )
    return cfg, params


def load_hf_model_dir(path: str):
    """(ModelConfig, params) from a local HF GPT-2 directory."""
    from transformers import GPT2LMHeadModel

    return import_hf_model(
        GPT2LMHeadModel.from_pretrained(path, local_files_only=True)
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("hf_path", help="local HF GPT-2 model directory")
    ap.add_argument("--out_dir", required=True)
    args = ap.parse_args()

    cfg, params = load_hf_model_dir(args.hf_path)

    import jax

    from pretraining_llm_tpu.config import Config, DataConfig
    from pretraining_llm_tpu.training import checkpoint as ckpt

    full_cfg = Config(
        model=cfg,
        data=DataConfig(tokenizer_name="gpt2"),
        name="imported-hf-gpt2",
    )
    params = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    path = ckpt.save_checkpoint(
        args.out_dir, 0, {"params": params},
        extra={"step": 0, "config": dataclasses.asdict(full_cfg),
               "preset": full_cfg.name, "source": os.path.abspath(args.hf_path)},
    )
    n = sum(int(np.prod(np.shape(l))) for l in jax.tree.leaves(params))
    print(f"imported {n/1e6:.1f}M params ({cfg.n_layers}L d{cfg.d_model} "
          f"h{cfg.n_heads} ctx{cfg.context_length} V{cfg.vocab_size}) -> {path}")


if __name__ == "__main__":
    main()
