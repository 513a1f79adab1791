#!/usr/bin/env python
"""Export a framework checkpoint as a Hugging Face GPT-2 model directory.

Inverse of import_hf_checkpoint.py: a model trained here (GPT-2 shape —
learned positions, LayerNorm, gelu, fused qkv with bias, output projection,
tied head) becomes a `GPT2LMHeadModel.from_pretrained`-loadable directory,
so the wider HF ecosystem (generation pipelines, evaluation harnesses,
quantizers) can consume checkpoints trained on TPU with this framework.

Usage:
  python scripts/export_hf_checkpoint.py checkpoints --out_dir hf_model
  # then anywhere:  GPT2LMHeadModel.from_pretrained("hf_model")
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pretraining_llm_tpu.utils.compile_cache import use_compile_cache

use_compile_cache()


def export_params_to_hf(params, cfg):
    """(framework params, ModelConfig) -> HF GPT2LMHeadModel (torch, CPU)."""
    import torch
    from transformers import GPT2Config, GPT2LMHeadModel

    required = {
        "pos_embed": cfg.pos_embed == "learned",
        "norm": cfg.norm == "layernorm",
        "activation": cfg.activation in ("gelu",),
        "use_output_proj": cfg.use_output_proj,
        "tie_embeddings": cfg.tie_embeddings,
        "qkv_bias": cfg.qkv_bias,
        "mlp_bias": cfg.mlp_bias,
        "mha (no GQA)": cfg.kv_heads == cfg.n_heads,
        "no MoE": cfg.n_experts == 0,
        # HF GPT-2 runs FULL causal attention: a windowed or doc-masked
        # model would load cleanly but compute different outputs.
        "no sliding_window": cfg.sliding_window == 0,
        "no doc_mask": cfg.doc_mask_token < 0,
    }
    bad = [k for k, ok in required.items() if not ok]
    if bad:
        raise ValueError(
            f"model is not the GPT-2 architecture HF expects; failing "
            f"properties: {bad}"
        )

    d, h, dh, nl = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.n_layers
    hf_cfg = GPT2Config(
        vocab_size=cfg.vocab_size,
        n_positions=cfg.context_length,
        n_embd=d,
        n_layer=nl,
        n_head=h,
        n_inner=int(cfg.mlp_ratio * d),
        activation_function="gelu_new",
        layer_norm_epsilon=cfg.norm_eps,
        # No dropout: this framework trains without it (SURVEY §2.5), and
        # an exported model should evaluate identically by default.
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
    )
    model = GPT2LMHeadModel(hf_cfg)

    def t(a) -> "torch.Tensor":
        return torch.from_numpy(np.asarray(a, np.float32))

    blocks = params["blocks"]
    sd = {
        "transformer.wte.weight": t(params["tok_embed"]["embedding"]),
        "transformer.wpe.weight": t(params["pos_embed"]["embedding"]),
        "transformer.ln_f.weight": t(params["final_norm"]["scale"]),
        "transformer.ln_f.bias": t(params["final_norm"]["bias"]),
        "lm_head.weight": t(params["tok_embed"]["embedding"]),  # tied
    }
    for i in range(nl):
        pre = f"transformer.h.{i}."
        sd[pre + "ln_1.weight"] = t(blocks["ln1"]["scale"][i])
        sd[pre + "ln_1.bias"] = t(blocks["ln1"]["bias"][i])
        sd[pre + "attn.c_attn.weight"] = t(
            np.asarray(blocks["attn"]["wqkv"][i]).reshape(d, 3 * h * dh)
        )
        sd[pre + "attn.c_attn.bias"] = t(
            np.asarray(blocks["attn"]["bqkv"][i]).reshape(3 * h * dh)
        )
        sd[pre + "attn.c_proj.weight"] = t(
            np.asarray(blocks["attn"]["wo"][i]).reshape(h * dh, d)
        )
        sd[pre + "attn.c_proj.bias"] = t(blocks["attn"]["bo"][i])
        sd[pre + "ln_2.weight"] = t(blocks["ln2"]["scale"][i])
        sd[pre + "ln_2.bias"] = t(blocks["ln2"]["bias"][i])
        sd[pre + "mlp.c_fc.weight"] = t(blocks["mlp"]["w1"][i])
        sd[pre + "mlp.c_fc.bias"] = t(blocks["mlp"]["b1"][i])
        sd[pre + "mlp.c_proj.weight"] = t(blocks["mlp"]["w2"][i])
        sd[pre + "mlp.c_proj.bias"] = t(blocks["mlp"]["b2"][i])

    missing, unexpected = model.load_state_dict(sd, strict=False)
    # The causal-mask buffers (h.*.attn.bias) are allowed to be missing —
    # they are constants the model rebuilds; anything else missing means a
    # mapping bug and must fail loudly.
    real_missing = [k for k in missing if not k.endswith(_MASK_SUFFIXES)]
    if real_missing or unexpected:
        raise ValueError(
            f"state_dict mismatch: missing={real_missing[:5]} "
            f"unexpected={list(unexpected)[:5]}"
        )
    return model


_MASK_SUFFIXES = (".attn.bias", ".attn.masked_bias")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("checkpoint", help="framework checkpoint directory")
    ap.add_argument("--out_dir", required=True)
    ap.add_argument("--ema", action="store_true",
                    help="export the EMA shadow params instead of the raw params")
    args = ap.parse_args()

    from pretraining_llm_tpu.generation.generate import load_model_for_inference

    params, cfg = load_model_for_inference(args.checkpoint, use_ema=args.ema)
    model = export_params_to_hf(params, cfg.model)
    model.save_pretrained(args.out_dir)
    n = sum(p.numel() for p in model.parameters())
    print(f"exported {n/1e6:.1f}M params -> {args.out_dir} "
          f"(GPT2LMHeadModel.from_pretrained-loadable)")


if __name__ == "__main__":
    main()
