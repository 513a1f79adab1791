"""GPT-2 (large, XL): key names of the source's ``config.json``, parameter
counts, canonical seeded weights and their place in the program's tree."""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax.numpy as jnp

from harness.weights import STD, dense_block, normal


def dims(arch: Dict[str, Any]) -> Dict[str, int]:
    d, h = arch["n_embd"], arch["n_head"]
    return dict(
        d=d, layers=arch["n_layer"], heads=h, kv_heads=h, head_dim=d // h, ffn=4 * d,
        vocab=arch["vocab_size"], vocab_rows=arch.get("padded_vocab_size", arch["vocab_size"]),
        ctx=arch["n_positions"],
    )


def layer_params(m: Dict[str, int]) -> int:
    d, f = m["d"], m["ffn"]
    attn = d * 3 * d + 3 * d + d * d + d  # c_attn + bias, c_proj + bias
    mlp = d * f + f + f * d + d
    return attn + mlp + 4 * d  # two LayerNorms


def other_params(m: Dict[str, int]) -> Tuple[int, int, int]:
    """(held outside the blocks, of those only looked up in training, of those
    only looked up in a decode step). The tied table is read whole by the head."""
    pos = m["ctx"] * m["d"]
    return m["vocab_rows"] * m["d"] + pos + 2 * m["d"], pos, pos


def layer(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    d, f, h, g, dh = m["d"], m["ffn"], m["heads"], m["kv_heads"], m["head_dim"]
    w = dense_block(m, k, dtype)
    w.update(
        bq=normal(k, 9, (h * dh,), STD, dtype), bk=normal(k, 10, (g * dh,), STD, dtype),
        bv=normal(k, 11, (g * dh,), STD, dtype), bo=normal(k, 12, (d,), STD, dtype),
        b_up=normal(k, 13, (f,), STD, dtype), b_down=normal(k, 14, (d,), STD, dtype),
        ln1_bias=normal(k, 15, (d,), STD, dtype), ln2_bias=normal(k, 16, (d,), STD, dtype),
    )
    return w


def globals_(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    return {
        "embed": normal(k, 0, (m["vocab_rows"], m["d"]), STD, dtype),
        "final_scale": 1 + normal(k, 1, (m["d"],), 0.1, dtype),
        "pos": normal(k, 2, (m["ctx"], m["d"]), STD, dtype),
        "final_bias": normal(k, 3, (m["d"],), STD, dtype),
    }


def program_layer(m: Dict[str, int], c: Dict[str, Any]) -> Dict[str, Any]:
    d, h, dh = m["d"], m["heads"], m["head_dim"]
    return {
        "ln1": {"scale": c["ln1_scale"], "bias": c["ln1_bias"]},
        "attn": {
            "wqkv": jnp.stack([c["wq"], c["wk"], c["wv"]], axis=1).reshape(d, 3, h, dh),
            "bqkv": jnp.stack([c["bq"], c["bk"], c["bv"]]).reshape(3, h, dh),
            "wo": c["wo"].reshape(h, dh, d), "bo": c["bo"],
        },
        "ln2": {"scale": c["ln2_scale"], "bias": c["ln2_bias"]},
        "mlp": {"w1": c["w_up"], "b1": c["b_up"], "w2": c["w_down"], "b2": c["b_down"]},
    }


def program_tree(blocks: Any, gl: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "tok_embed": {"embedding": gl["embed"]}, "blocks": blocks,
        "final_norm": {"scale": gl["final_scale"], "bias": gl["final_bias"]},
        "pos_embed": {"embedding": gl["pos"]},
    }


def model_kwargs(arch: Dict[str, Any], m: Dict[str, int]) -> Dict[str, Any]:
    return dict(
        mlp_ratio=m["ffn"] / m["d"], activation="gelu", norm="layernorm", pos_embed="learned", tie_embeddings=True,
        qkv_bias=True, mlp_bias=True, norm_eps=arch["layer_norm_epsilon"],
    )
