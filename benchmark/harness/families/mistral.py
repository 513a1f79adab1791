"""Mistral: key names of the source's ``config.json``, parameter counts,
canonical seeded weights and their place in the program's tree."""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax.numpy as jnp

from harness.weights import STD, dense_block, normal


def dims(arch: Dict[str, Any]) -> Dict[str, int]:
    return dict(
        d=arch["hidden_size"], layers=arch["num_hidden_layers"],
        heads=arch["num_attention_heads"], kv_heads=arch["num_key_value_heads"],
        head_dim=arch["hidden_size"] // arch["num_attention_heads"],
        ffn=arch["intermediate_size"], vocab=arch["vocab_size"],
        vocab_rows=arch["vocab_size"], ctx=arch["max_position_embeddings"],
    )


def layer_params(m: Dict[str, int]) -> int:
    d, f = m["d"], m["ffn"]
    q = d * m["heads"] * m["head_dim"]
    kv = 2 * d * m["kv_heads"] * m["head_dim"]
    o = m["heads"] * m["head_dim"] * d
    return q + kv + o + 3 * d * f + 2 * d  # swiglu: gate, up, down; two rmsnorms


def other_params(m: Dict[str, int]) -> Tuple[int, int, int]:
    """(held outside the blocks, of those only looked up in training, of those
    only looked up in a decode step). The untied input embedding is a lookup
    of a few rows in a decode step; in training its gradient is a scatter the
    6N rule counts like the program's own arithmetic does."""
    table = m["vocab_rows"] * m["d"]
    return 2 * table + m["d"], 0, table


def layer(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    w = dense_block(m, k, dtype)
    w["w_gate"] = normal(k, 8, (m["d"], m["ffn"]), STD, dtype)
    return w


def globals_(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    return {
        "embed": normal(k, 0, (m["vocab_rows"], m["d"]), STD, dtype),
        "final_scale": 1 + normal(k, 1, (m["d"],), 0.1, dtype),
        "head": normal(k, 4, (m["d"], m["vocab_rows"]), STD, dtype),
    }


def program_layer(m: Dict[str, int], c: Dict[str, Any]) -> Dict[str, Any]:
    d, h, g, dh = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    return {
        "ln1": {"scale": c["ln1_scale"]},
        "attn": {
            "wq": c["wq"].reshape(d, h, dh),
            "wkv": jnp.stack([c["wk"], c["wv"]], axis=1).reshape(d, 2, g, dh),
            "wo": c["wo"].reshape(h, dh, d),
            # the program always carries an output bias; Mistral has none
            "bo": jnp.zeros((d,), c["wo"].dtype),
        },
        "ln2": {"scale": c["ln2_scale"]},
        "mlp": {"w1": jnp.stack([c["w_gate"], c["w_up"]], axis=1), "w2": c["w_down"]},
    }


def program_tree(blocks: Any, gl: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "tok_embed": {"embedding": gl["embed"]}, "blocks": blocks,
        "final_norm": {"scale": gl["final_scale"]}, "lm_head": {"kernel": gl["head"]},
    }


def model_kwargs(arch: Dict[str, Any], m: Dict[str, int]) -> Dict[str, Any]:
    return dict(
        n_kv_heads=m["kv_heads"], mlp_ratio=m["ffn"] / m["d"], activation="swiglu",
        norm="rmsnorm", pos_embed="rope", rope_theta=arch["rope_theta"],
        tie_embeddings=False, lm_head_bias=False, qkv_bias=False, mlp_bias=False,
        norm_eps=arch["rms_norm_eps"], sliding_window=arch["sliding_window"],
    )
