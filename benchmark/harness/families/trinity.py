"""Trinity (``model_type`` ``afmoe``): key names of the source's
``config.json``, parameter and byte counts, canonical seeded weights and their
place in the program's tree.

As in ``families/joyai.py`` the harness hands a family no layer index and
stacks one homogeneous ``lax.map`` of layers, so the *expert* layers of the
stack are the family's layers (``dims()["layers"]``) and the leading dense
layers live among the globals as ``dense<i>_*``. An expert layer's weights are
the same whatever its attention kind; the kind of each published layer
(``layer_types``: ``sliding_attention`` | ``full_attention``) is the
configuration's, read by ``model_kwargs`` for the program (``attn_kinds``) and
by ``references/trinity.py`` for itself.

Canonical layout: matrices ``(in, out)``. RoPE rotates split halves of the
whole head in the published code and in the program alike, so no column moves.
A layer has four norms (``ln1`` before attention, ``ln1_post`` on its output,
``ln2`` before the FFN, ``ln2_post`` on its output), a norm a head on queries
and keys, and an element-wise output gate ``wg``; every one is seeded away from
the identity (norm scales 1 + N(0, 0.1), ``wg`` and the selection bias
non-zero), so that a dropped one changes the output.

Two cache lifetimes: a full layer keeps a row's K/V for its whole length, a
window layer for the last ``sliding_window`` positions. ``attn_step_bytes``
counts what one decode step must read of each; ``moe_step_bytes`` and
``decode_step_min_bytes`` are what ``readers/part_roofline.py`` calls.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax.numpy as jnp

from harness.weights import STD, normal

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def dims(arch: Dict[str, Any]) -> Dict[str, int]:
    dense = arch["num_dense_layers"]
    return dict(
        d=arch["hidden_size"], layers=arch["num_hidden_layers"] - dense, dense_layers=dense,
        heads=arch["num_attention_heads"], kv_heads=arch["num_key_value_heads"], head_dim=arch["head_dim"],
        ffn=arch["intermediate_size"], expert_ffn=arch["moe_intermediate_size"],
        experts=arch["num_experts"], experts_held=arch["num_experts"], top_k=arch["num_experts_per_tok"],
        shared=arch["num_shared_experts"], window=arch["sliding_window"],
        vocab=arch["vocab_size"], vocab_rows=arch["vocab_size"], ctx=arch["max_position_embeddings"],
    )


def kinds(arch: Dict[str, Any]) -> Tuple[str, ...]:
    """"window" | "full" for every layer of the stack as it is run."""
    if len(arch["layer_types"]) != arch["num_hidden_layers"]:
        raise ValueError("layer_types names a kind for each of num_hidden_layers layers")
    return tuple(KINDS[t] for t in arch["layer_types"])


# -- counts ------------------------------------------------------------------------


def attn_params(m: Dict[str, int]) -> int:
    """Queries, keys, values, the output gate, the output projection, two head norms."""
    d, q, kv = m["d"], m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + d * q + q * d + 2 * m["head_dim"]


def expert_params(m: Dict[str, int]) -> int:
    return 3 * m["d"] * m["expert_ffn"]


def layer_params(m: Dict[str, int]) -> int:
    """One expert layer: attention, four norms, the router and its bias, every
    expert and the shared one."""
    moe = m["d"] * m["experts"] + m["experts"] + (m["experts_held"] + m["shared"]) * expert_params(m)
    return attn_params(m) + 4 * m["d"] + moe


def dense_layer_params(m: Dict[str, int]) -> int:
    return attn_params(m) + 4 * m["d"] + 3 * m["d"] * m["ffn"]


def other_params(m: Dict[str, int]) -> Tuple[int, int, int]:
    """(held outside the stack's expert layers, of those only looked up in
    training, of those only looked up in a decode step)."""
    table = m["vocab_rows"] * m["d"]
    return 2 * table + m["d"] + m["dense_layers"] * dense_layer_params(m), 0, table


def kv_bytes_per_token_layer(arch: Dict[str, Any], bytes_per_el: int = 2) -> int:
    m = dims(arch)
    return 2 * m["kv_heads"] * m["head_dim"] * bytes_per_el


def window_tokens(arch: Dict[str, Any], rows: int, prompt: int, output: int) -> float:
    """Tokens a window layer must read a step, summed over the rows of the
    closed loop: a row's length is uniform over [prompt, prompt + output), and
    a window layer reads the last ``sliding_window`` of them."""
    w, lo, hi = dims(arch)["window"], prompt, prompt + output
    if w >= hi:
        return rows * (lo + hi) / 2.0
    if w <= lo:
        return float(rows * w)
    return rows * ((w - lo) * (lo + w) / 2.0 + (hi - w) * w) / (hi - lo)


def attn_step_bytes(arch: Dict[str, Any], kind: str, tokens: float, bytes_per_el: int = 2) -> float:
    """Bytes the attention of the layers of one ``kind`` must read in one
    decode step: K and V of ``tokens`` cached positions (summed over rows: the
    rows' whole lengths for ``full``, what lies inside the window for
    ``window``) in each such layer. The projections around it are counted by
    ``decode_step_min_bytes``; this is the paged kernel's share."""
    return kinds(arch).count(kind) * tokens * kv_bytes_per_token_layer(arch, bytes_per_el)


def moe_step_bytes(arch: Dict[str, Any], touched_share: float, bytes_per_el: int = 2) -> float:
    """Bytes the expert FFNs of one decode step must read: the experts some
    row chose (``touched_share`` of all, from the engine's counter), the shared
    expert, the router and its bias, in every expert layer."""
    m = dims(arch)
    per_layer = (touched_share * m["experts_held"] + m["shared"]) * expert_params(m) \
        + m["d"] * m["experts"] + m["experts"]
    return m["layers"] * per_layer * bytes_per_el


def decode_step_min_bytes(arch: Dict[str, Any], resident_tokens: float, rows: int, touched_share: float,
                          bytes_per_el: int = 2, window_resident: float = None) -> float:
    """All a decode step cannot avoid reading: every weight outside the routed
    experts once (the input embedding as ``rows`` rows), the experts touched,
    every resident token's K/V in the full layers and what lies inside the
    window in the window layers. ``window_resident`` (tokens inside the window,
    summed over rows); where the caller knows only ``resident_tokens`` the
    window layers count the smaller of a row's mean length and the window."""
    m = dims(arch)
    d = m["d"]
    if window_resident is None:
        window_resident = rows * min(m["window"], resident_tokens / max(rows, 1))
    fixed = (m["layers"] + m["dense_layers"]) * (attn_params(m) + 4 * d) + m["dense_layers"] * 3 * d * m["ffn"] \
        + m["vocab_rows"] * d + d + rows * d
    return fixed * bytes_per_el + moe_step_bytes(arch, touched_share, bytes_per_el) \
        + attn_step_bytes(arch, "full", resident_tokens, bytes_per_el) \
        + attn_step_bytes(arch, "window", window_resident, bytes_per_el)


# -- canonical weights -------------------------------------------------------------


def _resid_std(m: Dict[str, int]) -> float:
    return STD / (2 * (m["layers"] + m["dense_layers"])) ** 0.5


def _shared_block(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """What the dense and the expert layers both have: gated QK-normed
    grouped-query attention and four norms."""
    d, q, kv, dh = m["d"], m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"], m["head_dim"]
    return {
        "ln1_scale": 1 + normal(k, 0, (d,), 0.1, dtype),
        "ln1_post_scale": 1 + normal(k, 1, (d,), 0.1, dtype),
        "ln2_scale": 1 + normal(k, 2, (d,), 0.1, dtype),
        "ln2_post_scale": 1 + normal(k, 3, (d,), 0.1, dtype),
        "wq": normal(k, 4, (d, q), STD, dtype),
        "wk": normal(k, 5, (d, kv), STD, dtype),
        "wv": normal(k, 6, (d, kv), STD, dtype),
        "wg": normal(k, 7, (d, q), STD, dtype),
        "q_norm_scale": 1 + normal(k, 8, (dh,), 0.1, dtype),
        "k_norm_scale": 1 + normal(k, 9, (dh,), 0.1, dtype),
        "wo": normal(k, 10, (q, d), _resid_std(m), dtype),
    }


def layer(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """An expert layer. The selection bias ~ N(0, 0.002), a tenth of a
    matrix's scale, as ``families/ling.py`` and ``joyai.py`` set it."""
    d, f, e = m["d"], m["expert_ffn"], m["experts_held"]
    rs = _resid_std(m)
    w = _shared_block(m, k, dtype)
    w.update(
        router=normal(k, 11, (d, m["experts"]), STD, dtype),
        b_corr=normal(k, 12, (m["experts"],), STD / 10, dtype),
        e_gate=normal(k, 13, (e, d, f), STD, dtype), e_up=normal(k, 14, (e, d, f), STD, dtype),
        e_down=normal(k, 15, (e, f, d), rs, dtype),
        s_gate=normal(k, 16, (d, m["shared"] * f), STD, dtype),
        s_up=normal(k, 17, (d, m["shared"] * f), STD, dtype),
        s_down=normal(k, 18, (m["shared"] * f, d), rs, dtype),
    )
    return w


def globals_(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    import jax

    d, f = m["d"], m["ffn"]
    out = {
        "embed": normal(k, 0, (m["vocab_rows"], d), STD, dtype),
        "final_scale": 1 + normal(k, 1, (d,), 0.1, dtype),
        "head": normal(k, 4, (d, m["vocab_rows"]), STD, dtype),
    }
    for i in range(m["dense_layers"]):
        kd = jax.random.fold_in(k, 99 + i)
        dense = _shared_block(m, kd, dtype)
        dense.update(w_gate=normal(kd, 11, (d, f), STD, dtype), w_up=normal(kd, 12, (d, f), STD, dtype),
                     w_down=normal(kd, 13, (f, d), _resid_std(m), dtype))
        out.update({f"dense{i}_" + name: v for name, v in dense.items()})
    return out


# -- the program's tree ------------------------------------------------------------


def _program_shared(m: Dict[str, int], c: Dict[str, Any]) -> Dict[str, Any]:
    d, h, g, dh = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    return {
        "ln1": {"scale": c["ln1_scale"]}, "ln1_post": {"scale": c["ln1_post_scale"]},
        "ln2": {"scale": c["ln2_scale"]}, "ln2_post": {"scale": c["ln2_post_scale"]},
        "attn": {
            "wq": c["wq"].reshape(d, h, dh),
            "wkv": jnp.stack([c["wk"], c["wv"]], axis=1).reshape(d, 2, g, dh),
            "wg": c["wg"].reshape(d, h, dh),
            "q_norm": {"scale": c["q_norm_scale"]}, "k_norm": {"scale": c["k_norm_scale"]},
            "wo": c["wo"].reshape(h, dh, d),
            # the program always carries an output bias; Trinity has none
            "bo": jnp.zeros((d,), c["wo"].dtype),
        },
    }


def program_layer(m: Dict[str, int], c: Dict[str, Any]) -> Dict[str, Any]:
    out = _program_shared(m, c)
    out["mlp"] = {
        "router": c["router"], "router_bias": c["b_corr"],
        # as the grouped matmul reads them: gate columns, then up columns
        "experts": {"w1": jnp.concatenate([c["e_gate"], c["e_up"]], axis=-1), "w2": c["e_down"]},
        "shared": {"w1": jnp.stack([c["s_gate"], c["s_up"]], axis=1), "w2": c["s_down"]},
    }
    return out


def program_tree(blocks: Any, gl: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    a = blocks["attn"]  # (layers, ...): the widths every layer shares
    m = dict(d=a["wq"].shape[1], heads=a["wq"].shape[2], head_dim=a["wq"].shape[3], kv_heads=a["wkv"].shape[3])
    tree = {
        "tok_embed": {"embedding": gl["embed"]}, "blocks": blocks,
        "final_norm": {"scale": gl["final_scale"]}, "lm_head": {"kernel": gl["head"]},
    }
    dense, i = [], 0
    while f"dense{i}_wq" in gl:
        c = {k[len(f"dense{i}_"):]: v for k, v in gl.items() if k.startswith(f"dense{i}_")}
        blk = _program_shared(m, c)
        blk["mlp"] = {"w1": jnp.stack([c["w_gate"], c["w_up"]], axis=1), "w2": c["w_down"]}
        dense.append(blk)
        i += 1
    if dense:
        tree["dense_blocks"] = jax.tree.map(lambda *x: jnp.stack(x), *dense)
    return tree


def model_kwargs(arch: Dict[str, Any], m: Dict[str, int]) -> Dict[str, Any]:
    if arch["rope_scaling"] is not None or arch["n_group"] != 1 or arch["topk_group"] != 1:
        raise ValueError("the Trinity family runs plain RoPE and one routing group")
    return dict(
        n_layers=m["layers"] + m["dense_layers"], n_dense_layers=m["dense_layers"],
        n_kv_heads=m["kv_heads"], d_head=m["head_dim"], mlp_ratio=m["ffn"] / m["d"],
        activation="swiglu", norm="rmsnorm", pos_embed="rope", rope_theta=float(arch["rope_theta"]),
        tie_embeddings=False, lm_head_bias=False, qkv_bias=False, mlp_bias=False,
        norm_eps=arch["rms_norm_eps"], sliding_window=m["window"], attn_kinds=kinds(arch),
        rope_full_layers=False, qk_norm=True, attn_output_gate=True, sandwich_norm=True,
        embed_scale=bool(arch["mup_enabled"]),
        n_experts=m["experts"], experts_per_token=m["top_k"], moe_routing="dropless",
        moe_score=arch["score_func"], moe_score_bias=True, moe_norm_topk=bool(arch["route_norm"]),
        moe_routed_scale=float(arch["route_scale"]), n_shared_experts=m["shared"], d_expert=m["expert_ffn"],
    )
