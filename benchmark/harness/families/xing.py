"""Xing4.0 (``model_type`` ``xing4_0``): key names of the source's
``config.json``, parameter and byte counts, canonical seeded weights and their
place in the program's tree.

The harness hands a family no layer index and stacks one homogeneous
``lax.map`` of layers, so the *expert* layers are the family's layers
(``dims()["layers"]``) and the leading dense layer lives among the globals as
``dense0_*`` (the reference reads it there, ``program_tree`` puts it into the
program's ``dense_blocks``); ``model_kwargs`` sets the program's ``n_layers``
to all of them.

Canonical layout: matrices ``(in, out)``; the rotated slice of ``wq_b`` and
``wkv_a`` in DeepSeek's interleaved pairs ``(2i, 2i+1)``. The program rotates
split halves ``(j, j + half)``: ``program_layer`` permutes those columns, as a
checkpoint converter does, and the scores are the same numbers.

``harness/opcount.py`` counts per-head K/V and every weight; for a latent
cache and for experts of which a step touches some, the counts are here
(``latent_bytes_per_token``, ``moe_step_bytes``, ``latent_step_bytes``,
``decode_step_min_bytes``) and ``readers/part_roofline.py`` calls them.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax.numpy as jnp

from harness.weights import STD, normal


def dims(arch: Dict[str, Any]) -> Dict[str, int]:
    dense = arch["first_k_dense_replace"]
    return dict(
        d=arch["hidden_size"], layers=arch["num_hidden_layers"] - dense, dense_layers=dense,
        heads=arch["num_attention_heads"], kv_heads=arch["num_key_value_heads"],
        head_dim=arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"],
        nope=arch["qk_nope_head_dim"], rope=arch["qk_rope_head_dim"], v_dim=arch["v_head_dim"],
        q_rank=arch["q_lora_rank"], kv_rank=arch["kv_lora_rank"],
        ffn=arch["intermediate_size"], expert_ffn=arch["moe_intermediate_size"],
        experts=arch["n_routed_experts"], experts_held=arch.get("experts_held", arch["n_routed_experts"]),
        top_k=arch["num_experts_per_tok"], shared=arch["n_shared_experts"], hc=arch["hc_mult"],
        vocab=arch["vocab_size"], vocab_rows=arch["vocab_size"], ctx=arch["max_position_embeddings"],
    )


# -- counts ------------------------------------------------------------------------


def attn_params(m: Dict[str, int]) -> int:
    d, h = m["d"], m["heads"]
    q = d * m["q_rank"] + m["q_rank"] + m["q_rank"] * h * m["head_dim"]
    kv = d * (m["kv_rank"] + m["rope"]) + m["kv_rank"] + m["kv_rank"] * h * (m["nope"] + m["v_dim"])
    return q + kv + h * m["v_dim"] * d


def hc_params(m: Dict[str, int]) -> int:
    n = m["hc"]
    return 2 * ((n * m["d"] + 1) * (n * n + 2 * n) + 3)  # two sublayers: phi, b, three alphas


def expert_params(m: Dict[str, int]) -> int:
    return 3 * m["d"] * m["expert_ffn"]


def layer_params(m: Dict[str, int]) -> int:
    """One expert layer as held here: attention, two norms, the two stream
    wrappers, router and its bias, the experts held and the shared expert."""
    moe = m["d"] * m["experts"] + m["experts"] + (m["experts_held"] + m["shared"]) * expert_params(m)
    return attn_params(m) + 2 * m["d"] + hc_params(m) + moe


def dense_layer_params(m: Dict[str, int]) -> int:
    return attn_params(m) + 2 * m["d"] + hc_params(m) + 3 * m["d"] * m["ffn"]


def other_params(m: Dict[str, int]) -> Tuple[int, int, int]:
    """(held outside the expert layers, of those only looked up in training,
    of those only looked up in a decode step): both tables, the final norm and
    the leading dense layers; the input embedding is a lookup when decoding."""
    table = m["vocab_rows"] * m["d"]
    return 2 * table + m["d"] + m["dense_layers"] * dense_layer_params(m), 0, table


def latent_bytes_per_token(arch: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """The cache: one latent a token a layer, whatever the number of heads."""
    m = dims(arch)
    return (m["kv_rank"] + m["rope"]) * bytes_per_el * (m["layers"] + m["dense_layers"])


def moe_step_bytes(arch: Dict[str, Any], touched_share: float, bytes_per_el: int = 2) -> float:
    """Bytes the expert FFNs of one decode step must read, over all expert
    layers: the experts some row chose (``touched_share`` of those held, from
    the engine's counter), the shared expert, the router and its bias."""
    m = dims(arch)
    per_layer = (touched_share * m["experts_held"] + m["shared"]) * expert_params(m) \
        + m["d"] * m["experts"] + m["experts"]
    return m["layers"] * per_layer * bytes_per_el


def latent_step_bytes(arch: Dict[str, Any], resident_tokens: float, bytes_per_el: int = 2) -> float:
    """Bytes latent attention of one decode step must read: every resident
    token's latent once in every layer, and the absorbed projection ``wkv_b``."""
    m = dims(arch)
    n_layers = m["layers"] + m["dense_layers"]
    wkv_b = m["kv_rank"] * m["heads"] * (m["nope"] + m["v_dim"])
    return resident_tokens * latent_bytes_per_token(arch, bytes_per_el) + n_layers * wkv_b * bytes_per_el


def decode_step_min_bytes(arch: Dict[str, Any], resident_tokens: float, rows: int,
                          touched_share: float, bytes_per_el: int = 2) -> float:
    """All a decode step cannot avoid reading: every weight outside the routed
    experts once (the input embedding as ``rows`` rows), the experts touched,
    and every resident token's latent."""
    m = dims(arch)
    n_layers = m["layers"] + m["dense_layers"]
    fixed = n_layers * (attn_params(m) + 2 * m["d"] + hc_params(m)) \
        + m["dense_layers"] * 3 * m["d"] * m["ffn"] + m["vocab_rows"] * m["d"] + m["d"] + rows * m["d"]
    return fixed * bytes_per_el + moe_step_bytes(arch, touched_share, bytes_per_el) \
        + resident_tokens * latent_bytes_per_token(arch, bytes_per_el)


# -- canonical weights -------------------------------------------------------------


def _shared_block(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """What the dense and the expert layers both have: latent attention, two
    norms and the two stream wrappers. alpha ~ 0.5, b ~ N(0, 1): every
    coefficient far from a constant, so that a dropped term shows."""
    d, h, n = m["d"], m["heads"], m["hc"]
    rs = STD / (2 * (m["layers"] + m["dense_layers"])) ** 0.5
    w = {
        "ln1_scale": 1 + normal(k, 0, (d,), 0.1, dtype),
        "ln2_scale": 1 + normal(k, 1, (d,), 0.1, dtype),
        "wq_a": normal(k, 2, (d, m["q_rank"]), STD, dtype),
        "q_norm_scale": 1 + normal(k, 3, (m["q_rank"],), 0.1, dtype),
        "wq_b": normal(k, 4, (m["q_rank"], h * m["head_dim"]), STD, dtype),
        "wkv_a": normal(k, 5, (d, m["kv_rank"] + m["rope"]), STD, dtype),
        "kv_norm_scale": 1 + normal(k, 6, (m["kv_rank"],), 0.1, dtype),
        "wkv_b": normal(k, 7, (m["kv_rank"], h * (m["nope"] + m["v_dim"])), STD, dtype),
        "wo": normal(k, 8, (h * m["v_dim"], d), rs, dtype),
    }
    for i, name in enumerate(("hca", "hcm")):
        w[name + "_phi"] = normal(k, 20 + 3 * i, (n * d, n * n + 2 * n), STD, dtype)
        w[name + "_b"] = normal(k, 21 + 3 * i, (n * n + 2 * n,), 1.0, dtype)
        w[name + "_alpha"] = 0.5 + normal(k, 22 + 3 * i, (3,), 0.1, dtype)
    return w


def layer(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """An expert layer: the experts this chip holds, the published router."""
    d, f, e = m["d"], m["expert_ffn"], m["experts_held"]
    rs = STD / (2 * (m["layers"] + m["dense_layers"])) ** 0.5
    w = _shared_block(m, k, dtype)
    w.update(
        router=normal(k, 9, (d, m["experts"]), STD, dtype),
        b_corr=normal(k, 10, (m["experts"],), STD, dtype),
        e_gate=normal(k, 11, (e, d, f), STD, dtype), e_up=normal(k, 12, (e, d, f), STD, dtype),
        e_down=normal(k, 13, (e, f, d), rs, dtype),
        s_gate=normal(k, 14, (d, m["shared"] * f), STD, dtype),
        s_up=normal(k, 15, (d, m["shared"] * f), STD, dtype),
        s_down=normal(k, 16, (m["shared"] * f, d), rs, dtype),
    )
    return w


def globals_(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    import jax

    d, f = m["d"], m["ffn"]
    rs = STD / (2 * (m["layers"] + m["dense_layers"])) ** 0.5
    kd = jax.random.fold_in(k, 99)
    dense0 = _shared_block(m, kd, dtype)
    dense0.update(w_gate=normal(kd, 9, (d, f), STD, dtype), w_up=normal(kd, 10, (d, f), STD, dtype),
                  w_down=normal(kd, 11, (f, d), rs, dtype))
    out = {
        "embed": normal(k, 0, (m["vocab_rows"], d), STD, dtype),
        "final_scale": 1 + normal(k, 1, (d,), 0.1, dtype),
        "head": normal(k, 4, (d, m["vocab_rows"]), STD, dtype),
    }
    out.update({"dense0_" + name: v for name, v in dense0.items()})
    return out


# -- the program's tree ------------------------------------------------------------


def _split_halves(a: Any, rope_dim: int) -> Any:
    """The last ``rope_dim`` columns from interleaved pairs to split halves."""
    rot = a[..., -rope_dim:]
    return jnp.concatenate([a[..., :-rope_dim], rot[..., 0::2], rot[..., 1::2]], axis=-1)


def _program_shared(m: Dict[str, int], c: Dict[str, Any]) -> Dict[str, Any]:
    h = m["heads"]
    out = {
        "ln1": {"scale": c["ln1_scale"]}, "ln2": {"scale": c["ln2_scale"]},
        "attn": {
            "wq_a": c["wq_a"], "q_norm": {"scale": c["q_norm_scale"]},
            "wq_b": _split_halves(c["wq_b"].reshape(m["q_rank"], h, m["head_dim"]), m["rope"]),
            "wkv_a": _split_halves(c["wkv_a"], m["rope"]), "kv_norm": {"scale": c["kv_norm_scale"]},
            "wkv_b": c["wkv_b"].reshape(m["kv_rank"], h, m["nope"] + m["v_dim"]),
            "wo": c["wo"].reshape(h, m["v_dim"], m["d"]),
        },
    }
    for ours, theirs in (("hca", "hc_attn"), ("hcm", "hc_mlp")):
        out[theirs] = {"phi": c[ours + "_phi"], "b": c[ours + "_b"], "alpha": c[ours + "_alpha"]}
    return out


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

    c = {k[len("dense0_"):]: v for k, v in gl.items() if k.startswith("dense0_")}
    a = blocks["attn"]  # (layers, ...): the widths the dense layer shares
    m = dict(d=a["wq_a"].shape[1], q_rank=a["wq_b"].shape[1], heads=a["wq_b"].shape[2],
             head_dim=a["wq_b"].shape[3], kv_rank=a["wkv_b"].shape[1], v_dim=a["wo"].shape[2])
    m["rope"] = a["wkv_a"].shape[2] - m["kv_rank"]
    m["nope"] = m["head_dim"] - m["rope"]
    dense = _program_shared(m, c)
    dense["mlp"] = {"w1": jnp.stack([c["w_gate"], c["w_up"]], axis=1), "w2": c["w_down"]}
    return {
        "tok_embed": {"embedding": gl["embed"]}, "blocks": blocks,
        "dense_blocks": jax.tree.map(lambda a: a[None], dense),
        "final_norm": {"scale": gl["final_scale"]}, "lm_head": {"kernel": gl["head"]},
    }


def model_kwargs(arch: Dict[str, Any], m: Dict[str, int]) -> Dict[str, Any]:
    rs = arch["rope_scaling"]
    return dict(
        n_layers=m["layers"] + m["dense_layers"], n_dense_layers=m["dense_layers"],
        d_head=m["head_dim"], mlp_ratio=m["ffn"] / m["d"], activation="swiglu", norm="rmsnorm",
        pos_embed="rope", rope_theta=float(arch["rope_theta"]), tie_embeddings=False,
        lm_head_bias=False, qkv_bias=False, mlp_bias=False, norm_eps=arch["rms_norm_eps"],
        kv_lora_rank=m["kv_rank"], q_lora_rank=m["q_rank"], qk_nope_head_dim=m["nope"],
        qk_rope_head_dim=m["rope"], v_head_dim=m["v_dim"],
        rope_scaling=rs["type"], rope_factor=float(rs["factor"]),
        rope_original_context=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]), rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]), rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        n_experts=m["experts"], experts_per_token=m["top_k"], moe_routing="dropless",
        moe_score=arch["scoring_func"], moe_score_bias=arch["topk_method"] == "noaux_tc",
        moe_norm_topk=bool(arch["norm_topk_prob"]), moe_routed_scale=float(arch["routed_scaling_factor"]),
        n_shared_experts=m["shared"], d_expert=m["expert_ffn"],
        hc_mult=m["hc"], hc_sinkhorn_iters=arch["hc_sinkhorn_iters"], hc_eps=arch["hc_eps"],
        hc_res_clamp=float(arch["mhc_h_res_clamp_max"]),
    )
