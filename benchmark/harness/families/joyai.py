"""JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``, DeepSeek-V3's keys):
key names of the source's ``config.json``, parameter and byte counts,
canonical seeded weights and their place in the program's tree.

As in ``families/xing.py`` the harness hands a family no layer index and
stacks one homogeneous ``lax.map`` of layers, so the *expert* layers of the
stack are the family's layers (``dims()["layers"]``); the leading dense layer
lives among the globals as ``dense0_*`` and the multi-token-prediction module
as ``mtp_*`` (its two norms, the (2d, d) projection, one whole expert layer,
its final norm). ``program_tree`` puts them into the program's
``dense_blocks`` and ``mtp``; ``model_kwargs`` sets ``n_layers`` to dense +
expert layers and ``mtp_depth`` to ``num_nextn_predict_layers``.

This chip holds ``n_routed_experts`` of the ``n_experts_routed`` experts the
router scores (expert parallelism's share, the router's first ones), in the
stack's layers and in the module's block alike.

Canonical layout: matrices ``(in, out)``; the rotated slice of ``wq_b`` and
``wkv_a`` in the published interleaved pairs ``(2i, 2i+1)``
(``rope_interleave``); the program rotates split halves and ``program_layer``
permutes those columns. ``mtp_eh_proj`` takes the embedding half first, then
the hidden-state half (``assumed`` in the configuration file).

A self-drafting round is two queries a row through the stack and the module:
``moe_step_bytes``, ``latent_step_bytes`` and ``decode_step_min_bytes`` count
a *round* (the module's block one more expert layer and one more layer of
latents, the head read twice), and ``readers/part_roofline.py`` calls them;
``mtp_step_bytes`` counts the module's half alone (``readers/mtp_roofline.py``).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax.numpy as jnp

from harness.weights import STD, normal


def dims(arch: Dict[str, Any]) -> Dict[str, int]:
    dense = arch["first_k_dense_replace"]
    return dict(
        d=arch["hidden_size"], layers=arch["num_hidden_layers"] - dense, dense_layers=dense,
        mtp=arch["num_nextn_predict_layers"],
        heads=arch["num_attention_heads"], kv_heads=arch["num_key_value_heads"],
        head_dim=arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"],
        nope=arch["qk_nope_head_dim"], rope=arch["qk_rope_head_dim"], v_dim=arch["v_head_dim"],
        q_rank=arch["q_lora_rank"], kv_rank=arch["kv_lora_rank"],
        ffn=arch["intermediate_size"], expert_ffn=arch["moe_intermediate_size"],
        experts=arch.get("n_experts_routed", arch["n_routed_experts"]), experts_held=arch["n_routed_experts"],
        top_k=arch["num_experts_per_tok"], shared=arch["n_shared_experts"],
        vocab=arch["vocab_size"], vocab_rows=arch["vocab_size"], ctx=arch["max_position_embeddings"],
    )


# -- counts ------------------------------------------------------------------------


def attn_params(m: Dict[str, int]) -> int:
    d, h = m["d"], m["heads"]
    q = d * m["q_rank"] + m["q_rank"] + m["q_rank"] * h * m["head_dim"]
    kv = d * (m["kv_rank"] + m["rope"]) + m["kv_rank"] + m["kv_rank"] * h * (m["nope"] + m["v_dim"])
    return q + kv + h * m["v_dim"] * d


def expert_params(m: Dict[str, int]) -> int:
    return 3 * m["d"] * m["expert_ffn"]


def layer_params(m: Dict[str, int]) -> int:
    """One expert layer as held here: attention, two norms, the router over
    all experts and its bias, the experts held and the shared expert."""
    moe = m["d"] * m["experts"] + m["experts"] + (m["experts_held"] + m["shared"]) * expert_params(m)
    return attn_params(m) + 2 * m["d"] + moe


def dense_layer_params(m: Dict[str, int]) -> int:
    return attn_params(m) + 2 * m["d"] + 3 * m["d"] * m["ffn"]


def mtp_params(m: Dict[str, int]) -> int:
    """The module: one expert layer, the (2d, d) projection, three norms."""
    return m["mtp"] * (layer_params(m) + 2 * m["d"] * m["d"] + 3 * m["d"])


def other_params(m: Dict[str, int]) -> Tuple[int, int, int]:
    """(held outside the stack's expert layers, of those only looked up in
    training, of those only looked up in a decode step): both tables, the final
    norm, the leading dense layer and the module; the input embedding is a
    lookup when decoding."""
    table = m["vocab_rows"] * m["d"]
    held = 2 * table + m["d"] + m["dense_layers"] * dense_layer_params(m) + mtp_params(m)
    return held, 0, table


def cache_layers(m: Dict[str, int]) -> int:
    """Layers of latents a token: the stack's and the module's block."""
    return m["layers"] + m["dense_layers"] + m["mtp"]


def latent_bytes_per_token(arch: Dict[str, Any], bytes_per_el: int = 2) -> int:
    m = dims(arch)
    return (m["kv_rank"] + m["rope"]) * bytes_per_el * cache_layers(m)


def moe_step_bytes(arch: Dict[str, Any], touched_share: float, bytes_per_el: int = 2) -> float:
    """Bytes the expert FFNs of one round must read, over the stack's expert
    layers and the module's block: the experts some token of the round chose
    (``touched_share`` of those held, from the engine's counter), the shared
    expert, the router and its bias."""
    m = dims(arch)
    per_layer = (touched_share * m["experts_held"] + m["shared"]) * expert_params(m) \
        + m["d"] * m["experts"] + m["experts"]
    return (m["layers"] + m["mtp"]) * per_layer * bytes_per_el


def latent_step_bytes(arch: Dict[str, Any], resident_tokens: float, bytes_per_el: int = 2) -> float:
    """Bytes latent attention of one round must read: every resident token's
    latent once in every layer (both queries of a row read the same latents),
    and the absorbed projection ``wkv_b``."""
    m = dims(arch)
    wkv_b = m["kv_rank"] * m["heads"] * (m["nope"] + m["v_dim"])
    return resident_tokens * latent_bytes_per_token(arch, bytes_per_el) + cache_layers(m) * wkv_b * bytes_per_el


def mtp_step_bytes(arch: Dict[str, Any], resident_tokens: float, rows: int, touched_share: float,
                   bytes_per_el: int = 2, queries: int = 2) -> float:
    """Bytes the module's half of one round must read (``mtp.draft``): its
    block's attention, norms, router and shared expert, the experts its tokens
    touched (``touched_share`` of those held, from the engine's counter of the
    module's own layer), the (2d, d) projection and the three norms, the
    embedding as ``queries * rows`` rows, the head once, and every resident
    token's latent in the module's one layer."""
    m = dims(arch)
    d = m["d"]
    block = attn_params(m) + 2 * d + (touched_share * m["experts_held"] + m["shared"]) * expert_params(m) \
        + d * m["experts"] + m["experts"]
    fixed = block + 2 * d * d + 3 * d + queries * rows * d + m["vocab_rows"] * d
    return m["mtp"] * (fixed + resident_tokens * (m["kv_rank"] + m["rope"])) * bytes_per_el


def decode_step_min_bytes(arch: Dict[str, Any], resident_tokens: float, rows: int,
                          touched_share: float, bytes_per_el: int = 2, queries: int = 2) -> float:
    """All a round cannot avoid reading: every weight outside the routed
    experts once, except the head, which the stack and the module each read;
    the input embedding as ``queries * rows`` rows for each of the two; the
    experts touched; every resident token's latent."""
    m = dims(arch)
    d = m["d"]
    fixed = cache_layers(m) * (attn_params(m) + 2 * d) + m["dense_layers"] * 3 * d * m["ffn"] \
        + (1 + m["mtp"]) * (m["vocab_rows"] * d + d + queries * rows * d) + m["mtp"] * (2 * d * d + 2 * d)
    return fixed * bytes_per_el + moe_step_bytes(arch, touched_share, bytes_per_el) \
        + resident_tokens * latent_bytes_per_token(arch, bytes_per_el)


# -- canonical weights -------------------------------------------------------------


def _resid_std(m: Dict[str, int]) -> float:
    return STD / (2 * (m["layers"] + m["dense_layers"])) ** 0.5


def _shared_block(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """What the dense and the expert layers both have: latent attention and two norms."""
    d, h = m["d"], m["heads"]
    return {
        "ln1_scale": 1 + normal(k, 0, (d,), 0.1, dtype),
        "ln2_scale": 1 + normal(k, 1, (d,), 0.1, dtype),
        "wq_a": normal(k, 2, (d, m["q_rank"]), STD, dtype),
        "q_norm_scale": 1 + normal(k, 3, (m["q_rank"],), 0.1, dtype),
        "wq_b": normal(k, 4, (m["q_rank"], h * m["head_dim"]), STD, dtype),
        "wkv_a": normal(k, 5, (d, m["kv_rank"] + m["rope"]), STD, dtype),
        "kv_norm_scale": 1 + normal(k, 6, (m["kv_rank"],), 0.1, dtype),
        "wkv_b": normal(k, 7, (m["kv_rank"], h * (m["nope"] + m["v_dim"])), STD, dtype),
        "wo": normal(k, 8, (h * m["v_dim"], d), _resid_std(m), dtype),
    }


def layer(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """An expert layer: the published router over all experts, the experts this
    chip holds. The selection bias ~ N(0, 0.002), a tenth of a matrix's scale,
    as ``families/ling.py`` sets it: at 0.02 a seed's draw of it moves the share
    of pairs routed to the experts held here by a few percent."""
    d, f, e = m["d"], m["expert_ffn"], m["experts_held"]
    rs = _resid_std(m)
    w = _shared_block(m, k, dtype)
    w.update(
        router=normal(k, 9, (d, m["experts"]), STD, dtype),
        b_corr=normal(k, 10, (m["experts"],), STD / 10, dtype),
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
    kd = jax.random.fold_in(k, 99)
    dense0 = _shared_block(m, kd, dtype)
    dense0.update(w_gate=normal(kd, 9, (d, f), STD, dtype), w_up=normal(kd, 10, (d, f), STD, dtype),
                  w_down=normal(kd, 11, (f, d), _resid_std(m), dtype))
    out = {
        "embed": normal(k, 0, (m["vocab_rows"], d), STD, dtype),
        "final_scale": 1 + normal(k, 1, (d,), 0.1, dtype),
        "head": normal(k, 4, (d, m["vocab_rows"]), STD, dtype),
    }
    out.update({"dense0_" + name: v for name, v in dense0.items()})
    if m["mtp"]:
        km = jax.random.fold_in(k, 98)
        out.update({"mtp_" + name: v for name, v in layer(m, km, dtype).items()})
        out.update(
            mtp_enorm_scale=1 + normal(km, 30, (d,), 0.1, dtype),
            mtp_hnorm_scale=1 + normal(km, 31, (d,), 0.1, dtype),
            # rows 0..d-1 meet the normed embedding, rows d..2d-1 the normed hidden state
            mtp_eh_proj=normal(km, 32, (2 * d, d), STD, dtype),
            mtp_final_scale=1 + normal(km, 33, (d,), 0.1, dtype),
        )
    return out


# -- the program's tree ------------------------------------------------------------


def _split_halves(a: Any, rope_dim: int) -> Any:
    """The last ``rope_dim`` columns from interleaved pairs to split halves."""
    rot = a[..., -rope_dim:]
    return jnp.concatenate([a[..., :-rope_dim], rot[..., 0::2], rot[..., 1::2]], axis=-1)


def _program_shared(m: Dict[str, int], c: Dict[str, Any]) -> Dict[str, Any]:
    h = m["heads"]
    return {
        "ln1": {"scale": c["ln1_scale"]}, "ln2": {"scale": c["ln2_scale"]},
        "attn": {
            "wq_a": c["wq_a"], "q_norm": {"scale": c["q_norm_scale"]},
            "wq_b": _split_halves(c["wq_b"].reshape(m["q_rank"], h, m["head_dim"]), m["rope"]),
            "wkv_a": _split_halves(c["wkv_a"], m["rope"]), "kv_norm": {"scale": c["kv_norm_scale"]},
            "wkv_b": c["wkv_b"].reshape(m["kv_rank"], h, m["nope"] + m["v_dim"]),
            "wo": c["wo"].reshape(h, m["v_dim"], m["d"]),
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

    under = lambda prefix: {k[len(prefix):]: v for k, v in gl.items() if k.startswith(prefix)}
    a = blocks["attn"]  # (layers, ...): the widths every layer shares
    m = dict(d=a["wq_a"].shape[1], q_rank=a["wq_b"].shape[1], heads=a["wq_b"].shape[2],
             head_dim=a["wq_b"].shape[3], kv_rank=a["wkv_b"].shape[1], v_dim=a["wo"].shape[2])
    m["rope"] = a["wkv_a"].shape[2] - m["kv_rank"]
    m["nope"] = m["head_dim"] - m["rope"]
    c = under("dense0_")
    dense = _program_shared(m, c)
    dense["mlp"] = {"w1": jnp.stack([c["w_gate"], c["w_up"]], axis=1), "w2": c["w_down"]}
    tree = {
        "tok_embed": {"embedding": gl["embed"]}, "blocks": blocks,
        "dense_blocks": jax.tree.map(lambda a: a[None], dense),
        "final_norm": {"scale": gl["final_scale"]}, "lm_head": {"kernel": gl["head"]},
    }
    c = under("mtp_")
    if c:
        tree["mtp"] = {
            "enorm": {"scale": c["enorm_scale"]}, "hnorm": {"scale": c["hnorm_scale"]},
            "eh_proj": c["eh_proj"], "block": program_layer(m, c),
            "final_norm": {"scale": c["final_scale"]},
        }
    return tree


def model_kwargs(arch: Dict[str, Any], m: Dict[str, int]) -> Dict[str, Any]:
    if arch["rope_scaling"] is not None or arch["n_group"] != 1:
        raise ValueError("the JoyAI family runs plain RoPE and one routing group")
    return dict(
        n_layers=m["layers"] + m["dense_layers"], n_dense_layers=m["dense_layers"],
        d_head=m["head_dim"], mlp_ratio=m["ffn"] / m["d"], activation="swiglu", norm="rmsnorm",
        pos_embed="rope", rope_theta=float(arch["rope_theta"]), tie_embeddings=False,
        lm_head_bias=False, qkv_bias=False, mlp_bias=False, norm_eps=arch["rms_norm_eps"],
        kv_lora_rank=m["kv_rank"], q_lora_rank=m["q_rank"], qk_nope_head_dim=m["nope"],
        qk_rope_head_dim=m["rope"], v_head_dim=m["v_dim"],
        n_experts=m["experts"], n_experts_held=m["experts_held"] if m["experts_held"] < m["experts"] else 0,
        experts_per_token=m["top_k"], moe_routing="dropless",
        moe_score=arch["scoring_func"], moe_score_bias=arch["topk_method"] == "noaux_tc",
        moe_norm_topk=bool(arch["norm_topk_prob"]), moe_routed_scale=float(arch["routed_scaling_factor"]),
        n_shared_experts=m["shared"], d_expert=m["expert_ffn"],
        mtp_depth=m["mtp"],
    )
