"""Ling-3.0 (``model_type`` ``bailing_hybrid``): key names of the source's
``config.json``, parameter, operation and byte counts, canonical seeded weights
and their place in the program's tree.

The stack is hybrid: layer ``i`` is a latent-attention (MLA) layer when
``(i + 1) % layer_group_size == 0`` and a KDA linear-attention layer otherwise,
and the first ``first_k_dense_replace`` layers have a dense FFN where the rest
have experts. The harness hands a family no layer index and stacks one
homogeneous ``lax.map`` of layers, so the family's layers
(``dims()["layers"]``) are the *KDA layers with experts*, the most numerous
kind, and every other layer lives among the globals as ``L<i>_*`` (the
reference reads it there, ``program_tree`` puts it into its kind's stack of
the program's tree); ``model_kwargs`` sets the program's ``n_layers`` to all
of them.

This chip holds ``num_experts`` (128) of the ``num_experts_routed`` (512)
experts the router scores, its first two routing groups, and ``vocab_size``
(39,296) rows of the vocabulary: a share of a deployment, stated in the
configuration file.

Canonical layout: matrices ``(in, out)``; a convolution ``(channels, taps)``,
the last tap on the current token; the rotated slice of ``wq`` and ``wkv_a`` in
DeepSeek's interleaved pairs ``(2i, 2i+1)``. The program rotates split halves
``(j, j + half)``: ``program_layer`` permutes those columns, as a checkpoint
converter does, and the scores are the same numbers.

``harness/opcount.py`` counts per-head K/V and every weight; the counts of a
state that is read and written every step, of a latent cache and of experts of
which a step touches some are here, and ``readers/part_roofline.py`` and
``readers/kda_roofline.py`` call them. Each counts the *least* the work can
move: a roofline share above 100% is refused.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from harness.weights import STD, normal


def kinds(arch: Dict[str, Any]) -> List[Tuple[str, str]]:
    """(mixer, ffn) of every layer: ("kda" | "mla", "dense" | "moe")."""
    g, dense = arch["layer_group_size"], arch["first_k_dense_replace"]
    return [("mla" if (i + 1) % g == 0 else "kda", "dense" if i < dense else "moe")
            for i in range(arch["num_hidden_layers"])]


def dims(arch: Dict[str, Any]) -> Dict[str, int]:
    ks = kinds(arch)
    return dict(
        d=arch["hidden_size"], layers=ks.count(("kda", "moe")), all_layers=len(ks),
        group=arch["layer_group_size"], dense_layers=arch["first_k_dense_replace"],
        kda_dense=ks.count(("kda", "dense")), mla_dense=ks.count(("mla", "dense")),
        mla_moe=ks.count(("mla", "moe")),
        heads=arch["num_attention_heads"], kv_heads=arch["num_key_value_heads"],
        head_dim=arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"],
        nope=arch["qk_nope_head_dim"], rope=arch["qk_rope_head_dim"], v_dim=arch["v_head_dim"],
        kv_rank=arch["kv_lora_rank"], kda_dim=arch["head_dim"], taps=arch["short_conv_kernel_size"],
        ffn=arch["intermediate_size"], expert_ffn=arch["moe_intermediate_size"],
        experts=arch["num_experts_routed"], experts_held=arch["num_experts"],
        top_k=arch["num_experts_per_tok"], shared=arch["num_shared_experts"],
        vocab=arch["vocab_size"], vocab_rows=arch["vocab_size"], ctx=arch["max_position_embeddings"],
    )


# -- counts ------------------------------------------------------------------------


def kda_params(m: Dict[str, int]) -> int:
    """q, k, v, decay, output gate, output (6 d w), beta, three convolutions,
    A_log, dt_bias, the head norm's scale."""
    d, w = m["d"], m["heads"] * m["kda_dim"]
    return 6 * d * w + d * m["heads"] + 3 * w * m["taps"] + m["heads"] + w + m["kda_dim"]


def mla_params(m: Dict[str, int]) -> int:
    d, h = m["d"], m["heads"]
    kv = d * (m["kv_rank"] + m["rope"]) + m["kv_rank"] + m["kv_rank"] * h * (m["nope"] + m["v_dim"])
    return d * h * m["head_dim"] + kv + h * m["v_dim"] * d + d * h  # + the head-wise gate


def expert_params(m: Dict[str, int]) -> int:
    return 3 * m["d"] * m["expert_ffn"]


def moe_params(m: Dict[str, int]) -> int:
    """Router and its bias, the experts held, the shared expert."""
    return m["d"] * m["experts"] + m["experts"] + (m["experts_held"] + m["shared"]) * expert_params(m)


def layer_params(m: Dict[str, int]) -> int:
    """One KDA layer with experts, as held here."""
    return kda_params(m) + 2 * m["d"] + moe_params(m)


def other_params(m: Dict[str, int]) -> Tuple[int, int, int]:
    """(held outside the family's layers, of those only looked up in training,
    of those only looked up in a decode step): both tables, the final norm and
    every layer of another kind; the input embedding is a lookup when decoding."""
    table = m["vocab_rows"] * m["d"]
    dense = 3 * m["d"] * m["ffn"]
    others = (m["kda_dense"] * (kda_params(m) + dense) + m["mla_dense"] * (mla_params(m) + dense)
              + m["mla_moe"] * (mla_params(m) + moe_params(m))
              + 2 * m["d"] * (m["kda_dense"] + m["mla_dense"] + m["mla_moe"]))
    return 2 * table + m["d"] + others, 0, table


def _n(m: Dict[str, int]) -> Tuple[int, int, int]:
    """(KDA layers, MLA layers, expert layers)."""
    n_kda, n_mla = m["layers"] + m["kda_dense"], m["mla_dense"] + m["mla_moe"]
    return n_kda, n_mla, m["layers"] + m["mla_moe"]


def latent_bytes_per_token(arch: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """The page cache: one latent a token in every MLA layer, none in a KDA layer."""
    m = dims(arch)
    return (m["kv_rank"] + m["rope"]) * bytes_per_el * _n(m)[1]


def state_bytes_per_row(arch: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """The other cache: a float32 state and a conv tail a row in every KDA layer,
    whatever the row's length."""
    m = dims(arch)
    w = m["heads"] * m["kda_dim"]
    return _n(m)[0] * (4 * w * m["kda_dim"] + (m["taps"] - 1) * 3 * w * bytes_per_el)


def moe_step_bytes(arch: Dict[str, Any], touched_share: float, bytes_per_el: int = 2) -> float:
    """Bytes the expert FFNs of one decode step must read, over all expert
    layers: the experts some row chose (``touched_share`` of those held, from
    the engine's counter), the shared expert, the router and its bias."""
    m = dims(arch)
    per_layer = (touched_share * m["experts_held"] + m["shared"]) * expert_params(m) \
        + m["d"] * m["experts"] + m["experts"]
    return _n(m)[2] * per_layer * bytes_per_el


def latent_step_bytes(arch: Dict[str, Any], resident_tokens: float, bytes_per_el: int = 2) -> float:
    """Bytes latent attention of one decode step must read: every resident
    token's latent once in every MLA layer, and the absorbed projection ``wkv_b``."""
    m = dims(arch)
    wkv_b = m["kv_rank"] * m["heads"] * (m["nope"] + m["v_dim"])
    return resident_tokens * latent_bytes_per_token(arch, bytes_per_el) + _n(m)[1] * wkv_b * bytes_per_el


def kda_step_bytes(arch: Dict[str, Any], rows: int, bytes_per_el: int = 2) -> float:
    """Bytes ``kda.step`` of one decode step must move, over all KDA layers:
    every row's state read once and written once (float32), and the token's q, k,
    v, log-decay (float32, as the scope receives them), beta and output. The conv
    tails and the projections' weights belong to ``kda.conv`` and ``kda.proj``."""
    m = dims(arch)
    w = m["heads"] * m["kda_dim"]
    return _n(m)[0] * rows * (2 * 4 * w * m["kda_dim"] + 4 * (5 * w + m["heads"]))


def kda_chunk_ops_bytes(arch: Dict[str, Any], tokens: int) -> Tuple[float, float]:
    """(floating-point operations, bytes) ``kda.chunk`` needs for ``tokens``
    prompt tokens of one row, over all KDA layers. Per head and chunk of C = 64
    tokens of K = V = 128 channels, multiply-adds: A and P (2 C C K), the
    triangular solve (C C V / 2 for its right-hand side alone), K~ S, Q~ S and
    K^^T U (3 C K V), P U (C C V); 2 operations each. The pass over the MXU is
    counted once, though float32 takes several. Bytes: q, k, v, g in and o out in
    float32, the state read and written once a call."""
    m = dims(arch)
    c, k, h = 64, m["kda_dim"], m["heads"]
    chunks = math.ceil(tokens / c)
    mads = chunks * h * (2 * c * c * k + c * c * k / 2 + 3 * c * k * k + c * c * k)
    moved = 4 * (tokens * (5 * h * k + h) + 2 * h * k * k)
    return _n(m)[0] * 2.0 * mads, float(_n(m)[0] * moved)


def decode_step_min_bytes(arch: Dict[str, Any], resident_tokens: float, rows: int,
                          touched_share: float, bytes_per_el: int = 2) -> float:
    """All a decode step cannot avoid moving: every weight outside the routed
    experts once (the input embedding as ``rows`` rows), the experts touched,
    every row's KDA state read and written, and every resident token's latent."""
    m = dims(arch)
    n_kda, n_mla, _ = _n(m)
    fixed = n_kda * kda_params(m) + n_mla * mla_params(m) + 2 * m["d"] * m["all_layers"] \
        + (m["kda_dense"] + m["mla_dense"]) * 3 * m["d"] * m["ffn"] \
        + m["vocab_rows"] * m["d"] + m["d"] + rows * m["d"]
    w = m["heads"] * m["kda_dim"]
    state = n_kda * rows * 2 * 4 * w * m["kda_dim"]
    return fixed * bytes_per_el + moe_step_bytes(arch, touched_share, bytes_per_el) + state \
        + resident_tokens * latent_bytes_per_token(arch, bytes_per_el)


# -- canonical weights -------------------------------------------------------------


def _resid_std(m: Dict[str, int]) -> float:
    return STD / (2 * m["all_layers"]) ** 0.5


def _kda(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """A KDA mixer and the block's two norms. Every vector far from a constant,
    so that a dropped term shows: exp(A_log) from 0.5 to 4, dt_bias ~ N(-2, 1)
    (log-decays from about -0.01 to -4 a token: channels that forget in a token
    beside channels that keep hundreds). Convolution taps ~ N(0, 0.1): the
    convolved q, k, v then have a spread of 0.2 and pass SiLU near its linear
    part, zero in the mean. At N(0, 0.5) SiLU gives every token's v the same
    positive mean, the state piles it up, every KDA layer adds one vector to
    every token's residual, the routers and the head see mostly that vector,
    greedy decoding falls into a few tokens and 58% of the experts held are
    touched a step where even routing touches 86%, by a share that moves with
    the seed (my chip probes, PR 31: PERF.md section 6)."""
    d, h, n, taps = m["d"], m["heads"], m["kda_dim"], m["taps"]
    w = h * n
    out = {
        "ln1_scale": 1 + normal(k, 0, (d,), 0.1, dtype),
        "ln2_scale": 1 + normal(k, 1, (d,), 0.1, dtype),
        "wf": normal(k, 5, (d, w), STD, dtype),
        "A_log": jnp.log(0.5 + 3.5 * jax.random.uniform(jax.random.fold_in(k, 6), (h,))).astype(dtype),
        "dt_bias": (-2 + normal(k, 7, (w,), 1.0, jnp.float32)).astype(dtype),
        "wbeta": normal(k, 8, (d, h), STD, dtype),
        "wg": normal(k, 9, (d, w), STD, dtype),
        "o_norm_scale": 1 + normal(k, 30, (n,), 0.1, dtype),
        "wo": normal(k, 31, (w, d), _resid_std(m), dtype),
    }
    for i, name in enumerate("qkv"):
        out["w" + name] = normal(k, 2 + i, (d, w), STD, dtype)
        out["conv_" + name] = normal(k, 32 + i, (w, taps), 0.1, dtype)
    return out


def _mla(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    d, h = m["d"], m["heads"]
    return {
        "ln1_scale": 1 + normal(k, 0, (d,), 0.1, dtype),
        "ln2_scale": 1 + normal(k, 1, (d,), 0.1, dtype),
        "wq": normal(k, 2, (d, h * m["head_dim"]), STD, dtype),
        "wkv_a": normal(k, 5, (d, m["kv_rank"] + m["rope"]), STD, dtype),
        "kv_norm_scale": 1 + normal(k, 6, (m["kv_rank"],), 0.1, dtype),
        "wkv_b": normal(k, 7, (m["kv_rank"], h * (m["nope"] + m["v_dim"])), STD, dtype),
        "w_head_gate": normal(k, 8, (d, h), STD, dtype),
        "wo_attn": normal(k, 31, (h * m["v_dim"], d), _resid_std(m), dtype),
    }


def _moe(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """The published router over all experts, the experts this chip holds. The
    selection bias ~ N(0, 0.002), a tenth of a matrix's scale: at 0.02 a seed's
    draw of it moves the share of pairs routed to this chip's two groups
    between 23% and 27% (my chip probe, PR 31), the imbalance a trained
    router's bias updates exist to remove."""
    d, f, e = m["d"], m["expert_ffn"], m["experts_held"]
    rs = _resid_std(m)
    return dict(
        router=normal(k, 10, (d, m["experts"]), STD, dtype),
        b_corr=normal(k, 11, (m["experts"],), STD / 10, dtype),
        e_gate=normal(k, 12, (e, d, f), STD, dtype), e_up=normal(k, 13, (e, d, f), STD, dtype),
        e_down=normal(k, 14, (e, f, d), rs, dtype),
        s_gate=normal(k, 15, (d, m["shared"] * f), STD, dtype),
        s_up=normal(k, 16, (d, m["shared"] * f), STD, dtype),
        s_down=normal(k, 17, (m["shared"] * f, d), rs, dtype),
    )


def _dense(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    d, f = m["d"], m["ffn"]
    return dict(w_gate=normal(k, 20, (d, f), STD, dtype), w_up=normal(k, 21, (d, f), STD, dtype),
                w_down=normal(k, 22, (f, d), _resid_std(m), dtype))


def layer(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """A KDA layer with experts."""
    return {**_kda(m, k, dtype), **_moe(m, k, dtype)}


def _other_layers(m: Dict[str, int]) -> List[Tuple[int, str, str]]:
    """(index, mixer, ffn) of the layers that live among the globals: every
    layer of the pattern (``kinds``) that is not (kda, moe)."""
    ks = [("mla" if (i + 1) % m["group"] == 0 else "kda", "dense" if i < m["dense_layers"] else "moe")
          for i in range(m["all_layers"])]
    return [(i, mixer, ffn) for i, (mixer, ffn) in enumerate(ks) if (mixer, ffn) != ("kda", "moe")]


def globals_(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    out = {
        "embed": normal(k, 0, (m["vocab_rows"], m["d"]), STD, dtype),
        "final_scale": 1 + normal(k, 1, (m["d"],), 0.1, dtype),
        "head": normal(k, 4, (m["d"], m["vocab_rows"]), STD, dtype),
    }
    for i, mixer, ffn in _other_layers(m):
        kl = jax.random.fold_in(k, 100 + i)
        w = {**(_kda if mixer == "kda" else _mla)(m, kl, dtype),
             **(_dense if ffn == "dense" else _moe)(m, kl, dtype)}
        out.update({f"L{i}_{name}": v for name, v in w.items()})
    return out


# -- the program's tree ------------------------------------------------------------


def _split_halves(a: Any, rope_dim: int) -> Any:
    """The last ``rope_dim`` columns from interleaved pairs to split halves."""
    rot = a[..., -rope_dim:]
    return jnp.concatenate([a[..., :-rope_dim], rot[..., 0::2], rot[..., 1::2]], axis=-1)


def _program_mixer(m: Dict[str, int], c: Dict[str, Any]) -> Dict[str, Any]:
    h, n = m["heads"], m["kda_dim"]
    out = {"ln1": {"scale": c["ln1_scale"]}, "ln2": {"scale": c["ln2_scale"]}}
    if "wf" in c:
        d = c["wf"].shape[0]
        out["attn"] = {
            "wqkv": jnp.stack([c["w" + x].reshape(d, h, n) for x in "qkv"], axis=1),
            # (channels, taps) -> (taps, 3, H, n)
            "conv": jnp.stack([c["conv_" + x].T.reshape(-1, h, n) for x in "qkv"], axis=1),
            "wf": c["wf"].reshape(d, h, n), "A_log": c["A_log"], "dt_bias": c["dt_bias"].reshape(h, n),
            "wbeta": c["wbeta"], "wg": c["wg"].reshape(d, h, n),
            "o_norm": {"scale": c["o_norm_scale"]}, "wo": c["wo"].reshape(h, n, d),
        }
    else:
        d = c["wq"].shape[0]
        out["attn"] = {
            "wq": _split_halves(c["wq"].reshape(d, h, m["head_dim"]), m["rope"]),
            "wkv_a": _split_halves(c["wkv_a"], m["rope"]), "kv_norm": {"scale": c["kv_norm_scale"]},
            "wkv_b": c["wkv_b"].reshape(m["kv_rank"], h, m["nope"] + m["v_dim"]),
            "wgate": c["w_head_gate"], "wo": c["wo_attn"].reshape(h, m["v_dim"], d),
        }
    return out


def _program_ffn(c: Dict[str, Any]) -> Dict[str, Any]:
    if "w_gate" in c:
        return {"w1": jnp.stack([c["w_gate"], c["w_up"]], axis=1), "w2": c["w_down"]}
    return {
        "router": c["router"], "router_bias": c["b_corr"],
        # as the grouped matmul reads them: gate columns, then up columns
        "experts": {"w1": jnp.concatenate([c["e_gate"], c["e_up"]], axis=-1), "w2": c["e_down"]},
        "shared": {"w1": jnp.stack([c["s_gate"], c["s_up"]], axis=1), "w2": c["s_down"]},
    }


def program_layer(m: Dict[str, int], c: Dict[str, Any]) -> Dict[str, Any]:
    return {**_program_mixer(m, c), "mlp": _program_ffn(c)}


def program_tree(blocks: Any, gl: Dict[str, Any]) -> Dict[str, Any]:
    """``blocks`` (the KDA layers with experts, stacked in order) and the other
    layers from the globals, each kind in its own stack of the program's tree
    (``models/transformer.py::stack_key``), in layer order."""
    others = sorted({int(k.split("_")[0][1:]) for k in gl if k.startswith("L") and k[1].isdigit()})
    a = blocks["attn"]
    m = dict(heads=a["wqkv"].shape[3], kda_dim=a["wqkv"].shape[4])
    for i in others:
        if f"L{i}_wkv_b" in gl:
            wq, wkv_a, wkv_b, wo = (gl[f"L{i}_{x}"] for x in ("wq", "wkv_a", "wkv_b", "wo_attn"))
            m.update(head_dim=wq.shape[1] // m["heads"], kv_rank=wkv_b.shape[0],
                     v_dim=wo.shape[0] // m["heads"])
            m["rope"] = wkv_a.shape[1] - m["kv_rank"]
            m["nope"] = m["head_dim"] - m["rope"]
            break
    stacks: Dict[str, List[Any]] = {}
    for i in others:
        c = {k[len(f"L{i}_"):]: v for k, v in gl.items() if k.startswith(f"L{i}_")}
        key = ("attn_" if "wkv_b" in c else "") + ("dense_blocks" if "w_gate" in c else "blocks")
        stacks.setdefault(key, []).append(program_layer(m, c))
    out = {key: jax.tree.map(lambda *xs: jnp.stack(xs), *layers) for key, layers in stacks.items()}
    return {
        "tok_embed": {"embedding": gl["embed"]}, "blocks": blocks, **out,
        "final_norm": {"scale": gl["final_scale"]}, "lm_head": {"kernel": gl["head"]},
    }


def model_kwargs(arch: Dict[str, Any], m: Dict[str, int]) -> Dict[str, Any]:
    n = m["all_layers"]
    return dict(
        n_layers=n, n_dense_layers=arch["first_k_dense_replace"],
        d_head=m["head_dim"], mlp_ratio=m["ffn"] / m["d"], activation="swiglu", norm="rmsnorm",
        pos_embed="rope", rope_theta=float(arch["rope_theta"]), tie_embeddings=False,
        lm_head_bias=False, qkv_bias=False, mlp_bias=False, norm_eps=arch["rms_norm_eps"],
        kv_lora_rank=m["kv_rank"], q_lora_rank=arch["q_lora_rank"] or 0, qk_nope_head_dim=m["nope"],
        qk_rope_head_dim=m["rope"], v_head_dim=m["v_dim"],
        attn_output_gate=arch["gated_attention_proj_granularity_type"] == "head_wise",
        layer_group_size=arch["layer_group_size"], kda_head_dim=m["kda_dim"],
        kda_conv_kernel=m["taps"], kda_gate_lower_bound=float(arch["kda_lower_bound"]),
        n_experts=m["experts"], n_experts_held=m["experts_held"], experts_per_token=m["top_k"],
        moe_routing="dropless", moe_score=arch["scoring_func"],
        moe_score_bias=bool(arch["moe_router_enable_expert_bias"]),
        moe_norm_topk=bool(arch["norm_topk_prob"]), moe_routed_scale=float(arch["routed_scaling_factor"]),
        moe_n_group=arch["n_group"], moe_topk_group=arch["topk_group"],
        n_shared_experts=m["shared"], d_expert=m["expert_ffn"],
        moe_swiglu_limits=tuple(float(v) for v in arch["expert_swiglu_limit_list"][:n]),
        moe_shared_swiglu_limits=tuple(float(v) for v in arch["share_expert_swiglu_limit_list"][:n]),
    )
