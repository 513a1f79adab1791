"""Granite-4.0-H (``model_type`` ``granitemoehybrid``): key names of the source's
``config.json``, parameter, operation and byte counts, canonical seeded weights
and their place in the program's tree.

The stack is hybrid: ``layer_types[i]`` names each layer's mixer, ``"mamba"`` (a
Mamba-2 state-space layer) or ``"attention"`` (position-free grouped-query
attention); every layer has experts. The harness hands a family no layer index
and stacks one homogeneous ``lax.map`` of layers, so the family's layers
(``dims()["layers"]``) are the *Mamba-2 layers*, the most numerous kind, and each
attention layer lives among the globals as ``L<i>_*`` (the reference reads it
there, ``program_tree`` puts it into the program's ``attn_blocks``);
``model_kwargs`` sets the program's ``n_layers`` to all of them and hands it the
layer table.

This chip holds ``num_local_experts`` (18) of the ``n_experts_routed`` (72)
experts the router scores, the router's first ones, and ``vocab_size`` (25,088)
rows of the vocabulary: a share of a deployment, stated in the configuration
file.

Canonical layout: matrices ``(in, out)``; the convolution ``(channels, taps)``,
the last tap on the current token; ``w_in``'s columns ``[z | x B C | dt]``; the
attention layer's projections with their heads apart, ``(in, heads, head_dim)``
and ``(heads, head_dim, out)`` (``head_dim`` has no key in the source and ``wq``
is square, so the flat matrix would not say how many heads it holds).

``harness/opcount.py`` counts per-head K/V in every layer and every weight; the
counts of a state that is read and written every step, of pages in one layer of
ten and of experts of which a step touches some are here, and
``readers/part_roofline.py`` and ``readers/ssm_roofline.py`` call them. Each
counts the *least* the work can move: a roofline share above 100% is refused.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from harness.weights import STD, normal


def dims(arch: Dict[str, Any]) -> Dict[str, int]:
    types = arch["layer_types"][: arch["num_hidden_layers"]]
    if len(types) != arch["num_hidden_layers"] or set(types) - {"mamba", "attention"}:
        raise ValueError("layer_types names 'mamba' or 'attention' for every layer")
    d, h = arch["hidden_size"], arch["mamba_n_heads"]
    if arch["mamba_expand"] * d != h * arch["mamba_d_head"]:
        raise ValueError("mamba_expand x hidden_size is mamba_n_heads x mamba_d_head")
    return dict(
        d=d, layers=types.count("mamba"), all_layers=len(types), attn_layers=types.count("attention"),
        heads=arch["num_attention_heads"], kv_heads=arch["num_key_value_heads"],
        head_dim=d // arch["num_attention_heads"],
        ssm_heads=h, ssm_head_dim=arch["mamba_d_head"], d_state=arch["mamba_d_state"],
        groups=arch["mamba_n_groups"], taps=arch["mamba_d_conv"], chunk=arch["mamba_chunk_size"],
        ffn=arch["intermediate_size"], expert_ffn=arch["intermediate_size"],
        shared_ffn=arch["shared_intermediate_size"],
        experts=arch["n_experts_routed"], experts_held=arch["num_local_experts"],
        top_k=arch["num_experts_per_tok"],
        vocab=arch["vocab_size"], vocab_rows=arch["vocab_size"], ctx=arch["max_position_embeddings"],
        # not sizes, but what ``globals_`` (which is handed these dims alone) needs
        # to place and scale what it makes: where the attention layers sit, the
        # factors on the embedding and on the scores
        attn_at=tuple(i for i, t in enumerate(types) if t == "attention"),
        embed_mult=float(arch["embedding_multiplier"]), attn_mult=float(arch["attention_multiplier"]),
    )


def _widths(m: Dict[str, int]) -> Tuple[int, int]:
    """(d_in, channels of the convolution: x, then B and C of every group)."""
    w = m["ssm_heads"] * m["ssm_head_dim"]
    return w, w + 2 * m["groups"] * m["d_state"]


# -- counts ------------------------------------------------------------------------


def ssm_params(m: Dict[str, int]) -> int:
    """The input projection to [z | xBC | dt], the convolution's taps and bias,
    dt_bias, A_log and D a head, the gated norm's weight, the output projection."""
    w, c = _widths(m)
    return m["d"] * (w + c + m["ssm_heads"]) + c * (m["taps"] + 1) + 3 * m["ssm_heads"] + w + w * m["d"]


def attn_params(m: Dict[str, int]) -> int:
    return 2 * m["d"] * m["heads"] * m["head_dim"] + 2 * m["d"] * m["kv_heads"] * m["head_dim"]


def expert_params(m: Dict[str, int]) -> int:
    return 3 * m["d"] * m["expert_ffn"]


def moe_params(m: Dict[str, int]) -> int:
    """The router, the experts held, the shared expert."""
    return m["d"] * m["experts"] + m["experts_held"] * expert_params(m) + 3 * m["d"] * m["shared_ffn"]


def layer_params(m: Dict[str, int]) -> int:
    """One Mamba-2 layer with its experts, as held here."""
    return ssm_params(m) + 2 * m["d"] + moe_params(m)


def other_params(m: Dict[str, int]) -> Tuple[int, int, int]:
    """(held outside the family's layers, of those only looked up in training,
    of those only looked up in a decode step): the tied table (read whole by the
    head every step, so nothing of it is a mere lookup), the final norm and
    every attention layer."""
    others = m["attn_layers"] * (attn_params(m) + 2 * m["d"] + moe_params(m))
    return m["vocab_rows"] * m["d"] + m["d"] + others, 0, 0


def kv_bytes_per_token_layer(arch: Dict[str, Any], bytes_per_el: int = 2) -> int:
    m = dims(arch)
    return 2 * m["kv_heads"] * m["head_dim"] * bytes_per_el


def state_bytes_per_row(arch: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """The other cache: a float32 state and a conv tail a row in every Mamba-2
    layer, whatever the row's length."""
    m = dims(arch)
    w, c = _widths(m)
    return m["layers"] * (4 * w * m["d_state"] + (m["taps"] - 1) * c * bytes_per_el)


def moe_step_bytes(arch: Dict[str, Any], touched_share: float, bytes_per_el: int = 2) -> float:
    """Bytes the expert FFNs of one decode step must read, over all layers: the
    experts some row chose (``touched_share`` of those held, from the engine's
    counter), the shared expert and the router."""
    m = dims(arch)
    per_layer = touched_share * m["experts_held"] * expert_params(m) + 3 * m["d"] * m["shared_ffn"] \
        + m["d"] * m["experts"]
    return m["all_layers"] * per_layer * bytes_per_el


def attn_step_bytes(arch: Dict[str, Any], resident_tokens: float, bytes_per_el: int = 2) -> float:
    """Bytes the paged attention of one decode step must read: K and V of every
    resident token (summed over rows) in each attention layer. The projections
    around it are counted by ``decode_step_min_bytes``."""
    return dims(arch)["attn_layers"] * resident_tokens * kv_bytes_per_token_layer(arch, bytes_per_el)


def ssm_step_bytes(arch: Dict[str, Any], rows: int) -> float:
    """Bytes ``ssm.step`` of one decode step must move, over all Mamba-2 layers:
    every row's state read once and written once (float32), and the token's x,
    B, C, delta in and y out (float32, as the scope receives and leaves them).
    The conv tails and the projections' weights belong to ``ssm.conv`` and
    ``ssm.proj``."""
    m = dims(arch)
    w, _ = _widths(m)
    token = 2 * w + 2 * m["groups"] * m["d_state"] + m["ssm_heads"]
    return m["layers"] * rows * 4.0 * (2 * w * m["d_state"] + token)


def ssm_chunk_ops_bytes(arch: Dict[str, Any], tokens: int) -> Tuple[float, float]:
    """(floating-point operations, bytes) ``ssm.chunk`` needs for ``tokens``
    prompt tokens of one row, over all Mamba-2 layers. Per chunk of Q tokens,
    multiply-adds: C B^T a group (Q Q N), and a head the masked product with x
    (Q Q P), C S_in and the new state (2 Q P N); 2 operations each. The pass over
    the MXU is counted once, though float32 takes several. Bytes: x, B, C, delta in
    and y out in float32, the state read and written once a call."""
    m = dims(arch)
    q, n, p, h, g = m["chunk"], m["d_state"], m["ssm_head_dim"], m["ssm_heads"], m["groups"]
    chunks = math.ceil(tokens / q)
    mads = chunks * (g * q * q * n + h * (q * q * p + 2 * q * p * n))
    moved = 4 * (tokens * (2 * h * p + 2 * g * n + h) + 2 * h * p * n)
    return m["layers"] * 2.0 * mads, float(m["layers"] * moved)


def decode_step_min_bytes(arch: Dict[str, Any], resident_tokens: float, rows: int,
                          touched_share: float, bytes_per_el: int = 2) -> float:
    """All a decode step cannot avoid moving: every weight outside the routed
    experts once (the tied table once, by the head; the embedding's ``rows`` rows
    beside it), the experts touched, every row's state read and written in every
    Mamba-2 layer, and every resident token's K and V in the attention layer."""
    m = dims(arch)
    w, _ = _widths(m)
    fixed = m["layers"] * ssm_params(m) + m["attn_layers"] * attn_params(m) + 2 * m["d"] * m["all_layers"] \
        + m["vocab_rows"] * m["d"] + m["d"] + rows * m["d"]
    state = m["layers"] * rows * 2 * 4 * w * m["d_state"]
    return fixed * bytes_per_el + moe_step_bytes(arch, touched_share, bytes_per_el) + state \
        + attn_step_bytes(arch, resident_tokens, bytes_per_el)


# -- canonical weights -------------------------------------------------------------
#
# The model scales each sublayer's output by residual_multiplier (0.22, near
# 1/sqrt(2 x 10 layers)), so the output projections are N(0, STD) like every other
# matrix and take no 1/sqrt(2L) of their own. The embedding is N(0, STD /
# embedding_multiplier): the embedded token then has the scale every other
# configuration's has, and the tied head, which scores a position against the very
# rows it embeds, gives a token's own row a logit one standard deviation above
# the others and not forty (at N(0, STD) a greedy row repeats its last prompt
# token for ever, whatever the layers compute).


def _ssm(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """A Mamba-2 mixer and the block's two norms. Every vector far from a
    constant, so that a dropped term shows: exp(A_log) uniform in [1, 16],
    softplus(dt_bias) log-uniform in [1e-3, 1e-1] (heads that forget within a
    token beside heads that keep thousands), D ~ 1 + N(0, 0.1), four taps of
    N(0, 0.5) and a bias of N(0, 0.1) (the convolved xBC keeps the scale of the
    projected one)."""
    d, h, taps = m["d"], m["ssm_heads"], m["taps"]
    w, c = _widths(m)
    delta = jnp.exp(jax.random.uniform(
        jax.random.fold_in(k, 7), (h,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "ln1_scale": 1 + normal(k, 0, (d,), 0.1, dtype),
        "ln2_scale": 1 + normal(k, 1, (d,), 0.1, dtype),
        "w_in": normal(k, 2, (d, w + c + h), STD, dtype),
        "conv": normal(k, 3, (c, taps), 0.5, dtype),
        "conv_bias": normal(k, 4, (c,), 0.1, dtype),
        "A_log": jnp.log(jax.random.uniform(jax.random.fold_in(k, 6), (h,), jnp.float32, 1.0, 16.0)).astype(dtype),
        "dt_bias": (delta + jnp.log(-jnp.expm1(-delta))).astype(dtype),  # softplus's inverse
        "D": 1 + normal(k, 8, (h,), 0.1, dtype),
        "norm_scale": 1 + normal(k, 9, (w,), 0.1, dtype),
        "w_out": normal(k, 31, (w, d), STD, dtype),
    }


SCORE_SPREAD = 3.0  # standard deviation of a seeded attention layer's scores


def _attn(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """An attention layer and the block's two norms. ``attention_multiplier``
    is 1/head_dim where independent q and k give q.k a spread of sqrt(head_dim):
    at N(0, STD) the scores would spread by 0.15, every softmax would be a plain
    average over the whole context, the layer would add a hundredth of what a
    Mamba-2 layer adds and no fault in it could show. ``wq`` and ``wk`` are
    N(0, s) with s such that the scores spread by ``SCORE_SPREAD`` under the
    model's own multiplier: a row attends to a handful of positions, as trained
    heads do, and which ones depends on every factor of the score."""
    d, h, g, dh = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    qk_std = (SCORE_SPREAD / (d * dh ** 0.5 * m["attn_mult"])) ** 0.5
    return {
        "ln1_scale": 1 + normal(k, 0, (d,), 0.1, dtype),
        "ln2_scale": 1 + normal(k, 1, (d,), 0.1, dtype),
        "wq": normal(k, 2, (d, h, dh), qk_std, dtype),
        "wk": normal(k, 3, (d, g, dh), qk_std, dtype),
        "wv": normal(k, 4, (d, g, dh), STD, dtype),
        "wo": normal(k, 31, (h, dh, d), STD, dtype),
    }


def _moe(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """The published router over all experts (logits of a spread near 1.3 on a
    normed input: the ten gates kept run over a factor of four or five, neither
    uniform nor one-hot), the experts this chip holds, the shared expert."""
    d, f, e, s = m["d"], m["expert_ffn"], m["experts_held"], m["shared_ffn"]
    return dict(
        router=normal(k, 10, (d, m["experts"]), STD, dtype),
        e_gate=normal(k, 12, (e, d, f), STD, dtype), e_up=normal(k, 13, (e, d, f), STD, dtype),
        e_down=normal(k, 14, (e, f, d), STD, dtype),
        s_gate=normal(k, 15, (d, s), STD, dtype), s_up=normal(k, 16, (d, s), STD, dtype),
        s_down=normal(k, 17, (s, d), STD, dtype),
    )


def layer(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """A Mamba-2 layer with its experts."""
    return {**_ssm(m, k, dtype), **_moe(m, k, dtype)}


def globals_(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    out = {
        "embed": normal(k, 0, (m["vocab_rows"], m["d"]), STD / m["embed_mult"], dtype),
        "final_scale": 1 + normal(k, 1, (m["d"],), 0.1, dtype),
    }
    for i in m["attn_at"]:
        kl = jax.random.fold_in(k, 100 + i)
        out.update({f"L{i}_{name}": v for name, v in {**_attn(m, kl, dtype), **_moe(m, kl, dtype)}.items()})
    return out


# -- the program's tree ------------------------------------------------------------


def _program_ffn(c: Dict[str, Any]) -> Dict[str, Any]:
    return {
        "router": c["router"],
        # as the grouped matmul reads them: gate columns, then up columns
        "experts": {"w1": jnp.concatenate([c["e_gate"], c["e_up"]], axis=-1), "w2": c["e_down"]},
        "shared": {"w1": jnp.stack([c["s_gate"], c["s_up"]], axis=1), "w2": c["s_down"]},
    }


def program_layer(m: Dict[str, int], c: Dict[str, Any]) -> Dict[str, Any]:
    out = {"ln1": {"scale": c["ln1_scale"]}, "ln2": {"scale": c["ln2_scale"]}, "mlp": _program_ffn(c)}
    if "w_in" in c:
        out["attn"] = {
            "w_in": c["w_in"], "conv": c["conv"].T, "conv_bias": c["conv_bias"], "dt_bias": c["dt_bias"],
            "A_log": c["A_log"], "D": c["D"], "norm": {"scale": c["norm_scale"]}, "w_out": c["w_out"],
        }
    else:
        out["attn"] = {
            "wq": c["wq"], "wkv": jnp.stack([c["wk"], c["wv"]], axis=1), "wo": c["wo"],
            # the program always carries an output bias; Granite has none
            "bo": jnp.zeros((c["wo"].shape[-1],), c["wo"].dtype),
        }
    return out


def program_tree(blocks: Any, gl: Dict[str, Any]) -> Dict[str, Any]:
    """``blocks`` (the Mamba-2 layers, stacked in order) and the attention layers
    from the globals in the program's ``attn_blocks``
    (``models/transformer.py::stack_key``), in layer order. The head is the
    embedding (tied)."""
    at = sorted({int(k.split("_")[0][1:]) for k in gl if k.startswith("L") and k[1].isdigit()})
    layers = [
        program_layer({}, {k[len(f"L{i}_"):]: v for k, v in gl.items() if k.startswith(f"L{i}_")}) for i in at
    ]
    return {
        "tok_embed": {"embedding": gl["embed"]}, "blocks": blocks,
        "attn_blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *layers),
        "final_norm": {"scale": gl["final_scale"]},
    }


def model_kwargs(arch: Dict[str, Any], m: Dict[str, int]) -> Dict[str, Any]:
    if arch["position_embedding_type"] != "nope" or arch["rope_scaling"] is not None:
        raise ValueError("the Granite-4.0-H family runs without positions")
    if arch["mamba_proj_bias"] or arch["attention_bias"] or not arch["mamba_conv_bias"]:
        raise ValueError("the Granite-4.0-H family has no bias but the convolution's")
    if arch["shared_intermediate_size"] % arch["intermediate_size"]:
        raise ValueError("the shared expert is a whole number of expert widths")
    return dict(
        n_layers=m["all_layers"], n_kv_heads=m["kv_heads"], mlp_ratio=m["ffn"] / m["d"],
        activation="swiglu", norm="rmsnorm", pos_embed="none", tie_embeddings=bool(arch["tie_word_embeddings"]),
        lm_head_bias=False, qkv_bias=False, mlp_bias=False, norm_eps=arch["rms_norm_eps"],
        layer_mixers=tuple("attn" if t == "attention" else "mamba" for t in arch["layer_types"][: m["all_layers"]]),
        mamba_heads=m["ssm_heads"], mamba_head_dim=m["ssm_head_dim"], mamba_d_state=m["d_state"],
        mamba_n_groups=m["groups"], mamba_conv_kernel=m["taps"], mamba_chunk_size=m["chunk"],
        embed_scale=float(arch["embedding_multiplier"]), residual_multiplier=float(arch["residual_multiplier"]),
        attention_multiplier=float(arch["attention_multiplier"]), logits_scaling=float(arch["logits_scaling"]),
        n_experts=m["experts"], n_experts_held=m["experts_held"], experts_per_token=m["top_k"],
        moe_routing="dropless", moe_score="softmax", moe_norm_topk=True, moe_routed_scale=1.0,
        n_shared_experts=m["shared_ffn"] // m["expert_ffn"], d_expert=m["expert_ffn"],
    )
