"""Nemotron-H (``model_type`` ``nemotron_h``): key names of the source's
``config.json``, parameter, operation and byte counts, canonical seeded weights
and their place in the program's tree.

The stack is a table of **single sublayers**: ``hybrid_override_pattern[i]``
says what layer ``i`` is, a Mamba-2 mixer alone (``M``), an attention alone
(``*``) or an expert FFN alone (``E``), each ``x + f(N(x))`` under its one norm.
The harness hands a family no layer index, stacks one homogeneous ``lax.map`` of
units and asks every unit for a state-space layer's ``dt_bias`` and ``A_log``
(``harness/ssm_check.py``). With the attention layers taken out the pattern
alternates M, E from layer 0 on, so the family's unit (``dims()["layers"]`` of
them) is **a Mamba-2 layer and the expert layer that follows it**, and each
attention layer lives among the globals as ``L<i>_*`` (the reference reads it
there, ``program_tree`` sorts everything into the program's three stacks in
published order); ``model_kwargs`` sets the program's ``n_layers`` to all of
them and hands it the table.

This chip holds ``n_routed_experts`` (32) of the ``n_experts_routed`` (128)
experts the router scores, the router's first ones, and ``vocab_size`` (32,768)
rows of embedding and of head: a share of a deployment, stated in the
configuration file.

Canonical layout: matrices ``(in, out)``; the convolution ``(channels, taps)``,
the last tap on the current token; ``w_in``'s columns ``[z | x B C | dt]``; an
attention layer's projections with their heads apart, ``(in, heads, head_dim)``
and ``(heads, head_dim, out)``; an expert is TWO matrices, ``e_up (E, d, f)`` and
``e_down (E, f, d)``, no gate.

``harness/opcount.py`` counts per-head K/V in every layer and every weight; the
counts of a state that is read and written every step, of pages in three layers
of twenty-five and of experts of which a step touches some are here, and
``readers/part_roofline.py`` and ``readers/ssm_roofline.py`` call them. Each
counts the *least* the work can move, at the expert's real width (1,856, two
matrices): a roofline share above 100% is refused.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from harness.weights import STD, normal


def dims(arch: Dict[str, Any]) -> Dict[str, int]:
    pattern = arch["hybrid_override_pattern"][: arch["num_hidden_layers"]]
    units = pattern.count("M")
    if len(pattern) != arch["num_hidden_layers"] or pattern.replace("*", "") != "ME" * units or not units:
        raise ValueError(
            "hybrid_override_pattern names M, * or E for every layer, and with the attention layers taken "
            "out it alternates M, E from the first layer to the last (the family's unit of weights)")
    if arch["moe_shared_expert_intermediate_size"] % arch["moe_intermediate_size"]:
        raise ValueError("the shared expert is a whole number of expert widths")
    return dict(
        d=arch["hidden_size"], layers=units, all_layers=len(pattern), attn_layers=pattern.count("*"),
        heads=arch["num_attention_heads"], kv_heads=arch["num_key_value_heads"], head_dim=arch["head_dim"],
        ssm_heads=arch["mamba_num_heads"], ssm_head_dim=arch["mamba_head_dim"], d_state=arch["ssm_state_size"],
        groups=arch["n_groups"], taps=arch["conv_kernel"], chunk=arch["chunk_size"],
        ffn=arch["intermediate_size"], expert_ffn=arch["moe_intermediate_size"],
        shared_ffn=arch["n_shared_experts"] * arch["moe_shared_expert_intermediate_size"],
        experts=arch["n_experts_routed"], experts_held=arch["n_routed_experts"],
        top_k=arch["num_experts_per_tok"],
        vocab=arch["vocab_size"], vocab_rows=arch["vocab_size"], ctx=arch["max_position_embeddings"],
        # not a size, but what ``globals_`` (which is handed these dims alone) needs: where the attention layers sit
        attn_at=tuple(i for i, letter in enumerate(pattern) if letter == "*"),
    )


def _widths(m: Dict[str, int]) -> Tuple[int, int]:
    """(d_in, channels of the convolution: x, then B and C of every group).
    d_in is heads x head_dim (4,096), NOT expand x hidden_size (5,376)."""
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
    """Two matrices, no gate."""
    return 2 * m["d"] * m["expert_ffn"]


def shared_params(m: Dict[str, int]) -> int:
    return 2 * m["d"] * m["shared_ffn"]


def moe_params(m: Dict[str, int]) -> int:
    """The router and its selection bias, the experts held, the shared expert."""
    return m["d"] * m["experts"] + m["experts"] + m["experts_held"] * expert_params(m) + shared_params(m)


def layer_params(m: Dict[str, int]) -> int:
    """One unit as held here: a Mamba-2 layer and the expert layer behind it, a norm each."""
    return ssm_params(m) + moe_params(m) + 2 * m["d"]


def other_params(m: Dict[str, int]) -> Tuple[int, int, int]:
    """(held outside the family's units, of those only looked up in training,
    of those only looked up in a decode step): the embedding (looked up: ``rows``
    rows a step), the untied head (read whole), the final norm and every
    attention layer with its norm."""
    table = m["vocab_rows"] * m["d"]
    return 2 * table + m["d"] + m["attn_layers"] * (attn_params(m) + m["d"]), table, table


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
    """Bytes the expert FFNs of one decode step must read, over all expert
    layers: the experts some row chose (``touched_share`` of those held, from the
    engine's counter; two matrices of the real width each), the shared expert
    and the router."""
    m = dims(arch)
    per_layer = touched_share * m["experts_held"] * expert_params(m) + shared_params(m) + m["d"] * m["experts"]
    return m["layers"] * per_layer * bytes_per_el


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
    prompt tokens of one row, over all Mamba-2 layers (``families/granite.py``'s
    count, at this model's groups)."""
    m = dims(arch)
    q, n, p, h, g = m["chunk"], m["d_state"], m["ssm_head_dim"], m["ssm_heads"], m["groups"]
    chunks = math.ceil(tokens / q)
    mads = chunks * (g * q * q * n + h * (q * q * p + 2 * q * p * n))
    moved = 4 * (tokens * (2 * h * p + 2 * g * n + h) + 2 * h * p * n)
    return m["layers"] * 2.0 * mads, float(m["layers"] * moved)


def decode_step_min_bytes(arch: Dict[str, Any], resident_tokens: float, rows: int,
                          touched_share: float, bytes_per_el: int = 2) -> float:
    """All a decode step cannot avoid moving: every weight outside the routed
    experts once (the head whole; of the embedding ``rows`` rows), the experts
    touched, every row's state read and written in every Mamba-2 layer, and
    every resident token's K and V in the attention layers."""
    m = dims(arch)
    w, _ = _widths(m)
    fixed = m["layers"] * ssm_params(m) + m["attn_layers"] * attn_params(m) + m["d"] * (m["all_layers"] + 1) \
        + m["layers"] * m["experts"] + m["vocab_rows"] * m["d"] + rows * m["d"]
    state = m["layers"] * rows * 2 * 4 * w * m["d_state"]
    return fixed * bytes_per_el + moe_step_bytes(arch, touched_share, bytes_per_el) + state \
        + attn_step_bytes(arch, resident_tokens, bytes_per_el)


# -- canonical weights -------------------------------------------------------------
#
# The model has no multiplier anywhere, so the scales are the harness's own:
# every matrix N(0, STD) (0.02). On a normed input of 2,688 channels W1 u then
# spreads by about 1, relu(.)^2 of it has a mean square near 1.9, and W2 over
# 1,856 (3,712) such channels gives an expert an output near 1.2 (the shared
# one 1.6) a channel: an expert layer adds about 2 to the residual a layer, a
# Mamba-2 layer (a group-normed y through W_out) about 1.3, and after 25 layers
# the residual stands near 8 (CPU probe at the published widths, PR 58:
# ``tests/test_nemotron_h.py`` holds the toy's). Nothing vanishes and nothing
# nears bfloat16's 3e38; a 1/sqrt(2L) on the output projections, which the
# pre-norm stack does not need, is left out as Granite's family leaves it.


def _ssm(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """A Mamba-2 mixer and its layer's norm. Every vector far from a constant,
    so that a dropped term shows: exp(A_log) uniform in [1, 16], softplus(dt_bias)
    log-uniform in [1e-3, 1e-1] (heads that forget within a token beside heads
    that keep thousands), D ~ 1 + N(0, 0.1), four taps of N(0, 0.5) and a bias of
    N(0, 0.1) (the convolved xBC keeps the scale of the projected one)."""
    d, h, taps = m["d"], m["ssm_heads"], m["taps"]
    w, c = _widths(m)
    delta = jnp.exp(jax.random.uniform(
        jax.random.fold_in(k, 7), (h,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "ln_m_scale": 1 + normal(k, 0, (d,), 0.1, dtype),
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
# Of the router's selection bias. The sixth and seventh of 128 scores stand about 0.02 apart, so
# N(0, 0.01) changes the chosen six for a good share of tokens and leaves the load near even: at
# N(0, 0.1) the best-liked expert got seven times the mean and a step touched 62% of the experts
# held (my chip run, PR 58), which is a router's collapse and no model's steady state.
BIAS_SPREAD = 0.01


def _attn(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """An attention layer and its norm. At N(0, STD) the scores q.k / sqrt(128)
    would spread by 1: ``wq`` and ``wk`` are N(0, s) with s such that they spread
    by ``SCORE_SPREAD`` (s^2 d = 3), so that a row attends to a handful of
    positions, as trained heads do, and which ones depends on every factor."""
    d, h, g, dh = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    qk_std = (SCORE_SPREAD / d) ** 0.5
    return {
        "ln_scale": 1 + normal(k, 0, (d,), 0.1, dtype),
        "wq": normal(k, 2, (d, h, dh), qk_std, dtype),
        "wk": normal(k, 3, (d, g, dh), qk_std, dtype),
        "wv": normal(k, 4, (d, g, dh), STD, dtype),
        "wo": normal(k, 31, (h, dh, d), STD, dtype),
    }


def _centred(k: Any, i: int, shape: Tuple[int, ...], dtype: Any) -> Any:
    """A down-projection N(0, STD) whose every output channel's weights sum to
    zero over the hidden width. relu(.)^2 is never negative: its mean is half its
    spread, and an uncentred W2 turns that mean into ONE vector common to every
    token, 16% of the layer's output power (my CPU probe at the published widths,
    PR 58). The next router reads that vector as a selection bias a third of its
    logits' spread wide: the best-liked expert got five times the mean load and
    a step touched 89.5% of the experts held, seed by seed another share (my chip
    runs, PR 58; the probe reads 5.00 and, centred, 1.50 of 512 tokens). A trained
    model's correction bias exists to cancel just that; a seeded one is spared it."""
    w = jax.random.normal(jax.random.fold_in(k, i), shape, jnp.float32) * STD
    return (w - jnp.mean(w, axis=-2, keepdims=True)).astype(dtype)


def _moe(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """The published router over all experts (logits of a spread near 1 on a
    normed input: sigmoid scores from 0.1 to 0.9) and its selection bias
    (N(0, BIAS_SPREAD): it changes which six are chosen for some tokens and,
    by the equations, no gate; ``tests/test_nemotron_h.py`` holds both), the
    experts this chip holds, two matrices each, the shared expert; both kinds'
    down-projections centred over the hidden width (``_centred``)."""
    d, f, e, s = m["d"], m["expert_ffn"], m["experts_held"], m["shared_ffn"]
    return dict(
        ln_e_scale=1 + normal(k, 1, (d,), 0.1, dtype),
        router=normal(k, 10, (d, m["experts"]), STD, dtype),
        router_bias=normal(k, 11, (m["experts"],), BIAS_SPREAD, dtype),
        e_up=normal(k, 12, (e, d, f), STD, dtype), e_down=_centred(k, 14, (e, f, d), dtype),
        s_up=normal(k, 15, (d, s), STD, dtype), s_down=_centred(k, 17, (s, d), dtype),
    )


def layer(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """A unit: a Mamba-2 layer and the expert layer that follows it."""
    return {**_ssm(m, k, dtype), **_moe(m, k, dtype)}


def globals_(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    out = {
        "embed": normal(k, 0, (m["vocab_rows"], m["d"]), STD, dtype),
        "final_scale": 1 + normal(k, 1, (m["d"],), 0.1, dtype),
        "head": normal(k, 2, (m["d"], m["vocab_rows"]), STD, dtype),
    }
    for i in m["attn_at"]:
        out.update({f"L{i}_{name}": v for name, v in _attn(m, jax.random.fold_in(k, 100 + i), dtype).items()})
    return out


# -- the program's tree ------------------------------------------------------------


def program_layer(m: Dict[str, int], c: Dict[str, Any]) -> Dict[str, Any]:
    """A unit as two of the program's one-sublayer blocks, side by side (``blocks``
    a Mamba-2 mixer under ``ln1``, ``ffn_blocks`` the experts under ``ln2``:
    ``program_tree`` takes them apart), or an attention layer's block."""
    if "w_in" not in c:
        return {"ln1": {"scale": c["ln_scale"]}, "attn": {
            "wq": c["wq"], "wkv": jnp.stack([c["wk"], c["wv"]], axis=1), "wo": c["wo"],
            # the program always carries an output bias; the model has none
            "bo": jnp.zeros((c["wo"].shape[-1],), c["wo"].dtype),
        }}
    return {
        "blocks": {"ln1": {"scale": c["ln_m_scale"]}, "attn": {
            "w_in": c["w_in"], "conv": c["conv"].T, "conv_bias": c["conv_bias"], "dt_bias": c["dt_bias"],
            "A_log": c["A_log"], "D": c["D"], "norm": {"scale": c["norm_scale"]}, "w_out": c["w_out"],
        }},
        "ffn_blocks": {"ln2": {"scale": c["ln_e_scale"]}, "mlp": {
            "router": c["router"], "router_bias": c["router_bias"],
            # two matrices an expert, as the grouped matmul and the kernel read them
            "experts": {"w1": c["e_up"], "w2": c["e_down"]},
            "shared": {"w1": c["s_up"], "w2": c["s_down"]},
        }},
    }


def program_tree(blocks: Any, gl: Dict[str, Any]) -> Dict[str, Any]:
    """``blocks`` (the units, stacked in order: their Mamba-2 halves are the
    program's ``blocks``, their expert halves its ``ffn_blocks``, both in
    published order) and the attention layers from the globals in the program's
    ``attn_blocks`` (``models/transformer.py::stack_key``), in layer order."""
    at = sorted({int(k.split("_")[0][1:]) for k in gl if k.startswith("L") and k[1].isdigit()})
    layers = [
        program_layer({}, {k[len(f"L{i}_"):]: v for k, v in gl.items() if k.startswith(f"L{i}_")}) for i in at
    ]
    return {
        "tok_embed": {"embedding": gl["embed"]}, "blocks": blocks["blocks"], "ffn_blocks": blocks["ffn_blocks"],
        "attn_blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *layers),
        "final_norm": {"scale": gl["final_scale"]}, "lm_head": {"kernel": gl["head"]},
    }


def model_kwargs(arch: Dict[str, Any], m: Dict[str, int]) -> Dict[str, Any]:
    if arch["attention_bias"] or arch["mamba_proj_bias"] or arch["mlp_bias"] or arch["use_bias"] \
            or not arch["use_conv_bias"]:
        raise ValueError("the Nemotron-H family has no bias but the convolution's")
    if arch["mlp_hidden_act"] != "relu2" or arch["mamba_hidden_act"] != "silu":
        raise ValueError("the Nemotron-H family's experts are ungated relu^2 and its Mamba-2 gates SiLU")
    if arch["n_group"] != 1 or arch["topk_group"] != 1 or not arch["norm_topk_prob"]:
        raise ValueError("the Nemotron-H router has no group limit and renormalises its gates")
    from pretraining_llm_tpu.config import layers_from_pattern

    return dict(
        n_layers=m["all_layers"], n_kv_heads=m["kv_heads"], d_head=m["head_dim"], mlp_ratio=m["ffn"] / m["d"],
        activation="relu2", norm="rmsnorm", pos_embed="none", tie_embeddings=bool(arch["tie_word_embeddings"]),
        lm_head_bias=False, qkv_bias=False, mlp_bias=False, norm_eps=arch["layer_norm_epsilon"],
        **layers_from_pattern(arch["hybrid_override_pattern"][: m["all_layers"]]),
        mamba_heads=m["ssm_heads"], mamba_head_dim=m["ssm_head_dim"], mamba_d_state=m["d_state"],
        mamba_n_groups=m["groups"], mamba_conv_kernel=m["taps"], mamba_chunk_size=m["chunk"],
        n_experts=m["experts"], n_experts_held=m["experts_held"], experts_per_token=m["top_k"],
        moe_routing="dropless", moe_score="sigmoid", moe_score_bias=True, moe_norm_topk=True,
        moe_routed_scale=float(arch["routed_scaling_factor"]),
        n_shared_experts=m["shared_ffn"] // m["expert_ffn"], d_expert=m["expert_ffn"],
    )
