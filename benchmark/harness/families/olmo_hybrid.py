"""Olmo-Hybrid (``model_type`` ``olmo_hybrid``): key names of the source's
``config.json``, parameter, operation and byte counts, canonical seeded weights
and their place in the program's tree.

The stack is hybrid: ``layer_types[i]`` names each layer's mixer,
``"linear_attention"`` (a Gated DeltaNet layer) or ``"full_attention"``
(position-free multi-head attention under whole-width q/k norms); every layer
has a dense SwiGLU and norms its sublayers' outputs. The harness hands a family
no layer index and stacks one homogeneous ``lax.map`` of layers, so the family's
layers (``dims()["layers"]``) are the *Gated DeltaNet layers*, the most numerous
kind, and each attention layer lives among the globals as ``L<i>_*`` (the
reference reads it there, ``program_tree`` puts it into the program's
``attn_blocks``); ``model_kwargs`` sets the program's ``n_layers`` to all of
them and hands it the layer table. (``harness/families/granite.py`` does the same.)

Canonical layout: matrices ``(in, out)``, a Gated DeltaNet layer's heads side by
side in the columns; a convolution ``(channels, taps)``, the last tap on the
current token; an attention layer's projections with their heads apart,
``(in, heads, head_dim)`` and ``(heads, head_dim, out)`` (``head_dim`` has no key
in the source and ``wq`` is square).

``harness/opcount.py`` counts per-head K/V in every layer; the counts of a state
that is read and written every step and of pages in one layer of four are here,
and the readers call them (``kda_roofline`` asks a family for ``kda_step_bytes``
and ``kda_chunk_ops_bytes`` by those names: here they count ``gdn.step`` and
``gdn.chunk``, the same delta rule under a scalar gate). Each counts the *least*
the work can move: a roofline share above 100% is refused.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from harness.weights import STD, normal

CHUNK = 64  # tokens a chunk of the chunked form (the program's kda.CHUNK, written again)


def dims(arch: Dict[str, Any]) -> Dict[str, int]:
    types = arch["layer_types"][: arch["num_hidden_layers"]]
    if len(types) != arch["num_hidden_layers"] or set(types) - {"linear_attention", "full_attention"}:
        raise ValueError("layer_types names 'linear_attention' or 'full_attention' for every layer")
    if arch["linear_num_key_heads"] != arch["linear_num_value_heads"]:
        raise ValueError("as many key heads as value heads in a linear_attention layer")
    d = arch["hidden_size"]
    return dict(
        d=d, layers=types.count("linear_attention"), all_layers=len(types),
        attn_layers=types.count("full_attention"),
        heads=arch["num_attention_heads"], kv_heads=arch["num_key_value_heads"],
        head_dim=d // arch["num_attention_heads"],
        gdn_heads=arch["linear_num_value_heads"], dk=arch["linear_key_head_dim"],
        dv=arch["linear_value_head_dim"], taps=arch["linear_conv_kernel_dim"],
        ffn=arch["intermediate_size"], vocab=arch["vocab_size"], vocab_rows=arch["vocab_size"],
        ctx=arch["max_position_embeddings"],
        # not a size, but what ``globals_`` (which is handed these dims alone) needs
        attn_at=tuple(i for i, t in enumerate(types) if t == "full_attention"),
    )


def _widths(m: Dict[str, int]) -> Tuple[int, int, int]:
    """(all heads' keys, all heads' values, channels of the convolution: q, k, v)."""
    wk, wv = m["gdn_heads"] * m["dk"], m["gdn_heads"] * m["dv"]
    return wk, wv, 2 * wk + wv


# -- counts ------------------------------------------------------------------------


def gdn_params(m: Dict[str, int]) -> int:
    """W_q, W_k, W_v and the taps of their convolutions, W_a and W_b, the output
    gate and the output projection, A_log and dt_bias a head, the head norm."""
    wk, wv, c = _widths(m)
    return m["d"] * c + c * m["taps"] + 2 * m["d"] * m["gdn_heads"] + 2 * m["d"] * wv + 2 * m["gdn_heads"] + m["dv"]


def attn_params(m: Dict[str, int]) -> int:
    """Four projections and the two whole-width norms of q and k."""
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return 2 * m["d"] * q + 2 * m["d"] * kv + q + kv


def ffn_params(m: Dict[str, int]) -> int:
    return 3 * m["d"] * m["ffn"]


def layer_params(m: Dict[str, int]) -> int:
    """One Gated DeltaNet layer: mixer, SwiGLU, two norms."""
    return gdn_params(m) + ffn_params(m) + 2 * m["d"]


def attn_layer_params(m: Dict[str, int]) -> int:
    return attn_params(m) + ffn_params(m) + 2 * m["d"]


def other_params(m: Dict[str, int]) -> Tuple[int, int, int]:
    """(held outside the family's layers, of those only looked up in training,
    of those only looked up in a decode step): the embedding (looked up), the
    untied head, the final norm and every attention layer."""
    table = m["vocab_rows"] * m["d"]
    return 2 * table + m["d"] + m["attn_layers"] * attn_layer_params(m), table, table


def kv_bytes_per_token_layer(arch: Dict[str, Any], bytes_per_el: int = 2) -> int:
    m = dims(arch)
    return 2 * m["kv_heads"] * m["head_dim"] * bytes_per_el


def state_bytes_per_row(arch: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """The other cache: a float32 state and a conv tail a row in every Gated
    DeltaNet layer, whatever the row's length."""
    m = dims(arch)
    _, _, c = _widths(m)
    return m["layers"] * (4 * m["gdn_heads"] * m["dk"] * m["dv"] + (m["taps"] - 1) * c * bytes_per_el)


def attn_step_bytes(arch: Dict[str, Any], kind: str, resident_tokens: float, bytes_per_el: int = 2) -> float:
    """Bytes the paged attention of one decode step must read: K and V of every
    resident token (summed over rows) in each attention layer, the real heads'
    (a pool's padding heads are no work). ``kind`` is ``"full"``: the stack has
    no other (``readers/attn_kind_roofline.py`` names it)."""
    if kind != "full":
        raise ValueError("every attention layer of this family is a full one")
    return dims(arch)["attn_layers"] * resident_tokens * kv_bytes_per_token_layer(arch, bytes_per_el)


def kda_step_bytes(arch: Dict[str, Any], rows: int) -> float:
    """Bytes ``gdn.step`` of one decode step must move, over all Gated DeltaNet
    layers: every row's state read once and written once (float32), and the
    token's q, k, v, log-decay and beta in and o out (float32, as the scope
    receives and leaves them). The conv tails and the projections' weights belong
    to ``gdn.conv`` and ``gdn.proj``."""
    m = dims(arch)
    wk, wv, _ = _widths(m)
    token = 2 * wk + 2 * wv + 2 * m["gdn_heads"]
    return m["layers"] * rows * 4.0 * (2 * m["gdn_heads"] * m["dk"] * m["dv"] + token)


def kda_chunk_ops_bytes(arch: Dict[str, Any], tokens: int) -> Tuple[float, float]:
    """(floating-point operations, bytes) ``gdn.chunk`` needs for ``tokens``
    prompt tokens of one row, over all Gated DeltaNet layers. Per chunk of C
    tokens and head, multiply-adds, a causal product's lower triangle alone: K K^T
    and Q K^T (C C d_k), K S_0 and Q S_0 (2 C d_k d_v), the triangular solve and
    P U (C C d_v) and the new state (C d_k d_v); 2 operations each. The pass over the MXU is counted once,
    though float32 takes several. Bytes: q, k, v, g, beta in and o out in float32,
    the state read and written once a call."""
    m = dims(arch)
    c, h, dk, dv = CHUNK, m["gdn_heads"], m["dk"], m["dv"]
    chunks = math.ceil(tokens / c)
    mads = chunks * h * (c * c * dk + 3 * c * dk * dv + c * c * dv)
    moved = 4 * (tokens * h * (2 * dk + 2 * dv + 2) + 2 * h * dk * dv)
    return m["layers"] * 2.0 * mads, float(m["layers"] * moved)


def decode_step_min_bytes(arch: Dict[str, Any], resident_tokens: float, rows: int,
                          bytes_per_el: int = 2) -> float:
    """All a decode step cannot avoid moving: every weight once (the head whole,
    of the embedding ``rows`` rows), every row's state read and written in every
    Gated DeltaNet layer, and every resident token's K and V in each attention
    layer."""
    m = dims(arch)
    weights = m["layers"] * layer_params(m) + other_params(m)[0] - other_params(m)[2] + rows * m["d"]
    state = m["layers"] * rows * 2 * 4 * m["gdn_heads"] * m["dk"] * m["dv"]
    return weights * bytes_per_el + state + attn_step_bytes(arch, "full", resident_tokens, bytes_per_el)


# -- canonical weights -------------------------------------------------------------
#
# Every sublayer's output is normed before it joins the residual, so the scale of
# an output projection falls out of the function: W_o and W_down are N(0, STD)
# like every other matrix and take no 1/sqrt(2L). The residual grows by a vector
# of unit scale a sublayer and the sublayers read it un-normed, so the embedding
# is N(0, 1), the scale of what joins it: at N(0, STD) the first layer's beta and
# decay would be constants (W_b x and W_a x near 0) and its head norm would stand
# on its eps. With that, W_b x spreads by 1.2 times the residual's
# root mean square, so beta = 2 sigmoid(.) covers (0, 2) and passes 1 about half
# the time (``linear_allow_neg_eigval`` is exercised), and the whole-width norms of
# q and k give unit channels, a head's scores a spread near 1: a row attends to a
# handful of positions, nothing to tune.


def _ffn(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    d, f = m["d"], m["ffn"]
    return {
        "ln1_scale": 1 + normal(k, 0, (d,), 0.1, dtype),
        "ln2_scale": 1 + normal(k, 1, (d,), 0.1, dtype),
        "w_gate": normal(k, 20, (d, f), STD, dtype),
        "w_up": normal(k, 21, (d, f), STD, dtype),
        "w_down": normal(k, 22, (f, d), STD, dtype),
    }


def _gdn(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """A Gated DeltaNet mixer. Every vector far from a constant, so that a dropped
    term shows: exp(A_log) uniform in [1, 16], softplus(dt_bias) log-uniform in
    [1e-3, 1e-1] (heads that forget within a token beside heads that keep
    thousands), four taps of N(0, 0.5) (the convolved q, k, v keep the scale of
    the projected ones), the head norm 1 + N(0, 0.1). W_a is N(0, STD / 4): the
    decay's input moves a head's rate by a factor of e or so about its resting
    value, and a slow head stays slow (at N(0, STD) every head would lose its
    state to the rare large input within a few dozen tokens, and the state's
    precision could not show)."""
    d, h, dv, taps = m["d"], m["gdn_heads"], m["dv"], m["taps"]
    wk, wv, _ = _widths(m)
    delta = jnp.exp(jax.random.uniform(
        jax.random.fold_in(k, 7), (h,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "wq": normal(k, 2, (d, wk), STD, dtype),
        "wk": normal(k, 3, (d, wk), STD, dtype),
        "wv": normal(k, 4, (d, wv), STD, dtype),
        "conv_q": normal(k, 5, (wk, taps), 0.5, dtype),
        "conv_k": normal(k, 8, (wk, taps), 0.5, dtype),
        "conv_v": normal(k, 9, (wv, taps), 0.5, dtype),
        "wa": normal(k, 10, (d, h), STD / 4, dtype),
        "A_log": jnp.log(jax.random.uniform(jax.random.fold_in(k, 6), (h,), jnp.float32, 1.0, 16.0)).astype(dtype),
        "dt_bias": (delta + jnp.log(-jnp.expm1(-delta))).astype(dtype),  # softplus's inverse
        "wb": normal(k, 11, (d, h), STD, dtype),
        "wg": normal(k, 12, (d, wv), STD, dtype),
        "o_norm_scale": 1 + normal(k, 13, (dv,), 0.1, dtype),
        "wo": normal(k, 31, (wv, d), STD, dtype),
    }


def _attn(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """An attention layer's mixer, the projections with their heads apart (``wq``
    is square: the flat matrix would not say how many heads it holds)."""
    d, h, g, dh = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    return {
        "wq": normal(k, 2, (d, h, dh), STD, dtype),
        "wk": normal(k, 3, (d, g, dh), STD, dtype),
        "wv": normal(k, 4, (d, g, dh), STD, dtype),
        "q_norm_scale": 1 + normal(k, 5, (h * dh,), 0.1, dtype),
        "k_norm_scale": 1 + normal(k, 6, (g * dh,), 0.1, dtype),
        "wo": normal(k, 31, (h, dh, d), STD, dtype),
    }


def layer(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    """A Gated DeltaNet layer with its SwiGLU."""
    return {**_gdn(m, k, dtype), **_ffn(m, k, dtype)}


def globals_(m: Dict[str, int], k: Any, dtype: Any) -> Dict[str, Any]:
    out = {
        "embed": normal(k, 0, (m["vocab_rows"], m["d"]), 1.0, dtype),
        "final_scale": 1 + normal(k, 1, (m["d"],), 0.1, dtype),
        "head": normal(k, 2, (m["d"], m["vocab_rows"]), STD, dtype),
    }
    for i in m["attn_at"]:
        kl = jax.random.fold_in(k, 100 + i)
        out.update({f"L{i}_{name}": v for name, v in {**_attn(m, kl, dtype), **_ffn(m, kl, dtype)}.items()})
    return out


# -- the program's tree ------------------------------------------------------------


def program_layer(m: Dict[str, int], c: Dict[str, Any]) -> Dict[str, Any]:
    """A layer of either kind in the program's block; every size is read off the
    weights (``program_tree`` has no dims for the attention layers)."""
    d = c["ln1_scale"].shape[0]
    out = {
        "ln1": {"scale": c["ln1_scale"]}, "ln2": {"scale": c["ln2_scale"]},
        "mlp": {"w1": jnp.stack([c["w_gate"], c["w_up"]], axis=1), "w2": c["w_down"]},
    }
    if "wa" in c:
        h, dv = c["wa"].shape[1], c["o_norm_scale"].shape[0]
        out["attn"] = {
            # one projection and one convolution over [q | k | v]
            "w_in": jnp.concatenate([c["wq"], c["wk"], c["wv"]], axis=1),
            "conv": jnp.concatenate([c["conv_q"], c["conv_k"], c["conv_v"]], axis=0).T,
            "wa": c["wa"], "A_log": c["A_log"], "dt_bias": c["dt_bias"], "wbeta": c["wb"],
            "wg": c["wg"].reshape(d, h, dv), "o_norm": {"scale": c["o_norm_scale"]},
            "wo": c["wo"].reshape(h, dv, d),
        }
    else:
        if c["wk"].shape != c["wq"].shape:
            raise ValueError("the family's attention is multi-head: as many KV heads as heads")
        out["attn"] = {
            "wqkv": jnp.stack([c["wq"], c["wk"], c["wv"]], axis=1),
            "q_norm": {"scale": c["q_norm_scale"]}, "k_norm": {"scale": c["k_norm_scale"]},
            "wo": c["wo"],
            # the program always carries an output bias; this family has none
            "bo": jnp.zeros((d,), c["wo"].dtype),
        }
    return out


def program_tree(blocks: Any, gl: Dict[str, Any]) -> Dict[str, Any]:
    """``blocks`` (the Gated DeltaNet layers, stacked in order) and the attention
    layers from the globals in the program's ``attn_blocks``
    (``models/transformer.py::stack_key``), in layer order."""
    at = sorted({int(k.split("_")[0][1:]) for k in gl if k.startswith("L") and k[1].isdigit()})
    layers = [
        program_layer({}, {k[len(f"L{i}_"):]: v for k, v in gl.items() if k.startswith(f"L{i}_")}) for i in at
    ]
    return {
        "tok_embed": {"embedding": gl["embed"]}, "blocks": blocks,
        "attn_blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *layers),
        "final_norm": {"scale": gl["final_scale"]}, "lm_head": {"kernel": gl["head"]},
    }


def model_kwargs(arch: Dict[str, Any], m: Dict[str, int]) -> Dict[str, Any]:
    if arch["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("the Olmo-Hybrid family runs without positions (rope_theta null)")
    if arch["attention_bias"] or arch["hidden_act"] != "silu":
        raise ValueError("the Olmo-Hybrid family has no bias and a SiLU-gated FFN")
    if m["kv_heads"] != m["heads"]:
        raise ValueError("the family's attention is multi-head: as many KV heads as heads")
    return dict(
        n_layers=m["all_layers"], n_kv_heads=m["kv_heads"], mlp_ratio=m["ffn"] / m["d"],
        activation="swiglu", norm="rmsnorm", pos_embed="none", tie_embeddings=bool(arch["tie_word_embeddings"]),
        lm_head_bias=False, qkv_bias=False, mlp_bias=False, norm_eps=arch["rms_norm_eps"],
        layer_mixers=tuple(
            "attn" if t == "full_attention" else "gdn" for t in arch["layer_types"][: m["all_layers"]]),
        gdn_heads=m["gdn_heads"], gdn_key_dim=m["dk"], gdn_value_dim=m["dv"], gdn_conv_kernel=m["taps"],
        gdn_allow_neg_eigval=bool(arch["linear_allow_neg_eigval"]),
        qk_norm_whole=True, norm_placement="output",
    )
