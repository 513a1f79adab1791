"""Mixture-of-experts MLP with expert parallelism over the 'expert' mesh axis.

Beyond-parity component: the reference has only a dense MLP
(`/root/reference/src/models/mlp.py:24-26`); SURVEY §2.2 lists EP as the one
parallelism strategy left open. This is the TPU-native design:

  - **Grouped dense einsum dispatch** (Switch/Mixtral-style token choice with
    a static per-expert capacity): routing is expressed as einsums against
    one-hot dispatch/combine tensors, so every shape is static, everything
    lands on the MXU, and under `pjit` the dispatch contraction over the token
    dim *is* the all-to-all — XLA inserts the collective from the shardings
    (tokens sharded over 'data', experts over 'expert'), no hand-written
    routing tables or ragged buffers.
  - Routing is computed per **group** of `cfg.moe_group_size` tokens
    (flaxformer-style), with capacity proportional to the group size, so the
    dispatch/combine tensors are O(S * k * C_group) — linear in the batch —
    instead of the O(S^2) a single global capacity pool costs. Group count
    depends only on the token count (never the mesh), so routing decisions
    are identical across mesh shapes (sharding-invariance holds); the group
    dim stays sharded over the data axes while experts shard over 'expert'.
  - Top-k gating with renormalized weights, slot-major capacity priority
    (every token's 1st choice is placed before any token's 2nd choice),
    dropped tokens fall back to the residual stream (their MoE output is 0).
  - Switch-style load-balance auxiliary loss in fp32, threaded through the
    block scan and added to the task loss as `router_aux_coef * aux`.

That capacity path is the training path. Serving has a second one at the end
of this module, ``cfg.moe_routing == "dropless"``: no capacity and no drop
(sort the (token, choice) pairs by expert, one grouped matmul a projection),
sigmoid or softmax scores, a selection-only bias, a shared expert; a token's
output there never depends on what shares its batch or its padded bucket.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from pretraining_llm_tpu.config import ModelConfig
from pretraining_llm_tpu.models.layers import weight as _weight
from pretraining_llm_tpu.ops import pallas_moe
from pretraining_llm_tpu.parallel.sharding import constrain, current_mesh

Params = Dict[str, Any]


def init_moe_params(
    cfg: ModelConfig, key: jax.Array, resid_std: float, dtype: jnp.dtype
) -> Params:
    """Per-block MoE params: router (D, E) + stacked expert FFNs (E, ...)."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    k_router, k_w1, k_w2 = jax.random.split(key, 3)

    def normal(k: jax.Array, shape: Tuple[int, ...], s: float = 0.02) -> jax.Array:
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    if cfg.activation == "swiglu":
        experts: Params = {
            "w1": normal(k_w1, (e, d, 2, f)),
            "w2": normal(k_w2, (e, f, d), resid_std),
        }
        if cfg.mlp_bias:
            experts["b1"] = jnp.zeros((e, 2, f), dtype)
            experts["b2"] = jnp.zeros((e, d), dtype)
    else:
        experts = {
            "w1": normal(k_w1, (e, d, f)),
            "w2": normal(k_w2, (e, f, d), resid_std),
        }
        if cfg.mlp_bias:
            experts["b1"] = jnp.zeros((e, f), dtype)
            experts["b2"] = jnp.zeros((e, d), dtype)
    return {"router": normal(k_router, (d, e)), "experts": experts}


def expert_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    """Static per-expert slot count for a batch of n_tokens."""
    cap = int(cfg.expert_capacity_factor * cfg.experts_per_token * n_tokens / cfg.n_experts)
    return max(1, min(cap, n_tokens))


def route(
    router_logits: jax.Array, cfg: ModelConfig, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Token-choice top-k routing with capacity.

    router_logits: (S, E) fp32. Returns (dispatch (S, E, C) 0/1,
    combine (S, E, C) gate weights, aux scalar load-balance loss).
    """
    s, e = router_logits.shape
    k = cfg.experts_per_token
    probs = jax.nn.softmax(router_logits, axis=-1)  # (S, E) fp32
    gate, idx = jax.lax.top_k(probs, k)  # (S, K)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (S, K, E)

    # Slot-major priority: flatten to (K*S, E) with the choice-rank major so
    # every token's 1st choice outranks any token's 2nd choice, then a cumsum
    # assigns each (token, choice) its position within the expert's capacity.
    slot_major = onehot.transpose(1, 0, 2).reshape(k * s, e)
    pos = jnp.cumsum(slot_major, axis=0) - slot_major  # positions from 0
    pos = jnp.sum(pos * slot_major, axis=-1).reshape(k, s).T  # (S, K)
    keep = (pos < capacity).astype(jnp.float32)  # dropped tokens contribute 0
    pos_onehot = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
    pos_onehot = pos_onehot * keep[..., None]

    combine = jnp.einsum("sk,ske,skc->sec", gate * keep, onehot, pos_onehot)
    dispatch = jnp.einsum("ske,skc->sec", onehot, pos_onehot)

    # Switch-style balance loss: E * sum_e(assignment fraction * mean prob).
    frac = jnp.mean(jnp.sum(onehot, axis=1), axis=0) / k  # (E,)
    mean_prob = jnp.mean(probs, axis=0)  # (E,)
    aux = e * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def _group_count(s: int, group_size: int) -> int:
    """Number of routing groups: S/group_size rounded to a divisor of S.

    Depends only on the token count (never the mesh) so routing is identical
    across mesh shapes.
    """
    if group_size <= 0 or s <= group_size:
        return 1
    g = s // group_size
    while s % g != 0:  # token counts are powers of two in practice; be safe
        g -= 1
    return g


def moe_mlp(
    mlp: Params, h: jax.Array, cfg: ModelConfig, *, decode: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN on normed input h (B, T, D) -> (output (B, T, D), aux loss).

    ``decode=True`` (KV-cached generation) routes without a capacity bound:
    per-step token counts are tiny and a capacity drop there would make a
    token's output depend on which other sequences are co-batched.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    b, t, d = h.shape
    s = b * t
    g = 1 if decode else _group_count(s, cfg.moe_group_size)
    sg = s // g
    x = h.reshape(g, sg, d)

    router_logits = jnp.einsum(
        "gsd,de->gse",
        x.astype(jnp.float32),
        mlp["router"].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    capacity = sg if decode else expert_capacity(cfg, sg)
    dispatch, combine, aux = jax.vmap(
        lambda lg: route(lg, cfg, capacity)
    )(router_logits)
    aux = jnp.mean(aux)

    # Contracting the (data-sharded) token dim against the dispatch mask IS
    # the all-to-all: XLA lowers it to collectives between the 'data' and
    # 'expert' mesh axes. The group dim rides the data axes.
    # Accumulation precision is a non-issue here (each (e, c) slot gathers
    # exactly one token), but the grouped form makes these genuinely batched
    # dots and the CPU backend has no batched-bf16 DotThunk — route them
    # through fp32 there. TPU keeps bf16 (MXU accumulates fp32 natively).
    ddt = jnp.float32 if jax.default_backend() == "cpu" else cdt
    xin = jnp.einsum("gsec,gsd->gecd", dispatch.astype(ddt), x.astype(ddt)).astype(cdt)
    # Fold (g, c) into one per-expert row dim: each expert runs ONE
    # (G*C, D) @ (D, F) matmul — bigger MXU tiles than G separate dots, and
    # the same non-batched lowering the CPU backend supports in bf16.
    gc = g * capacity
    xin = xin.transpose(1, 0, 2, 3).reshape(cfg.n_experts, gc, d)
    xin = constrain(xin, "expert", None, None)

    ex = mlp["experts"]
    if cfg.activation == "swiglu":
        gates = jnp.einsum(
            "ecd,edgf->ecgf", xin, ex["w1"].astype(cdt), preferred_element_type=jnp.float32
        ).astype(cdt)
        if "b1" in ex:
            gates = gates + ex["b1"].astype(cdt)[:, None, :, :]
        hidden = jax.nn.silu(gates[..., 0, :]) * gates[..., 1, :]
    else:
        hidden = jnp.einsum(
            "ecd,edf->ecf", xin, ex["w1"].astype(cdt), preferred_element_type=jnp.float32
        ).astype(cdt)
        if "b1" in ex:
            hidden = hidden + ex["b1"].astype(cdt)[:, None, :]
        hidden = jax.nn.relu(hidden) if cfg.activation == "relu" else jax.nn.gelu(
            hidden, approximate=True
        )
    out = jnp.einsum(
        "ecf,efd->ecd", hidden, ex["w2"].astype(cdt), preferred_element_type=jnp.float32
    ).astype(cdt)
    if "b2" in ex:
        out = out + ex["b2"].astype(cdt)[:, None, :]
    out = constrain(out, "expert", None, None)
    out = out.reshape(cfg.n_experts, g, capacity, d).transpose(1, 0, 2, 3)

    # Combine sums exactly experts_per_token (~2) terms per token: bf16
    # accumulation is exact enough; same CPU batched-dot dtype caveat as xin.
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(ddt), out.astype(ddt))
    return y.astype(h.dtype).reshape(b, t, d), aux


# ---------------------------------------------------------------------------
# Dropless routing (serving; cfg.moe_routing == "dropless")
# ---------------------------------------------------------------------------


def init_dropless_params(
    cfg: ModelConfig, key: jax.Array, resid_std: float, dtype: jnp.dtype
) -> Params:
    """Router (D, E) [+ selection bias (E,)], experts stored as the grouped
    matmul and ``ops/pallas_moe.py`` both read them, and the shared expert as the
    model's dense FFN. A SwiGLU expert (``cfg.activation == "swiglu"``) is w1
    (E, D, 2F) with the gate columns before the up columns and w2 (E, F, D); an
    ungated relu^2 expert (``"relu2"``) is two matrices, w1 (E, D, F) and w2
    (E, F, D), at its own width (no padding to lane tiles: the kernel cuts F in
    sublane tiles of both)."""
    d, f, e, held = cfg.d_model, cfg.expert_width, cfg.n_experts, cfg.experts_held
    gated = cfg.activation == "swiglu"
    ks = jax.random.split(key, 5)

    def normal(k: jax.Array, shape: Tuple[int, ...], s: float = 0.02) -> jax.Array:
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    out: Params = {
        "router": normal(ks[0], (d, e)),
        "experts": {
            "w1": normal(ks[1], (held, d, (1 + gated) * f)), "w2": normal(ks[2], (held, f, d), resid_std)
        },
    }
    if cfg.moe_score_bias:
        out["router_bias"] = jnp.zeros((e,), dtype)
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        w1_shape = (d, 2, fs) if gated else (d, fs)
        out["shared"] = {"w1": normal(ks[3], w1_shape), "w2": normal(ks[4], (fs, d), resid_std)}
    return out


def swiglu(gate: jax.Array, up: jax.Array, limit: Any = None) -> jax.Array:
    """silu(gate) * up; under a clamp ``limit`` (a scalar, 0 = off)
    silu(min(gate, L)) * clip(up, -L, L)."""
    if limit is not None:
        lim = jnp.where(limit > 0, limit, jnp.inf).astype(gate.dtype)
        gate, up = jnp.minimum(gate, lim), jnp.clip(up, -lim, lim)
    return jax.nn.silu(gate) * up


def route_dropless(mlp: Params, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x (S, D) -> (expert ids (S, K) int32, gates (S, K) float32). Scores in
    float32; the bias enters the selection only; the gates are the unbiased
    scores of the selected experts, renormalised and scaled."""
    logits = jnp.einsum(
        "sd,de->se", x.astype(jnp.float32), mlp["router"].astype(jnp.float32),
        preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST,
    )
    scores = jax.nn.sigmoid(logits) if cfg.moe_score == "sigmoid" else jax.nn.softmax(logits, -1)
    select = scores + mlp["router_bias"].astype(jnp.float32) if "router_bias" in mlp else scores
    if cfg.moe_n_group > 1:
        # group-limited: a group scores the sum of its two best, the experts of
        # the groups that lose leave the selection
        grouped = select.reshape(select.shape[0], cfg.moe_n_group, -1)
        best2, _ = jax.lax.top_k(grouped, 2)
        _, keep = jax.lax.top_k(jnp.sum(best2, axis=-1), cfg.moe_topk_group)
        kept = jnp.zeros(grouped.shape[:2], bool).at[jnp.arange(keep.shape[0])[:, None], keep].set(True)
        select = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(select.shape)
    _, idx = jax.lax.top_k(select, cfg.experts_per_token)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.moe_norm_topk:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), gates * cfg.moe_routed_scale


# Sorted rows an expert (S * K / n_experts, what even routing gives each) up to
# which the expert FFN runs as ops/pallas_moe.py's kernel, a visit as wide as
# ``pallas_moe.windows`` makes it (two row tiles up to 16 rows an expert, past
# that the span that holds a group of twice the mean in one read of its
# weights). Timed on the v5e against the ragged_dot pair at three cells' expert
# shapes, routing skewed so that the busiest expert gets about twice the mean
# (``scripts/chip_kernels.py --time-moe``; PERF.md section 6, PR 44). Ms a layer,
# kernel / pair:
#
#   rows an    Ling: 128 of 512      Xing: 64 of 64       Granite: 18 of 72
#   expert     held, 2560 x 768      held, 3584 x 1024    held, 4096 x 768
#       2        1.70 / 4.21           1.81 / 2.32          0.41 / 0.50
#       8        2.48 / 5.12           2.14 / 4.69          0.58 / 0.68
#      16        3.23 / 5.25           2.40 / 4.75          0.70 / 0.76
#      24        3.74 / 5.40           2.17 / 4.82          0.59 / 0.81
#      32        4.29 / 5.49           2.41 / 4.82          0.60 / 0.93
#      48        4.77 / 5.67           2.40 / 5.05          0.60 / 0.91
#      64        5.69 / 6.08           2.45 / 5.20          0.62 / 1.31
#     128        9.37 / 6.92           3.42 / 5.79          (not run) / 1.45
#
# The kernel leads at all three through 64 rows an expert and has lost at Ling's
# shape by 128 (its row gather moves every sorted row, three quarters of them
# other chips'). The bound is the last timed figure under 64, where the cells'
# prefills begin (Trinity's and JoyAI's stand at 64 exactly) and where the lead
# is 16% at its thinnest; from 64 on ragged_dot stays (ROADMAP S11).
KERNEL_ROWS_PER_EXPERT = 48


def experts_form(
    rows: int, cfg: ModelConfig, experts: Params, mesh: Any = None, backend: Any = None
) -> str:
    """The form a dropless layer's expert FFN takes for ``rows`` sorted (token,
    choice) pairs over ``cfg.n_experts``: ``"kernel"`` (``ops/pallas_moe.py``: each
    touched expert's weights streamed once, its rows held in one visit) or
    ``"grouped"`` (two ``jax.lax.ragged_dot``s). Read from the input as
    ``mla.decode_form`` reads a latent pool's. The rule measures rows an expert,
    ``rows / cfg.n_experts``, whatever program brings them: up to
    ``KERNEL_ROWS_PER_EXPERT`` (every cell's decode step, and an admission short
    enough to stand under it, as Ling's of one or two 1,024-token prompts) over
    unquantized bfloat16 experts of whole 128-lane tiles that no mesh shards it
    takes the kernel where Mosaic compiles; more rows an expert (the cells'
    prefills, from 64 up), int8 or float32 experts, a mesh and every other
    backend keep the grouped form. An ungated expert (w1 as wide as w2 is tall:
    ``pallas_moe.ungated``) needs whole lane tiles of D only and a width of
    whole 16-row sublane tiles: the kernel reads both its matrices with F on the
    sublanes (1,856 is 14.5 lane tiles and 116 sublane tiles), and at such a
    width it takes every call whatever its rows (the comment below). The engine reports the decode step's form in
    ``pool_info()``, and ``experts_plan`` the kernel's activation and tiles."""
    w1 = experts["w1"]
    ungated = pallas_moe.ungated(w1, experts["w2"])
    if not (
        w1.dtype == jnp.dtype(cfg.compute_dtype) == jnp.bfloat16
        and mesh is None
        and (backend or jax.default_backend()) == "tpu"
        and w1.shape[-2] % 128 == 0
        and w1.shape[-1] % (pallas_moe.ROW_TILE if ungated else 256) == 0
    ):
        return "grouped"
    if ungated and w1.shape[-1] % 128:
        # The TPU lays (.., D, F) out with D minor-most where F is no whole number
        # of lane tiles (no padding), ``ragged_dot`` wants F there, and XLA
        # re-lays the whole stack for every call of it: 3.4 GB a call at 11 x 32
        # experts of 2,688 x 1,856, which does not fit beside the pools. The
        # kernel reads the stack as it lies, so it takes every call, a prefill's
        # too, at more visits an expert (``pallas_moe.MAX_WINDOWS``).
        return "kernel"
    return "kernel" if rows <= KERNEL_ROWS_PER_EXPERT * cfg.n_experts else "grouped"


def prefill_form(cfg: ModelConfig, experts: Params, mesh: Any = None) -> str:
    """The form these experts take in a prefill: at the first count of rows past
    the rule's bound, so ``"kernel"`` only where the shape takes the kernel at
    every size. What the engine sizes its admission programs by."""
    return experts_form((KERNEL_ROWS_PER_EXPERT + 1) * cfg.n_experts, cfg, experts, mesh)


def experts_plan(rows: int, cfg: ModelConfig, experts: Params) -> str:
    """What ``ops/pallas_moe.py`` would run for ``rows`` sorted pairs over these
    experts, in words for ``pool_info()``: the activation, the weight tiles a
    grid step takes and their F tile, the row windows a visit holds."""
    w1 = experts["w1"]
    d, itemsize, w = w1.shape[-2], w1.dtype.itemsize, pallas_moe.windows(rows, cfg.n_experts)
    gated = not pallas_moe.ungated(w1, experts["w2"])
    f = w1.shape[-1] // (1 + gated)
    tf = pallas_moe.f_tile(d, f, itemsize, w, gated)
    return f"{'swiglu, 3' if gated else 'relu2, 2'} tiles a step of {tf} of F {f}, {w} windows a visit"


def _in_stack(w1: jax.Array, w2: jax.Array, sizes: jax.Array, layer: jax.Array):
    """A stack's (L, E, ...) weights as L * E groups for the grouped matmul,
    every group of another layer than ``layer`` empty."""
    n_stack, held = w1.shape[:2]
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((n_stack * held,), jnp.int32), sizes, (layer * held,)
    )
    return w1.reshape((n_stack * held,) + w1.shape[2:]), w2.reshape((n_stack * held,) + w2.shape[2:]), sizes


def _grouped_pair(xs: jax.Array, w1: jax.Array, w2: jax.Array, sizes: jax.Array, limit: Any):
    """The two grouped matmuls and the activation between them, which the
    shapes name: w1 twice as wide as w2 is tall is a SwiGLU's gate and up
    columns, w1 as wide an ungated expert's, relu(.)^2."""
    f, cdt = w2.shape[-2], xs.dtype
    up = jax.lax.ragged_dot(xs, w1, sizes, preferred_element_type=cdt)
    if pallas_moe.ungated(w1, w2):
        hidden = jnp.square(jax.nn.relu(up))
    else:
        hidden = swiglu(up[:, :f], up[:, f:], limit)
    return jax.lax.ragged_dot(hidden, w2, sizes, preferred_element_type=cdt)


def experts_grouped(
    xs: jax.Array, w1: jax.Array, w2: jax.Array, sizes: jax.Array, layer: Any = None, limit: Any = None
) -> jax.Array:
    """The expert FFN of sorted rows ``xs`` (N, D), ``sizes[e]`` of them for
    held expert e, as two grouped matmuls; ``layer`` for a stack's weights."""
    if layer is not None:
        w1, w2, sizes = _in_stack(w1, w2, sizes, layer)
    return _grouped_pair(xs, w1, w2, sizes, limit)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def experts_kernel(
    xs: jax.Array, w1: jax.Array, w2: jax.Array, sizes: jax.Array, layer: Any = None, limit: Any = None,
    windows: int = 2,
) -> jax.Array:
    """``experts_grouped`` through ``ops/pallas_moe.py`` (rows past the last
    group are promised by neither), a visit ``windows`` row tiles wide
    (``pallas_moe.windows`` of the rows an expert the rule measured); its VJP
    is the grouped form's."""
    return pallas_moe.expert_ffn(xs, w1, w2, sizes, layer, limit, w=windows)


def _experts_kernel_fwd(xs, w1, w2, sizes, layer, limit, windows):
    return experts_kernel(xs, w1, w2, sizes, layer, limit, windows), (xs, w1, w2, sizes, layer, limit)


def _experts_kernel_bwd(windows, res, g):
    xs, w1, w2, sizes, layer, limit = res
    _, vjp = jax.vjp(lambda x, a, b, lim: experts_grouped(x, a, b, sizes, layer, lim), xs, w1, w2, limit)
    d_xs, d_w1, d_w2, d_limit = vjp(g)
    return d_xs, d_w1, d_w2, None, None, d_limit


experts_kernel.defvjp(_experts_kernel_fwd, _experts_kernel_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def experts_visits(
    xs: jax.Array, w1: jax.Array, w2: jax.Array, sizes: jax.Array, visits: Any, layer: Any = None,
    limit: Any = None, windows: int = 2,
) -> jax.Array:
    """``experts_kernel`` without the way back to sorted order: ``xs`` (whole
    row tiles of sorted rows) under ``visits``, the first three of
    ``pallas_moe.plan(sizes, xs.shape[0], windows)`` -> the visits' output, in
    which row i of group e lies at the plan's ``offset[e] + i``. What the layer
    calls: it gathers each pair's row from there once. Its VJP brings the
    cotangent to sorted order and is then the grouped form's."""
    return pallas_moe.expert_visits(xs, w1, w2, sizes, visits, layer, limit, w=windows)


def _experts_visits_fwd(xs, w1, w2, sizes, visits, layer, limit, windows):
    return experts_visits(xs, w1, w2, sizes, visits, layer, limit, windows), (xs, w1, w2, sizes, layer, limit)


def _experts_visits_bwd(windows, res, g):
    xs, sizes = res[0], res[3]
    offset = pallas_moe.plan(sizes, xs.shape[0], windows)[3]
    g = g[pallas_moe.sorted_positions(sizes, offset, xs.shape[0], g.shape[0])]
    g = jnp.where(jnp.arange(xs.shape[0])[:, None] < jnp.sum(sizes), g, 0)  # past the last group: nobody's
    d_xs, d_w1, d_w2, _, _, d_limit = _experts_kernel_bwd(windows, res, g)
    return d_xs, d_w1, d_w2, None, (None, None, None), None, d_limit


experts_visits.defvjp(_experts_visits_fwd, _experts_visits_bwd)


# Pairs a block of ``_places``: a pair is compared with the 256 of its block.
COUNT_BLOCK = 256


def _of_expert(flat: jax.Array, table: jax.Array) -> jax.Array:
    """``table[..., flat]`` for ``flat`` (...,) in [0, E] and ``table`` (E,), or
    one that broadcasts against ``flat``'s leading axes, (..., 1, E): a compare
    against all E summed, 0 for E itself (a pair held elsewhere). A gather of N
    scalars costs the TPU 9 us at 1,280 (PERF.md section 6, PR 59); this fuses
    into what reads it."""
    chose = flat[..., None] == jnp.arange(table.shape[-1], dtype=jnp.int32)
    return jnp.sum(jnp.where(chose, table, 0), axis=-1)


def _places(flat: jax.Array, held: int) -> Tuple[jax.Array, jax.Array]:
    """A pair's place, counted once and in token order. ``flat`` (N,) is each
    (token, choice) pair's held expert, ``held`` for one that lives elsewhere ->
    (sizes (held,) pairs of each held expert, rank (N,) the pairs before a pair
    that chose its expert: its place in its group under a stable sort).

    The pairs before a pair are those of its own block of ``COUNT_BLOCK`` (one
    compare of the block against itself under a triangle, summed) and those of
    the blocks before (the cumulative sum of the blocks' totals, N / 256 rows of
    ``held``). Nothing of N x held is stored; no sort, no search, no scatter. A
    cumulative sum down all N pairs is a reduce-window of 28 us at 1,280 pairs
    on the TPU, five times a sort of them (PERF.md section 6, PR 59)."""
    n = flat.shape[0]
    blocks = -(-n // COUNT_BLOCK)
    fb = jnp.pad(flat, (0, blocks * COUNT_BLOCK - n), constant_values=held).reshape(blocks, COUNT_BLOCK)
    earlier = jnp.tril(jnp.ones((COUNT_BLOCK, COUNT_BLOCK), bool), -1)
    within = jnp.sum((fb[:, :, None] == fb[:, None, :]) & earlier, axis=2, dtype=jnp.int32)
    totals = jnp.sum(fb[:, :, None] == jnp.arange(held, dtype=jnp.int32), axis=1, dtype=jnp.int32)  # (blocks, held)
    rank = within + _of_expert(fb, (jnp.cumsum(totals, axis=0) - totals)[:, None, :])
    return jnp.sum(totals, axis=0), rank.reshape(blocks * COUNT_BLOCK)[:n]


def moe_mlp_dropless(
    mlp: Params, h: jax.Array, cfg: ModelConfig, dense_mlp: Any
) -> Tuple[jax.Array, jax.Array]:
    """Dropless expert FFN (SwiGLU or ungated relu^2 experts) on normed input
    h (B, T, D) -> (output, tokens routed to each expert (E,) int32).

    The (token, choice) pairs are sorted by expert, the experts held run over
    their rows in the form the input picks (``experts_form``: one grouped
    matmul a projection, ``jax.lax.ragged_dot``, for a prefill's hundreds of
    rows an expert; one Pallas kernel that streams each touched expert's
    weights once for a decode step's handful to few dozen, its visit as wide as
    ``pallas_moe.windows`` of the same figure), and the K weighted parts of a
    token are summed. A token's output is a function of that token alone.

    A pair's place is planned once, in token order: one stable sort gives the
    order of the way in, one count (``_places``) the sizes of the groups and
    each pair's rank in its group, and arithmetic on those the row of the
    experts' output that holds the pair's (the kernel's output lies a span a
    visit, ``pallas_moe.plan``; the grouped form's in sorted order). Each row is
    gathered once on the way in (the kernel's windows read sorted rows) and once
    on the way out, straight from that output into token order, where it is
    weighted: nothing is brought back to sorted order only to be un-sorted, no
    second sort inverts the first, no search finds an expert again.

    The layer holds the experts its weights carry, ``w1.shape[0]`` of the
    ``cfg.n_experts`` the router scores, from ``cfg``'s first expert on: a
    pair routed to an expert that lives elsewhere adds nothing here (expert
    parallelism's share of the result, without the exchange).
    ``dense_mlp(params, h)`` is the model's dense FFN (a SwiGLU, or the ungated
    relu^2 of a model whose experts are), for the shared expert. The experts'
    own activation is read from their shapes (``_grouped_pair``).

    Inside a layer stack ``mlp["experts"]`` is the stack's weights, (L, E, ...),
    and ``mlp["expert_layer"]`` says which layer this is: the grouped matmul
    then runs over all L * E groups with every other layer's group empty, the
    kernel addresses its tiles ``(layer * E + expert, ...)``. A per-layer slice
    of the stack would be copied each call (0.94 + 0.47 GB a layer at 64
    experts of 3584 x 1024); the stack is read where it lies.
    ``mlp["expert_limit"]``, if there, is the layer's SwiGLU clamp (``swiglu``).
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    b, t, d = h.shape
    s, k = b * t, cfg.experts_per_token
    x = h.reshape(s, d)
    ex = mlp["experts"]
    w1, w2 = _weight(ex, "w1", cdt), _weight(ex, "w2", cdt)
    held = w1.shape[-3]
    layer, limit = mlp.get("expert_layer"), mlp.get("expert_limit")
    form = experts_form(s * k, cfg, ex, current_mesh())
    with jax.named_scope("moe.router"):
        idx, gates = route_dropless(mlp, x, cfg)
    with jax.named_scope("moe.dispatch"):
        n = s * k
        flat = idx.reshape(n)
        flat = jnp.where(flat < held, flat, held)  # experts held elsewhere sort last
        order = jnp.argsort(flat, stable=True)
        sizes, rank = _places(flat, held)
        if form == "kernel":
            windows = pallas_moe.windows(n, cfg.n_experts)
            order = jnp.pad(order, (0, -n % pallas_moe.ROW_TILE))  # whole row tiles: the pad rows are nobody's
            # the kernel writes a span a visit: where each group's first row lies in that
            *visits, first_row = pallas_moe.plan(sizes, order.shape[0], windows)
        else:
            # the grouped form writes a row where it read it: a group begins after those before it
            first_row, group_sizes = jnp.cumsum(sizes) - sizes, sizes
            if layer is not None:
                w1, w2, group_sizes = _in_stack(w1, w2, sizes, layer)
        # the row of the experts' output that holds the pair's (some row of the first group's for one held elsewhere)
        dest = rank + _of_expert(flat, first_row)
        xs = x[order // k].astype(cdt)  # rows grouped by expert
    with jax.named_scope("moe.experts"):
        if form == "kernel":
            out = experts_visits(xs, w1, w2, sizes, tuple(visits), layer, limit, windows)
        else:
            out = _grouped_pair(xs, w1, w2, group_sizes, limit)
    with jax.named_scope("moe.combine"):
        # each pair's row straight from the experts' output, choice-major: K
        # slabs of S whole rows whatever K is, summed slab by slab in choice order
        here, weight = idx < held, jnp.where(idx < held, gates, 0.0)
        rows = out[dest.reshape(s, k).T.reshape(n)]
        y = None
        for j in range(k):
            part = rows[j * s : (j + 1) * s].astype(jnp.float32) * weight[:, j, None]
            if held < cfg.n_experts:
                # rows past the last group belong to no expert held: the grouped
                # matmul promises nothing for them, so select, do not multiply by 0
                part = jnp.where(here[:, j, None], part, 0.0)
            # rounded to the compute dtype as an array of it would be (the TPU's
            # compiler drops a cast there and back and would sum the unrounded products)
            part = jax.lax.reduce_precision(part, jnp.finfo(cdt).nexp, jnp.finfo(cdt).nmant)
            y = part if y is None else y + part
        y = y.astype(cdt).reshape(b, t, d)
    if "shared" in mlp:
        with jax.named_scope("moe.shared"):
            y = y + dense_mlp(mlp["shared"], h)
    return y.astype(h.dtype), sizes
