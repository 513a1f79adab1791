"""MoE layer: routing numerics, capacity behavior, expert-parallel training.

Beyond-parity coverage (the reference has only a dense MLP, mlp.py:24-26).
The key numeric check: with k = n_experts and unbounded capacity, token-choice
top-k routing degenerates to a softmax-weighted mixture of all experts, which
we compare against a direct per-expert loop.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.config import ModelConfig, get_preset
from pretraining_llm_tpu.models import moe, transformer
from pretraining_llm_tpu.ops import pallas_moe
from pretraining_llm_tpu.training import train_step as ts


def _moe_cfg(**kw):
    base = dict(
        vocab_size=97,
        context_length=32,
        d_model=32,
        n_heads=4,
        n_layers=2,
        n_experts=4,
        experts_per_token=2,
        param_dtype="float32",
        compute_dtype="float32",
    )
    base.update(kw)
    return ModelConfig(**base)


def test_moe_param_count_matches_analytic():
    cfg = _moe_cfg()
    params = transformer.init_params(cfg, jax.random.key(0))
    actual = sum(np.prod(p.shape) for p in jax.tree.leaves(params))
    assert actual == cfg.num_params()


def test_moe_param_count_matches_analytic_swiglu():
    cfg = _moe_cfg(activation="swiglu", mlp_bias=False, tie_embeddings=False)
    params = transformer.init_params(cfg, jax.random.key(0))
    actual = sum(np.prod(p.shape) for p in jax.tree.leaves(params))
    assert actual == cfg.num_params()


def test_active_params_counts_only_routed_experts():
    cfg = _moe_cfg(n_experts=4, experts_per_token=2)
    dense = _moe_cfg(n_experts=0)
    # Active params = dense model + router + one extra active expert FFN.
    per_expert = cfg.d_model * cfg.d_ff * 2 + cfg.d_ff + cfg.d_model
    router = cfg.d_model * cfg.n_experts
    expected = dense.num_params() + cfg.n_layers * (router + per_expert)
    assert cfg.num_active_params() == expected
    assert cfg.num_active_params() < cfg.num_params()
    assert dense.num_active_params() == dense.num_params()
    # MFU math uses active params, so MoE FLOPs/token ~ top-k not n_experts.
    assert cfg.flops_per_token() < 6 * cfg.num_params()


def test_forward_finite_and_shaped():
    cfg = _moe_cfg()
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, cfg.context_length), 0, cfg.vocab_size)
    logits, _, aux = transformer.forward(params, tokens, cfg, return_aux=True)
    assert logits.shape == (2, cfg.context_length, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    assert np.isfinite(float(aux))


def test_full_routing_equals_dense_mixture():
    """k = E with ample capacity => output is the softmax-weighted expert sum."""
    cfg = _moe_cfg(n_experts=4, experts_per_token=4, expert_capacity_factor=8.0)
    key = jax.random.key(0)
    mlp = moe.init_moe_params(cfg, key, resid_std=0.02, dtype=jnp.float32)
    h = jax.random.normal(jax.random.key(1), (2, 8, cfg.d_model), jnp.float32)

    out, _ = moe.moe_mlp(mlp, h, cfg)

    # Direct computation: softmax(router) over ALL experts, dense expert FFNs.
    x = h.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(x @ mlp["router"], axis=-1)  # (S, E)
    w1, w2 = mlp["experts"]["w1"], mlp["experts"]["w2"]
    b1, b2 = mlp["experts"]["b1"], mlp["experts"]["b2"]
    expected = jnp.zeros_like(x)
    for e in range(cfg.n_experts):
        hidden = jax.nn.gelu(x @ w1[e] + b1[e], approximate=True)
        expected = expected + probs[:, e : e + 1] * (hidden @ w2[e] + b2[e])
    np.testing.assert_allclose(
        np.asarray(out.reshape(-1, cfg.d_model)), np.asarray(expected), rtol=2e-4, atol=2e-5
    )


def test_tiny_capacity_drops_but_stays_finite():
    cfg = _moe_cfg(expert_capacity_factor=0.05)
    mlp = moe.init_moe_params(cfg, jax.random.key(0), resid_std=0.02, dtype=jnp.float32)
    h = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model), jnp.float32)
    out, aux = moe.moe_mlp(mlp, h, cfg)
    assert np.isfinite(np.asarray(out)).all()
    assert np.isfinite(float(aux))
    # Capacity 0.05 * 2 * 32 / 4 < 1 -> clamped to 1 slot per expert: at most
    # E slots filled, so most tokens' MoE output is exactly zero.
    flat = np.asarray(out.reshape(-1, cfg.d_model))
    nonzero_tokens = (np.abs(flat).max(axis=-1) > 0).sum()
    assert nonzero_tokens <= cfg.n_experts * 1 * 2  # k slots may double-serve a token


def test_aux_loss_near_one_at_init():
    """Near-uniform router at init => Switch aux loss ~= 1."""
    cfg = _moe_cfg()
    mlp = moe.init_moe_params(cfg, jax.random.key(0), resid_std=0.02, dtype=jnp.float32)
    h = jax.random.normal(jax.random.key(1), (4, 32, cfg.d_model), jnp.float32)
    _, aux = moe.moe_mlp(mlp, h, cfg)
    assert 0.8 < float(aux) < 1.3


def test_grads_flow_to_router_and_experts():
    cfg = _moe_cfg()
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, cfg.context_length), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    grads = jax.grad(transformer.loss_fn)(params, tokens, targets, cfg)
    blk = grads["blocks"]["mlp"]
    assert float(jnp.abs(blk["router"]).max()) > 0
    assert float(jnp.abs(blk["experts"]["w1"]).max()) > 0
    assert float(jnp.abs(blk["experts"]["w2"]).max()) > 0
    assert np.isfinite(float(jnp.abs(blk["router"]).max()))


def test_grouped_routing_matches_global_when_capacity_ample():
    """With no capacity contention, per-group routing == one global pool:
    token-choice decisions are independent per token, so splitting the
    capacity pool only matters when drops occur."""
    cfg_global = _moe_cfg(expert_capacity_factor=8.0, moe_group_size=0)
    cfg_grouped = dataclasses.replace(cfg_global, moe_group_size=16)
    mlp = moe.init_moe_params(cfg_global, jax.random.key(0), resid_std=0.02, dtype=jnp.float32)
    h = jax.random.normal(jax.random.key(1), (4, 16, cfg_global.d_model), jnp.float32)
    out_global, aux_global = moe.moe_mlp(mlp, h, cfg_global)
    out_grouped, aux_grouped = moe.moe_mlp(mlp, h, cfg_grouped)
    assert moe._group_count(4 * 16, 16) == 4  # actually exercising groups
    np.testing.assert_allclose(
        np.asarray(out_grouped), np.asarray(out_global), rtol=1e-5, atol=1e-6
    )
    # Aux is computed per group then averaged (the Switch formulation —
    # balance is enforced within every group): close to, but not bit-equal
    # with, the single global pool's value.
    np.testing.assert_allclose(float(aux_grouped), float(aux_global), rtol=2e-2)


def test_group_count_mesh_independent_and_divisor():
    assert moe._group_count(32768, 2048) == 16
    assert moe._group_count(1000, 2048) == 1
    assert moe._group_count(1000, 300) == 2  # rounds down to a divisor
    assert moe._group_count(4096, 0) == 1


def test_decode_routing_is_batch_composition_independent():
    """decode=True routes without a capacity bound: a token's MoE output must
    not depend on which other sequences are co-batched (the capacity-drop
    inconsistency the training-time bound would introduce)."""
    cfg = _moe_cfg(expert_capacity_factor=0.05)  # starved at train time
    mlp = moe.init_moe_params(cfg, jax.random.key(0), resid_std=0.02, dtype=jnp.float32)
    row = jax.random.normal(jax.random.key(1), (1, 1, cfg.d_model), jnp.float32)
    other_a = jax.random.normal(jax.random.key(2), (3, 1, cfg.d_model), jnp.float32)
    other_b = jax.random.normal(jax.random.key(3), (3, 1, cfg.d_model), jnp.float32)
    out_a, _ = moe.moe_mlp(mlp, jnp.concatenate([row, other_a]), cfg, decode=True)
    out_b, _ = moe.moe_mlp(mlp, jnp.concatenate([row, other_b]), cfg, decode=True)
    # Slot assignment order differs with batch composition; values agree up
    # to summation-order noise.
    np.testing.assert_allclose(np.asarray(out_a[0]), np.asarray(out_b[0]), rtol=1e-5, atol=1e-8)
    # And nothing is dropped in decode: output is a full top-k mixture.
    assert float(jnp.abs(out_a[0]).max()) > 0


def test_moe_real_batch_dispatch_compiles_within_memory(mesh_exp4):
    """moe-8x350m at its real token count (32k tokens/step): the grouped
    dispatch must keep per-step temp memory bounded (the global-capacity
    dispatch was O(S^2) ~ 10 GB of fp32 at this batch)."""
    preset = get_preset("moe-8x350m")
    cfg = preset.replace(
        model=dataclasses.replace(preset.model, n_layers=2, remat="full"),
        mesh=dataclasses.replace(preset.mesh, data=2, fsdp=1, expert=4),
    )
    b, t = cfg.train.batch_size, cfg.model.context_length
    assert b * t >= 32768, "preset shrank: test no longer covers the real batch"
    state = ts.init_train_state(cfg, jax.random.key(0))
    state = ts.shard_train_state(state, mesh_exp4)
    x = jnp.zeros((b, t), jnp.int32)
    # Compile only (CPU execution at 32k tokens x 8 experts is minutes).
    from pretraining_llm_tpu.parallel.sharding import activation_mesh
    from pretraining_llm_tpu.models import transformer as tf

    def loss(params, xb, yb):
        with activation_mesh(mesh_exp4):
            return tf.loss_fn(params, xb, yb, cfg.model)

    compiled = jax.jit(jax.grad(loss)).lower(state["params"], x, x).compile()
    temp_gb = compiled.memory_analysis().temp_size_in_bytes / 2**30
    # Aggregate across the 8 virtual devices; the old dispatch alone was
    # ~10 GB fp32 per layer-pair. Generous bound to stay hardware-agnostic.
    assert temp_gb < 24, f"temp {temp_gb:.1f} GB: grouped dispatch regressed"


def test_expert_parallel_train_step_matches_single_device(mesh_exp4):
    """Same step on a 2-data x 4-expert mesh and on one device => same loss."""
    cfg = get_preset("tiny").replace(
        model=dataclasses.replace(
            get_preset("tiny").model,
            n_experts=4,
            experts_per_token=2,
            expert_capacity_factor=4.0,  # ample: no drops => mesh-invariant
        ),
    )
    cfg = cfg.replace(
        mesh=dataclasses.replace(cfg.mesh, data=2, expert=4),
        train=dataclasses.replace(cfg.train, batch_size=8, microbatches=1),
    )
    x = jax.random.randint(jax.random.key(1), (8, cfg.model.context_length), 0,
                           cfg.model.vocab_size)
    y = jnp.roll(x, -1, axis=1)

    state = ts.init_train_state(cfg, jax.random.key(0))
    sharded = ts.shard_train_state(jax.tree.map(jnp.copy, state), mesh_exp4)
    step = ts.build_train_step(cfg, mesh_exp4)
    sharded, metrics = step(sharded, (x, y))
    sharded_loss = float(metrics["loss"])

    single_step = ts.build_train_step(cfg, mesh=None)
    state, metrics1 = single_step(state, (x, y))
    # bf16 compute + mesh-dependent reduction order => small numeric slack
    np.testing.assert_allclose(sharded_loss, float(metrics1["loss"]), rtol=1e-3)
    assert int(jax.device_get(sharded["step"])) == 1


# -- the dropless layer plans its rows once (PR 59) -----------------------------------


def _layer_before_pr59(mlp, h, cfg, dense_mlp, form):
    """``moe.moe_mlp_dropless`` as it stood before PR 59, kept here as the oracle:
    two sorts, a ``bincount``, the experts' output brought back to sorted order
    (inside ``moe.experts_kernel``) and then to token order by a second row
    gather, gates and masks gathered into sorted order for the product."""
    cdt = jnp.dtype(cfg.compute_dtype)
    b, t, d = h.shape
    s, k = b * t, cfg.experts_per_token
    x = h.reshape(s, d)
    w1, w2 = mlp["experts"]["w1"].astype(cdt), mlp["experts"]["w2"].astype(cdt)
    held = w1.shape[-3]
    layer, limit = mlp.get("expert_layer"), mlp.get("expert_limit")
    idx, gates = moe.route_dropless(mlp, x, cfg)
    flat = idx.reshape(s * k)
    flat = jnp.where(flat < held, flat, held)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.bincount(flat, length=held + 1).astype(jnp.int32)
    xs = x[order // k].astype(cdt)
    sizes = counts[:held]
    if form == "kernel":
        ys = moe.experts_kernel(xs, w1, w2, sizes, layer, limit, pallas_moe.windows(s * k, cfg.n_experts))
    else:
        ys = moe.experts_grouped(xs, w1, w2, sizes, layer, limit)
    here = flat[order] < held
    g_sorted = jnp.where(here, gates.reshape(s * k)[order], 0.0)
    ys = ys.astype(jnp.float32) * g_sorted[:, None]
    if held < cfg.n_experts:
        ys = jnp.where(here[:, None], ys, 0.0)
    ys = ys.astype(cdt)
    y = jnp.sum(ys[jnp.argsort(order)].reshape(s, k, d).astype(jnp.float32), axis=1).astype(cdt).reshape(b, t, d)
    if "shared" in mlp:
        y = y + dense_mlp(mlp["shared"], h)
    return y.astype(h.dtype), counts[:held]


# (experts held of 8, a stack, activation, clamp, form, tokens, routing): every
# value of each beside every value of the others at least once. 7 tokens x 2
# choices is no whole row tile of sorted rows; "one-takes-all" has one choice a
# token and a bias that sends every token to expert 1; "one-takes-none" a bias
# that keeps expert 0 out of every selection.
_PLANNED_ONCE = [
    (8, False, "swiglu", None, "kernel", 16, "scored"),
    (8, True, "swiglu", 0.5, "grouped", 7, "scored"),
    (8, True, "relu2", None, "kernel", 7, "one-takes-all"),
    (8, False, "relu2", None, "grouped", 16, "one-takes-none"),
    (2, True, "swiglu", None, "kernel", 7, "one-takes-none"),
    (2, False, "swiglu", 0.5, "kernel", 16, "one-takes-all"),
    (2, False, "relu2", None, "grouped", 7, "scored"),
    (2, True, "relu2", None, "kernel", 16, "scored"),
    (2, True, "swiglu", 0.5, "grouped", 16, "one-takes-all"),
    (8, False, "swiglu", 0.5, "kernel", 7, "one-takes-none"),
    (8, True, "swiglu", None, "grouped", 16, "one-takes-all"),
    (2, False, "swiglu", None, "grouped", 7, "one-takes-none"),
    (8, False, "swiglu", None, "kernel", 40, "scored"),  # 10 rows an expert: three row tiles' span and more visits
    (2, True, "swiglu", 0.0, "kernel", 200, "scored"),  # 50 rows an expert, a clamp that is off
]


@pytest.mark.parametrize(
    "held,stacked,activation,clamp,form,tokens,routing", _PLANNED_ONCE,
    ids=["-".join(str(v) for v in case) for case in _PLANNED_ONCE],
)
def test_the_layer_that_plans_its_rows_once_is_the_layer_before_to_the_bit(
    monkeypatch, held, stacked, activation, clamp, form, tokens, routing
):
    """One count and one gather each way give what two sorts, two searches and
    three gathers gave, bit for bit: a gather commutes with an elementwise
    product, so the float32 product with the gate, the rounding to bfloat16, the
    float32 sum over the choices in choice order and the last rounding stand
    where they stood. The kernel is interpreted here."""
    n_experts, d, f, n_stack = 8, 128, 128, 3
    cfg = ModelConfig(
        vocab_size=64, context_length=256, d_model=d, n_heads=2, n_layers=1, activation=activation, norm="rmsnorm",
        compute_dtype="bfloat16", param_dtype="bfloat16", n_experts=n_experts, n_experts_held=held % n_experts,
        experts_per_token=1 if routing == "one-takes-all" else 2, moe_routing="dropless", moe_score="sigmoid",
        moe_score_bias=True, n_shared_experts=1, d_expert=f, mlp_bias=False,
    )
    mlp = moe.init_dropless_params(cfg, jax.random.key(held + tokens), 0.02, jnp.bfloat16)
    mlp["experts"] = {k: v * 8 for k, v in mlp["experts"].items()}  # outputs of a size that rounding shows
    bias = {"scored": [0.0] * 8, "one-takes-all": [0, 9, 0, 0, 0, 0, 0, 0], "one-takes-none": [-9] + [0.0] * 7}
    mlp["router_bias"] = jnp.asarray(bias[routing], jnp.bfloat16)
    if stacked:
        keys = jax.random.split(jax.random.key(7), n_stack)
        others = [moe.init_dropless_params(cfg, k_, 0.02, jnp.bfloat16)["experts"] for k_ in keys]
        others[1] = mlp["experts"]
        mlp["experts"] = jax.tree.map(lambda *a: jnp.stack(a), *others)
        mlp["expert_layer"] = jnp.int32(1)
    if clamp is not None:
        mlp["expert_limit"] = jnp.float32(clamp)
    h = jax.random.normal(jax.random.key(tokens), (1, tokens, d), jnp.bfloat16)
    dense = lambda shared, hh: transformer._dense_mlp(shared, hh, cfg)
    monkeypatch.setattr(moe, "experts_form", lambda *a, **kw: form)

    got, want = jax.jit(lambda m, x: (
        moe.moe_mlp_dropless(m, x, cfg, dense), _layer_before_pr59(m, x, cfg, dense, form)))(mlp, h)
    np.testing.assert_array_equal(got[1], want[1])
    counts = np.asarray(got[1])
    assert counts.shape == (held,) and (held < 8 or counts.sum() == tokens * cfg.experts_per_token)
    if routing == "one-takes-all":
        assert counts[1] == tokens and counts.sum() == tokens
    if routing == "one-takes-none":
        assert counts[0] == 0 and counts[1:].all()
    assert float(jnp.max(jnp.abs(want[0].astype(jnp.float32)))) > 0.05
    np.testing.assert_array_equal(np.asarray(got[0], np.float32), np.asarray(want[0], np.float32))
