"""The single compiled SPMD train step.

The reference's step is many separate device launches — autocast forward,
scaled backward, DDP bucketed all-reduce, scaler step, zero_grad
(`/root/reference/scripts/train_transformer.py:64-94`). Here the *entire*
optimizer step is one `jit`-compiled XLA program over the global mesh:

    grads = mean over microbatches (lax.scan)   # grad accumulation, done right
    clip -> AdamW -> new params                  # fused into the same program
    collectives inserted by XLA from shardings   # no NCCL calls to write

Gradient accumulation via `lax.scan` fixes the reference's broken
every-other-step sync gating (SURVEY §A B7) by construction: the optimizer
sees exactly the mean gradient of the full global batch.

State is a plain dict pytree {'params', 'opt', 'step'} so checkpointing and
sharding rules treat it uniformly.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pretraining_llm_tpu.config import Config
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.observability import witness
from pretraining_llm_tpu.parallel.sharding import (
    activation_mesh,
    batch_pspec,
    named_sharding_tree,
    param_pspec_tree,
)
from pretraining_llm_tpu.training import optimizer as opt

TrainState = Dict[str, Any]


def init_train_state(cfg: Config, key: jax.Array) -> TrainState:
    params = transformer.init_params(cfg.model, key)
    state = {
        "params": params,
        "opt": opt.optimizer_init(params, cfg.train),
        "step": jnp.zeros((), jnp.int32),
    }
    if cfg.train.ema_decay > 0:
        # Exponential moving average of the params for evaluation/serving
        # (beyond-reference): fp32 shadow updated after every optimizer
        # step; checkpointed and sharded exactly like the params.
        # copy=True: fp32 params' astype would alias the SAME buffer,
        # and the jitted step donates the state — donating params and
        # ema as one buffer is an XLA error (and would be wrong anyway).
        state["ema"] = jax.tree.map(
            lambda p: jnp.array(p, dtype=jnp.float32, copy=True), params
        )
    return state


def state_pspec_tree(
    state: TrainState, pipeline: bool = False, *, tensor_size: int = 1
) -> Any:
    """PartitionSpecs for the full train state (moments mirror params)."""
    kw = {"tensor_size": tensor_size}
    pspecs = param_pspec_tree(state["params"], pipeline, **kw)
    if "v" in state["opt"]:
        # Adafactor: the factored statistics are ~0.3 bytes/param — too
        # small to be worth sharding (and their shapes don't match the
        # param sharding rules). Replicate every statistic array.
        opt_pspecs = {
            "v": jax.tree.map(lambda _: P(), state["opt"]["v"]),
            "count": P(),
        }
    elif "s" in state["opt"]:
        # Muon: every per-leaf state array (muon momentum "m", or adam
        # "mu"/"nu" for the non-matrix leaves) mirrors its param's shape —
        # shard each exactly like the param (FSDP shards momentum for
        # free, same as adamw's moments). tree.map flattens the per-leaf
        # state dict UP TO the param pspec tree, so each dict maps to
        # {key: param_pspec}.
        opt_pspecs = {
            "s": jax.tree.map(
                lambda ps, sd: {k: ps for k in sd}, pspecs, state["opt"]["s"]
            ),
            "count": P(),
        }
    else:
        opt_pspecs = {
            "mu": param_pspec_tree(state["opt"]["mu"], pipeline, **kw),
            "nu": param_pspec_tree(state["opt"]["nu"], pipeline, **kw),
            "count": P(),
        }
    out = {
        "params": pspecs,
        "opt": opt_pspecs,
        "step": P(),
    }
    if "ema" in state:
        out["ema"] = param_pspec_tree(state["ema"], pipeline, **kw)
    return out


def _tensor_size(mesh: Optional[Mesh]) -> int:
    return mesh.shape.get("tensor", 1) if mesh is not None else 1


def _is_pipelined(cfg: Config, mesh: Optional[Mesh]) -> bool:
    return (
        cfg.model.pipeline_stages > 1
        and mesh is not None
        and mesh.shape.get("pipe", 1) > 1
    )


def shard_train_state(state: TrainState, mesh: Mesh, cfg: Optional[Config] = None) -> TrainState:
    """Place the train state on the mesh (and bake the pipeline layout).

    With an interleaved pipeline (pipeline_interleave>1 on a pipe>1 mesh),
    block params AND optimizer moments are stored rank-major
    (parallel.pipeline.interleave_layout) so the P('pipe') shards hold each
    rank's V depth chunks directly — the schedule then runs with no per-step
    cross-rank reshard (VERDICT r2 next #5). Checkpoints remain canonical
    depth-major; the trainer converts at save/load.
    """
    pipeline = cfg is not None and _is_pipelined(cfg, mesh)
    if cfg is not None and uses_baked_layout(cfg, mesh):
        state = bake_state_layout(state, cfg, forward=True)
    shardings = named_sharding_tree(
        mesh, state_pspec_tree(state, pipeline, tensor_size=_tensor_size(mesh))
    )
    return jax.device_put(state, shardings)


def bake_state_layout(state: TrainState, cfg: Config, forward: bool = True) -> TrainState:
    """Convert blocks (+ mirrored moments) between canonical depth-major and
    the interleaved rank-major layout. ``forward=True``: depth -> rank-major
    (entering pipelined training); ``False``: back to canonical (checkpoint
    save, export)."""
    from pretraining_llm_tpu.parallel import pipeline as pp

    s = cfg.model.pipeline_stages
    v = cfg.model.pipeline_interleave
    f = pp.interleave_layout if forward else pp.deinterleave_layout
    out = dict(state)
    out["params"] = dict(state["params"])
    out["params"]["blocks"] = f(state["params"]["blocks"], s, v)
    if "opt" in state:
        out["opt"] = dict(state["opt"])
        # Every moment container mirroring the params' structure (adamw:
        # mu/nu; adafactor: v — whose blocks arrays all keep the leading
        # stacked-layer axis by the factoring rule) gets the same layout
        # permutation as the params.
        for m, sub in state["opt"].items():
            if isinstance(sub, dict) and "blocks" in sub:
                out["opt"][m] = dict(sub)
                out["opt"][m]["blocks"] = f(sub["blocks"], s, v)
    if "ema" in state:
        out["ema"] = dict(state["ema"])
        out["ema"]["blocks"] = f(state["ema"]["blocks"], s, v)
    return out


def _loss_and_metrics(params, xb, yb, model_cfg, blocks_baked=False):
    loss = transformer.loss_fn(params, xb, yb, model_cfg, blocks_baked=blocks_baked)
    return loss


def uses_baked_layout(cfg: Config, mesh: Optional[Mesh]) -> bool:
    """True when the train state stores blocks in the rank-major interleaved
    layout (baked once by shard_train_state instead of re-permuted per step)."""
    return _is_pipelined(cfg, mesh) and cfg.model.pipeline_interleave > 1


def _make_step_fn(cfg: Config, mesh: Optional[Mesh] = None):
    """The raw (unjitted) SPMD step: grads -> clip -> AdamW -> metrics."""
    model_cfg = cfg.model
    tcfg = cfg.train
    n_micro = tcfg.microbatches
    baked = uses_baked_layout(cfg, mesh)

    def step_fn(state: TrainState, batch: Tuple[jax.Array, jax.Array]):
        x, y = batch
        if tcfg.grad_dtype == "bfloat16":
            # HBM lever (the 1B b8 knee): cast each gradient leaf to bf16
            # IMMEDIATELY after the backward produces it — XLA fuses the
            # convert into the producing fusion, so the end-of-backward
            # state holds a 2-byte/param tree (and the microbatch
            # accumulator below matches). Chosen over differentiating a
            # bf16 param view after AOT memory analysis (2026-08-02): the
            # up-front bf16 param copy stays PINNED across the whole
            # backward (+2.8 GiB at 1B), cancelling the saving, while
            # this form keeps the fp32 cotangent chain (grads are the
            # fp32-path values rounded once) and adds no pinned copy.
            # Clip and the optimizer updates upcast per-leaf internally.
            def grad_fn(params, mx, my, mcfg, bk):
                loss, g = jax.value_and_grad(_loss_and_metrics)(
                    params, mx, my, mcfg, bk
                )
                g = jax.tree.map(
                    lambda leaf: leaf.astype(jnp.bfloat16)
                    if leaf.dtype == jnp.float32 else leaf,
                    g,
                )
                return loss, g
        else:
            grad_fn = jax.value_and_grad(_loss_and_metrics)

        if n_micro == 1:
            loss, grads = grad_fn(state["params"], x, y, model_cfg, baked)
        else:
            b = x.shape[0]
            xm = x.reshape(n_micro, b // n_micro, -1)
            ym = y.reshape(n_micro, b // n_micro, -1)

            def micro_step(carry, mb):
                loss_acc, grads_acc = carry
                mx, my = mb
                loss, grads = grad_fn(state["params"], mx, my, model_cfg, baked)
                return (
                    loss_acc + loss,
                    jax.tree.map(jnp.add, grads_acc, grads),
                ), None

            # The accumulator matches the grad storage dtype (bf16 halves
            # it too under grad_dtype="bfloat16" — mean-of-microbatches in
            # bf16 is the documented precision trade of that knob).
            gdt = (
                jnp.bfloat16 if tcfg.grad_dtype == "bfloat16" else None
            )
            zero_grads = jax.tree.map(
                lambda p: jnp.zeros_like(
                    p,
                    dtype=gdt if (gdt and p.dtype == jnp.float32) else p.dtype,
                ),
                state["params"],
            )
            with jax.named_scope("microbatch"):
                (loss_sum, grad_sum), _ = jax.lax.scan(
                    micro_step, (jnp.zeros((), jnp.float32), zero_grads), (xm, ym)
                )
                loss = loss_sum / n_micro
                grads = jax.tree.map(lambda g: g / n_micro, grad_sum)

        with jax.named_scope("grad_clip"):
            if tcfg.grad_clip > 0:
                grads, grad_norm = opt.clip_by_global_norm(grads, tcfg.grad_clip)
            else:
                grad_norm = opt.global_norm(grads)

        with jax.named_scope("optimizer"):
            lr = opt.learning_rate(state["step"], tcfg)
            new_params, new_opt = opt.optimizer_update(
                grads, state["opt"], state["params"], lr, tcfg
            )
        new_state = {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
        if "ema" in state:
            d = tcfg.ema_decay
            new_state["ema"] = jax.tree.map(
                lambda e, p: d * e + (1.0 - d) * p.astype(jnp.float32),
                state["ema"], new_params,
            )
        metrics = {"loss": loss, "grad_norm": grad_norm, "lr": lr}
        return new_state, metrics

    return step_fn


def build_train_step(
    cfg: Config, mesh: Optional[Mesh] = None
) -> Callable[[TrainState, Tuple[jax.Array, jax.Array]], Tuple[TrainState, Dict[str, jax.Array]]]:
    """Compile the train step. batch: (x, y) each (B, T) int32, B = global batch."""
    model_cfg = cfg.model
    step_fn = _make_step_fn(cfg, mesh)
    witness.ensure()  # whoever drives the step (the trainer, the benchmark) runs under the witness

    if mesh is None:
        return jax.jit(step_fn, donate_argnums=0)

    def traced(state, batch):
        with activation_mesh(mesh):
            return step_fn(state, batch)

    # Shardings are derived from the live state at first call (the pytree
    # structure depends on model flags), then the compiled fn is memoized.
    batch_sharding = NamedSharding(mesh, batch_pspec(model_cfg.sequence_parallel))
    compiled_cache: Dict[Any, Any] = {}

    pipelined = _is_pipelined(cfg, mesh)

    def wrapper(state, batch):
        key = jax.tree.structure(state)
        fn = compiled_cache.get(key)
        if fn is None:
            state_shardings = named_sharding_tree(
                mesh, state_pspec_tree(state, pipelined, tensor_size=_tensor_size(mesh))
            )
            fn = jax.jit(
                traced,
                in_shardings=(state_shardings, (batch_sharding, batch_sharding)),
                out_shardings=(state_shardings, None),
                donate_argnums=0,
            )
            compiled_cache[key] = fn
        return fn(state, batch)

    return wrapper


def lower_train_step(cfg: Config, mesh: Optional[Mesh] = None):
    """AOT-lower the EXACT jitted train-step program (same in/out shardings,
    same donation) from shape specs alone — no params materialize, no data
    loads. Returns the jax.stages.Lowered; `.compile().memory_analysis()`
    gives XLA's per-device memory breakdown (scripts/train.py --compile-only
    uses this to size big configs before burning pod time on an OOM)."""
    state_shapes = jax.eval_shape(lambda: init_train_state(cfg, jax.random.key(0)))
    b, t = cfg.train.batch_size, cfg.model.context_length
    if mesh is None:
        step = build_train_step(cfg, None)
        batch_sds = jax.ShapeDtypeStruct((b, t), jnp.int32)
        return step.lower(state_shapes, (batch_sds, batch_sds))
    batch_sharding = NamedSharding(mesh, batch_pspec(cfg.model.sequence_parallel))
    state_shardings = named_sharding_tree(
        mesh,
        state_pspec_tree(
            state_shapes, _is_pipelined(cfg, mesh), tensor_size=_tensor_size(mesh)
        ),
    )
    step_fn = _make_step_fn(cfg, mesh)

    def traced(state, batch):
        with activation_mesh(mesh):
            return step_fn(state, batch)

    fn = jax.jit(
        traced,
        in_shardings=(state_shardings, (batch_sharding, batch_sharding)),
        out_shardings=(state_shardings, None),
        donate_argnums=0,
    )
    batch_sds = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=batch_sharding)
    return fn.lower(state_shapes, (batch_sds, batch_sds))


def build_eval_step(
    cfg: Config, mesh: Optional[Mesh] = None
) -> Callable[[TrainState, Tuple[jax.Array, jax.Array]], jax.Array]:
    model_cfg = cfg.model
    baked = uses_baked_layout(cfg, mesh)

    def eval_fn(state: TrainState, batch):
        x, y = batch
        with activation_mesh(mesh):
            # Pure CE (no MoE router aux): val_loss comparable across models.
            return transformer.loss_fn(
                state["params"], x, y, model_cfg, include_aux=False,
                blocks_baked=baked,
            )

    return jax.jit(eval_fn)


def build_eval_loop(
    cfg: Config, mesh: Optional[Mesh] = None
) -> Callable[[TrainState, Tuple[jax.Array, jax.Array]], jax.Array]:
    """Mean eval loss over a stacked batch set in ONE dispatch.

    batches: (x, y) each (N, B, T). A `lax.scan` over the N eval batches runs
    device-side — versus N individual eval_fn dispatches (each a host round
    trip on remote platforms), this is one launch and one scalar fetch.
    """
    model_cfg = cfg.model
    baked = uses_baked_layout(cfg, mesh)

    def eval_many(state: TrainState, batches: Tuple[jax.Array, jax.Array]) -> jax.Array:
        def body(acc, xy):
            x, y = xy
            with activation_mesh(mesh):
                loss = transformer.loss_fn(
                    state["params"], x, y, model_cfg, include_aux=False,
                    blocks_baked=baked,
                )
            return acc + loss, None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), batches)
        return total / batches[0].shape[0]

    return jax.jit(eval_many)
