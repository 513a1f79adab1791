"""Serving `correct` for a state-slot model whose FFNs are routed experts:
``ssm_check.compare``'s logits and state slots, and beside them **every expert
layer by itself, fed the reference's own input**.

On a model of many routed layers the logits cannot hold the experts or their
router. Where the sixth and seventh of 128 scores stand a few hundredths
apart, the rounding of bfloat16 activations alone puts a share of the tokens
on the other side of that tie in every expert layer; a flipped choice
exchanges a sixth of the layer's routed output and moves the row's logits by a
tenth and more. Over eleven such layers most of the compared rows carry a
flip, so the root mean square of the logits' error (and any quantile of the
rows) reads the flips, 0.15-0.19 here, and what a router computed in bfloat16
or experts fed int8 operands add to them disappears in it (PERF.md section 2:
a bfloat16 router alone reads what the sound program reads).

A flip is a disagreement about the *input* of the router, not a fault of the
layer. So this check takes the disagreement away, as teacher-forced tokens
take it away from the decode steps: the reference keeps the normed hidden
state it handed each expert layer at the sampled sequences' last positions
(``references/<family>.forward(..., expert_rows=...)``), rounded to the served
dtype; the program's expert layer (``models/moe.py::moe_mlp_dropless`` on the
engine's own stacked weights, a decode step's batch of rows at a time, so the
form, the kernel and its row windows are the decode step's) and the
reference's (``references/<family>.experts``) are both handed those very
numbers. Both score the same bits in float32, so a sound program chooses what
the reference chooses, and what is left is the rounding inside the experts:
half a percent. A router whose scores are not float32 flips choices on
identical inputs, experts of lower precision miss by their own error, and
either shows undiluted. The number held is ``expert_layer_rel_err``: the
largest, over the expert layers, of ``||program - reference|| /
||reference||`` over the layer's rows of routed output (the experts held and
their gates; the shared expert, a dense FFN, is in the logits). Every layer's
own figure is logged.

The logits' root mean square, the state slots' two numbers and the engine's
own tokens stay held as ``closed_decode_ssm`` holds them: they tell a wrong
page, slot, position or form, and the state's precision; their limits stand
over the flips. Limits: the configuration file's ``check_limits``; PERF.md
section 2 gives the readings each stands between.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, List, Tuple

import numpy as np

from harness import opcount, serving_check, ssm_check

Sample = serving_check.Sample


def reference(arch: Dict[str, Any], seed: int, sample: Sample, seqs: List[np.ndarray], rows: int,
              quant: Any = None, control: str = ""):
    """``ssm_check.reference``'s three (logits rows, states, decay rates), and every
    expert layer's input at each sequence's last ``rows`` positions, rounded to the
    served dtype: (layers, sequences * rows, d) float32."""
    import jax
    import jax.numpy as jnp

    from harness import weights

    ref = importlib.import_module(f"references.{arch['family']}")
    key, dtype = weights.seed_key(seed), jnp.dtype(arch["serving_dtype"])
    make_layer = jax.jit(lambda k, l: weights.layer(arch, k, l, dtype))
    gw = jax.jit(lambda k: weights.globals_(arch, k, dtype))(key)
    logits, states, handed = [], [], []
    for (p, k), toks in zip(sample, seqs):
        full, kept, inputs = ref.forward(jnp.asarray(toks[: p + k]), lambda l: make_layer(key, l), gw, arch,
                                         quant=quant, control=control, states=True, expert_rows=rows)
        logits.append(np.asarray(full[p - 1 : p + k], np.float32))
        states.append(np.stack([np.asarray(s, np.float32) for s in kept]))
        handed.append(np.stack([np.asarray(u.astype(dtype).astype(jnp.float32)) for u in inputs]))
    rate = []
    for l in range(opcount.dims(arch)["layers"]):
        w = make_layer(key, l)
        rate.append(np.asarray(jax.nn.softplus(w["dt_bias"].astype(jnp.float32))
                               * jnp.exp(w["A_log"].astype(jnp.float32))))
    return logits, np.stack(states), np.stack(rate), np.concatenate(handed, axis=1)


def reference_experts(arch: Dict[str, Any], seed: int, handed: np.ndarray,
                      quant: Any = None, control: str = "") -> np.ndarray:
    """The routed output of every expert layer on its rows of ``handed`` (layers,
    n, d), by the reference: (layers, n, d) float32."""
    import jax
    import jax.numpy as jnp

    from harness import weights

    ref = importlib.import_module(f"references.{arch['family']}")
    key, dtype = weights.seed_key(seed), jnp.dtype(arch["serving_dtype"])
    make_layer = jax.jit(lambda k, l: weights.layer(arch, k, l, dtype))
    return np.stack([
        np.asarray(ref.experts(jnp.asarray(u), make_layer(key, l), arch, quant, control=control, shared=False))
        for l, u in enumerate(handed)
    ])


def program_experts(params: Any, cfg: Any, handed: np.ndarray, batch: int) -> np.ndarray:
    """The same of the program: ``moe.moe_mlp_dropless`` on each layer's place in the
    engine's own expert stack, ``batch`` rows a call (the decode step's)."""
    import jax
    import jax.numpy as jnp

    from pretraining_llm_tpu.models import moe

    stack = params["ffn_blocks"]["mlp"]
    cdt = jnp.dtype(cfg.compute_dtype)

    @jax.jit
    def layer(router, bias, experts, l, h):
        mlp = {"router": router, "router_bias": bias, "experts": experts, "expert_layer": l}
        return moe.moe_mlp_dropless(mlp, h[None], cfg, None)[0][0]

    n = handed.shape[1]
    pad = -n % batch
    out = []
    for l, u in enumerate(handed):
        h = jnp.asarray(np.concatenate([u, np.zeros((pad, u.shape[1]), u.dtype)]), cdt)
        got = [layer(stack["router"][l], stack["router_bias"][l], stack["experts"], jnp.int32(l), h[i : i + batch])
               for i in range(0, n + pad, batch)]
        out.append(np.asarray(jnp.concatenate(got)[:n], np.float32))
    return np.stack(out)


def layer_errors(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """(layers,): each layer's ``||program - reference|| / ||reference||`` over its rows."""
    diff = np.sum((prog.astype(np.float64) - ref) ** 2, axis=(1, 2))
    return np.sqrt(diff / np.sum(ref.astype(np.float64) ** 2, axis=(1, 2)))


def compare(ctx: Any, eng: Any, params: Any, cfg: Any) -> Dict[str, Tuple[float, float]]:
    """{name: (value, limit)} of the logits', the state slots' and the expert
    layers' comparison on an engine whose rows have been released; frees the
    engine's pool before the reference runs."""
    sample = [tuple(s) for s in ctx.traffic["check_sample"]]
    seqs = serving_check.sample_tokens(ctx.seed, opcount.dims(ctx.arch)["vocab"], sample)
    prog, eng.pools = serving_check.program_logits(
        params, cfg, eng.pools, eng.alloc, eng.max_batch, eng.max_blocks, eng.block_size, sample, seqs,
    )
    held = ssm_check.slot_states(eng.pools, len(sample))
    del eng.pools
    rows = min(eng.max_batch, min(p + k for p, k in sample))
    want, states, rate, handed = reference(ctx.arch, ctx.seed, sample, seqs, rows)
    errors = ssm_check.head_errors(held, states)
    ctx.log(f"state slots: {errors.size} heads' states compared over {errors.shape[1]} layers, all as one vector "
            f"{ssm_check.whole_rel_err(held, states):.6g}; a head's own error median {np.median(errors):.6g} "
            f"largest {errors.max():.6g}")
    by_layer = layer_errors(program_experts(params, cfg, handed, eng.max_batch),
                            reference_experts(ctx.arch, ctx.seed, handed))
    ctx.log(f"expert layers: {handed.shape[0]} layers x {handed.shape[1]} rows of routed output on the reference's "
            f"input, a layer's error " + " ".join(f"{e:.4g}" for e in by_layer))
    limits = ctx.arch["check_limits"]
    return {
        "logits_rel_err": (serving_check.rel_err(prog, want), limits["logits_rel_err"]),
        "state_rel_err": (ssm_check.state_rel_err(errors, rate), limits["state_rel_err"]),
        "state_first_rel_err": (ssm_check.state_rel_err(errors, rate, slice(0, 1)), limits["state_first_rel_err"]),
        "expert_layer_rel_err": (float(by_layer.max()), limits["expert_layer_rel_err"]),
    }
