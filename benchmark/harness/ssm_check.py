"""Serving `correct` for a model whose recurrent layers keep a state a row
(``cfg.hybrid`` over a state-space mixer): ``serving_check.compare``'s logits,
and beside them **the state slots themselves**, against the plain reference.

The logits of 128 teacher-forced steps cannot tell a float32 state from one
rounded to bfloat16 after every token (PERF.md section 2: 0.036-0.052 against
sound runs of 0.026-0.029): a head's output is a sum over its whole state, the
roundings of a step are independent, and the sum averages them away. The state
does not: a head that forgets slowly (``delta x exp(A_log)`` small) carries
every rounding of its last few hundred tokens, so its state drifts from the
reference's by a few percent where a float32 state fed bfloat16 activations
stays within the activations' own rounding.

So after ``serving_check.program_logits`` has prefilled the sampled prompts
(the chunked form, the state written into slots 0, 1, ...) and teacher-forced
the decode steps through slots and pool, this reads the sampled rows' slots out
of every state-space layer's ``state_pool`` and compares each head's state with
the reference's after the same tokens (``references/<family>.forward(...,
states=True)``: the token-by-token scan's carry). Every (row, layer, head) has
its own ``||S_program - S_reference|| / ||S_reference||``; a number compared is
the root mean square of those over the ``SLOW_SHARE`` of a layer's heads that
forget most slowly (ranked by the reference's own weights: the decay a token
at the head's resting delta, ``softplus(dt_bias) x exp(A_log)``). Each head
counts alike whatever its state's size, so the heads with a large delta, whose
state is large and a dozen tokens old, do not drown the ones the rounding shows
in. Two such numbers are held:

``state_rel_err``, over every state-space layer. A layer's input carries the
rounding of all the layers before it, which moves every head's state alike
(the ninth layer's heads stand 2-4% from the reference's in a sound run), so
this one tells a state rounded at every token of the prompt and no less.

``state_first_rel_err``, over the first state-space layer alone, whose input
is the embedding itself: a sound run's slow heads stand 0.3% off there, ten
times nearer than a rounded state's, and 128 roundings of a decode step alone
(a pool kept in bfloat16: about 1.8% on a head that keeps them all) show too.

The relative error of all states as one vector is logged beside them and not
held. Limits: the configuration file's ``check_limits``; PERF.md section 2
gives the readings each stands between.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, List, Tuple

import numpy as np

from harness import opcount, serving_check

Sample = serving_check.Sample
SLOW_SHARE = 1 / 16  # of a layer's heads, the slowest to forget


def slot_states(pools: Any, rows: int) -> np.ndarray:
    """(rows, layers, H, P, N): slots 0..rows-1 of every state-space layer's
    pool, in layer order."""
    kept = [np.asarray(lp["state_pool"][:rows], np.float32) for lp in pools["layers"] if "state_pool" in lp]
    return np.stack(kept, axis=1)


def reference(arch: Dict[str, Any], seed: int, sample: Sample, seqs: List[np.ndarray],
              quant: Any = None, control: str = "") -> Tuple[List[np.ndarray], np.ndarray, np.ndarray]:
    """The reference over each whole sequence: rows p-1 .. p+k-1 of its logits,
    every state-space layer's state after the last token (rows, layers, H, P, N),
    and each head's decay a token at its resting delta (layers, H)."""
    import jax
    import jax.numpy as jnp

    from harness import weights

    ref = importlib.import_module(f"references.{arch['family']}")
    key, dtype = weights.seed_key(seed), jnp.dtype(arch["serving_dtype"])
    make_layer = jax.jit(lambda k, l: weights.layer(arch, k, l, dtype))
    gw = jax.jit(lambda k: weights.globals_(arch, k, dtype))(key)
    logits, states = [], []
    for (p, k), toks in zip(sample, seqs):
        full, kept = ref.forward(jnp.asarray(toks[: p + k]), lambda l: make_layer(key, l), gw, arch,
                                 quant=quant, control=control, states=True)
        logits.append(np.asarray(full[p - 1 : p + k], np.float32))
        states.append(np.stack([np.asarray(s, np.float32) for s in kept]))
    rate = []
    for l in range(opcount.dims(arch)["layers"]):
        w = make_layer(key, l)
        rate.append(np.asarray(jax.nn.softplus(w["dt_bias"].astype(jnp.float32))
                               * jnp.exp(w["A_log"].astype(jnp.float32))))
    return logits, np.stack(states), np.stack(rate)


def head_errors(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """(rows, layers, H): each head's ``||S_program - S_reference|| / ||S_reference||``."""
    diff = np.sum((prog.astype(np.float64) - ref) ** 2, axis=(-2, -1))
    return np.sqrt(diff / np.sum(ref.astype(np.float64) ** 2, axis=(-2, -1)))


def state_rel_err(errors: np.ndarray, rate: np.ndarray, layers: slice = slice(None)) -> float:
    """The root mean square of ``errors`` (rows, layers, H) over the ``SLOW_SHARE``
    of heads with the smallest ``rate`` (layers, H) in each of ``layers``."""
    n = max(1, int(round(SLOW_SHARE * rate.shape[-1])))
    slow = np.argsort(rate, axis=-1)[:, :n]  # (layers, n)
    picked = np.take_along_axis(errors, slow[None], axis=-1)[:, layers]
    return float(np.sqrt(np.mean(picked ** 2)))


def whole_rel_err(prog: np.ndarray, ref: np.ndarray) -> float:
    return float(np.sqrt(np.sum((prog.astype(np.float64) - ref) ** 2) / np.sum(ref.astype(np.float64) ** 2)))


def compare(ctx: Any, eng: Any, params: Any, cfg: Any) -> Dict[str, Tuple[float, float]]:
    """{name: (value, limit)} of the logits' and the state slots' comparison on
    an engine whose rows have been released; frees the engine's pool before the
    reference runs."""
    sample = [tuple(s) for s in ctx.traffic["check_sample"]]
    seqs = serving_check.sample_tokens(ctx.seed, opcount.dims(ctx.arch)["vocab"], sample)
    prog, eng.pools = serving_check.program_logits(
        params, cfg, eng.pools, eng.alloc, eng.max_batch, eng.max_blocks, eng.block_size, sample, seqs,
    )
    held = slot_states(eng.pools, len(sample))
    del eng.pools
    want, states, rate = reference(ctx.arch, ctx.seed, sample, seqs)
    errors = head_errors(held, states)
    ctx.log(f"state slots: {errors.size} heads' states compared over {errors.shape[1]} layers, all as one vector "
            f"{whole_rel_err(held, states):.6g}; a head's own error median {np.median(errors):.6g} "
            f"largest {errors.max():.6g}")
    limits = ctx.arch["check_limits"]
    return {
        "logits_rel_err": (serving_check.rel_err(prog, want), limits["logits_rel_err"]),
        "state_rel_err": (state_rel_err(errors, rate), limits["state_rel_err"]),
        "state_first_rel_err": (state_rel_err(errors, rate, slice(0, 1)), limits["state_first_rel_err"]),
    }
