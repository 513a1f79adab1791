"""Speculative decoding: a small draft model proposes k tokens, the target
verifies them in ONE forward pass.

Serving-latency feature beyond the reference (whose generation is a
cache-less batch-1 loop, generate_text.py:41-42; this framework's standard
path is `generation.generate`). Decode is memory-bound — each target step
streams the full weights for one token — so letting a cheap draft model
propose k tokens and the target verify all of them in a single (k+1)-token
forward multiplies tokens-per-weight-stream by the acceptance rate.

Correctness contract (tested):
  - GREEDY (temperature=0) speculative output equals target-only greedy
    decoding for ANY draft model — acceptance compares the target argmax
    against the proposal, and the correction token is the target argmax
    itself. Bit-identical at fp32 (pinned by test); under bf16 compute a
    NEAR-TIE argmax can differ, because the (k+1)-token verify forward and
    the 1-token decode forward reduce in different orders.
  - Sampling uses the standard accept/reject rule (Leviathan et al. 2023;
    Chen et al. 2023): accept d_i with prob min(1, p(d_i)/q(d_i)); on the
    first rejection resample from norm(max(p - q, 0)); if all k accepted,
    sample the bonus token from the target's (k+1)-th distribution. The
    output distribution equals target-only sampling.

This module is the contiguous, batch-1 path with a SEPARATE draft model, and
the device-side chaining of rounds (``spec_next_inputs``) the serving engine
uses. Over the paged pools the rounds live in ``generation/paged.py``:
``paged_spec_round`` (a separate draft model with a pool of its own; per-head
K/V pools only) and ``paged_mtp_round`` (the model's own multi-token-
prediction module as the draft, ``models/mtp.py``: verify then draft, the
module's cache one more layer of the same pool, latent pools included). Both
decide acceptance by one rule, ``paged._accept_reject``. Not built: a separate
draft over a latent pool, a rollback of state slots (linear-attention
layers), several modules chained, top-k/top-p inside a round (ROADMAP R10).

Design (one jitted program, batch 1 — the latency-bound serving shape):
  - Both models keep KV caches over the SAME slot layout: after a round,
    slots [0, P+k] are written in both; the accepted frontier advances by
    n_acc + 1 and the garbage above it is masked by causality, then
    overwritten by the next round's writes (the cached-decode forward
    masks kv positions >= cache_index + Tq).
  - The draft phase runs k sampling steps plus one WRITE-ONLY step for the
    k-th proposal, so the draft cache always covers the same slots as the
    target cache regardless of how many proposals are accepted.
  - A `lax.while_loop` round emits between 1 and k+1 tokens into a fixed
    (max_new + k + 1) buffer; the loop stops once max_new tokens exist.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from pretraining_llm_tpu.config import ModelConfig
from pretraining_llm_tpu.models import transformer


def _sanitize(cfg: ModelConfig) -> ModelConfig:
    """Decode-time config hygiene (mirrors generate()): doc masking is a
    training-time structure; ring/ulysses fall back inside dispatch."""
    if cfg.doc_mask_token >= 0:
        cfg = dataclasses.replace(cfg, doc_mask_token=-1)
    return cfg


def _probs(logits: jax.Array, temperature: float) -> jax.Array:
    """(V,) float32 target/draft distribution at the round's temperature.
    temperature=0 -> one-hot argmax (greedy acceptance/correction)."""
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        return jax.nn.one_hot(jnp.argmax(logits), logits.shape[-1])
    return jax.nn.softmax(logits / temperature)


def _sample_from(probs: jax.Array, key: jax.Array, temperature: float) -> jax.Array:
    """ONE sampling rule for every site (seed, draft steps, correction):
    greedy argmax at temperature 0, categorical over the dist otherwise."""
    if temperature == 0.0:
        return jnp.argmax(probs).astype(jnp.int32)
    return jax.random.categorical(key, jnp.log(probs + 1e-30)).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("cfg_t", "cfg_d", "total", "max_new_tokens", "k",
                     "temperature"),
)
def _spec_jit(params_t, params_d, prompt, key, *, cfg_t, cfg_d, total,
              max_new_tokens, k, temperature):
    """Module-level jit so repeated calls with the same static config
    hit the compile cache (a per-call closure would recompile every
    invocation — the repo-wide _generate_jit pattern)."""
    v = cfg_t.vocab_size
    p_len = prompt.shape[1]
    t_cache = transformer.make_kv_cache(cfg_t, 1, total)
    d_cache = transformer.make_kv_cache(cfg_d, 1, total)

    # Prefill both models; the target's last position seeds token 0.
    t_logits, t_cache = transformer.forward(
        params_t, prompt, cfg_t, kv_cache=t_cache, cache_index=jnp.int32(0)
    )
    _, d_cache = transformer.forward(
        params_d, prompt, cfg_d, kv_cache=d_cache, cache_index=jnp.int32(0)
    )
    key, sub = jax.random.split(key)
    t0 = _sample_from(_probs(t_logits[0, -1], temperature), sub, temperature)

    out = jnp.zeros((max_new_tokens + k + 1,), jnp.int32)
    out = out.at[0].set(t0)

    def round_body(carry):
        t_cache, d_cache, out, count, last, idx, key, stats = carry
        # idx = slot of `last` (the newest accepted token, not yet in
        # either cache); this round writes slots [idx, idx + k].

        # --- draft: k sampling steps + 1 write-only step -------------
        def draft_step(c, _):
            d_cache, tok, key, j = c
            logits, d_cache = transformer.forward(
                params_d, tok[None, None], cfg_d, kv_cache=d_cache,
                cache_index=idx + j,
            )
            q = _probs(logits[0, 0], temperature)
            key, sub = jax.random.split(key)
            nxt = _sample_from(q, sub, temperature)
            return (d_cache, nxt, key, j + 1), (nxt, q)

        (d_cache, d_last, key, _), (drafts, q_dists) = jax.lax.scan(
            draft_step, (d_cache, last, key, jnp.int32(0)), None, length=k
        )
        # Write-only: park d_k's K/V so the draft cache covers slot
        # idx + k like the target's will (logits unused).
        _, d_cache = transformer.forward(
            params_d, d_last[None, None], cfg_d, kv_cache=d_cache,
            cache_index=idx + k,
        )

        # --- target: verify all k proposals in ONE forward -----------
        seq = jnp.concatenate([last[None], drafts])  # (k+1,)
        t_logits, t_cache = transformer.forward(
            params_t, seq[None], cfg_t, kv_cache=t_cache, cache_index=idx
        )
        p_dists = jax.vmap(lambda l: _probs(l, temperature))(
            t_logits[0]
        )  # (k+1, V): p_dists[i] is the target dist AFTER seq[i]

        # --- accept / reject -----------------------------------------
        key, sub_u, sub_r = jax.random.split(key, 3)
        p_at = p_dists[jnp.arange(k), drafts]  # p_i(d_i)
        q_at = q_dists[jnp.arange(k), drafts]  # q_i(d_i)
        if temperature == 0.0:
            accepts = p_at > 0.0  # one-hot: accepted iff argmax == d_i
        else:
            u = jax.random.uniform(sub_u, (k,))
            accepts = u < jnp.minimum(1.0, p_at / jnp.maximum(q_at, 1e-30))
        n_acc = jnp.sum(jnp.cumprod(accepts.astype(jnp.int32))).astype(jnp.int32)

        # Final token of the round: the target's correction at the
        # first rejected position, or the bonus after k acceptances.
        # (greedy: both reduce to the target argmax at position n_acc.)
        p_final = p_dists[n_acc]
        if temperature == 0.0:
            final = _sample_from(p_final, sub_r, temperature)
        else:
            q_pad = jnp.concatenate(
                [q_dists, jnp.zeros((1, v), jnp.float32)]
            )  # bonus position: residual vs q=0 == p itself
            resid = jnp.maximum(p_final - q_pad[n_acc], 0.0)
            resid = resid / jnp.maximum(jnp.sum(resid), 1e-30)
            final = _sample_from(resid, sub_r, temperature)

        emit = jnp.concatenate([drafts, jnp.zeros((1,), jnp.int32)])
        emit = emit.at[n_acc].set(final)  # (k+1,); valid prefix n_acc+1
        out = jax.lax.dynamic_update_slice(out, emit, (count,))
        n_emit = n_acc + 1
        stats = {
            "rounds": stats["rounds"] + 1,
            "proposed": stats["proposed"] + k,
            "accepted": stats["accepted"] + n_acc,
        }
        return (
            t_cache, d_cache, out, count + n_emit, emit[n_acc],
            idx + n_emit, key, stats,
        )

    def round_cond(carry):
        return carry[3] < max_new_tokens

    stats0 = {
        "rounds": jnp.int32(0), "proposed": jnp.int32(0),
        "accepted": jnp.int32(0),
    }
    (_, _, out, count, _, _, _, stats) = jax.lax.while_loop(
        round_cond,
        round_body,
        (t_cache, d_cache, out, jnp.int32(1), t0, jnp.int32(p_len), key,
         stats0),
    )
    return out[:max_new_tokens], stats


@jax.jit
def spec_next_inputs(
    emit: jax.Array,      # (B, k+1) int32 round emissions
    n_emit: jax.Array,    # (B,) int32 tokens emitted per row (>= 1)
    seq_lens: jax.Array,  # (B,) int32 frontier the round was dispatched at
) -> Tuple[jax.Array, jax.Array]:
    """Next round's (seed token, frontier) chained on-device from a
    ``paged_spec_round`` result, without a host sync. The last emitted
    token of row b is ``emit[b, n_emit[b]-1]`` — by construction the
    round's ``final`` token, i.e. exactly the token the synchronous
    scheduler would feed back after consuming the round on the host. This
    is what lets speculative rounds join the serving engine's in-flight
    window queue: the device chains round k+1 off round k while the host
    is still reaping round k-1."""
    b = emit.shape[0]
    nxt = emit[jnp.arange(b), jnp.maximum(n_emit, 1) - 1]
    return nxt, seq_lens + n_emit


def generate_speculative(
    params_target: Any,
    cfg_target: ModelConfig,
    params_draft: Any,
    cfg_draft: ModelConfig,
    prompt_tokens: jax.Array,  # (P,) or (1, P) int32
    max_new_tokens: int,
    key: jax.Array,
    *,
    k: int = 4,
    temperature: float = 0.0,
) -> Tuple[jax.Array, dict]:
    """Returns ((max_new_tokens,) sampled ids, stats dict).

    stats: {"rounds": int, "proposed": int, "accepted": int} — acceptance
    telemetry for tuning k (accepted/proposed is the draft's hit rate).
    """
    cfg_t = _sanitize(cfg_target)
    cfg_d = _sanitize(cfg_draft)
    if cfg_t.vocab_size != cfg_d.vocab_size:
        raise ValueError(
            f"draft vocab ({cfg_d.vocab_size}) must equal target vocab "
            f"({cfg_t.vocab_size})"
        )
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    prompt = jnp.atleast_2d(jnp.asarray(prompt_tokens, jnp.int32))
    if prompt.shape[0] != 1:
        raise ValueError(
            "speculative decoding is the batch-1 latency path; use "
            "generation.generate for batched throughput decoding"
        )
    p_len = int(prompt.shape[1])
    total = p_len + max_new_tokens + k + 1  # slack: a round may overshoot
    for cfg, name in ((cfg_t, "target"), (cfg_d, "draft")):
        if total > cfg.context_length:
            raise ValueError(
                f"prompt({p_len}) + max_new({max_new_tokens}) + k({k}) "
                f"exceeds the {name} context ({cfg.context_length})"
            )

    out, stats = _spec_jit(
        params_target, params_draft, prompt, key, cfg_t=cfg_t, cfg_d=cfg_d,
        total=total, max_new_tokens=max_new_tokens, k=k,
        temperature=temperature,
    )
    return out, {name: int(val) for name, val in stats.items()}
