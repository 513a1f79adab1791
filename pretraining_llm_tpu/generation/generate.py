"""KV-cached autoregressive generation, fully jitted.

The reference's `generate` re-forwards the entire window for every new token —
O(n * T^2) with no cache (`/root/reference/src/models/transformer.py:96-114`,
SURVEY §3.2). TPU-native redesign:

  - prefill once over the prompt (one big MXU-friendly forward),
  - then a `lax.scan` of single-token decode steps against a per-layer KV
    cache (`transformer.make_kv_cache`) — O(n * T) total, one compiled
    program for the whole generation (no per-token Python dispatch),
  - sampling semantics match the reference by default (temperature-1
    categorical) with temperature/top-k/top-p extensions.

`generate_text` mirrors the reference CLI entry
(`/root/reference/scripts/generate_text.py:7-46`): load checkpoint, rebuild
model from its stored config, encode with GPT-2 BPE, generate, decode.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pretraining_llm_tpu.config import Config, ModelConfig
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.generation.sampling import sample_logits


def cast_params_for_inference(params: Any, cfg: ModelConfig) -> Any:
    """One-time fp32 -> compute-dtype cast of the matmul weights.

    Explicit serving-prep step (like `shard_params_for_inference`): call it
    once after checkpoint load and drop the fp32 tree. The forward casts
    every matmul weight to `compute_dtype` at its use site; fp32 params
    flowing into the decode scan therefore read 2x the bytes per step
    (fp32 source) unless XLA's loop-invariant code motion happens to hoist
    the converts — which it must trade against the extra live copy, so it
    is not guaranteed. Pre-casting makes the per-step weight traffic the
    bf16 minimum and (once the caller drops the fp32 tree) halves param
    HBM, with BIT-IDENTICAL results: the same cast happens at every use
    site anyway. Leaves the forward deliberately consumes in fp32 are NOT
    cast — norm scales/biases (fp32 norm math, layers.layernorm/rmsnorm),
    the lm_head bias (added to fp32 logits, transformer.py:585), and the
    MoE router (fp32 routing scores, moe.py) — casting those would change
    numerics.
    """
    cdt = jnp.dtype(cfg.compute_dtype)

    def cast(path, x):
        if not jnp.issubdtype(x.dtype, jnp.floating) or x.dtype == cdt:
            return x
        names = [str(getattr(k, "key", "")) for k in path]
        if any(n.startswith("ln") or "norm" in n for n in names):
            return x
        if names[-1] == "router":
            return x
        if len(names) >= 2 and names[-2] == "lm_head" and names[-1] == "bias":
            return x
        return x.astype(cdt)

    return jax.tree_util.tree_map_with_path(cast, params)


def _bucket_len(prompt_len: int, ctx: int, max_new_tokens: int) -> int:
    """Pad target for the prompt: next power of two (>=16), capped so the
    padded prompt + generation still fits the context. Prompt LENGTH is a
    traced value — only the bucket is a compile key, so all prompts in a
    bucket share one executable instead of one compile per length."""
    b = 16
    while b < prompt_len:
        b *= 2
    return max(prompt_len, min(b, ctx - max_new_tokens))


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "temperature", "top_k", "top_p",
                     "min_p", "mesh"),
)
def _generate_jit(
    params: Any,
    prompt: jax.Array,  # (B, P_bucket) zero-padded prompt
    prompt_len: jax.Array,  # () int32 — true length, traced
    key: jax.Array,
    cfg: ModelConfig,
    max_new_tokens: int,
    temperature: float,
    top_k: Optional[int],
    top_p: Optional[float],
    min_p: Optional[float] = None,
    mesh: Any = None,
    prompt_lengths: Optional[jax.Array] = None,  # (B,) int32 — ragged rows
    stop_token: Optional[jax.Array] = None,  # () int32 — traced, no recompile per id
) -> jax.Array:
    from pretraining_llm_tpu.parallel.sharding import activation_mesh

    b = prompt.shape[0]
    bucket = prompt.shape[1]
    total = bucket + max_new_tokens
    with activation_mesh(mesh):
        cache = transformer.make_kv_cache(cfg, b, total)

        key, sub = jax.random.split(key)
        if prompt_lengths is None:
            pad_off = None
            # Prefill: one forward over the whole padded prompt. Causality
            # keeps pad positions (>= prompt_len) invisible to real ones,
            # and each pad slot's garbage K/V is overwritten by the decoded
            # token that lands there before the kv_mask ever exposes it.
            # (A recurrent layer's state has no slot to overwrite: it takes
            # the true length and leaves the padding out, models/recurrent.py.)
            logits, cache = transformer.forward(
                params, prompt, cfg, kv_cache=cache, cache_index=jnp.int32(0),
                lengths=jnp.broadcast_to(prompt_len.astype(jnp.int32), (b,))
                if cfg.hybrid else None,
            )
            idx = jnp.broadcast_to(
                (prompt_len - 1).astype(jnp.int32), (b, 1, logits.shape[-1])
            )
            last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
            start_index = prompt_len.astype(jnp.int32)
        else:
            # RAGGED rows. Prefill runs RIGHT-padded — plain causal
            # attention, so real tokens never see the trailing pads, RoPE/
            # learned positions are already logical, and the FLASH prefill
            # shortcut applies (no (Tq, Tmax) scores at long prompts). The
            # written cache is then rolled right per row so every prompt
            # ends at slot bucket-1: the batch decodes in lockstep at
            # shared slot indices, with per-row pad_offsets driving logical
            # positions + the kv mask. Slots [0, offset_i) hold garbage
            # copies that the decode kv mask never exposes.
            pad_off = (bucket - prompt_lengths).astype(jnp.int32)
            logits, cache = transformer.forward(
                params, prompt, cfg, kv_cache=cache, cache_index=jnp.int32(0),
                lengths=prompt_lengths.astype(jnp.int32) if cfg.hybrid else None,
            )
            idx = jnp.broadcast_to(
                (prompt_lengths - 1).astype(jnp.int32)[:, None, None],
                (b, 1, logits.shape[-1]),
            )
            last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
            src = jnp.clip(
                jnp.arange(total)[None, :] - pad_off[:, None], 0, total - 1
            )  # (B, total)
            # per-layer leaves are (B, T, ...); a KDA layer keeps a state as
            # of its row's last real token and no slots, so nothing to roll
            cache = {"layers": tuple(
                lyr if "state" in lyr else jax.tree.map(
                    lambda c: jnp.take_along_axis(
                        c, src.reshape(src.shape + (1,) * (c.ndim - 2)), axis=1),
                    lyr,
                )
                for lyr in cache["layers"]
            )}
            start_index = jnp.int32(bucket)
        next_tok = sample_logits(
            last, sub, temperature=temperature, top_k=top_k, top_p=top_p,
            min_p=min_p,
        )

        def decode_step(carry, _):
            cache, tok, key, index = carry
            logits, cache = transformer.forward(
                params, tok[:, None], cfg, kv_cache=cache, cache_index=index,
                pad_offsets=pad_off,
            )
            key, sub = jax.random.split(key)
            nxt = sample_logits(
                logits[:, 0], sub, temperature=temperature, top_k=top_k,
                top_p=top_p, min_p=min_p,
            )
            if stop_token is not None:
                # A finished row keeps emitting its stop token: the scan
                # stays fixed-length (XLA-friendly), the caller truncates.
                done = tok == stop_token
                nxt = jnp.where(done, stop_token.astype(jnp.int32), nxt)
            return (cache, nxt, key, index + 1), tok

        (_, _, _, _), toks = jax.lax.scan(
            decode_step,
            (cache, next_tok, key, start_index),
            None,
            length=max_new_tokens,
        )
    # Each step emits its carry-in token, so toks == the max_new_tokens
    # sampled ids in order (the final carry token is the unused n+1-th).
    return toks.T


def generate(
    params: Any,
    cfg: ModelConfig,
    prompt_tokens: jax.Array,
    max_new_tokens: int,
    key: jax.Array,
    *,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    mesh: Any = None,
    prompt_lengths: Optional[Any] = None,
    stop_token: Optional[int] = None,
) -> jax.Array:
    """Generate continuations. prompt_tokens: (B, P) or (P,) int32.

    ``stop_token``: once a row samples it, the row keeps emitting it for
    the remaining steps (fixed-length device program; strip the trailing
    stop tokens host-side). The reference has no stop handling at all
    (generate loops a fixed count, transformer.py:96-114).

    ``prompt_lengths`` ((B,) int32) enables RAGGED batches: rows of
    different true lengths, right-padded to P on input. Internally each row
    is left-shifted so every prompt ends at the same slot and the whole
    batch decodes in lockstep — one compiled program, no per-row loops;
    row i's continuation starts right after its own last prompt token
    (serving-grade batched decode; the reference generates batch-1 only,
    generate_text.py:41-42). Not supported for MoE models (pad slots would
    compete for expert capacity during prefill).

    Returns (B, max_new_tokens) of sampled ids. The whole prompt+generation
    must fit the model context (the KV cache is position-table bound).

    Prompts are zero-padded to a power-of-two bucket, so XLA compiles once
    per (bucket, max_new_tokens, batch) — not once per prompt length.

    ``mesh``: optional jax.sharding.Mesh for sharded decode of models too big
    for one chip — pass params already placed with
    `shard_params_for_inference`; activations follow the param shardings.
    """
    if cfg.doc_mask_token >= 0:
        # Packed-document masking is a TRAINING-time attention structure; a
        # decode session is a single document, so the mask is vacuous — and
        # forward() rejects the combination with a KV cache. A checkpoint
        # trained with packing must still decode (the e2e contract), so
        # drop it here.
        import dataclasses as _dc

        cfg = _dc.replace(cfg, doc_mask_token=-1)
    prompt = jnp.atleast_2d(jnp.asarray(prompt_tokens, jnp.int32))
    prompt_len = int(prompt.shape[1])
    if prompt_len + max_new_tokens > cfg.context_length:
        raise ValueError(
            f"prompt({prompt_len}) + max_new_tokens({max_new_tokens}) exceeds "
            f"context_length={cfg.context_length}"
        )
    if prompt_lengths is not None:
        if cfg.moe_capacity:
            raise ValueError(
                "ragged prompt_lengths is unsupported for capacity-routed MoE "
                "models: left-pad slots would compete for expert capacity "
                "during prefill (dropless routing has none)"
            )
        lengths = jnp.asarray(prompt_lengths, jnp.int32).reshape(-1)
        if lengths.shape[0] != prompt.shape[0]:
            raise ValueError(
                f"prompt_lengths has {lengths.shape[0]} rows for a batch of "
                f"{prompt.shape[0]}"
            )
        if int(jnp.max(lengths)) > prompt_len or int(jnp.min(lengths)) < 1:
            raise ValueError(
                "prompt_lengths must lie in [1, P] for (B, P) prompt_tokens"
            )
    else:
        lengths = None
    # MoE prefill routes with a capacity proportional to the token count and
    # pad tokens would compete for expert slots, perturbing real tokens'
    # hidden states — bucketing is for dense models only.
    bucket = (
        prompt_len
        if cfg.moe_capacity
        else _bucket_len(prompt_len, cfg.context_length, max_new_tokens)
    )
    # Ragged rows occupy slots up to bucket+max_new (dead left-pads
    # included): always within the context, since the earlier prompt_len
    # check plus _bucket_len's cap give bucket <= ctx - max_new_tokens.
    assert bucket + max_new_tokens <= cfg.context_length
    if bucket > prompt_len:
        prompt = jnp.pad(prompt, ((0, 0), (0, bucket - prompt_len)))
    stop = jnp.int32(stop_token) if stop_token is not None else None
    return _generate_jit(
        params, prompt, jnp.int32(prompt_len), key, cfg, max_new_tokens,
        temperature, top_k, top_p, min_p, mesh, lengths, stop,
    )


def shard_params_for_inference(params: Any, mesh: Any) -> Any:
    """Place params on a mesh with the training partition rules (TP/FSDP) so
    `generate(..., mesh=mesh)` decodes models that exceed one chip's HBM."""
    from pretraining_llm_tpu.parallel.sharding import named_sharding_tree, param_pspec_tree

    tensor_size = mesh.shape.get("tensor", 1)
    return jax.device_put(
        params,
        named_sharding_tree(mesh, param_pspec_tree(params, tensor_size=tensor_size)),
    )


# ---------------------------------------------------------------------------
# Checkpoint-driven text generation (CLI surface)
# ---------------------------------------------------------------------------


def _checkpoint_step_dir(model_path: str) -> str:
    """A step-N dir as given, else the newest one under `model_path`."""
    from pretraining_llm_tpu.training import checkpoint as ckpt

    if model_path.rstrip("/").split("/")[-1].startswith("step-"):
        return model_path
    latest = ckpt.latest_checkpoint(model_path)
    if latest is None:
        raise FileNotFoundError(f"no checkpoints under {model_path}")
    return latest


def load_config_for_inference(model_path: str) -> Config:
    """The Config a checkpoint was trained with — metadata only: no params
    are read and no device backend is touched (a fleet parent whose workers
    own the chips needs the config and tokenizer, not the weights)."""
    with open(f"{_checkpoint_step_dir(model_path)}/metadata.json") as f:
        meta = json.load(f)
    return Config.from_json(json.dumps(meta["extra"]["config"]))


def load_model_for_inference(
    model_path: str, *, use_ema: bool = False
) -> Tuple[Any, Config]:
    """Load params + config from a framework checkpoint directory.

    ``use_ema=True`` loads the exponential-moving-average shadow instead of
    the raw params (requires the run to have trained with
    `train.ema_decay > 0`; fails loudly otherwise)."""
    from pretraining_llm_tpu.training import checkpoint as ckpt

    path = _checkpoint_step_dir(model_path)
    cfg = load_config_for_inference(path)
    key = "ema" if use_ema else "params"
    # Shape-only template: no throwaway init of the full model.
    template = jax.eval_shape(
        lambda: {key: transformer.init_params(cfg.model, jax.random.key(0))}
    )
    try:
        restored, _ = ckpt.load_checkpoint(path, template)
    except ValueError as e:
        if use_ema and "missing leaves" in str(e):
            raise ValueError(
                f"checkpoint {path} has no EMA shadow (the run trained "
                "with train.ema_decay=0); drop --ema or retrain with "
                "ema_decay > 0"
            ) from e
        raise
    # NOTE: returns the RAW checkpoint dtypes — callers that only run the
    # forward should apply cast_params_for_inference (the generation CLIs
    # below do); callers that re-export weights (export_torch_checkpoint)
    # need the fp32 masters untouched.
    return jax.device_put(restored[key]), cfg


def generate_text(
    model_path: str,
    input_text: str,
    max_new_tokens: int = 100,
    *,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    seed: int = 0,
    tokenizer: Optional[str] = None,
    stop_token: Optional[int] = None,
    ema: bool = False,
) -> str:
    """Mirror of the reference's `generate_text(model_path, input_text,
    max_new_tokens)` (generate_text.py:7): checkpoint -> text continuation.

    `tokenizer` overrides the name stored in the checkpoint's config (e.g. a
    checkpoint trained elsewhere whose BPE files aren't available here)."""
    return generate_text_batch(
        model_path,
        [input_text],
        max_new_tokens,
        temperature=temperature,
        top_k=top_k,
        top_p=top_p,
        min_p=min_p,
        seed=seed,
        tokenizer=tokenizer,
        stop_token=stop_token,
        ema=ema,
    )[0]


def generate_text_batch(
    model_path: str,
    input_texts: list,
    max_new_tokens: int = 100,
    *,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
    seed: int = 0,
    tokenizer: Optional[str] = None,
    stop_token: Optional[int] = None,
    ema: bool = False,
) -> list:
    """Batched continuation of DIFFERENT-length prompts in one compiled
    ragged decode (`generate(..., prompt_lengths=...)`) — one device
    program for the whole batch instead of a per-prompt loop. Returns one
    continuation string per input; a row's output TRUNCATES at (excludes)
    its first ``stop_token``."""
    from pretraining_llm_tpu.data.tokenizer import get_tokenizer

    if not input_texts:
        raise ValueError("input_texts is empty (nothing to generate)")
    params, cfg = load_model_for_inference(model_path, use_ema=ema)
    # Serving prep: bf16 matmul weights (bit-identical forward — see
    # cast_params_for_inference); the fp32 tree is dropped here, halving
    # param HBM and the per-step weight reads for the generation CLIs.
    params = cast_params_for_inference(params, cfg.model)
    enc = get_tokenizer(tokenizer or cfg.data.tokenizer_name)
    encoded = [
        np.asarray(enc.encode_ordinary(t), np.int32) for t in input_texts
    ]
    empty = [i for i, e in enumerate(encoded) if len(e) == 0]
    if empty:
        raise ValueError(
            f"prompts at indices {empty} encode to zero tokens; ragged "
            "decode needs at least one real token per row"
        )
    lengths = np.asarray([len(e) for e in encoded], np.int32)
    pmax = int(lengths.max())
    batch = np.zeros((len(encoded), pmax), np.int32)
    for i, e in enumerate(encoded):
        batch[i, : len(e)] = e
    # MoE models reject ragged rows (pad slots would compete for expert
    # capacity); a uniform-length batch — incl. every single-prompt call —
    # needs no ragged machinery, which keeps generate_text working for MoE.
    uniform = bool((lengths == lengths[0]).all())
    if cfg.model.moe_capacity and not uniform:
        raise ValueError(
            "capacity-routed MoE models require equal-length prompts per "
            "batch (ragged left-pad slots would compete for expert capacity); "
            "generate each prompt separately or group by length"
        )
    use_lengths = None if uniform else lengths
    out = np.asarray(
        generate(
            params,
            cfg.model,
            batch,
            max_new_tokens,
            jax.random.key(seed),
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            min_p=min_p,
            prompt_lengths=use_lengths,
            stop_token=stop_token,
        )
    )

    def ids(row: np.ndarray) -> list:
        toks = row.tolist()
        if stop_token is not None and stop_token in toks:
            toks = toks[: toks.index(stop_token)]
        return toks

    return [
        t + enc.decode(ids(out[i])) for i, t in enumerate(input_texts)
    ]


def generate_text_speculative(
    model_path: str,
    draft_model_path: str,
    input_text: str,
    max_new_tokens: int = 100,
    *,
    k: int = 4,
    temperature: float = 0.0,
    seed: int = 0,
    tokenizer: Optional[str] = None,
) -> str:
    """Speculative continuation: a small draft checkpoint proposes k tokens
    per round, the target verifies them in one forward (see
    generation.speculative; greedy output is identical to target-only
    decoding). Both checkpoints must share a vocabulary."""
    import sys as _sys

    from pretraining_llm_tpu.data.tokenizer import get_tokenizer
    from pretraining_llm_tpu.generation.speculative import generate_speculative

    params_t, cfg_t = load_model_for_inference(model_path)
    params_d, cfg_d = load_model_for_inference(draft_model_path)
    params_t = cast_params_for_inference(params_t, cfg_t.model)
    params_d = cast_params_for_inference(params_d, cfg_d.model)
    enc = get_tokenizer(tokenizer or cfg_t.data.tokenizer_name)
    prompt = np.asarray(enc.encode_ordinary(input_text), np.int32)
    if len(prompt) == 0:
        raise ValueError("prompt encodes to zero tokens")
    out, stats = generate_speculative(
        params_t, cfg_t.model, params_d, cfg_d.model, prompt[None],
        max_new_tokens, jax.random.key(seed), k=k, temperature=temperature,
    )
    rate = stats["accepted"] / max(stats["proposed"], 1)
    print(
        f"[speculative] rounds={stats['rounds']} "
        f"acceptance={stats['accepted']}/{stats['proposed']} ({rate:.0%})",
        file=_sys.stderr,
    )
    return input_text + enc.decode(np.asarray(out).tolist())
