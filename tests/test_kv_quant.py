"""int8 KV cache: quantization math, memory halving, decode fidelity.

Serving feature beyond the reference (whose generate has no cache at all,
transformer.py:96-114): the persistent decode cache — the HBM term that
scales with L*B*T — stores int8 values + per-(token, head) fp32 amax
scales instead of bf16/fp32 elements.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.config import ModelConfig, get_preset
from pretraining_llm_tpu.generation.generate import generate
from pretraining_llm_tpu.models import transformer

CFG = dataclasses.replace(
    get_preset("tiny").model, compute_dtype="float32", kv_cache_dtype="int8"
)


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.key(0), (2, 16, 4, 8), jnp.float32) * 3.0
    q, scale = transformer._kv_quantize(x)
    assert q.dtype == jnp.int8
    back = transformer._kv_dequantize(q, scale, jnp.float32)
    # Symmetric int8: error <= half a quantization step = amax/254 per row.
    bound = np.broadcast_to(np.asarray(scale) / 254.0 + 1e-7, x.shape)
    np.testing.assert_array_less(np.abs(np.asarray(back - x)), bound)


def test_int8_cache_structure_and_memory():
    # Structure assertions target the STACKED container explicitly (the
    # default is the per-layer tuple, same fields/leaves).
    cache = transformer.make_kv_cache(CFG, 2, 32, stacked=True)
    assert set(cache) == {"k", "v", "k_scale", "v_scale"}
    un = transformer.make_kv_cache(CFG, 2, 32)
    assert set(un) == {"layers"} and len(un["layers"]) == CFG.n_layers
    assert set(un["layers"][0]) == {"k", "v", "k_scale", "v_scale"}
    assert cache["k"].dtype == jnp.int8
    assert cache["k_scale"].shape == cache["k"].shape[:-1] + (1,)
    # vs bf16 cache: ~1.9x smaller at Dh=64 (1 + 4/Dh bytes vs 2 per elem).
    dense = transformer.make_kv_cache(
        dataclasses.replace(CFG, kv_cache_dtype="compute", compute_dtype="bfloat16"),
        2, 32,
    )
    int8_bytes = sum(a.nbytes for a in jax.tree.leaves(cache))
    bf16_bytes = sum(a.nbytes for a in jax.tree.leaves(dense))
    dh = CFG.head_dim
    expected = (1 + 4 / dh) / 2
    assert int8_bytes / bf16_bytes == pytest.approx(expected, rel=1e-6)


def test_int8_decode_logits_close_to_exact():
    """Prefill + per-token decode through the int8 cache tracks the exact
    uncached forward closely (per-head amax int8 is a mild perturbation)."""
    params = transformer.init_params(CFG, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 12), 0, CFG.vocab_size)
    exact, _ = transformer.forward(params, tokens, CFG)

    cache = transformer.make_kv_cache(CFG, 2, 12)
    logits_p, cache = transformer.forward(
        params, tokens[:, :6], CFG, kv_cache=cache, cache_index=jnp.int32(0)
    )
    logits = [logits_p]
    for i in range(6, 12):
        step, cache = transformer.forward(
            params, tokens[:, i : i + 1], CFG, kv_cache=cache,
            cache_index=jnp.int32(i),
        )
        logits.append(step)
    got = jnp.concatenate(logits, axis=1)
    err = float(jnp.abs(got - exact).max())
    spread = float(jnp.abs(exact).max())
    assert err < 0.05 * spread, (err, spread)
    # And the quantization is actually in play (not bit-exact).
    assert err > 0.0


@pytest.mark.parametrize("lengths", [None, [3, 8, 5]])
def test_int8_generation_matches_exact_greedy(lengths):
    """Greedy generation with the int8 cache equals the exact-cache output
    for a well-separated (trained-free random-init) tiny model — argmax is
    robust to the small quantization perturbation here; equality is checked
    for dense AND ragged batches."""
    params = transformer.init_params(CFG, jax.random.key(0))
    b = 3 if lengths else 2
    pmax = max(lengths) if lengths else 8
    prompt = jax.random.randint(jax.random.key(2), (b, pmax), 0, CFG.vocab_size)
    kw = dict(temperature=0.0)
    if lengths:
        kw["prompt_lengths"] = jnp.asarray(lengths)
    exact_cfg = dataclasses.replace(CFG, kv_cache_dtype="compute")
    want = np.asarray(generate(params, exact_cfg, prompt, 8, jax.random.key(3), **kw))
    got = np.asarray(generate(params, CFG, prompt, 8, jax.random.key(3), **kw))
    np.testing.assert_array_equal(got, want)


def test_int8_cache_rejects_explicit_dtype():
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        transformer.make_kv_cache(CFG, 1, 8, dtype="float32")


def test_int8_ragged_stop_token_compose():
    """The three serving features compose: int8 cache + ragged batch +
    stop token produce exactly the exact-cache result under greedy."""
    params = transformer.init_params(CFG, jax.random.key(0))
    lengths = jnp.asarray([3, 7, 5])
    prompt = jax.random.randint(jax.random.key(4), (3, 7), 0, CFG.vocab_size)
    exact_cfg = dataclasses.replace(CFG, kv_cache_dtype="compute")
    base = np.asarray(
        generate(
            params, exact_cfg, prompt, 8, jax.random.key(5), temperature=0.0,
            prompt_lengths=lengths,
        )
    )
    stop = int(base[1, 1])  # a token actually emitted mid-stream
    want = np.asarray(
        generate(
            params, exact_cfg, prompt, 8, jax.random.key(5), temperature=0.0,
            prompt_lengths=lengths, stop_token=stop,
        )
    )
    got = np.asarray(
        generate(
            params, CFG, prompt, 8, jax.random.key(5), temperature=0.0,
            prompt_lengths=lengths, stop_token=stop,
        )
    )
    np.testing.assert_array_equal(got, want)
    # Stop semantics held somewhere: row 1 froze after its stop token.
    hits = np.where(want[1] == stop)[0]
    assert hits.size and (want[1, hits[0]:] == stop).all()
