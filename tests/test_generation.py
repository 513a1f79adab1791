"""Generation: cached decode == uncached forward, sampling semantics, CLI path."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.generation.generate import generate, load_model_for_inference
from pretraining_llm_tpu.generation.sampling import sample_logits
from pretraining_llm_tpu.models import transformer

CFG = dataclasses.replace(get_preset("tiny").model, compute_dtype="float32")


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


@pytest.mark.parametrize("kind", ["exact", "int8", "latent"])
def test_dense_cache_containers_agree(params, kind):
    """make_kv_cache's two containers — per-layer leaves (python layer loop,
    in-place updates; stacked once for a long call) and stacked fields
    (riding the depth scan) — hold the same values and give the same logits
    through a prefill and single-token steps."""
    if kind == "latent":  # two layer groups, latent + rope fields
        cfg = dataclasses.replace(get_preset("xing-mini").model, compute_dtype="float32")
        p = transformer.init_params(cfg, jax.random.key(0))
    else:
        cfg = dataclasses.replace(CFG, kv_cache_dtype="int8" if kind == "int8" else "compute")
        p = params
    tokens = jax.random.randint(jax.random.key(11), (2, 15), 0, cfg.vocab_size)
    per_layer = transformer.make_kv_cache(cfg, 2, 16)
    stacked = transformer.make_kv_cache(cfg, 2, 16, stacked=True)
    assert set(stacked) == set(per_layer["layers"][0]) and len(per_layer["layers"]) == cfg.n_layers
    # a prefill longer than decode_loop_max_tokens, then steps through the loop
    for start, stop in [(0, 12), (12, 13), (13, 14), (14, 15)]:
        step = lambda cache: transformer.forward(
            p, tokens[:, start:stop], cfg, kv_cache=cache, cache_index=jnp.int32(start)
        )
        (want, stacked), (got, per_layer) = step(stacked), step(per_layer)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    for name, buf in stacked.items():
        restacked = jnp.stack([lyr[name] for lyr in per_layer["layers"]])
        assert restacked.dtype == buf.dtype
        np.testing.assert_allclose(
            np.asarray(restacked[:, :, :15], np.float32), np.asarray(buf[:, :, :15], np.float32),
            rtol=1e-5, atol=1 if buf.dtype == jnp.int8 else 1e-5,
        )


def test_greedy_cached_matches_uncached(params):
    """KV-cached greedy decode must equal argmax over full re-forwards
    (the reference's cache-less loop, transformer.py:96-114)."""
    prompt = jax.random.randint(jax.random.key(1), (1, 8), 0, CFG.vocab_size)
    n_new = 10
    got = np.asarray(generate(params, CFG, prompt, n_new, jax.random.key(2), temperature=0.0))

    # Uncached reference loop: full forward each step, argmax.
    seq = np.asarray(prompt)
    for _ in range(n_new):
        logits, _ = transformer.forward(params, jnp.asarray(seq), CFG)
        nxt = int(jnp.argmax(logits[0, -1]))
        seq = np.concatenate([seq, [[nxt]]], axis=1)
    want = seq[:, 8:]
    np.testing.assert_array_equal(got, want)


def test_prefill_cache_matches_full_forward(params):
    """Logits from incremental cached decode == full-sequence forward."""
    tokens = jax.random.randint(jax.random.key(3), (1, 12), 0, CFG.vocab_size)
    full_logits, _ = transformer.forward(params, tokens, CFG)

    cache = transformer.make_kv_cache(CFG, 1, 12, dtype="float32")
    logits_p, cache = transformer.forward(
        params, tokens[:, :4], CFG, kv_cache=cache, cache_index=jnp.int32(0)
    )
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(full_logits[:, :4]), rtol=2e-4, atol=2e-4
    )
    # Decode one token at a time
    for i in range(4, 12):
        step_logits, cache = transformer.forward(
            params, tokens[:, i : i + 1], CFG, kv_cache=cache, cache_index=jnp.int32(i)
        )
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]),
            np.asarray(full_logits[:, i]),
            rtol=2e-4,
            atol=2e-4,
        )


def test_generate_respects_context_bound(params):
    prompt = jnp.zeros((1, 60), jnp.int32)
    with pytest.raises(ValueError, match="context_length"):
        generate(params, CFG, prompt, 10, jax.random.key(0))  # 60+10 > 64


def test_batched_generation(params):
    prompt = jax.random.randint(jax.random.key(4), (3, 8), 0, CFG.vocab_size)
    out = generate(params, CFG, prompt, 5, jax.random.key(5))
    assert out.shape == (3, 5)
    assert (np.asarray(out) >= 0).all() and (np.asarray(out) < CFG.vocab_size).all()


def test_sampling_temperature_zero_is_argmax():
    logits = jnp.asarray([[1.0, 3.0, 2.0], [0.5, 0.1, 0.9]])
    out = sample_logits(logits, jax.random.key(0), temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out), [1, 2])


def test_sampling_top_k_restricts_support():
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0, 4.0]])
    draws = set()
    for i in range(50):
        draws.add(int(sample_logits(logits, jax.random.key(i), temperature=1.0, top_k=2)[0]))
    assert draws <= {3, 4}


def test_sampling_min_p_restricts_support():
    """min-p keeps exactly the tokens with prob >= min_p * max prob, and
    the support adapts to confidence (peaked dist -> smaller support)."""
    logits = jnp.asarray([[3.0, 2.9, 0.0, -5.0]])
    ids = [
        int(sample_logits(jnp.asarray(logits), jax.random.key(i),
                          temperature=1.0, min_p=0.5)[0])
        for i in range(64)
    ]
    # p(2.9)/p(3.0) = e^-0.1 ~ 0.90 >= 0.5 kept; p(0)/p(3) ~ 0.05 < 0.5 cut
    assert set(ids) <= {0, 1}
    assert len(set(ids)) == 2  # both survivors actually sampled
    peaked = jnp.asarray([[10.0, 2.9, 0.0, -5.0]])
    ids_p = [
        int(sample_logits(peaked, jax.random.key(i), temperature=1.0,
                          min_p=0.5)[0])
        for i in range(32)
    ]
    assert set(ids_p) == {0}  # confident dist -> support collapses


def test_sampling_top_p_restricts_support():
    # Peaked distribution: token 0 carries ~88% of the mass.
    logits = jnp.asarray([[5.0, 3.0, 0.0, -1.0, -2.0]])
    draws = set()
    for i in range(50):
        draws.add(int(sample_logits(logits, jax.random.key(i), temperature=1.0, top_p=0.5)[0]))
    assert draws == {0}


def test_generate_text_from_checkpoint(tmp_path):
    """Full CLI path: train 2 steps -> checkpoint -> load -> generate text."""
    from pretraining_llm_tpu.training.trainer import Trainer

    # Byte tokenizer (always available offline); vocab covers its 257 ids.
    cfg = get_preset("tiny").with_overrides(
        {
            "model.vocab_size": 512,
            "data.tokenizer_name": "byte",
            "train.train_steps": 2,
            "train.checkpoint_interval": 0,
            "train.eval_interval": 0,
            "train.log_interval": 100,
            "train.checkpoint_dir": str(tmp_path / "ck"),
        }
    )
    t = Trainer(cfg, synthetic_data=True, resume=False)
    t.train()

    params, loaded_cfg = load_model_for_inference(str(tmp_path / "ck"))
    assert loaded_cfg.model.vocab_size == 512
    assert loaded_cfg.data.tokenizer_name == "byte"

    from pretraining_llm_tpu.generation.generate import generate_text

    text = generate_text(str(tmp_path / "ck"), "Hello", max_new_tokens=5, seed=0)
    assert text.startswith("Hello")
    assert len(text) > len("Hello")


def test_prompt_bucketing_reuses_compilation(params):
    """Prompts of different lengths within one power-of-two bucket share a
    compiled executable; greedy output is unaffected by the padding."""
    import importlib

    # The package re-exports the `generate` FUNCTION under the submodule's
    # name, so plain `import ... as` resolves to the function; go via importlib.
    gen_mod = importlib.import_module("pretraining_llm_tpu.generation.generate")

    gen_mod._generate_jit.clear_cache()
    for plen in (17, 23, 30):
        prompt = jax.random.randint(jax.random.key(plen), (1, plen), 0, CFG.vocab_size)
        generate(params, CFG, prompt, 4, jax.random.key(0), temperature=0.0)
    assert gen_mod._generate_jit._cache_size() == 1  # one bucket, one compile

    # Correctness under padding: bucketed greedy == uncached reference loop.
    prompt = jax.random.randint(jax.random.key(9), (1, 19), 0, CFG.vocab_size)
    got = np.asarray(generate(params, CFG, prompt, 6, jax.random.key(2), temperature=0.0))
    seq = np.asarray(prompt)
    for _ in range(6):
        logits, _ = transformer.forward(params, jnp.asarray(seq), CFG)
        seq = np.concatenate([seq, [[int(jnp.argmax(logits[0, -1]))]]], axis=1)
    np.testing.assert_array_equal(got, seq[:, 19:])


def test_sharded_decode_matches_single_device(params, mesh8):
    """generate(..., mesh=) with TP/FSDP-sharded params == unsharded decode."""
    from pretraining_llm_tpu.generation.generate import shard_params_for_inference

    prompt = jax.random.randint(jax.random.key(5), (2, 12), 0, CFG.vocab_size)
    want = np.asarray(generate(params, CFG, prompt, 5, jax.random.key(7), temperature=0.0))
    sharded = shard_params_for_inference(params, mesh8)
    got = np.asarray(
        generate(sharded, CFG, prompt, 5, jax.random.key(7), temperature=0.0, mesh=mesh8)
    )
    np.testing.assert_array_equal(got, want)


def test_moe_generation_not_bucketed_and_matches_reference():
    """Pad tokens would enter capacitated MoE routing and perturb real
    tokens' outputs — MoE prompts must not be padded (and greedy decode must
    match the uncached reference loop at an awkward prompt length)."""
    cfg = dataclasses.replace(
        CFG, n_experts=4, experts_per_token=2, expert_capacity_factor=1.25
    )
    params = transformer.init_params(cfg, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (1, 17), 0, cfg.vocab_size)
    got = np.asarray(generate(params, cfg, prompt, 6, jax.random.key(2), temperature=0.0))
    seq = np.asarray(prompt)
    for _ in range(6):
        logits, _ = transformer.forward(params, jnp.asarray(seq), cfg)
        seq = np.concatenate([seq, [[int(jnp.argmax(logits[0, -1]))]]], axis=1)
    np.testing.assert_array_equal(got, seq[:, 17:])


def test_evaluate_cli(tmp_path):
    """Train briefly, then the standalone eval CLI reports a sane loss and
    is deterministic across invocations."""
    import json
    import subprocess
    import sys

    import numpy as np

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ckdir = str(tmp_path / "ck")
    # Real-ish token file: biased byte stream (so val loss < ln(256)).
    rng = np.random.default_rng(0)
    tokens = rng.choice(64, size=80_000).astype(np.uint16)
    data = tmp_path / "val.bin"
    tokens.tofile(data)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "train.py"),
         "--preset", "tiny", "--no-resume",
         "--override", "train.train_steps=30", "train.checkpoint_interval=30",
         "train.eval_interval=0", f"train.checkpoint_dir={ckdir}",
         f"data.train_path={data}", f"data.val_path={data}"],
        capture_output=True, text=True, env=env, timeout=600, cwd=repo,
    )
    assert r.returncode == 0, r.stderr[-2000:]

    def run_eval():
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "scripts", "evaluate.py"),
             "--model_path", ckdir, "--data", str(data), "--iters", "4"],
            capture_output=True, text=True, env=env, timeout=600, cwd=repo,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    a, b = run_eval(), run_eval()
    assert a["val_loss"] == b["val_loss"]  # deterministic eval set
    assert 0 < a["val_loss"] < 6.0
    assert abs(a["val_ppl"] - np.exp(a["val_loss"])) < 1e-2 * a["val_ppl"]


def test_flash_prefill_matches_naive_prefill(params):
    """VERDICT r2 #6: with attention_impl='flash' the cached prefill routes
    through the flash kernel over the local block (no (Tq, Tmax) scores) and
    must match the naive masked-einsum prefill and the full forward."""
    cfg_flash = dataclasses.replace(CFG, attention_impl="flash")
    tokens = jax.random.randint(jax.random.key(5), (2, 16), 0, CFG.vocab_size)
    full_logits, _ = transformer.forward(params, tokens, CFG)

    cache = transformer.make_kv_cache(cfg_flash, 2, 24, dtype="float32")
    logits_f, cache_f = transformer.forward(
        params, tokens, cfg_flash, kv_cache=cache, cache_index=jnp.int32(0)
    )
    np.testing.assert_allclose(
        np.asarray(logits_f), np.asarray(full_logits), rtol=2e-4, atol=2e-4
    )
    # The cache written by the flash prefill then drives correct decode.
    nxt = jnp.argmax(logits_f[:, -1], axis=-1)[:, None]
    step_logits, _ = transformer.forward(
        params, nxt, cfg_flash, kv_cache=cache_f, cache_index=jnp.int32(16)
    )
    ext = jnp.concatenate([tokens, nxt], axis=1)
    full_ext, _ = transformer.forward(params, ext, CFG)
    np.testing.assert_allclose(
        np.asarray(step_logits[:, 0]), np.asarray(full_ext[:, -1]),
        rtol=2e-4, atol=2e-4,
    )


def test_generate_flash_equals_naive_greedy(params):
    """End-to-end: greedy generation is implementation-invariant."""
    cfg_flash = dataclasses.replace(CFG, attention_impl="flash")
    prompt = jax.random.randint(jax.random.key(6), (2, 8), 0, CFG.vocab_size)
    got_n = np.asarray(generate(params, CFG, prompt, 8, jax.random.key(7), temperature=0.0))
    got_f = np.asarray(
        generate(params, cfg_flash, prompt, 8, jax.random.key(7), temperature=0.0)
    )
    np.testing.assert_array_equal(got_n, got_f)


@pytest.mark.parametrize(
    "pos,impl",
    [("learned", "naive"), ("rope", "naive"), ("rope", "flash")],
)
def test_ragged_batched_generation_matches_per_row(params, pos, impl):
    """Serving-grade ragged batches: rows with different prompt lengths
    decode in ONE lockstep program (right-padded flash-capable prefill,
    per-row cache roll, left-pad lockstep decode) and each row's greedy
    continuation must equal generating that row alone."""
    cfg = dataclasses.replace(CFG, pos_embed=pos, attention_impl=impl)
    p = (
        params
        if (pos, impl) == ("learned", "naive")
        else transformer.init_params(cfg, jax.random.key(0))
    )
    lengths = [3, 8, 5]
    pmax = max(lengths)
    rows = []
    for i, ln in enumerate(lengths):
        row = jax.random.randint(jax.random.key(20 + i), (ln,), 0, cfg.vocab_size)
        rows.append(jnp.pad(row, (0, pmax - ln)))  # right-pad to P
    batch = jnp.stack(rows)
    n_new = 6

    got = np.asarray(
        generate(
            p, cfg, batch, n_new, jax.random.key(9), temperature=0.0,
            prompt_lengths=jnp.asarray(lengths),
        )
    )
    for i, ln in enumerate(lengths):
        want = np.asarray(
            generate(
                p, cfg, batch[i, :ln][None], n_new, jax.random.key(9),
                temperature=0.0,
            )
        )
        np.testing.assert_array_equal(got[i], want[0], err_msg=f"row {i} (len {ln})")


def test_ragged_generation_validation(params):
    with pytest.raises(ValueError, match="prompt_lengths"):
        generate(
            params, CFG, jnp.zeros((2, 4), jnp.int32), 4, jax.random.key(0),
            prompt_lengths=jnp.asarray([2, 3, 4]),  # wrong batch size
        )
    with pytest.raises(ValueError, match="prompt_lengths"):
        generate(
            params, CFG, jnp.zeros((2, 4), jnp.int32), 4, jax.random.key(0),
            prompt_lengths=jnp.asarray([2, 9]),  # exceeds P
        )


def test_generate_text_batch_ragged_cli(tmp_path):
    """Batched ragged text generation from a checkpoint: one compiled
    program for prompts of different lengths; each output extends its own
    prompt and matches the single-prompt path under greedy decoding."""
    from pretraining_llm_tpu.generation.generate import (
        generate_text,
        generate_text_batch,
    )
    from pretraining_llm_tpu.training.trainer import Trainer

    cfg = get_preset("tiny").with_overrides(
        {
            "model.vocab_size": 512,
            "data.tokenizer_name": "byte",
            "train.train_steps": 2,
            "train.checkpoint_interval": 0,
            "train.eval_interval": 0,
            "train.log_interval": 100,
            "train.checkpoint_dir": str(tmp_path / "ck"),
        }
    )
    Trainer(cfg, synthetic_data=True, resume=False).train()

    prompts = ["Hello", "ab", "The quick brown"]
    outs = generate_text_batch(
        str(tmp_path / "ck"), prompts, max_new_tokens=5, temperature=0.0
    )
    assert len(outs) == 3
    for prompt, out in zip(prompts, outs):
        assert out.startswith(prompt)
        # (No length assertion: a 2-step byte model can argmax ids outside
        # the byte-decodable range, which decode to "".) The real check:
        # the ragged batch row equals the single-prompt path exactly.
        single = generate_text(
            str(tmp_path / "ck"), prompt, max_new_tokens=5, temperature=0.0
        )
        assert out == single, (out, single)


def test_stop_token_freezes_finished_rows(params):
    """Once a row samples the stop token it emits only the stop token for
    the remaining steps; tokens before the stop match the un-stopped run."""
    prompt = jax.random.randint(jax.random.key(30), (2, 6), 0, CFG.vocab_size)
    base = np.asarray(
        generate(params, CFG, prompt, 10, jax.random.key(3), temperature=0.0)
    )
    stop = int(base[0, 2])  # a token the greedy run actually emits
    got = np.asarray(
        generate(
            params, CFG, prompt, 10, jax.random.key(3), temperature=0.0,
            stop_token=stop,
        )
    )
    for row in range(2):
        hits = np.where(base[row] == stop)[0]
        if hits.size == 0:
            np.testing.assert_array_equal(got[row], base[row])
            continue
        first = int(hits[0])
        np.testing.assert_array_equal(got[row, : first + 1], base[row, : first + 1])
        assert (got[row, first:] == stop).all(), got[row]


def test_generate_text_works_for_moe_checkpoint(tmp_path):
    """generate_text must keep working for MoE checkpoints: single-prompt
    (uniform-length) batches bypass the ragged machinery MoE rejects."""
    from pretraining_llm_tpu.generation.generate import (
        generate_text,
        generate_text_batch,
    )
    from pretraining_llm_tpu.training.trainer import Trainer

    cfg = get_preset("tiny").with_overrides(
        {
            "model.vocab_size": 512,
            "model.n_experts": 2,
            "model.experts_per_token": 1,
            "model.expert_capacity_factor": 4.0,
            "data.tokenizer_name": "byte",
            "train.train_steps": 2,
            "train.checkpoint_interval": 0,
            "train.eval_interval": 0,
            "train.log_interval": 100,
            "train.checkpoint_dir": str(tmp_path / "ck"),
        }
    )
    Trainer(cfg, synthetic_data=True, resume=False).train()
    text = generate_text(str(tmp_path / "ck"), "Hello", max_new_tokens=4, temperature=0.0)
    assert text.startswith("Hello")
    # Ragged (different-length) MoE batches are rejected with a clear error.
    with pytest.raises(ValueError, match="equal-length"):
        generate_text_batch(
            str(tmp_path / "ck"), ["Hello", "ab"], max_new_tokens=4
        )


@pytest.mark.parametrize("gqa", [False, True])
@pytest.mark.parametrize("cache_kind", ["compute", "int8"])
def test_chunked_prefill_blockwise_matches_full_forward(gqa, cache_kind):
    """Chunked prefill at a nonzero offset routes through rectangular
    blockwise attention (O(block) memory, no (Tq, Tmax) scores, grouped
    cache never expanded) and must track the full-sequence forward — MHA
    and GQA, exact and int8-quantized caches."""
    cfg = dataclasses.replace(
        CFG, attention_impl="flash", n_kv_heads=2 if gqa else None,
        pos_embed="rope", kv_cache_dtype=cache_kind,
    )
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(7), (2, 24), 0, cfg.vocab_size)
    full, _ = transformer.forward(params, tokens, cfg)

    cache = transformer.make_kv_cache(
        cfg, 2, 24, dtype=None if cache_kind == "int8" else "float32"
    )
    got = []
    for start in (0, 8, 16):  # chunk 0 takes the flash shortcut, rest blockwise
        logits, cache = transformer.forward(
            params, tokens[:, start : start + 8], cfg, kv_cache=cache,
            cache_index=jnp.int32(start),
        )
        got.append(logits)
    got = jnp.concatenate(got, axis=1)
    if cache_kind == "int8":
        err = float(jnp.abs(got - full).max())
        assert err < 0.05 * float(jnp.abs(full).max()), err
    else:
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(full), rtol=2e-4, atol=2e-4
        )


def test_chunked_prefill_with_traced_offset_matches_full_forward():
    """The TRACED-offset sub-path (cache_index as a jit argument: no
    frontier slice, offset flows into the causal mask inside the scan)
    must match the full forward too."""
    cfg = dataclasses.replace(CFG, attention_impl="flash", pos_embed="rope")
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(8), (2, 24), 0, cfg.vocab_size)
    full, _ = transformer.forward(params, tokens, cfg)

    @jax.jit
    def chunk(params, toks, cache, idx):
        return transformer.forward(
            params, toks, cfg, kv_cache=cache, cache_index=idx
        )

    cache = transformer.make_kv_cache(cfg, 2, 24, dtype="float32")
    got = []
    for start in (0, 8, 16):
        logits, cache = chunk(
            params, tokens[:, start : start + 8], cache, jnp.int32(start)
        )
        got.append(logits)
    got = jnp.concatenate(got, axis=1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(full), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize(
    "variant", ["plain", "biased_head", "moe"]
)
def test_cast_params_for_inference_bit_identical(variant):
    """Pre-casting matmul weights to compute dtype is bit-identical (the
    forward casts at every use site anyway) and leaves the fp32-consumed
    leaves alone: norm params, the lm_head bias, the MoE router."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    from jax.tree_util import tree_flatten_with_path

    from pretraining_llm_tpu.config import get_preset
    from pretraining_llm_tpu.generation.generate import (
        cast_params_for_inference, generate,
    )
    from pretraining_llm_tpu.models import transformer

    cfg = get_preset("tiny").model
    if variant == "biased_head":
        cfg = dc.replace(cfg, tie_embeddings=False, lm_head_bias=True)
    elif variant == "moe":
        cfg = dc.replace(cfg, n_experts=4, experts_per_token=2)
    p = transformer.init_params(cfg, jax.random.key(0))
    # Zero-initialized leaves (lm_head bias, norm biases) would make the
    # forward comparison vacuous (0.0 rounds exactly to bf16): randomize
    # EVERY float leaf so a wrongly-cast leaf actually changes the logits.
    leaves, treedef = jax.tree_util.tree_flatten(p)
    keys = jax.random.split(jax.random.key(99), len(leaves))
    p = jax.tree_util.tree_unflatten(treedef, [
        (jax.random.normal(k, l.shape, jnp.float32) * 0.05).astype(l.dtype)
        if jnp.issubdtype(l.dtype, jnp.floating) else l
        for k, l in zip(keys, leaves)
    ])
    pc = cast_params_for_inference(p, cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    # Hand-listed fp32-consumed leaf names (independent of the
    # implementation's path predicate).
    fp32_expected = {"ln1/scale", "ln1/bias", "ln2/scale", "ln2/bias",
                     "final_norm/scale", "final_norm/bias", "lm_head/bias"}
    fp32_suffixes = tuple(fp32_expected) + ("router",)
    for path, leaf in tree_flatten_with_path(pc)[0]:
        name = "/".join(str(getattr(k, "key", "")) for k in path)
        if name.endswith(fp32_suffixes):
            assert leaf.dtype == jnp.float32, name
        elif jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == cdt, name

    x = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    l1, l2 = transformer.forward(p, x, cfg), transformer.forward(pc, x, cfg)
    if isinstance(l1, tuple):
        l1, l2 = l1[0], l2[0]
    assert bool(jnp.all(l1 == l2))
    if variant != "moe":  # ragged-free dense decode path
        g1 = generate(p, cfg, x, 8, jax.random.key(2), temperature=0.0)
        g2 = generate(pc, cfg, x, 8, jax.random.key(2), temperature=0.0)
        assert bool(jnp.all(g1 == g2))


def _sample_logits_fullsort_reference(
    logits, key, *, temperature=1.0, top_k=None, top_p=None, min_p=None
):
    """The pre-top_k-rework sampler (full jnp.sort for the k-th threshold
    and a second sort for top-p), inlined as the distribution-identity
    reference: filters are value-threshold masks, so the lax.top_k
    rework must pick the SAME token for the same key, ties included."""
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    bad = jnp.any(jnp.isnan(logits) | (logits == jnp.inf), axis=-1)
    logits = logits / temperature
    if min_p is not None and 0.0 < min_p <= 1.0:
        cutoff = jnp.max(logits, axis=-1, keepdims=True) + jnp.log(min_p)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    if top_k is not None and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and 0.0 < top_p < 1.0:
        sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff_logit = jnp.take_along_axis(
            sorted_desc, cutoff_idx[:, None], axis=-1
        )
        logits = jnp.where(logits < cutoff_logit, -jnp.inf, logits)
    sampled = jax.random.categorical(key, logits, axis=-1)
    return jnp.where(bad, jnp.int32(-1), sampled.astype(jnp.int32))


@pytest.mark.parametrize("knobs", [
    dict(top_k=4),
    dict(top_k=1),
    dict(top_p=0.7),
    dict(top_k=4, top_p=0.7),
    dict(top_k=3, top_p=0.95, min_p=0.01),
    dict(top_k=50),  # k >= V: no-op filter
])
def test_sample_logits_topk_rework_distribution_identity(knobs):
    """The lax.top_k sampler must be token-for-token identical to the
    old full-sort implementation — same masked distribution, same
    categorical draw per key — including logits with exact ties AT the
    k-th value and at the nucleus cutoff."""
    rng = np.random.default_rng(42)
    for trial in range(6):
        logits = rng.normal(size=(5, 16)).astype(np.float32) * 3.0
        if trial % 2:
            # Inject ties straddling the thresholds: rows where several
            # entries share the k-th-largest value exactly.
            logits[0, :6] = 1.25
            logits[1, 3:9] = logits[1, 3]
            logits[2] = 0.0
        jl = jnp.asarray(logits)
        for seed in range(3):
            key = jax.random.key(trial * 10 + seed)
            got = sample_logits(jl, key, temperature=0.8, **knobs)
            want = _sample_logits_fullsort_reference(
                jl, key, temperature=0.8, **knobs
            )
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
