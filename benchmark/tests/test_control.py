"""The controls of `correct`, at a size a test run can hold: computed in the
nearest precision below the stated one, each must read at least three times
what the sound program reads (the on-chip readings at the cells' own sizes,
and the limits set from them, are in PERF.md section 2)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from harness import opcount, program, registry, serving_check as sc, training_check as tc, weights
from references.common import fp8_fake_quant, int8_fake_quant

TOY = os.path.join(registry.BENCH_DIR, "tests", "toy")


def toy(family):
    with open(os.path.join(TOY, f"{family}.json")) as f:
        return dict(json.load(f), name=family)


def test_serving_control_fails_where_the_program_passes():
    from pretraining_llm_tpu.models import quantize

    arch = toy("mistral_control")
    traffic = registry.load_traffic("decode_closed")
    traffic.update(traffic.pop("rehearsal"))
    cfg = program.model_config(arch, traffic["engine"]["max_seq"])
    sample = [tuple(s) for s in traffic["check_sample"]]
    errs = {"sound": [], "program_int8": [], "reference_int8": []}
    for seed in (3, 2 ** 31 + 5, 4000000001):
        seqs = sc.sample_tokens(seed, opcount.dims(arch)["vocab"], sample)
        ref = sc.reference_logits(arch, seed, sample, seqs)
        params = weights.serving_params(arch, seed)
        for name, p in (("sound", params), ("program_int8", quantize.quantize_params_for_serving(params, cfg))):
            eng = program.serving_engine(p, cfg, traffic)
            prog, _ = sc.program_logits(p, cfg, eng.pools, eng.alloc, eng.max_batch, eng.max_blocks,
                                        eng.block_size, sample, seqs)
            errs[name].append(sc.rel_err(prog, ref))
        errs["reference_int8"].append(
            sc.rel_err(sc.reference_logits(arch, seed, sample, seqs, quant=int8_fake_quant), ref))
    # at this width (d 512, 8 layers) the program's int8 weights read 3.1 x the bf16 program and
    # the reference with int8 operands 4.1 x; at the cell's own size 3.2 x and 6.1 x (PERF.md)
    assert min(errs["program_int8"]) > 2.5 * max(errs["sound"]), errs
    assert min(errs["reference_int8"]) > 3 * max(errs["sound"]), errs


def test_engine_token_control_fails_where_the_engine_passes():
    """Tokens the engine emits through its own tables, admission and fused
    sampling lie on the reference's argmax or a near-tie; held to the position
    before their own (an engine one off in a length or a table), or to another
    request's prompt (a wrong row), they read far above the limit."""
    arch = toy("mistral_control")
    traffic = registry.load_traffic("decode_closed")
    traffic.update(traffic.pop("rehearsal"))
    cfg = program.model_config(arch, traffic["engine"]["max_seq"])
    pad_to, limit = traffic["engine"]["max_seq"], arch["check_limits"]["engine_token_regret"]
    vocab = opcount.dims(arch)["vocab"]
    for seed in (3, 2 ** 31 + 5, 4000000001):
        eng = program.serving_engine(weights.serving_params(arch, seed), cfg, traffic)
        rng = np.random.default_rng(seed)
        prompts = {}
        for n_prompt, n_out in ((24, 16), (9, 12), (33, 8), (17, 16)):
            prompt = rng.integers(0, vocab, n_prompt).tolist()
            prompts[eng.submit(prompt, n_out)] = prompt
        done = eng.run()
        emitted = [(prompts[rid], list(done[rid])) for rid in sorted(prompts)]
        own, off = sc.token_regrets(arch, seed, emitted, pad_to)
        # at this toy width the model soon repeats itself, so one position off often lands on the
        # same token: 1.5-2.9 here, 0.004-0.013 for the engine; the cells' own readings are in PERF.md
        assert own.size == 52 and 3 * own.max() < limit < off.max(), (own.max(), off.max())
        wrong_row, _ = sc.token_regrets(arch, seed, sc.wrong_rows(emitted), pad_to)
        assert wrong_row.max() > 3 * limit, wrong_row.max()  # 4.1-5.4 here


def test_training_control_fails_on_the_gradient_and_not_on_its_norm():
    arch = toy("gpt2_control")
    traffic = registry.load_traffic("train_dense_1k")
    traffic.update(traffic.pop("rehearsal"))
    cfg, _ = program.train_config(arch, traffic, jax.devices()[:1], 0)
    leaves = jax.tree.leaves
    sq = lambda t: sum(float(jnp.sum(a ** 2)) for a in leaves(t))
    sound, fp8, int8, fp8_norm = [], [], [], []
    for seed in (3, 2 ** 31 + 5, 4000000001):
        rng = np.random.default_rng(seed)
        x = rng.integers(0, arch["vocab_size"], (2, traffic["sequence_length"]), dtype=np.int32)
        y = np.roll(x, -1, axis=1)
        ref = tc.Reference(arch, seed, jax.devices()[:1])
        _, g_ref = ref.loss_and_grads(x, y)
        sound.append(tc.grad_rel_err(arch, tc.program_grads(arch, seed, cfg, None, x, y), g_ref))
        for quant, out in ((fp8_fake_quant, fp8), (int8_fake_quant, int8)):
            _, g = ref.loss_and_grads(x, y, quant=quant)
            diff = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(leaves(g), leaves(g_ref)))
            out.append((diff / sq(g_ref)) ** 0.5)
            if quant is fp8_fake_quant:
                fp8_norm.append(abs(sq(g) ** 0.5 - sq(g_ref) ** 0.5) / sq(g_ref) ** 0.5)
    # float8 operands, the control: far above the bf16 program and above the limit it is held to
    assert min(fp8) > 3 * max(sound), (sound, fp8)
    assert min(fp8) > arch["check_limits"]["grad_rel_err"] > max(sound), (sound, fp8)
    # int8 operands are noisier than the bf16 program, yet not by the factor of three a limit needs
    assert min(int8) > max(sound), (sound, int8)
    # a difference of norms averages even the float8 noise away: why the norm is no precision check
    assert max(fp8_norm) < min(fp8) / 5, (fp8_norm, fp8)
