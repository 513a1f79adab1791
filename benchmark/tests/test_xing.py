"""The Xing family's own counts (a latent cache, experts of which a step
touches some: what ``opcount`` cannot count) against ISSUE 27's arithmetic,
the program's view of the same configuration, and the control of `correct`
at a toy width."""

import json
import os
import types

import pytest

from harness import families, opcount, program, registry, serving_check as sc, weights
from references.common import int8_fake_quant

ARCH = registry.load_config("xing4.0-29b-a4b")
FAM = families.of(ARCH)


def test_parameter_counts_are_the_issues_and_the_programs():
    m = opcount.dims(ARCH)
    assert round(FAM.attn_params(m) / 1e6, 1) == 28.4 and round(FAM.expert_params(m) / 1e6, 1) == 11.0
    assert round(64 * FAM.expert_params(m) / 1e6, 1) == 704.6
    assert round(FAM.dense_layer_params(m) / 1e6, 1) == 128.2
    assert round(2 * m["vocab_rows"] * m["d"] / 1e6, 1) == 939.5
    assert round(opcount.weight_bytes(ARCH) / 1e9, 2) == 9.59  # 1 + 5 layers, every expert, the whole vocabulary
    cfg = program.model_config(ARCH, 8256)
    assert cfg.num_params() == opcount.num_params(ARCH) and cfg.n_layers == 6 and cfg.latent_dim == 576
    assert cfg.d_ff == 9216 and cfg.expert_width == 1024 and cfg.head_dim == 192


def test_step_bytes_count_the_latents_and_the_experts_touched():
    assert FAM.latent_bytes_per_token(ARCH) == 576 * 2 * 6  # 1,152 B a layer against Mistral's 4,096
    pool = 4097 * 64 * FAM.latent_bytes_per_token(ARCH)
    assert round(pool / 1e9, 2) == 1.81
    touched = 1 - (15 / 16) ** 32  # 32 rows x top-4 of 64, routed evenly
    moe = FAM.moe_step_bytes(ARCH, touched)
    assert 6.1e9 < 5 * 64 * touched * FAM.expert_params(opcount.dims(ARCH)) * 2 < 6.2e9 < moe < 6.3e9  # of 7.05 GB
    resident = 32 * (7168 + 512)
    assert round(resident * FAM.latent_bytes_per_token(ARCH) / 1e9, 1) == 1.7
    whole = FAM.decode_step_min_bytes(ARCH, resident, 32, touched)
    assert whole > moe + FAM.latent_step_bytes(ARCH, resident) - 6 * 512 * 32 * 256 * 2
    assert 11.0 < 1e3 * whole / 819e9 < 12.0  # ISSUE 27 reckons 11.5 ms a step at the roofline
    # fewer experts touched, fewer bytes; the rest does not move
    assert FAM.decode_step_min_bytes(ARCH, resident, 32, 0.5) == whole - FAM.moe_step_bytes(ARCH, touched) \
        + FAM.moe_step_bytes(ARCH, 0.5)


def test_reference_in_int8_fails_where_the_program_passes():
    """At a toy width the bf16 program stays under the limit and the reference
    with int8 matmul operands, the precision below the stated one, does not."""
    with open(os.path.join(registry.BENCH_DIR, "tests", "toy", "xing_control.json")) as f:
        arch = dict(json.load(f), name="xing_control")
    traffic = registry.load_traffic("decode_closed_7k")
    traffic.update(traffic.pop("rehearsal"))
    cfg = program.model_config(arch, traffic["engine"]["max_seq"])
    sample = [tuple(s) for s in traffic["check_sample"]]
    sound, control = [], []
    for seed in (3, 2 ** 31 + 5):
        seqs = sc.sample_tokens(seed, opcount.dims(arch)["vocab"], sample)
        ref = sc.reference_logits(arch, seed, sample, seqs)
        params = weights.serving_params(arch, seed)
        eng = program.serving_engine(params, cfg, traffic)
        prog, _ = sc.program_logits(params, cfg, eng.pools, eng.alloc, eng.max_batch, eng.max_blocks,
                                    eng.block_size, sample, seqs)
        sound.append(sc.rel_err(prog, ref))
        control.append(sc.rel_err(sc.reference_logits(arch, seed, sample, seqs, quant=int8_fake_quant), ref))
    limit = arch["check_limits"]["logits_rel_err"]
    assert max(sound) < limit < min(control), (sound, control)
    assert min(control) > 3 * max(sound), (sound, control)


def test_new_readers_on_a_recorded_trace_and_on_a_program_without_counters():
    """On PR 25's small xplane (one program ``jit(prog)`` with the scopes ``mlp``
    and ``attn.core``, spans without routing counters): the scope seconds of
    the program found by its id, and None from every reader that needs the
    counters, as the parent's program gives. Nothing raises."""
    from harness import program_trace as pt
    from readers import moe_counter, part_roofline

    tr = pt.load(os.path.join(os.path.dirname(__file__), "data", "small_program_v5e.xplane.pb"))
    red = pt.reduce(tr)
    in_mlp = part_roofline.decode_scope_seconds(tr, "jit_prog", ["mlp"])
    assert in_mlp == pytest.approx(pt.scope_seconds(red.by_path, ["mlp"]), rel=1e-9) and in_mlp > 0
    assert part_roofline.decode_scope_seconds(tr, "jit_other", ["mlp"]) == 0.0
    ctx = types.SimpleNamespace(_program_trace=red, arch=ARCH, devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    assert moe_counter.totals(ctx) is None and moe_counter.touched_share(ctx) is None
    assert moe_counter.read(None, None, ctx, stat="touched_share") is None
    result = types.SimpleNamespace(observed={"resident_tokens": 1000.0, "rows": 4})
    summary = {"module_runs_s": {"jit_paged_decode_steps(1)": [0.03]}}
    for part in ("moe", "latent_attn", "all"):
        assert part_roofline.read(result, summary, ctx, part=part, match="jit_paged_decode_step") is None
    # with counters on the commit spans the shares are what the counts say
    commit = lambda **meta: types.SimpleNamespace(span=types.SimpleNamespace(name="serving.commit", meta=meta))
    counted = types.SimpleNamespace(_program_trace=types.SimpleNamespace(uses=[
        commit(moe_steps=1, moe_layers=5, moe_experts=64, moe_touched=280, moe_routed=640, moe_busiest=25),
        commit(moe_steps=1, moe_layers=5, moe_experts=64, moe_touched=270, moe_routed=640, moe_busiest=35),
        commit(rows=3)]))
    assert moe_counter.read(None, None, counted, stat="touched_share") == pytest.approx(100 * 550 / 640)
    assert moe_counter.read(None, None, counted, stat="load_max_over_mean") == pytest.approx(60 * 64 / 1280)
