"""The Ling family's own counts (a state a row beside a latent cache, a share
of the experts and of the vocabulary: what ``opcount`` cannot count) against
ISSUE 31's arithmetic, the program's view of the same configuration, the
control of `correct` at a toy width, and the new readers."""

import json
import os
import types

import pytest

from harness import families, opcount, program, registry, serving_check as sc, weights
from references.common import int8_fake_quant

ARCH = registry.load_config("ling-3.0-flash")
FAM = families.of(ARCH)


def test_parameter_counts_are_the_issues_and_the_programs():
    m = opcount.dims(ARCH)
    assert round(FAM.kda_params(m) / 1e6, 2) == 63.05 and round(FAM.mla_params(m) / 1e6, 2) == 31.97
    assert round(FAM.expert_params(m) / 1e6, 3) == 5.898 and round(3 * m["d"] * m["ffn"] / 1e6, 2) == 47.19
    assert round(m["d"] * m["experts"] / 1e6, 2) == 1.31 and round(2 * m["vocab_rows"] * m["d"] / 1e6, 1) == 201.2
    assert round(FAM.layer_params(m) / 1e6, 1) == 825.2  # a KDA layer with 128 experts held
    assert FAM.kinds(ARCH) == [("kda", "dense")] + [("kda", "moe")] * 4 + [("mla", "moe")]
    assert opcount.num_params(ARCH) == 4_406_550_816 and round(opcount.weight_bytes(ARCH) / 1e9, 2) == 8.81  # ISSUE 31: 4,406.5 M
    # the published stack: 35 KDA and 7 MLA layers, a mean mixer of 57.9 M
    assert round((35 * FAM.kda_params(m) + 7 * FAM.mla_params(m)) / 42 / 1e6, 1) == 57.9
    cfg = program.model_config(ARCH, 5184)
    assert cfg.num_params() == opcount.num_params(ARCH) and cfg.n_layers == 6 and cfg.n_kda_layers == 5
    assert cfg.layer_runs == ((0, 1), (1, 5), (5, 6)) and cfg.latent_dim == 576 and cfg.vocab_size == 39296
    assert cfg.d_ff == 6144 and cfg.expert_width == 768 and cfg.head_dim == 192 and cfg.kda_head_dim == 128
    assert (cfg.n_experts, cfg.experts_held, cfg.moe_n_group, cfg.moe_topk_group) == (512, 128, 8, 4)
    assert not any(cfg.moe_swiglu_limits) and not any(cfg.moe_shared_swiglu_limits)  # the published zeros


def test_the_configuration_file_keeps_every_published_number_but_the_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "Ling-3.0-flash")
    differs = sorted(k for k, v in entry["config"].items() if ARCH.get(k, "absent") != v)
    in_manifest = next(c for c in registry.manifest()["configs"] if c["name"] == "ling-3.0-flash")["reduced"]
    assert differs == sorted(ARCH["reduced"]) == sorted(in_manifest)
    assert ARCH["source"] == entry["source_url"] and set(ARCH["changed"]) == set(ARCH["reduced"])


def test_step_bytes_count_the_state_the_latents_and_the_experts_touched():
    assert FAM.latent_bytes_per_token(ARCH) == 576 * 2  # one MLA layer of six
    assert round(6657 * 64 * FAM.latent_bytes_per_token(ARCH) / 1e9, 2) == 0.49
    per_row = FAM.state_bytes_per_row(ARCH)
    assert per_row == 5 * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2)  # 2.10 MB of state and 74 kB of tail a layer
    assert round(128 * per_row / 1e9, 2) == 1.39
    touched = 0.86
    moe = FAM.moe_step_bytes(ARCH, touched)
    assert 6.4e9 < 5 * 128 * touched * FAM.expert_params(opcount.dims(ARCH)) * 2 < 6.5e9 < moe < 6.6e9
    assert round(FAM.kda_step_bytes(ARCH, 128) / 1e9, 2) == 2.74  # ISSUE 31: 2.7 GB of state read and written
    resident = 128 * (1024 + 2048)
    assert round(FAM.latent_step_bytes(ARCH, resident) / 1e9, 2) == 0.46
    whole = FAM.decode_step_min_bytes(ARCH, resident, 128, touched)
    assert 12.5 < 1e3 * whole / 819e9 < 13.5  # ISSUE 31 reckons 13 ms a step at the roofline
    assert FAM.decode_step_min_bytes(ARCH, resident, 128, 0.5) == whole - moe + FAM.moe_step_bytes(ARCH, 0.5)
    ops, moved = FAM.kda_chunk_ops_bytes(ARCH, 1024)
    # 16 chunks x 32 heads x 5 layers of small float32 matmuls; q, k, v, g in and o out in float32
    # weigh more: the byte bound (0.54 ms a 1,024-token prompt) is the larger of the two (0.13 ms)
    assert ops == 5 * 2 * 16 * 32 * (3.5 * 64 * 64 * 128 + 3 * 64 * 128 * 128)
    assert moved == 5 * 4 * (1024 * (5 * 4096 + 32) + 2 * 32 * 128 * 128)
    assert moved / 819e9 > ops / 197e12


def test_reference_in_int8_fails_where_the_program_passes():
    """At a toy width the bf16 program stays under the limit and the reference
    with int8 matmul operands, the precision below the stated one, does not."""
    with open(os.path.join(registry.BENCH_DIR, "tests", "toy", "ling_control.json")) as f:
        arch = dict(json.load(f), name="ling_control")
    traffic = registry.load_traffic("decode_closed_1k_4k")
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
    and ``attn.core``, spans without counters, no ``kda.*`` scope): None from
    every new reader, as the parent's program gives, and nothing raises. With
    counters on the commit spans the shares are what the counts say."""
    from harness import program_trace as pt
    from readers import commit_counter, kda_roofline

    path = os.path.join(os.path.dirname(__file__), "data", "small_program_v5e.xplane.pb")
    red = pt.reduce(pt.load(path))
    ctx = types.SimpleNamespace(_program_trace=red, arch=ARCH, trace_dir=os.path.dirname(path),
                                devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    result = types.SimpleNamespace(observed={"resident_tokens": 1000.0, "rows": 4, "prefill_tokens": 1024})
    for stat in ("state_slots_peak", "routed_here_share", "load_max_over_mean"):
        assert commit_counter.read(result, None, ctx, stat=stat) is None
    no_runs = {"module_runs_s": {"jit_other(1)": [0.03]}}
    assert kda_roofline.read(result, no_runs, ctx, part="step", match="jit_paged_decode_step", scopes=["kda.step"]) is None
    commit = lambda **meta: types.SimpleNamespace(span=types.SimpleNamespace(name="serving.commit", meta=meta))
    counted = types.SimpleNamespace(_program_trace=types.SimpleNamespace(uses=[
        commit(moe_steps=1, moe_layers=5, moe_experts=128, moe_routed=5120, moe_routed_here=1200, moe_busiest=40,
               state_slots=120),
        commit(moe_steps=1, moe_layers=5, moe_experts=128, moe_routed=5120, moe_routed_here=1360, moe_busiest=60,
               state_slots=128),
        commit(rows=3)]))
    result = types.SimpleNamespace(observed={"rows": 128})
    assert commit_counter.read(result, None, counted, stat="routed_here_share") == pytest.approx(25.0)
    assert commit_counter.read(result, None, counted, stat="load_max_over_mean") == pytest.approx(100 * 128 / 2560)
    assert commit_counter.read(result, None, counted, stat="state_slots_peak") == pytest.approx(100.0)
    # a dense model's commit spans carry none of it
    bare = types.SimpleNamespace(_program_trace=types.SimpleNamespace(uses=[commit(rows=3)]))
    assert commit_counter.read(result, None, bare, stat="routed_here_share") is None
    assert commit_counter.read(result, None, bare, stat="state_slots_peak") is None
