"""The Nemotron-H family's own counts (a state a row in eleven layers beside
three layers' pages, a share of ungated experts of TWO matrices and of an
untied vocabulary: what ``opcount`` cannot count) against ISSUE 58's arithmetic,
the program's view of the same configuration, the cell's files, and the
controls of `correct` at a toy width (``toy/nemotron_h_control.json``; the
rehearsal of the cell's own driver on ``toy/nemotron_h.json`` is
``test_rehearsal.py``'s, which runs every cell of the manifest)."""

import json
import os

import pytest

from harness import families, opcount, program, registry, serving_check as sc, weights
from references.common import int8_fake_quant

ARCH = registry.load_config("nemotron-3-nano-30b-a3b")
FAM = families.of(ARCH)
CELL = "serve_nemotron_h_decode_1k_4k"


def test_parameter_counts_are_the_issues_and_the_programs():
    m = opcount.dims(ARCH)
    assert round(FAM.ssm_params(m) / 1e6, 2) == 38.74 and round((FAM.attn_params(m) + m["d"]) / 1e6, 2) == 23.40
    assert round(FAM.expert_params(m) / 1e6, 3) == 9.978 and round(FAM.shared_params(m) / 1e6, 2) == 19.96
    assert round(m["d"] * m["experts"] / 1e6, 2) == 0.34 and round(m["vocab_rows"] * m["d"] / 1e6, 2) == 88.08
    assert round((FAM.moe_params(m) + m["d"]) / 1e6, 1) == 339.6  # an expert layer with 32 held
    assert m["attn_at"] == (5, 12, 19) and (m["layers"], m["all_layers"], m["attn_layers"]) == (11, 25, 3)
    assert round(opcount.num_params(ARCH) / 1e6) == 4408 and round(opcount.weight_bytes(ARCH) / 1e9, 2) == 8.82
    # the published model by the same functions: 31.58 B (described_as: 31.6B)
    full = dict(ARCH, num_hidden_layers=52, n_routed_experts=128, vocab_size=131072,
                hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    mf = dict(m, experts_held=128, vocab_rows=131072)
    whole = 23 * (FAM.ssm_params(mf) + mf["d"]) + 23 * (FAM.moe_params(mf) + mf["d"]) + 6 * (FAM.attn_params(mf) + mf["d"]) \
        + 2 * 131072 * mf["d"] + mf["d"]
    assert round(whole / 1e9, 2) == 31.58 and full["hybrid_override_pattern"].count("*") == 6
    # by hand: the mixer's five parts, at heads x head_dim and not expand x hidden
    d, w, c, h = 2688, 4096, 4096 + 2 * 8 * 128, 64
    assert FAM.ssm_params(m) == d * (w + c + h) + c * 4 + c + 3 * h + w + w * d
    cfg = program.model_config(ARCH, 5184)
    # the program's attention layers carry a zero output bias of d each that the model does not have
    assert cfg.num_params() == opcount.num_params(ARCH) + 3 * m["d"] and cfg.n_layers == 25
    assert (cfg.n_state_layers, cfg.n_page_layers, cfg.n_cacheless_layers) == (11, 3, 11)
    assert "".join({("mamba", "none"): "M", ("attn", "none"): "*", ("none", "moe"): "E"}[k] for k in cfg.layer_kinds) \
        == ARCH["hybrid_override_pattern"] == "MEMEM*EMEMEM*EMEMEM*EMEME"
    assert len(cfg.layer_runs) == 25 and cfg.vocab_size == 32768 and not cfg.tie_embeddings
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state, cfg.mamba_n_groups, cfg.mamba_conv_kernel,
            cfg.mamba_chunk_size) == (64, 64, 128, 8, 4, 128)
    assert (cfg.mamba_d_inner, cfg.mamba_conv_dim, cfg.n_heads, cfg.head_dim, cfg.kv_heads) == (4096, 6144, 32, 128, 2)
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token, cfg.expert_width, cfg.n_shared_experts) == (
        128, 32, 6, 1856, 2)
    assert (cfg.activation, cfg.moe_score, cfg.moe_score_bias, cfg.moe_norm_topk, cfg.moe_routed_scale) == (
        "relu2", "sigmoid", True, True, 2.5)
    assert cfg.pos_embed == "none" and cfg.embed_scale == 0.0 and cfg.residual_multiplier == 1.0


def test_the_configuration_file_keeps_every_published_number_but_the_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    differs = sorted(k for k, v in entry["config"].items() if ARCH.get(k, "absent") != v)
    in_manifest = next(c for c in registry.manifest()["configs"] if c["name"] == "nemotron-3-nano-30b-a3b")["reduced"]
    assert differs == sorted(ARCH["reduced"]) == sorted(in_manifest)
    assert ARCH["source"] == entry["source_url"] and set(ARCH["changed"]) == set(ARCH["reduced"])
    assert ARCH["hybrid_override_pattern"] == entry["config"]["hybrid_override_pattern"][:25]  # the first stage, as published
    assert ARCH["n_experts_routed"] == entry["config"]["n_routed_experts"] == 128
    # no width moved
    assert (ARCH["hidden_size"], ARCH["moe_intermediate_size"], ARCH["moe_shared_expert_intermediate_size"],
            ARCH["mamba_num_heads"], ARCH["mamba_head_dim"], ARCH["ssm_state_size"], ARCH["n_groups"], ARCH["head_dim"],
            ARCH["num_attention_heads"], ARCH["num_key_value_heads"], ARCH["num_experts_per_tok"]) == (
        2688, 1856, 3712, 64, 64, 128, 8, 128, 32, 2, 6)


def test_step_bytes_count_the_state_the_pages_and_two_matrices_an_expert():
    per_row = FAM.state_bytes_per_row(ARCH)
    assert per_row == 11 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert round(129 * per_row / 1e9, 2) == 3.03  # ISSUE 58: 3.00 GB at 128 rows, and the scratch slot
    assert FAM.kv_bytes_per_token_layer(ARCH) == 1024  # 2 KV heads x 128 x K and V x 2 bytes
    assert round(3 * 6657 * 64 * FAM.kv_bytes_per_token_layer(ARCH) / 1e9, 2) == 1.31
    state = 2 * 128 * 11 * 64 * 64 * 128 * 4
    assert round(state / 1e9, 2) == 5.91
    assert FAM.ssm_step_bytes(ARCH, 128) == state + 11 * 128 * 4 * (2 * 4096 + 2 * 8 * 128 + 64)
    resident = 128 * (1024 + 2048)
    assert round(FAM.attn_step_bytes(ARCH, resident) / 1e9, 2) == 1.21
    m = opcount.dims(ARCH)
    moe = FAM.moe_step_bytes(ARCH, 1.0)
    # TWO matrices an expert at the real 1,856 (no padding to 1,920 is stored or counted)
    assert moe == 11 * 2 * (32 * 2 * 2688 * 1856 + 2 * 2688 * 3712 + 2688 * 128)
    assert round(11 * 32 * FAM.expert_params(m) * 2 / 1e9, 2) == 7.02 and round(11 * FAM.shared_params(m) * 2 / 1e9, 2) == 0.44
    whole = FAM.decode_step_min_bytes(ARCH, resident, 128, 1.0)
    assert 15.7e9 < whole < 15.9e9 and 19.0 < 1e3 * whole / 819e9 < 19.5  # ISSUE 58: 15.8 GB, 19.2 ms
    assert 0.44 < 11 * 32 * FAM.expert_params(m) * 2 / whole < 0.46 and 0.36 < state / whole < 0.38  # 45% and 37%
    assert FAM.decode_step_min_bytes(ARCH, resident, 128, 0.5) == whole - moe + FAM.moe_step_bytes(ARCH, 0.5)
    ops, moved = FAM.ssm_chunk_ops_bytes(ARCH, 1024)
    assert ops == 11 * 2 * 8 * (8 * 128 * 128 * 128 + 64 * (128 * 128 * 64 + 2 * 128 * 64 * 128))
    assert moved == 11 * 4 * (1024 * (2 * 4096 + 2 * 8 * 128 + 64) + 2 * 64 * 64 * 128)


def test_the_cell_names_what_the_files_say():
    man = registry.manifest()
    cell = registry.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-nano-30b-a3b", "decode_closed_ssm_moe_1k_4k", 1)
    assert cell == man["workloads"][-1] and man["configs"][-1]["name"] == cell["config"]
    # the traffic is Granite's, number for number (the two cells are a pair); the file differs in the driver it
    # names, whose `correct` holds the expert layers too, and in the words that say so
    ours = registry.load_traffic(cell["traffic"])
    theirs = registry.load_traffic(registry.cell("serve_granite_decode_1k_4k")["traffic"])
    assert {k for k in ours if ours[k] != theirs[k]} == {"name", "kind", "what", "why_check_sample"}
    assert (theirs["kind"], ours["kind"]) == ("closed_decode_ssm", "closed_decode_ssm_moe")
    traced = {m["name"] for m in registry.metrics_for(CELL, trace=True)}
    theirs = {m["name"] for m in registry.metrics_for("serve_granite_decode_1k_4k", trace=True)}
    # every metric Granite's cell reports, one more shared one and the two of its own suffix
    assert traced == theirs | {"paged_attn_time_share.decode", "scope_coverage.ndecode", "ssm_time_share.ndecode"}
    assert [n for n in registry.list_all()["layer_metrics"] if n.endswith(".ndecode")] == [
        "scope_coverage.ndecode", "ssm_time_share.ndecode"]
    assert [m["name"] for m in man["per_layer"]][-2:] == ["scope_coverage.ndecode", "ssm_time_share.ndecode"]
    assert CELL in next(m for m in man["end_to_end"] if m["name"] == "output_tokens_per_s")["workloads"]
    assert {m["name"] for m in registry.metrics_for(CELL, trace=False)} == {"output_tokens_per_s", "setup_s"}
    scopes = set(registry.layer_metric_spec("scope_coverage.ndecode")["args"]["scopes"])
    assert {"ssm.step", "moe.experts", "attn.core", "blk.norm", "lm_head"} <= scopes and not any(s.startswith("gdn") for s in scopes)
    assert list(ARCH["check_limits"]) == ["logits_rel_err", "state_rel_err", "state_first_rel_err",
                                          "expert_layer_rel_err", "engine_token_regret"]
    assert sum(1 for w in man["workloads"] if w["chips"] == 4) == 1 and len(man["workloads"]) == 11


LONG = {"engine": {"max_batch": 4, "n_blocks": 161, "max_seq": 640, "block_size": 8},
        "check_sample": [[600, 8], [300, 8]]}  # a head keeps hundreds of roundings before a rounded state shows


@pytest.mark.parametrize("control", ["int8", "swiglu_act", "rope", "bf16_state"])
def test_a_control_fails_where_the_program_passes(control):
    """At a toy width the bf16 program stays under the limits, and the reference
    with int8 matmul operands (the precision below the stated one), with SiLU
    for relu^2 or with rotary positions does not, by the logits; nor with its
    state rounded to bfloat16 after every token (below the state's float32), by
    the two numbers of the state slots, which is what the logits cannot tell.
    (A router whose scores are rounded to bfloat16 shows only where a rounded
    score flips a choice, and experts of int8 operands hide behind the flips of
    a deep stack: the next test holds both, a layer at a time.)"""
    from harness import ssm_check

    with open(os.path.join(registry.BENCH_DIR, "tests", "toy", "nemotron_h_control.json")) as f:
        arch = dict(json.load(f), name="nemotron_h_control")
    traffic = registry.load_traffic("decode_closed_ssm_moe_1k_4k")
    traffic.update(traffic.pop("rehearsal"))
    held = ("state_rel_err", "state_first_rel_err") if control == "bf16_state" else ("logits_rel_err",)
    if control == "bf16_state":
        traffic.update(LONG)
    cfg = program.model_config(arch, traffic["engine"]["max_seq"])
    sample = [tuple(s) for s in traffic["check_sample"]]
    sound, departed = [], []

    def numbers(logits, states, want, want_states, rate):
        errors = ssm_check.head_errors(states, want_states)
        return {"logits_rel_err": sc.rel_err(logits, want), "state_rel_err": ssm_check.state_rel_err(errors, rate),
                "state_first_rel_err": ssm_check.state_rel_err(errors, rate, slice(0, 1))}

    for seed in (3, 2 ** 31 + 5):
        seqs = sc.sample_tokens(seed, opcount.dims(arch)["vocab"], sample)
        want, want_states, rate = ssm_check.reference(arch, seed, sample, seqs)
        params = weights.serving_params(arch, seed)
        eng = program.serving_engine(params, cfg, traffic)
        prog, pools = sc.program_logits(params, cfg, eng.pools, eng.alloc, eng.max_batch, eng.max_blocks,
                                        eng.block_size, sample, seqs)
        sound.append(numbers(prog, ssm_check.slot_states(pools, len(sample)), want, want_states, rate))
        kw = dict(quant=int8_fake_quant) if control == "int8" else dict(control=control)
        got, got_states, _ = ssm_check.reference(arch, seed, sample, seqs, **kw)
        departed.append(numbers(got, got_states, want, want_states, rate))
    for name in held:
        limit = arch["check_limits"][name]
        assert max(s[name] for s in sound) * 1.5 < limit < min(d[name] for d in departed) / 1.5, (name, sound, departed)
    if control == "bf16_state":  # and the logits alone would have passed it
        assert max(d["logits_rel_err"] for d in departed) < arch["check_limits"]["logits_rel_err"]


@pytest.mark.parametrize("control", ["bf16_router", "int8_experts", "swiglu_act"])
def test_an_expert_layer_on_the_references_input_tells_its_router_and_its_experts(control):
    """``ssm_moe_check``: each expert layer by itself, program and reference
    handed the same rows (the reference's own normed hidden state in the served
    dtype), so both score the same bits and no choice flips in a sound program:
    what is left is the rounding inside the experts. A router whose scores are
    bfloat16 flips choices on identical inputs, experts fed int8 operands miss
    by their own error, and neither needs the logits to show. On the toy with a
    real choice (top 2 of 8 scored, 4 held): sound 0.0048 on both seeds, int8
    experts 0.015 and 0.021 (rows of 64 and 48 numbers quantise gently; at the
    cell's widths 0.024 against 0.0049), a bfloat16 router and SiLU for relu^2
    far over."""
    from harness import ssm_moe_check as mc

    with open(os.path.join(registry.BENCH_DIR, "tests", "toy", "nemotron_h.json")) as f:
        arch = dict(json.load(f), name="nemotron_h_toy")
    cfg = program.model_config(arch, 640)
    sample, limit = [(600, 8), (300, 8)], arch["check_limits"]["expert_layer_rel_err"]
    sound, departed = [], []
    for seed in (3, 2 ** 31 + 5):
        seqs = sc.sample_tokens(seed, opcount.dims(arch)["vocab"], sample)
        handed = mc.reference(arch, seed, sample, seqs, rows=256)[3]
        assert handed.shape == (3, 512, 64)
        want = mc.reference_experts(arch, seed, handed)
        sound.append(mc.layer_errors(mc.program_experts(weights.serving_params(arch, seed), cfg, handed, 128), want).max())
        kw = dict(quant=int8_fake_quant) if control == "int8_experts" else dict(control=control)
        departed.append(mc.layer_errors(mc.reference_experts(arch, seed, handed, **kw), want).max())
    assert max(sound) * 1.5 < limit < min(departed) / 1.5, (sound, departed)
