"""The Granite family's own counts (a state a row beside one layer's pages, a
share of the experts and of the tied vocabulary: what ``opcount`` cannot count)
against ISSUE 43's arithmetic, the program's view of the same configuration, the
controls of `correct` at a toy width, and the new reader."""

import json
import os
import types

import pytest

from harness import families, opcount, program, registry, serving_check as sc, weights
from references.common import int8_fake_quant

ARCH = registry.load_config("granite-4.0-h-small")
FAM = families.of(ARCH)
CELL = "serve_granite_decode_1k_4k"


def test_parameter_counts_are_the_issues_and_the_programs():
    m = opcount.dims(ARCH)
    assert round(FAM.ssm_params(m) / 1e6, 2) == 102.29 and round(FAM.attn_params(m) / 1e6, 2) == 41.94
    assert round(FAM.expert_params(m) / 1e6, 2) == 9.44 and round(3 * m["d"] * m["shared_ffn"] / 1e6, 2) == 18.87
    assert round(m["d"] * m["experts"] / 1e6, 2) == 0.29 and round(m["vocab_rows"] * m["d"] / 1e6, 1) == 102.8
    assert round(FAM.layer_params(m) / 1e6, 1) == 291.3  # a Mamba-2 layer with 18 experts held
    assert round((FAM.attn_params(m) + 2 * m["d"] + FAM.moe_params(m)) / 1e6, 1) == 231.0  # the attention layer
    assert m["attn_at"] == (5,) and (m["layers"], m["all_layers"]) == (9, 10)
    assert opcount.num_params(ARCH) == 2_955_758_208 and round(opcount.weight_bytes(ARCH) / 1e9, 2) == 5.91
    # by hand: the mixer's five parts
    d, w, c, h = 4096, 8192, 8448, 128
    assert FAM.ssm_params(m) == d * (w + c + h) + c * 4 + c + 3 * h + w + w * d
    cfg = program.model_config(ARCH, 5184)
    # the program's attention layer carries a zero output bias of d that the model does not have
    assert cfg.num_params() == opcount.num_params(ARCH) + m["d"] and cfg.n_layers == 10 and cfg.n_state_layers == 9
    assert cfg.layer_mixers == ("mamba",) * 5 + ("attn",) + ("mamba",) * 4
    assert cfg.layer_runs == ((0, 5), (5, 6), (6, 10)) and cfg.vocab_size == 25088 and cfg.tie_embeddings
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state, cfg.mamba_conv_kernel, cfg.mamba_chunk_size) == (
        128, 64, 128, 4, 256)
    assert (cfg.mamba_d_inner, cfg.mamba_conv_dim, cfg.head_dim, cfg.kv_heads) == (8192, 8448, 128, 8)
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token, cfg.expert_width, cfg.n_shared_experts) == (
        72, 18, 10, 768, 2)
    assert (cfg.embed_scale, cfg.residual_multiplier, cfg.attention_multiplier, cfg.logits_scaling) == (
        12.0, 0.22, 0.0078125, 16.0)
    assert cfg.pos_embed == "none" and cfg.moe_score == "softmax"


def test_the_configuration_file_keeps_every_published_number_but_the_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "granite-4.0-h-small")
    differs = sorted(k for k, v in entry["config"].items() if ARCH.get(k, "absent") != v)
    in_manifest = next(c for c in registry.manifest()["configs"] if c["name"] == "granite-4.0-h-small")["reduced"]
    assert differs == sorted(ARCH["reduced"]) == sorted(in_manifest)
    assert ARCH["source"] == entry["source_url"] and set(ARCH["changed"]) == set(ARCH["reduced"])
    assert ARCH["layer_types"] == entry["config"]["layer_types"][:10]  # one whole period, as published
    assert ARCH["n_experts_routed"] == entry["config"]["num_local_experts"] == 72


def test_step_bytes_count_the_state_the_pages_and_the_experts_touched():
    per_row = FAM.state_bytes_per_row(ARCH)
    assert per_row == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2) == 9 * 4_244_992
    assert round(129 * per_row / 1e9, 2) == 4.93  # ISSUE 43: 129 slots x 9 layers
    assert FAM.kv_bytes_per_token_layer(ARCH) == 4096  # 8 KV heads x 128 x K and V x 2 bytes, one layer of ten
    assert round(6657 * 64 * FAM.kv_bytes_per_token_layer(ARCH) / 1e9, 2) == 1.75
    # ISSUE 43: state read and written 2 x 128 x 9 x 4.19 MB = 9.66 GB; the token's operands beside it
    state = 2 * 128 * 9 * 128 * 64 * 128 * 4
    assert round(state / 1e9, 2) == 9.66
    assert FAM.ssm_step_bytes(ARCH, 128) == state + 9 * 128 * 4 * (2 * 8192 + 2 * 128 + 128)
    resident = 128 * (1024 + 2048)
    assert round(FAM.attn_step_bytes(ARCH, resident) / 1e9, 2) == 1.61
    m = opcount.dims(ARCH)
    moe = FAM.moe_step_bytes(ARCH, 1.0)
    assert moe == 10 * 2 * (18 * FAM.expert_params(m) + 3 * 4096 * 1536 + 4096 * 72)
    whole = FAM.decode_step_min_bytes(ARCH, resident, 128, 1.0)
    assert 17.1e9 < whole < 17.3e9 and 20.5 < 1e3 * whole / 819e9 < 21.5  # ISSUE 43: 17.2 GB, 21 ms
    assert 0.55 < state / whole < 0.57  # the state is 56% of the step's bytes
    assert FAM.decode_step_min_bytes(ARCH, resident, 128, 0.5) == whole - moe + FAM.moe_step_bytes(ARCH, 0.5)
    ops, moved = FAM.ssm_chunk_ops_bytes(ARCH, 1024)
    # 4 chunks of 256 a layer: C B^T once a group, and a head the masked product and the two state matmuls
    assert ops == 9 * 2 * 4 * (256 * 256 * 128 + 128 * (256 * 256 * 64 + 2 * 256 * 64 * 128))
    assert moved == 9 * 4 * (1024 * (2 * 8192 + 2 * 128 + 128) + 2 * 128 * 64 * 128)
    assert moved / 819e9 > ops / 197e12  # the byte bound is the larger for a 1,024-token prompt


def test_the_cell_names_what_the_files_say():
    man = registry.manifest()
    cell = registry.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("granite-4.0-h-small", "decode_closed_ssm_1k_4k", 1)
    traffic, lings = registry.load_traffic(cell["traffic"]), registry.load_traffic("decode_closed_1k_4k")
    same = ("rows", "prompt_tokens", "output_tokens", "first_wave_group", "warm_ticks", "trace_seconds",
            "engine", "check_sample", "check_requests", "rehearsal")
    assert all(traffic[k] == lings[k] for k in same)  # Ling's numbers, number for number
    assert traffic["engine"] == {"max_batch": 128, "n_blocks": 6657, "max_seq": 5184, "block_size": 64}
    traced = [m["name"] for m in registry.metrics_for(CELL, trace=True)]
    assert traced[-1] == "ssm_decode_hbm_roofline.gdecode" and len(traced) == 18
    # what it shares with the other cells stands under the metric moved, not under Ling's suffix (PR 53)
    assert all(name.endswith(".decode") for name in traced[:-1])
    assert {"state_slots_peak.decode", "moe_routed_here_share.decode", "moe_held_load_max_over_mean.decode",
            "decode_touched_hbm_roofline.decode"} <= set(traced)
    assert CELL in next(m for m in man["end_to_end"] if m["name"] == "output_tokens_per_s")["workloads"]
    # every metric file of the cell's own suffix is one the manifest lists
    assert [n for n in registry.list_all()["layer_metrics"] if n.endswith(".gdecode")] == [traced[-1]]
    # `correct` holds the state slots beside the logits: a driver of its own around closed_decode.run
    assert traffic["kind"] == "closed_decode_ssm" and lings["kind"] == "closed_decode"
    assert list(ARCH["check_limits"]) == ["logits_rel_err", "state_rel_err", "state_first_rel_err", "engine_token_regret"]


LONG = {"engine": {"max_batch": 4, "n_blocks": 161, "max_seq": 640, "block_size": 8},
        "check_sample": [[600, 8], [300, 8]]}  # a head keeps hundreds of roundings before a rounded state shows


@pytest.mark.parametrize("control", ["int8", "rope", "sqrt_scale", "no_decay", "bf16_state"])
def test_a_control_fails_where_the_program_passes(control):
    """At a toy width the bf16 program stays under the limits, and the reference
    with int8 matmul operands (the precision below the stated one), with rotary
    positions on the attention layer, with scores scaled by 1/sqrt(head_dim) or
    with the decay left at 1 does not, by the logits; nor with its state rounded
    to bfloat16 after every token (the precision below the state's), by the two
    numbers of the state slots, which is what the logits cannot tell."""
    from harness import ssm_check

    with open(os.path.join(registry.BENCH_DIR, "tests", "toy", "granite_control.json")) as f:
        arch = dict(json.load(f), name="granite_control")
    traffic = registry.load_traffic("decode_closed_ssm_1k_4k")
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


def test_the_new_reader_on_a_recorded_trace_and_on_a_family_without_the_counts(monkeypatch):
    """On PR 25's small xplane (one program ``jit(prog)`` with the scopes ``mlp``
    and ``attn.core``, no ``ssm.*`` scope): None from every part, as the parent's
    program gives, and nothing raises; None for a family that counts no such bytes."""
    from harness import reduce_trace
    from readers import ssm_roofline

    path = os.path.join(os.path.dirname(__file__), "data", "small_program_v5e.xplane.pb")
    monkeypatch.setattr(reduce_trace, "find_xplane", lambda trace_dir: path)
    ctx = types.SimpleNamespace(arch=ARCH, trace_dir=os.path.dirname(path),
                                devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    result = types.SimpleNamespace(observed={"resident_tokens": 1000.0, "rows": 4, "prefill_tokens": 1024})
    spec = registry.layer_metric_spec("ssm_decode_hbm_roofline.gdecode")
    assert spec == {"reader": "ssm_roofline", "args": {"match": "jit_paged_decode_step", "scopes": ["ssm.step"]}}
    no_runs = {"module_runs_s": {"jit_other(1)": [0.03]}}
    assert ssm_roofline.read(result, no_runs, ctx, **spec["args"]) is None
    runs = {"module_runs_s": {"jit_prog(1)": [0.03]}}
    assert ssm_roofline.read(result, runs, ctx, match="jit_prog", scopes=["ssm.step"]) is None
    # the recorded program's attention scope, read as if it were the state's pass: a share, and not None
    got = ssm_roofline.read(result, runs, ctx, match="jit_prog", scopes=["attn.core"])
    assert got is not None and got > 0
    ling = types.SimpleNamespace(arch=registry.load_config("ling-3.0-flash"), trace_dir=ctx.trace_dir, devices=ctx.devices)
    assert ssm_roofline.read(result, runs, ling, match="jit_prog", scopes=["attn.core"]) is None
