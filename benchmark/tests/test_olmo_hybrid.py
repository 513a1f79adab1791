"""The Olmo-Hybrid family's own counts (a rectangular float32 state a row beside
two layers' 30-head pages: what ``opcount`` cannot count) against ISSUE 54's
arithmetic, the program's view of the same configuration, the controls of
`correct` at a toy width, and the new reader."""

import json
import os
import types

import pytest

from harness import families, opcount, program, registry, serving_check as sc, weights
from references.common import int8_fake_quant

ARCH = registry.load_config("olmo-hybrid-7b")
FAM = families.of(ARCH)
CELL = "serve_olmo_hybrid_decode_512_2k"


def test_parameter_counts_are_the_issues_and_the_programs():
    m = opcount.dims(ARCH)
    assert FAM.gdn_params(m) == 88_750_332 and FAM.ffn_params(m) == 126_812_160
    assert FAM.layer_params(m) == 215_570_172 and FAM.attn_layer_params(m) == 185_809_920
    assert FAM.attn_params(m) == 58_990_080  # four 3,840 x 3,840 projections and the two whole-width norms
    assert 3 * FAM.layer_params(m) + FAM.attn_layer_params(m) == 832_520_436  # a period of four
    assert m["attn_at"] == (3, 7) and (m["layers"], m["all_layers"], m["attn_layers"]) == (6, 8, 2)
    assert opcount.num_params(ARCH) == 2_435_748_072 and round(opcount.weight_bytes(ARCH) / 1e9, 2) == 4.87
    # ISSUE 54's other depths: 16, 20 and 32 layers
    other = 2 * 100_352 * 3840 + 3840
    assert [round((n * 832_520_436 + other) * 2 / 1e9, 2) for n in (4, 5, 8)] == [8.20, 9.87, 14.86]
    cfg = program.model_config(ARCH, 2624)
    # the program's attention layers carry a zero output bias of d that the model does not have
    assert cfg.num_params() == opcount.num_params(ARCH) + 2 * m["d"] and cfg.n_layers == 8 and cfg.n_state_layers == 6
    assert cfg.layer_mixers == ("gdn", "gdn", "gdn", "attn") * 2
    assert cfg.layer_runs == ((0, 3), (3, 4), (4, 7), (7, 8)) and cfg.vocab_size == 100352 and not cfg.tie_embeddings
    assert (cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_conv_kernel, cfg.gdn_conv_dim) == (
        30, 96, 192, 4, 11520)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff) == (30, 30, 128, 11008)
    assert cfg.pos_embed == "none" and cfg.norm_placement == "output" and cfg.qk_norm_whole
    assert cfg.gdn_allow_neg_eigval and cfg.norm_eps == 1e-6 and not cfg.n_experts


def test_the_configuration_file_keeps_every_published_number_but_the_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "Olmo-Hybrid-7B")
    differs = sorted(k for k, v in entry["config"].items() if ARCH.get(k, "absent") != v)
    in_manifest = next(c for c in registry.manifest()["configs"] if c["name"] == "olmo-hybrid-7b")["reduced"]
    assert differs == sorted(ARCH["reduced"]) == sorted(in_manifest) == ["layer_types", "num_hidden_layers"]
    assert ARCH["source"] == entry["source_url"] and set(ARCH["changed"]) == set(ARCH["reduced"])
    assert ARCH["layer_types"] == entry["config"]["layer_types"][:8]  # two whole periods, as published
    assert ARCH["rope_parameters"] == {"rope_theta": None} and ARCH["vocab_size"] == 100352
    for key in ("assumed", "deployment", "stated_precision", "check_limits"):
        assert ARCH[key]


def test_step_bytes_count_the_state_and_the_two_layers_pages():
    per_row = FAM.state_bytes_per_row(ARCH)
    assert per_row == 6 * (30 * 96 * 192 * 4 + 3 * 11520 * 2) == 6 * (2_211_840 + 69_120)
    assert round(129 * per_row / 1e9, 2) == 1.77  # ISSUE 54: state and tails 1.75 (128 rows; the pool has a scratch slot)
    assert FAM.kv_bytes_per_token_layer(ARCH) == 15360  # 30 KV heads x 128 x K and V x 2 bytes: 3.75 x Mistral's 4,096
    # ISSUE 54: three Gated DeltaNet layers move 3 x 2 x 2.21 MB a row and step, the period's one full layer
    # 15,360 B a token: they cross at 864 tokens
    assert round(3 * 2 * 2_211_840 / 15360) == 864
    resident = 128 * (512 + 1024)
    assert resident == 196_608 and round(FAM.attn_step_bytes(ARCH, "full", resident) / 1e9, 2) == 6.04
    state = 2 * 128 * 6 * 2_211_840
    assert round(state / 1e9, 2) == 3.40
    assert FAM.kda_step_bytes(ARCH, 128) == state + 6 * 128 * 4 * (2 * 2880 + 2 * 5760 + 60)
    whole = FAM.decode_step_min_bytes(ARCH, resident, 128)
    m = opcount.dims(ARCH)
    weights_read = (opcount.num_params(ARCH) - 100_352 * 3840 + 128 * 3840) * 2
    assert whole == weights_read + state + FAM.attn_step_bytes(ARCH, "full", resident)
    assert round(weights_read / 1e9, 2) == 4.10 and 13.4e9 < whole < 13.6e9  # ISSUE 54: 13.5 GB
    assert 16.3 < 1e3 * whole / 819e9 < 16.7 and 7600 < 128 / (whole / 819e9) < 7800  # 16.5 ms, a ceiling near 7,740 tokens/s
    assert 0.24 < state / whole < 0.26 and 0.44 < FAM.attn_step_bytes(ARCH, "full", resident) / whole < 0.46
    with pytest.raises(ValueError, match="full"):
        FAM.attn_step_bytes(ARCH, "window", resident)
    ops, moved = FAM.kda_chunk_ops_bytes(ARCH, 512)
    assert ops == 6 * 2 * 8 * 30 * (64 * 64 * 96 + 3 * 64 * 96 * 192 + 64 * 64 * 192)
    assert moved == 6 * 4 * (512 * 30 * (2 * 96 + 2 * 192 + 2) + 2 * 30 * 96 * 192)
    assert moved / 819e9 > ops / 197e12  # the byte bound is the larger for a 512-token prompt
    assert m["vocab"] == m["vocab_rows"] == 100352


def test_the_cell_names_what_the_files_say():
    man = registry.manifest()
    cell = registry.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("olmo-hybrid-7b", "decode_closed_gdn_512_2k", 1)
    traffic, granites = registry.load_traffic(cell["traffic"]), registry.load_traffic("decode_closed_ssm_1k_4k")
    same = ("rows", "first_wave_group", "warm_ticks", "trace_seconds", "check_requests", "rehearsal", "kind")
    assert all(traffic[k] == granites[k] for k in same)
    assert (traffic["prompt_tokens"], traffic["output_tokens"]) == (512, 2048)
    # sized as Granite's: max_seq a page past prompt + output, 13/12 of the mean residency's pages and the scratch block
    assert traffic["engine"] == {"max_batch": 128, "n_blocks": 128 * 26 + 1, "max_seq": 512 + 2048 + 64, "block_size": 64}
    assert granites["engine"]["n_blocks"] == 128 * 52 + 1 and granites["engine"]["max_seq"] == 1024 + 4096 + 64
    traced = [m["name"] for m in registry.metrics_for(CELL, trace=True)]
    own = [n for n in traced if n.endswith(".odecode")]
    assert own == ["gdn_time_share.odecode", "gdn_decode_hbm_roofline.odecode", "gdn_prefill_roofline.odecode",
                   "full_attn_hbm_roofline.odecode", "scope_coverage.odecode", "decode_state_hbm_roofline.odecode"]
    assert traced[-6:] == own and all(n.endswith(".decode") for n in traced[:-6]) and len(traced) == 19
    assert {"state_slots_peak.decode", "paged_attn_time_share.decode", "mlp_time_share.decode",
            "decode_step_ms.decode", "hbm_resident_gb.decode"} <= set(traced)
    assert CELL in next(m for m in man["end_to_end"] if m["name"] == "output_tokens_per_s")["workloads"]
    assert [n for n in registry.list_all()["layer_metrics"] if n.endswith(".odecode")] == sorted(own)
    for m in man["per_layer"]:
        if m["name"] in own:
            assert m["workloads"] == [CELL] and m["moves"] == "output_tokens_per_s"
    assert list(ARCH["check_limits"]) == ["logits_rel_err", "state_rel_err", "state_first_rel_err", "engine_token_regret"]


LONG = {"engine": {"max_batch": 4, "n_blocks": 321, "max_seq": 1280, "block_size": 8},
        "check_sample": [[1200, 8], [800, 8]]}  # a head keeps hundreds of roundings before a rounded state shows


@pytest.mark.parametrize("control", ["int8", "pre_norm", "dropped_norm", "head_qk_norm", "beta_one", "channel_decay",
                                     "rope", "bf16_state"])
def test_a_control_fails_where_the_program_passes(control):
    """At a toy width the bf16 program stays under the limits, and the reference
    with int8 matmul operands (the precision below the stated one), with a norm
    moved or dropped, per-head q/k norms, beta in (0, 1), a decay per channel or
    rotary positions does not, by the logits; nor with its state rounded to
    bfloat16 after every token (the precision below the state's), by the first
    layer's state slots (over all layers the bfloat16 activations that feed the
    later layers weigh more than the state's own rounding, in the sound program
    and the control alike: ``state_rel_err`` is held for what moves every layer,
    as a dropped norm does). The reference's ``bf16_softmax`` is no control of
    the cell: the kernels themselves hand the MXU bfloat16 probabilities, and its
    0.0014 lies thirty times under the bfloat16 activations' 0.04 here."""
    from harness import ssm_check

    with open(os.path.join(registry.BENCH_DIR, "tests", "toy", "olmo_hybrid_control.json")) as f:
        arch = dict(json.load(f), name="olmo_hybrid_control")
    traffic = registry.load_traffic("decode_closed_gdn_512_2k")
    traffic.update(traffic.pop("rehearsal"))
    held = ("state_first_rel_err",) if control == "bf16_state" else ("logits_rel_err",)
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
    print(control, "sound", sound, "departed", departed)
    for name in held:
        limit = arch["check_limits"][name]
        assert max(s[name] for s in sound) * 1.5 < limit < min(d[name] for d in departed) / 1.5, (name, sound, departed)


def test_the_new_reader_on_a_recorded_trace_and_on_a_family_without_the_counts(monkeypatch):
    """``readers/state_step_roofline.py``: the family's own bytes of a whole step
    over the decode program's mean run; None without a decode program, for a
    family that keeps no state and for one whose count wants the experts touched."""
    from readers import state_step_roofline

    ctx = types.SimpleNamespace(arch=ARCH, devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    result = types.SimpleNamespace(observed={"resident_tokens": 196608.0, "rows": 128})
    spec = registry.layer_metric_spec("decode_state_hbm_roofline.odecode")
    assert spec == {"reader": "state_step_roofline", "args": {"match": "jit_paged_decode_step"}}
    assert state_step_roofline.read(result, {"module_runs_s": {"jit_other(1)": [0.03]}}, ctx, **spec["args"]) is None
    runs = {"module_runs_s": {"jit_paged_decode_step(1)": [0.033, 0.033]}}
    got = state_step_roofline.read(result, runs, ctx, **spec["args"])
    assert got == pytest.approx(100 * FAM.decode_step_min_bytes(ARCH, 196608.0, 128) / 819e9 / 0.033) and 49 < got < 51
    for other in ("mistral-7b-v0.1", "granite-4.0-h-small", "ling-3.0-flash"):
        theirs = types.SimpleNamespace(arch=registry.load_config(other), devices=ctx.devices)
        assert state_step_roofline.read(result, runs, theirs, **spec["args"]) is None
    # the readers that exist serve the family by the names they ask for
    for name, reader in (("gdn_decode_hbm_roofline.odecode", "kda_roofline"),
                         ("gdn_prefill_roofline.odecode", "kda_roofline"),
                         ("full_attn_hbm_roofline.odecode", "attn_kind_roofline"),
                         ("gdn_time_share.odecode", "scope_share"), ("scope_coverage.odecode", "scope_share")):
        assert registry.layer_metric_spec(name)["reader"] == reader
    assert hasattr(FAM, "kda_step_bytes") and hasattr(FAM, "kda_chunk_ops_bytes") and not hasattr(FAM, "moe_step_bytes")
