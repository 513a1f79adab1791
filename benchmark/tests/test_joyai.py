"""The JoyAI family's own counts (a module beside the stack, a share of the
experts: what ``opcount`` cannot count) against ISSUE 35's arithmetic, the
program's view of the same configuration, the controls of the round's two
comparisons at a toy width, the new reader and the new driver's exchange of
one comparison for another."""

import json
import os
import types

import pytest

from harness import families, mtp_check, opcount, program, registry, serving_check as sc, weights
from harness.drivers import closed_decode, closed_decode_mtp
from references.common import int8_fake_quant

ARCH = registry.load_config("joyai-llm-flash")
FAM = families.of(ARCH)
CELL = "serve_joyai_mtp_decode_2k"


def test_parameter_counts_are_the_issues_and_the_programs():
    m = opcount.dims(ARCH)
    assert round(FAM.attn_params(m) / 1e6, 2) == 26.35 and round(FAM.dense_layer_params(m) / 1e6, 2) == 70.39
    assert round(FAM.expert_params(m) / 1e6, 4) == 4.7186 and round(FAM.layer_params(m) / 1e6, 2) == 635.57
    assert round(FAM.mtp_params(m) / 1e6, 2) == 643.97 and round(2 * m["vocab_rows"] * m["d"] / 1e6, 2) == 529.53
    assert round(opcount.num_params(ARCH) / 1e6, 1) == 3786.2 and round(opcount.weight_bytes(ARCH) / 1e9, 2) == 7.57
    # all 256 experts at the same depth with the module: 13.6 GB before any page
    whole = dict(ARCH, n_routed_experts=256)
    assert round(opcount.weight_bytes(whole) / 1e9, 1) == 13.6
    traffic = registry.load_traffic(registry.cell(CELL)["traffic"])
    cfg = program.model_config(ARCH, traffic["engine"]["max_seq"])
    assert cfg.num_params() == opcount.num_params(ARCH) and (cfg.n_layers, cfg.mtp_depth) == (5, 1)
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token) == (256, 128, 8)
    assert cfg.d_ff == 7168 and cfg.expert_width == 768 and cfg.head_dim == 192 and cfg.latent_dim == 576
    assert cfg.rope_theta == 32e6 and cfg.rope_scaling == "none" and cfg.moe_routed_scale == 2.5


def test_the_configuration_file_keeps_every_published_number_but_the_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "JoyAI-LLM-Flash")
    differs = sorted(k for k, v in entry["config"].items() if ARCH.get(k, "absent") != v)
    in_manifest = next(c for c in registry.manifest()["configs"] if c["name"] == "joyai-llm-flash")["reduced"]
    assert differs == sorted(ARCH["reduced"]) == sorted(in_manifest) == ["n_routed_experts", "num_hidden_layers"]
    assert ARCH["source"] == entry["source_url"] and set(ARCH["changed"]) == set(ARCH["reduced"])
    assert ARCH["num_nextn_predict_layers"] == 1 and next(iter(ARCH["assumed"])) == "mtp wiring"


def test_a_rounds_bytes_count_the_module_and_the_head_twice():
    traffic = registry.load_traffic(registry.cell(CELL)["traffic"])
    assert FAM.latent_bytes_per_token(ARCH) == 6 * 1152  # five layers of the stack and the module's block
    blocks = traffic["engine"]["n_blocks"]
    assert round(blocks * 64 * FAM.latent_bytes_per_token(ARCH) / 1e9, 2) == 1.84  # every row at its longest
    resident = traffic["rows"] * (traffic["prompt_tokens"] + traffic["output_tokens"] / 2)
    assert resident == 196608 and blocks - 1 == traffic["rows"] * traffic["engine"]["max_seq"] // 64
    m = opcount.dims(ARCH)
    touched = 126 / 128  # 1,024 pairs over 256 experts touch 126 of the 128 held (ISSUE 35: 98%)
    moe = FAM.moe_step_bytes(ARCH, touched)
    assert 5.9e9 < 5 * 126 * FAM.expert_params(m) * 2 < 5.96e9 < moe < 6.1e9  # 4 expert layers and the module's block
    assert round(FAM.latent_step_bytes(ARCH, resident) / 1e9, 2) == 1.41  # 1.36 GB of latents, 6 x wkv_b
    whole = FAM.decode_step_min_bytes(ARCH, resident, traffic["rows"], touched)
    rest = whole - moe - resident * FAM.latent_bytes_per_token(ARCH)
    assert round(rest / 1e9, 2) == 1.48  # the head twice (1.06 GB) and 0.42 GB of attention, dense FFN, projection
    assert 10.0 < 1e3 * whole / 819e9 < 11.0  # ISSUE 35 reckons ~10 ms a round at the roofline
    assert FAM.decode_step_min_bytes(ARCH, resident, 64, 0.5) == whole - moe + FAM.moe_step_bytes(ARCH, 0.5)
    # the module's half alone: its block, the projection, the head once, its one layer of latents
    half = FAM.mtp_step_bytes(ARCH, resident, traffic["rows"], touched)
    assert round(half / 1e9, 2) == 2.03 and half < whole / 2
    assert half > 2 * (m["vocab_rows"] * m["d"] + 126 * FAM.expert_params(m) + resident * 576)


def test_each_control_fails_where_the_program_passes():
    """At a toy width the bf16 program stays under both limits; the reference
    with int8 matmul operands, the precision below the stated one, passes
    neither, and a module wired one position off reads near 1."""
    with open(os.path.join(registry.BENCH_DIR, "tests", "toy", "joyai_control.json")) as f:
        arch = dict(json.load(f), name="joyai_control")
    traffic = registry.load_traffic(registry.cell(CELL)["traffic"])
    traffic.update(traffic.pop("rehearsal"))
    cfg = program.model_config(arch, traffic["engine"]["max_seq"])
    sample = [tuple(s) for s in traffic["check_sample"]]
    sound, int8, wired = [], [], []
    for seed in (3, 2 ** 31 + 5):
        seqs = mtp_check.sample_tokens(seed, opcount.dims(arch)["vocab"], sample)
        params = weights.serving_params(arch, seed)
        eng = program.serving_engine(params, cfg, traffic)
        assert eng.self_draft and eng.pool_info()["draft"] == "mtp"
        prog = mtp_check.program_logits(params, cfg, eng.pools, eng.alloc, eng.max_batch, eng.max_blocks,
                                        eng.block_size, sample, seqs)[:2]
        ref = mtp_check.reference_logits(arch, seed, sample, seqs)
        sound.append([sc.rel_err(p, r) for p, r in zip(prog, ref)])
        low = mtp_check.reference_logits(arch, seed, sample, seqs, quant=int8_fake_quant)
        int8.append([sc.rel_err(c, r) for c, r in zip(low, ref)])
        off = mtp_check.reference_logits(arch, seed, sample, seqs, hidden_shift=1)
        assert sc.rel_err(off[0], ref[0]) == 0.0  # the stack does not read the module
        wired.append(sc.rel_err(off[1], ref[1]))
    for i, name in enumerate(("verify_logits_rel_err", "draft_logits_rel_err")):
        limit = arch["check_limits"][name]
        assert max(s[i] for s in sound) < limit < min(c[i] for c in int8), (name, sound, int8)
    assert min(wired) > 0.5 > 10 * max(s[1] for s in sound), (wired, sound)


def test_spec_counter_on_a_recorded_trace_and_on_commit_spans_with_counts():
    from harness import program_trace as pt
    from readers import spec_counter

    path = os.path.join(os.path.dirname(__file__), "data", "small_program_v5e.xplane.pb")
    ctx = types.SimpleNamespace(_program_trace=pt.reduce(pt.load(path)))
    for stat in ("accept_rate", "tokens_per_round"):  # the parent's spans carry no such counter
        assert spec_counter.read(None, None, ctx, stat=stat) is None
    commit = lambda **meta: types.SimpleNamespace(span=types.SimpleNamespace(name="serving.commit", meta=meta))
    counted = types.SimpleNamespace(_program_trace=types.SimpleNamespace(uses=[
        commit(rows=64, spec_proposed=64, spec_accepted=1, spec_emitted=65),
        commit(rows=64, spec_proposed=62, spec_accepted=0, spec_emitted=61),
        commit(rows=3)]))
    assert spec_counter.read(None, None, counted, stat="accept_rate") == pytest.approx(100 / 126)
    assert spec_counter.read(None, None, counted, stat="tokens_per_round") == pytest.approx(1.0)
    with pytest.raises(ValueError, match="no stat"):
        spec_counter.read(None, None, counted, stat="other")


def test_mtp_roofline_reads_the_modules_own_touched_experts_or_nothing():
    """The module's half of a round against its own bytes: nothing to read on
    the parent's trace (no such counter) or for a family without the count."""
    from harness import program_trace as pt
    from readers import mtp_roofline

    path = os.path.join(os.path.dirname(__file__), "data", "small_program_v5e.xplane.pb")
    result = types.SimpleNamespace(observed={"resident_tokens": 1000, "rows": 4})
    summary = {"module_runs_s": {"jit_paged_mtp_round(123)": [0.03, 0.03]}}
    ctx = types.SimpleNamespace(arch=ARCH, _program_trace=pt.reduce(pt.load(path)))
    assert mtp_roofline.read(result, summary, ctx, match="jit_paged_mtp_round", scopes=["mtp.block"]) is None
    commit = lambda **meta: types.SimpleNamespace(span=types.SimpleNamespace(name="serving.commit", meta=meta))
    ctx = types.SimpleNamespace(arch=ARCH, _program_trace=types.SimpleNamespace(
        uses=[commit(mtp_touched=120, moe_experts=128)]))
    no_runs = {"module_runs_s": {"jit_paged_decode_step(1)": [0.01]}}
    assert mtp_roofline.read(result, no_runs, ctx, match="jit_paged_mtp_round", scopes=["mtp.block"]) is None
    other = types.SimpleNamespace(arch=registry.load_config("mistral-7b-v0.1"), _program_trace=ctx._program_trace)
    assert mtp_roofline.read(result, summary, other, match="jit_paged_mtp_round", scopes=["mtp.block"]) is None


def test_the_token_check_is_serving_checks_number_on_the_compared_rows_alone():
    """``mtp_check.token_regrets`` (the head on the rows that score an emitted
    token) gives ``serving_check.token_regrets``' regrets (the head on every row)."""
    import numpy as np

    with open(os.path.join(registry.BENCH_DIR, "tests", "toy", "joyai.json")) as f:
        arch = dict(json.load(f), name="joyai-toy")
    rng = np.random.default_rng(5)
    emitted = [(rng.integers(0, 256, n).tolist(), rng.integers(0, 256, k).tolist()) for n, k in ((9, 7), (14, 3))]
    theirs, _ = sc.token_regrets(arch, 11, emitted, 32)
    mine = mtp_check.token_regrets(arch, 11, emitted, 32)
    assert mine.shape == theirs.shape == (10,) and mine.max() > 1.0
    np.testing.assert_allclose(mine, theirs, rtol=1e-4, atol=1e-5)


def test_the_driver_exchanges_the_comparison_for_the_call_alone(monkeypatch):
    """``closed_decode.run`` is called with the round's comparison in the decode
    step's place and gets its own back afterwards, whatever happens inside; an
    engine that does not draft for itself is refused by name."""
    theirs = closed_decode.serving_check
    seen = {}

    def fake_run(ctx):
        seen["inside"] = closed_decode.serving_check
        eng = types.SimpleNamespace(self_draft=False)
        return closed_decode.serving_check.compare(ctx, eng, None, None)

    monkeypatch.setattr(closed_decode, "run", fake_run)
    with pytest.raises(RuntimeError, match="does not draft with the model's own module"):
        closed_decode_mtp.run(types.SimpleNamespace())
    assert closed_decode.serving_check is theirs and seen["inside"] is not theirs
    assert seen["inside"].compare_tokens is mtp_check.compare_tokens


def test_the_cell_names_only_what_the_files_say():
    man = registry.manifest()
    cell = registry.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == "joyai-llm-flash" and len(cell["why"]) <= 200
    traffic = registry.load_traffic(cell["traffic"])
    assert traffic["kind"] == "closed_decode_mtp" and traffic["engine"]["spec_k"] == 1
    assert (traffic["rows"], traffic["prompt_tokens"], traffic["output_tokens"]) == (64, 2048, 2048)
    assert closed_decode.first_wave(64, 2048, 2048)[-1] == (4064, 32)  # the longest prompt of the first wave
    assert CELL in next(m for m in man["end_to_end"] if m["name"] == "output_tokens_per_s")["workloads"]
    # 23 readings: what the round changes under the cell's own suffix (PR 53 gave the rest one name a metric)
    mine = registry.metrics_for(CELL, trace=True)
    assert len(mine) == 23 and all(m["moves"] == "output_tokens_per_s" for m in mine)
    own = [m for m in mine if m["name"].endswith(".jdecode")]
    assert own == [m for m in man["per_layer"] if m["name"].endswith(".jdecode")]
    assert len(own) == 9 and all(m["workloads"] == [CELL] for m in own)
    assert all(m["name"].endswith(".decode") and CELL in m["workloads"] for m in mine if m not in own)
    assert {m["layer"] for m in mine if m["name"].split(".")[0] in (
        "mtp_time_share", "mtp_draft_hbm_roofline", "spec_accept_rate", "spec_tokens_per_round")} == {"draft module"}
    # every one has its reader's file, and ISSUE 35's list is all there
    assert all(os.path.exists(os.path.join(registry.BENCH_DIR, "layer_metrics", m["name"] + ".json")) for m in mine)
    assert {"decode_step_ms", "decode_hbm_roofline", "latent_attn_hbm_roofline", "moe_experts_hbm_roofline",
            "scope_coverage"} <= {m["name"].split(".")[0] for m in mine}
