"""The Trinity family's own counts (two kinds of attention layer, two cache
lifetimes: what ``opcount`` cannot count) against ISSUE 41's arithmetic and a
hand count, the program's view of the same configuration, the rehearsal of the
cell and its controls at a toy width, the new readers on a trace without what
they read, and the new driver's exchange of one comparison for another."""

import json
import os
import types

import numpy as np
import pytest

from harness import families, mtp_check, opcount, program, registry, serving_check as sc, trinity_check, weights
from harness.drivers import closed_decode, closed_decode_window
from references.common import int8_fake_quant

ARCH = registry.load_config("trinity-mini")
FAM = families.of(ARCH)
CELL = "serve_trinity_decode_1k_8k"
TOY = os.path.join(registry.BENCH_DIR, "tests", "toy")


def test_parameter_counts_are_the_issues_and_the_programs():
    m = opcount.dims(ARCH)
    assert round(FAM.attn_params(m) / 1e6, 2) == 27.26 and round(FAM.dense_layer_params(m) / 1e6, 1) == 65.0
    assert round(FAM.layer_params(m) / 1e6, 1) == 839.1 and round(128 * FAM.expert_params(m) / 1e6, 1) == 805.3
    assert round(2 * m["vocab_rows"] * m["d"] / 1e6, 1) == 820.0
    assert round(opcount.num_params(ARCH) / 1e6, 1) == 4241.5 and round(opcount.weight_bytes(ARCH) / 1e9, 2) == 8.48
    traffic = registry.load_traffic(registry.cell(CELL)["traffic"])
    cfg = program.model_config(ARCH, traffic["engine"]["max_seq"])
    # the program carries an output bias a layer that Trinity does not have
    assert cfg.num_params() == opcount.num_params(ARCH) + 5 * 2048
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (5, 1, 32, 4, 128)
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token, cfg.expert_width) == (128, 128, 8, 1024)
    assert cfg.d_ff == 6144 and cfg.sliding_window == 2048 and cfg.moe_routed_scale == 2.826
    assert cfg.attn_kinds == ("window",) * 4 + ("full",) and cfg.two_lifetimes and not cfg.rope_full_layers
    assert cfg.qk_norm and cfg.attn_output_gate and cfg.sandwich_norm and cfg.embed_scale
    assert cfg.rope_theta == 1e4 and cfg.rope_scaling == "none" and cfg.norm_eps == 1e-5


def test_the_configuration_file_keeps_every_published_number_but_the_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "Trinity-Mini")
    differs = sorted(k for k, v in entry["config"].items() if ARCH.get(k, "absent") != v)
    in_manifest = next(c for c in registry.manifest()["configs"] if c["name"] == "trinity-mini")["reduced"]
    assert differs == sorted(ARCH["reduced"]) == sorted(in_manifest) == ["layer_types", "num_dense_layers",
                                                                          "num_hidden_layers"]
    assert ARCH["source"] == entry["source_url"] and set(ARCH["changed"]) == set(ARCH["reduced"])
    # the five layers kept are published layers 1, 4, 5, 6, 7 in order
    assert ARCH["layer_types"] == [entry["config"]["layer_types"][i] for i in (0, 3, 4, 5, 6)][:1] + \
        [entry["config"]["layer_types"][i] for i in (4, 5, 6, 7)]
    assert {"output gate", "qk norm", "rope", "four norms", "router", "embedding scale"} <= set(ARCH["assumed"])
    assert ARCH["stated_precision"].startswith("bfloat16") and "pipeline stages" in ARCH["deployment"]


def test_a_steps_bytes_by_hand():
    """The byte counts the rooflines divide by, against a count by hand at the
    cell's sizes (ISSUE 41: experts 6.3 GB, attention 1.7 GB, head 0.82, the
    rest 0.4: 11.3 ms at 819 GB/s)."""
    traffic = registry.load_traffic(registry.cell(CELL)["traffic"])
    rows, p, o = traffic["rows"], traffic["prompt_tokens"], traffic["output_tokens"]
    assert FAM.kv_bytes_per_token_layer(ARCH) == 2 * 4 * 128 * 2 == 2048
    resident = rows * (p + o / 2)
    assert resident == 64 * 5120
    # a row's length is uniform over [1024, 9216): an eighth of the rows stand inside the window
    inside = FAM.window_tokens(ARCH, rows, p, o)
    assert inside == 64 * ((2048 - 1024) * (1024 + 2048) / 2 + (9216 - 2048) * 2048) / 8192 == 64 * 1984
    assert FAM.window_tokens(ARCH, 2, 4096, 100) == 2 * 2048 and FAM.window_tokens(ARCH, 2, 100, 200) == 2 * 200
    full = FAM.attn_step_bytes(ARCH, "full", resident)
    window = FAM.attn_step_bytes(ARCH, "window", inside)
    assert full == 1 * 64 * 5120 * 2048 and round(full / 1e9, 2) == 0.67
    assert window == 4 * 64 * 1984 * 2048 and round(window / 1e9, 2) == 1.04
    touched = 0.98
    moe = FAM.moe_step_bytes(ARCH, touched)
    by_hand = 4 * ((0.98 * 128 + 1) * 3 * 2048 * 1024 + 2048 * 128 + 128) * 2
    assert moe == by_hand and round(moe / 1e9, 1) == 6.4  # 6.3 GB of experts, 0.05 of shared experts, 0.002 of routers
    whole = FAM.decode_step_min_bytes(ARCH, resident, rows, touched, window_resident=inside)
    rest = whole - moe - full - window
    # head 0.82 GB, five layers of attention 0.27, the dense FFN 0.075, four norms a layer, 64 embedding rows
    assert rest == pytest.approx((5 * (27_263_232 + 4 * 2048) + 3 * 2048 * 6144 + 200192 * 2048 + 2048 + 64 * 2048) * 2)
    assert round(rest / 1e9, 2) == 1.17 and 11.0 < 1e3 * whole / 819e9 < 11.6
    # a reader that knows the rows' mean length alone counts the window layers a little high, never low
    loose = FAM.decode_step_min_bytes(ARCH, resident, rows, touched)
    assert loose - whole == pytest.approx(4 * 64 * (2048 - 1984) * 2048) and 0 < loose - whole < 0.004 * whole
    # one block list for all five layers: what the window pool saves
    pages = (64 * 145 + 1) * 64 * 2048
    assert round(5 * pages / 1e9, 2) == 6.08 and round((pages + 4 * (64 * 34 + 1) * 64 * 2048) / 1e9, 2) == 2.36


def test_each_control_fails_where_the_program_passes():
    """At a toy width the bf16 program's rows stay under the limit on their
    median; the reference with int8 matmul operands (the precision below the
    stated one) does not, nor, on the sample prefilled past the window, the
    reference with every layer full or with RoPE on the full layer."""
    with open(os.path.join(TOY, "trinity_control.json")) as f:
        arch = dict(json.load(f), name="trinity_control")
    traffic = registry.load_traffic(registry.cell(CELL)["traffic"])
    traffic.update(traffic.pop("rehearsal"))
    cfg = program.model_config(arch, traffic["engine"]["max_seq"])
    sample = [tuple(s) for s in traffic["check_sample"]]
    limit = arch["check_limits"]["logits_row_median_err"]
    median = lambda a, b: float(np.median(trinity_check.row_errors(a, b)))
    for seed in (3, 2 ** 31 + 5):
        seqs = sc.sample_tokens(seed, opcount.dims(arch)["vocab"], sample)
        params = weights.serving_params(arch, seed)
        eng = program.serving_engine(params, cfg, traffic)
        assert eng.two_lifetimes and eng.pool_info()["window_layers"] == 4
        prog, did = trinity_check.program_logits(params, cfg, eng, sample, seqs)
        assert did["window_pages_released"] > 0
        ref = trinity_check.reference_logits(arch, seed, sample, seqs)
        assert median(prog, ref) < limit
        assert median(trinity_check.reference_logits(arch, seed, sample, seqs, quant=int8_fake_quant), ref) > limit
        for control in ("all_full", "rope_on_full"):
            other = trinity_check.reference_logits(dict(arch, control=control), seed, sample[-1:], seqs[-1:])
            assert median(other, ref[-1:]) > 2 * limit, control
        mtp_check._REFERENCES.clear()


def test_the_rehearsal_runs_the_cells_own_driver(tmp_path):
    """``rehearse.py``'s run of the cell at the toy width: the loop, both pools,
    the check through both, counts that a CPU can give."""
    import time

    import jax

    from harness.context import Ctx

    with open(os.path.join(TOY, "trinity.json")) as f:
        arch = dict(json.load(f), name="trinity-mini")
    traffic = registry.load_traffic(registry.cell(CELL)["traffic"])
    traffic.update(traffic.pop("rehearsal"))
    ctx = Ctx(cell=registry.cell(CELL), arch=arch, traffic=traffic, seed=2 ** 31 + 11, seconds=0.5, trace=False,
              devices=jax.devices()[:1], out_dir=str(tmp_path), t_start=time.perf_counter(), tag="t", rehearsal=True)
    result = registry.driver(traffic["kind"])(ctx)
    assert set(result.compared) == {"logits_rel_err", "logits_row_median_err", "engine_token_regret",
                                    "requests_wrong_length"}
    assert all(v <= limit for v, limit in result.compared.values()) and result.failed == 0
    obs = result.observed
    assert obs["preemptions"] == 0 and obs["compiles_in_window"] == 0
    assert 0 < obs["window_blocks_peak"] <= obs["window_blocks_total"] == 4 * (16 // 8 + 2)
    assert obs["window_pages_released"] > 0 and obs["kv_blocks_peak"] <= obs["kv_blocks_total"]
    assert 0 < obs["window_attn_pages_live"] <= obs["window_attn_pages_tabled"] < obs["attn_pages_tabled"]
    assert closed_decode.serving_check is sc and closed_decode.program is program  # handed back


def test_the_new_readers_find_nothing_on_a_trace_without_their_scopes_or_spans(monkeypatch):
    from harness import program_trace as pt, reduce_trace
    from readers import attn_kind_roofline, scope_within, span_counter

    path = os.path.join(os.path.dirname(__file__), "data", "small_program_v5e.xplane.pb")
    monkeypatch.setattr(reduce_trace, "find_xplane", lambda trace_dir: path)
    red = pt.reduce(pt.load(path))
    ctx = types.SimpleNamespace(_program_trace=red, arch=ARCH, trace_dir=os.path.dirname(path),
                                traffic=registry.load_traffic("decode_closed_1k_8k"),
                                devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    assert scope_within.read(None, None, ctx, outer="attn.window", scopes=["attn.core"]) is None
    assert scope_within.read(None, None, ctx, outer="attn.full") is None
    assert span_counter.read(None, None, ctx, span="serving.release_pages", key="released") is None
    result = types.SimpleNamespace(observed={"resident_tokens": 1000.0, "rows": 4})
    summary = {"module_runs_s": {"jit_paged_decode_steps(1)": [0.01, 0.01]}}
    for kind in ("window", "full"):  # a decode program with no op under attn.<kind>: the parent's
        assert attn_kind_roofline.read(result, summary, ctx, kind=kind, match="jit_paged_decode_step") is None
    assert attn_kind_roofline.read(result, {"module_runs_s": {}}, ctx, kind="full", match="jit_paged_decode_step") is None
    other = types.SimpleNamespace(**{**vars(ctx), "arch": registry.load_config("mistral-7b-v0.1")})
    assert attn_kind_roofline.read(result, summary, other, kind="full", match="jit_paged_decode_step") is None
    # and on spans and paths that carry them, the sums
    release = lambda n: types.SimpleNamespace(span=types.SimpleNamespace(name="serving.release_pages", meta={"released": n}))
    counted = types.SimpleNamespace(_program_trace=types.SimpleNamespace(uses=[release(3), release(0), release(2)]))
    assert span_counter.read(None, None, counted, span="serving.release_pages", key="released") == 5.0
    by_path = {("jit(f)/attn.window/attn.core/x", "a"): 0.2, ("jit(f)/attn.full/attn.core/x", "b"): 0.1,
               ("jit(f)/attn.window/attn.qkv/x", "c"): 0.4, ("jit(f)/mlp/x", "d"): 0.3}
    assert scope_within.seconds(by_path, "attn.window", ["attn.core", "attn.gate"]) == 0.2
    assert scope_within.seconds(by_path, "attn.window") == pytest.approx(0.6)
    assert scope_within.seconds(by_path, "attn.full", ["attn.core"]) == 0.1


def test_the_driver_exchanges_the_comparison_and_the_engine_for_the_call_alone(monkeypatch):
    theirs = closed_decode.serving_check, closed_decode.program
    seen = {}

    def fake_run(ctx):
        seen["inside"] = closed_decode.serving_check, closed_decode.program
        eng = types.SimpleNamespace(two_lifetimes=False, stats={}, pool_info=lambda: {"decode_attention": "gather"})
        return closed_decode.serving_check.compare(ctx, eng, None, None)

    monkeypatch.setattr(closed_decode, "run", fake_run)
    with pytest.raises(RuntimeError, match="keeps one block list a row"):
        closed_decode_window.run(types.SimpleNamespace(log=lambda msg: None))
    assert (closed_decode.serving_check, closed_decode.program) == theirs
    assert seen["inside"][0] is not theirs[0] and seen["inside"][0].compare_tokens is mtp_check.compare_tokens
    assert seen["inside"][1].model_config is program.model_config


def test_the_cell_names_only_what_the_files_say():
    man = registry.manifest()
    cell = registry.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == "trinity-mini" and len(cell["why"]) <= 200
    traffic = registry.load_traffic(cell["traffic"])
    assert traffic["kind"] == "closed_decode_window"
    assert traffic["engine"] == {"max_batch": 64, "n_blocks": 9281, "max_seq": 9280, "block_size": 64}
    assert (traffic["rows"], traffic["prompt_tokens"], traffic["output_tokens"]) == (64, 1024, 8192)
    assert (traffic["first_wave_group"], traffic["warm_ticks"]) == (8, 96)
    wave = closed_decode.first_wave(64, 1024, 8192)
    assert wave[1] == (1152, 8064) and wave[-1] == (9088, 128) and sum(p > 2048 for p, _ in wave) == 55
    assert traffic["check_sample"] == [[1024, 128], [2000, 128], [9000, 128]]
    assert CELL in next(m for m in man["end_to_end"] if m["name"] == "output_tokens_per_s")["workloads"]
    # 23 readings: the two lifetimes' own under the cell's suffix (PR 53 gave the rest one name a metric)
    mine = registry.metrics_for(CELL, trace=True)
    assert len(mine) == 23 and all(m["moves"] == "output_tokens_per_s" for m in mine)
    own = [m for m in mine if m["name"].endswith(".tdecode")]
    assert own == [m for m in man["per_layer"] if m["name"].endswith(".tdecode")]
    assert len(own) == 8 and all(m["workloads"] == [CELL] for m in own) and man["workloads"].index(cell) >= 7
    assert all(m["name"].endswith(".decode") and CELL in m["workloads"] for m in mine if m not in own)
    assert all(os.path.exists(os.path.join(registry.BENCH_DIR, "layer_metrics", m["name"] + ".json")) for m in mine)
    names = {m["name"].split(".")[0] for m in mine}
    assert {"decode_step_ms", "prefill_device_share", "device_idle_share", "compiles_in_window", "batch_occupancy",
            "host_blocked_share", "tick_host_ms", "kv_blocks_peak", "hbm_resident_gb", "preemptions", "scope_coverage",
            "decode_touched_hbm_roofline", "moe_time_share", "moe_experts_hbm_roofline", "moe_experts_touched_share",
            "moe_load_max_over_mean", "window_attn_time_share", "full_attn_time_share", "window_attn_hbm_roofline",
            "full_attn_hbm_roofline", "window_blocks_peak", "window_pages_released", "release_host_ms"} == names
    layers = {m["name"].split(".")[0]: m["layer"] for m in mine}
    assert {layers[n] for n in ("window_blocks_peak", "window_pages_released", "release_host_ms")} == {"kv manager"}
    assert layers["window_attn_hbm_roofline"] == layers["full_attn_hbm_roofline"] == "paged attention"
