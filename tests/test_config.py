"""Config layer: presets, validation, overrides, analytic param counts."""

import dataclasses
import json

import pytest

from pretraining_llm_tpu.config import _RETIRED_KEYS, Config, MeshConfig, ModelConfig, get_preset, list_presets


def test_presets_exist():
    names = list_presets()
    for required in (
        "gpt2-124m",
        "gpt2-350m-dp",
        "gpt2-1p3b-fsdp",
        "llama-1b",
        "gpt2-8k-sp",
        "reference-3b",
        "tiny",
    ):
        assert required in names


def test_reference_3b_param_count():
    # SURVEY.md §2.5: the reference's default config is 3.161B params
    # (103.0M tok-embed + 1.0M pos-embed + 64 x 46.16M blocks + 103.1M lm_head).
    cfg = get_preset("reference-3b").model
    n = cfg.num_params()
    assert abs(n - 3.161e9) / 3.161e9 < 0.01, n


def test_gpt2_124m_param_count():
    cfg = get_preset("gpt2-124m").model
    n = cfg.num_params()
    assert abs(n - 124e6) / 124e6 < 0.05, n


def test_unknown_override_rejected():
    cfg = get_preset("tiny")
    with pytest.raises(KeyError):
        cfg.with_overrides({"model.not_a_key": 1})
    with pytest.raises(KeyError):
        cfg.with_overrides({"nonsection.x": 1})


def test_override_applies():
    cfg = get_preset("tiny").with_overrides({"model.n_layers": 3, "train.lr": 1e-5})
    assert cfg.model.n_layers == 3
    assert cfg.train.lr == 1e-5


def test_validation_rejects_bad_values():
    with pytest.raises(ValueError):
        ModelConfig(activation="tanh")
    with pytest.raises(ValueError):
        ModelConfig(d_model=30, n_heads=4)
    with pytest.raises(ValueError):
        ModelConfig(tie_embeddings=True, lm_head_bias=True)


def test_mesh_sizes():
    assert MeshConfig(data=-1, fsdp=2).sizes(8) == (4, 2, 1, 1, 1, 1)
    assert MeshConfig(data=2, fsdp=2, tensor=2).sizes(8) == (2, 2, 2, 1, 1, 1)
    assert MeshConfig(data=-1, expert=4).sizes(8) == (2, 1, 1, 1, 4, 1)
    with pytest.raises(ValueError):
        MeshConfig(data=3).sizes(8)


def test_moe_validation():
    with pytest.raises(ValueError):
        ModelConfig(n_experts=4, experts_per_token=5)
    with pytest.raises(ValueError):
        ModelConfig(n_experts=4, expert_capacity_factor=0.0)
    ModelConfig(n_experts=4, experts_per_token=2)  # valid


def test_json_roundtrip():
    cfg = get_preset("llama-1b")
    restored = Config.from_json(cfg.to_json())
    assert restored == cfg


# What the parents of PR 29, PR 47 and PR 50 wrote into every checkpoint's saved
# config, and what a command line could still ask for: (key, value, the value
# named in the error, or None where the key is dropped and the config loads).
_RETIRED = [
    ("decode_cache_layout", "unstacked", None),
    ("decode_unroll_layers", False, None),
    ("scan_unroll", 1, None),
    ("decode_cache_layout", "stacked", "'unstacked'"),
    ("decode_unroll_layers", True, "False"),
    ("scan_unroll", 2, "=1"),
    ("ce_impl", "fused", "'chunked'"),
    ("remat", "save_big", "'save_attn_res'"),
    ("remat", "save_qkv_attn", "'save_attn_res'"),
    ("paged_attention_impl", "gather", None),
    ("ragged_kv_splits", 1, None),
    ("ragged_amla", False, None),
    ("paged_attention_impl", "kernel", "'gather'"),
    ("ragged_kv_splits", 0, "=1"),
    ("ragged_kv_splits", 4, "=1"),
    ("ragged_amla", True, "False"),
    ("flash_heads_major", False, None),
    ("flash_heads_major", True, "False"),
]


@pytest.mark.parametrize("key,value,use", _RETIRED, ids=[f"{k}={v}" for k, v, _ in _RETIRED])
def test_retired_model_keys(key, value, use):
    """A saved config and a dotted override meet one table: a retired field
    at the value the remaining path implements is dropped, a removed path is
    refused by name with the value to use."""
    saved = json.loads(Config().to_json())
    assert key not in saved["model"] or use is not None  # the field is gone
    saved["model"].update(decode_cache_layout="unstacked", decode_unroll_layers=False, scan_unroll=1,
                          paged_attention_impl="gather", ragged_kv_splits=1, ragged_amla=False,
                          flash_heads_major=False)
    saved["model"][key] = value
    if use is None:
        assert Config.from_json(json.dumps(saved)) == Config()
        assert Config().with_overrides({f"model.{key}": value}) == Config()
        return
    for load in (
        lambda: Config.from_json(json.dumps(saved)),
        lambda: Config().with_overrides({f"model.{key}": value}),
    ):
        removed_in = _RETIRED_KEYS[f"model.{key}"][2]
        with pytest.raises(ValueError, match=rf"model\.{key}={value!r}.*removed in {removed_in}") as e:
            load()
        assert use in str(e.value)


def test_serving_config_wiring():
    from pretraining_llm_tpu.config import ServingConfig

    cfg = get_preset("tiny").with_overrides(
        {"serving.pipeline_depth": 3, "serving.admit_batch": 4}
    )
    assert cfg.serving.pipeline_depth == 3
    assert cfg.serving.admit_batch == 4
    assert Config.from_json(cfg.to_json()).serving == cfg.serving
    # Pre-serving checkpoints (no "serving" section) load with defaults.
    import json as _json

    raw = _json.loads(get_preset("tiny").to_json())
    raw.pop("serving")
    legacy = Config.from_json(_json.dumps(raw))
    assert legacy.serving == ServingConfig()
    with pytest.raises(ValueError):
        ServingConfig(pipeline_depth=0)
    with pytest.raises(ValueError):
        ServingConfig(admit_batch=-1)


# Perf-preset intent table. Round 4 found the 350M preset silently running
# NAIVE attention for every pre-2026-08-01 measurement (only gpt2-124m set
# attention_impl="flash") — caught by a human reading a profile. This table
# makes that a class that cannot recur: every preset used for performance
# work must match its declared attention/remat/CE intent exactly, so a
# silently-defaulted knob fails CI instead of burning a hardware session.
# "tiny" is deliberately absent (test-only, perf knobs irrelevant).
_PERF_INTENT = {
    #                   attention_impl  remat             ce_impl
    "gpt2-124m":       ("flash",        "none",           "chunked"),
    "gpt2-350m-dp":    ("flash",        "none",           "chunked"),
    "gpt2-1p3b-fsdp":  ("flash",        "dots_saveable",  "chunked"),
    "llama-1b":        ("flash",        "dots_saveable",  "chunked"),
    "gpt2-8k-sp":      ("ring",         "save_attn",      "chunked"),
    "gpt2-8k-gqa":     ("ring",         "save_attn",      "chunked"),
    "reference-3b":    ("flash",        "dots_saveable",  "chunked"),
    "llama3-1b-gqa":   ("flash",        "dots_saveable",  "chunked"),
    "moe-8x350m":      ("flash",        "dots_saveable",  "chunked"),
    # a smoke preset like "tiny", with every Xing4.0 mechanism: naive on purpose
    "xing-mini":       ("naive",        "none",           "chunked"),
    # the same for every Ling-3.0 mechanism
    "ling-mini":       ("naive",        "none",           "chunked"),
    # the same for the JoyAI mechanisms (latent attention, held experts, an MTP module)
    "joyai-mini":      ("naive",        "none",           "chunked"),
    # the same for the Trinity mechanisms (window and full layers, gated QK-normed GQA, four norms)
    "trinity-toy":     ("naive",        "none",           "chunked"),
    "granite-toy":     ("naive",        "none",           "chunked"),
    "olmo-hybrid-toy": ("naive",        "none",           "chunked"),
    # the same for the Nemotron-H mechanisms (a table of single sublayers, ungated relu^2 experts)
    "nemotron-h-toy":  ("naive",        "none",           "chunked"),
}


def test_every_perf_preset_has_declared_intent():
    """Every registered preset is either in the intent table or 'tiny'."""
    missing = set(list_presets()) - set(_PERF_INTENT) - {"tiny"}
    assert not missing, (
        f"presets {sorted(missing)} have no declared perf intent; add them to "
        "_PERF_INTENT so attention/remat/CE knobs cannot silently default"
    )


@pytest.mark.parametrize("name", sorted(_PERF_INTENT))
def test_preset_perf_knobs_match_intent(name):
    attn, remat, ce = _PERF_INTENT[name]
    m = get_preset(name).model
    assert m.attention_impl == attn, (
        f"{name}: attention_impl={m.attention_impl!r}, intent {attn!r} "
        "(the round-4 350M silent-naive bug class)"
    )
    assert m.remat == remat, f"{name}: remat={m.remat!r}, intent {remat!r}"
    assert m.ce_impl == ce, f"{name}: ce_impl={m.ce_impl!r}, intent {ce!r}"


@pytest.mark.parametrize("name", ["tiny", "xing-mini", "ling-mini", "joyai-mini", "trinity-toy", "granite-toy",
                                  "olmo-hybrid-toy", "nemotron-h-toy", "moe-8x350m"])
def test_every_count_of_layers_asks_the_table(name):
    """The table's counts add up for every kind of stack, and the parameter
    count walks the same table: an FFN alone is no recurrent layer, keeps no
    cache and has no attention term; a whole layer counts once in each column."""
    m = get_preset(name).model
    kinds = m.layer_kinds
    assert len(kinds) == m.n_layers and m.n_cache_layers == m.n_layers + m.mtp_depth
    assert m.n_state_layers + (m.n_page_layers - m.mtp_depth) + m.n_cacheless_layers == m.n_layers
    assert m.single_sublayers == (name == "nemotron-h-toy") == bool(m.n_cacheless_layers)
    assert (m.state_mixer is not None) == m.hybrid == bool(m.n_state_layers)
    assert sum(b - a for a, b in m.layer_runs) == m.n_layers
    ffns = [f for _, f in kinds]
    assert ffns.count("moe") == (0 if not m.n_experts else m.n_layers - m.n_dense_layers - ffns.count("none"))
    inactive = (m.experts_held - m.experts_per_token) * m._per_expert_params() if m.n_experts else 0
    mtp = m.num_params() - dataclasses.replace(m, mtp_depth=0).num_params()
    assert m.num_params() - m.num_active_params() == ffns.count("moe") * inactive + mtp

