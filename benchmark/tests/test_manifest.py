"""The per-layer manifest after PR 53 made room in it: one entry for every set
of equal data files that move one end-to-end metric, and every cell still
reports every reading it reported before, under the names of the table kept
here (PERF.md section 3 has the same table)."""

import os

from harness import registry

CELL_SUFFIXES = ("decode", "xdecode", "ldecode", "jdecode", "tdecode")
# bases -> the cell suffixes whose entries became one, `<base>.decode`
MERGED = {
    "batch_occupancy compiles_in_window device_idle_share hbm_resident_gb host_blocked_share kv_blocks_peak "
    "preemptions prefill_device_share tick_host_ms": "decode xdecode ldecode jdecode tdecode",
    "decode_step_ms": "decode xdecode ldecode tdecode",
    "moe_time_share moe_experts_touched_share": "xdecode ldecode jdecode tdecode",
    "moe_experts_hbm_roofline": "xdecode ldecode tdecode",
    "latent_attn_time_share": "xdecode ldecode jdecode",
    "latent_attn_hbm_roofline": "xdecode ldecode",
    "moe_routed_here_share": "ldecode jdecode",
    "moe_load_max_over_mean": "xdecode tdecode",  # every expert held: readers/moe_counter
}
RENAMED = {f"{base}.{suffix}": f"{base}.decode"
           for bases, suffixes in MERGED.items() for base in bases.split() for suffix in suffixes.split()}
RENAMED.update({
    # the expert families count a step's bytes from the experts it touches (readers/part_roofline);
    # `decode_hbm_roofline.decode` stays the dense model's (readers/decode_hbm_roofline)
    "decode_hbm_roofline.xdecode": "decode_touched_hbm_roofline.decode",
    "decode_hbm_roofline.ldecode": "decode_touched_hbm_roofline.decode",
    "decode_hbm_roofline.tdecode": "decode_touched_hbm_roofline.decode",
    # a share of the experts held: readers/commit_counter divides by the pairs that met a held expert
    "moe_load_max_over_mean.ldecode": "moe_held_load_max_over_mean.decode",
    "moe_load_max_over_mean.jdecode": "moe_held_load_max_over_mean.decode",
    "state_slots_peak.ldecode": "state_slots_peak.decode",
    # serving.tick less host_blocked AND dispatch_window: both wait for the device in this cell
    "tick_host_ms.chat": "tick_host_nowait_ms.chat",
})

# what each cell reported before PR 53: (its suffix, the bases under it, names under another suffix)
COMMON = ("decode_step_ms prefill_device_share device_idle_share compiles_in_window batch_occupancy "
          "host_blocked_share kv_blocks_peak hbm_resident_gb tick_host_ms preemptions")
EXPERTS = "decode_hbm_roofline moe_time_share moe_experts_hbm_roofline moe_experts_touched_share moe_load_max_over_mean"
TRAIN = ("compiles_in_window device_idle_share flash_attn_roofline flash_attn_time_share hbm_resident_gb "
         "host_gap_share mfu optimizer_time_share remat_recompute_time_share ce_head_time_share scope_coverage")
OLD = {
    "serve_mistral_decode": ("decode", COMMON + " decode_hbm_roofline paged_attn_time_share mlp_time_share "
                             "scope_coverage", ()),
    "train_gpt2large_1chip": ("train", TRAIN, ()),
    "serve_mistral_chat_open": (
        "chat", "batch_occupancy compiles_in_window device_idle_share generator_lag_p95_ms prefill_device_share "
        "queue_wait_p50_ms ttft_mean_ms ttft_p50_ms paged_attn_time_share scope_coverage tick_host_ms "
        "admit_ms_p95 loop_overhead_ms_per_turn", ()),
    "train_gpt2xl_fsdp4": ("train", TRAIN + " collective_time_share", ()),
    "serve_xing_decode_7k": ("xdecode", f"{COMMON} {EXPERTS} latent_attn_time_share latent_attn_hbm_roofline "
                             "hc_time_share scope_coverage", ()),
    "serve_ling_decode_4k": (
        "ldecode", f"{COMMON} {EXPERTS} latent_attn_time_share latent_attn_hbm_roofline scope_coverage "
        "kda_time_share kda_decode_hbm_roofline kda_prefill_roofline state_slots_peak moe_routed_here_share", ()),
    "serve_joyai_mtp_decode_2k": (
        "jdecode", f"{COMMON} {EXPERTS} latent_attn_time_share latent_attn_hbm_roofline scope_coverage "
        "moe_routed_here_share mtp_time_share mtp_draft_hbm_roofline spec_accept_rate spec_tokens_per_round", ()),
    "serve_trinity_decode_1k_8k": (
        "tdecode", f"{COMMON} {EXPERTS} scope_coverage window_attn_time_share full_attn_time_share "
        "window_attn_hbm_roofline full_attn_hbm_roofline window_blocks_peak window_pages_released "
        "release_host_ms", ()),
    # Granite stood under Ling's suffix for seventeen of its eighteen
    "serve_granite_decode_1k_4k": ("ldecode", f"{COMMON} {EXPERTS} state_slots_peak moe_routed_here_share",
                                   ("ssm_decode_hbm_roofline.gdecode",)),
}

# readings that came since: the chat cell's tail left `end_to_end` (the driver's check of PR 53 read its
# spread at 8% of the median, PERF.md section 2) and is reported per layer
ADDED = {"serve_mistral_chat_open": {"ttft_p95_ms.chat"}}


def old_names(cell):
    suffix, bases, others = OLD[cell]
    return {f"{base}.{suffix}" for base in bases.split()} | set(others)


def data(name):
    with open(os.path.join(registry.BENCH_DIR, "layer_metrics", name + ".json"), "rb") as f:
        return f.read()


def test_no_two_entries_that_move_one_metric_name_equal_data_files():
    seen = {}
    for m in registry.manifest()["per_layer"]:
        twin = seen.setdefault((m["moves"], data(m["name"])), m["name"])
        assert twin == m["name"], f"{m['name']} and {twin} move {m['moves']} through equal data files: one entry"


def test_every_cell_reports_what_it_reported_under_the_tables_names():
    man = registry.manifest()
    assert [w["name"] for w in man["workloads"]] == list(OLD)
    assert sum(len(old_names(c)) for c in ("serve_granite_decode_1k_4k", "serve_joyai_mtp_decode_2k")) == 18 + 23
    for cell in OLD:
        new = [m["name"] for m in registry.metrics_for(cell, trace=True)]
        assert len(new) == len(set(new))
        assert set(new) - ADDED.get(cell, set()) == {RENAMED.get(n, n) for n in old_names(cell)}, cell
        assert len(new) == len(old_names(cell)) + len(ADDED.get(cell, ()))  # no two old readings fell into one
    # the training cells' entries are what they were, and no cell stands under another cell's suffix
    for m in man["per_layer"]:
        suffix = m["name"].rsplit(".", 1)[1]
        if suffix in CELL_SUFFIXES[1:] + ("gdecode",):
            assert len(m["workloads"]) == 1, m["name"]
        if suffix == "decode":
            assert m["moves"] == "output_tokens_per_s"


def test_the_manifest_has_room_and_no_file_without_an_entry():
    man = registry.manifest()
    assert len(man["per_layer"]) <= 80  # of 128: what the next configuration's own names need
    assert sorted(m["name"] for m in man["per_layer"]) == registry.list_all()["layer_metrics"]
