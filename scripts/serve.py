#!/usr/bin/env python
"""Continuous-batching serving CLI over the paged KV cache.

Unlike `generate_text.py --input_file` (ONE compiled ragged program, all
rows enter and leave together), this drives
`generation.serving.ServingEngine`: requests flow through a fixed set of
batch rows, short ones finish early and free their pool blocks for
waiting ones — the online-serving execution model, exercised offline on
a prompt file. The reference has no serving stack at all (batch-1
fixed-count generate, /root/reference/src/models/transformer.py:96-114).

Example:
  python scripts/serve.py --model_path checkpoints \
      --input_file prompts.txt --max_new_tokens 100 \
      --max_batch 8 --steps_per_sched 8 --output results.jsonl

With ``--http`` the same engine goes ONLINE: a continuous engine loop
(frontend.EngineLoop) plus a stdlib HTTP/SSE gateway serving
POST /v1/generate, GET /healthz and GET /metrics until interrupted:

  python scripts/serve.py --model_path checkpoints --http --port 8000
  curl -s localhost:8000/v1/generate -d '{"prompt": "hi", "max_new_tokens": 16}'
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pretraining_llm_tpu.utils.compile_cache import use_compile_cache

use_compile_cache()


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model_path", required=True,
                        help="checkpoint dir (or a step-N dir)")
    parser.add_argument("--input_file", default="",
                        help="one prompt per line (required unless --http)")
    parser.add_argument("--max_new_tokens", type=int, default=100)
    parser.add_argument("--max_batch", type=int, default=8,
                        help="concurrent decode rows (the compiled width)")
    parser.add_argument("--n_blocks", type=int, default=256,
                        help="KV pool size in blocks (block 0 is reserved). A "
                        "model of window and full attention layers "
                        "(model.attn_kinds) keeps two pools: this is its full "
                        "layers'; the window layers' is sized by the engine "
                        "(max_batch x (window / block_size + 2) + 1) and gives "
                        "pages back behind the window; such a model is served "
                        "without --prefix_cache, --kv_checksum, --quantize "
                        "int8-kv, --spec_k and --prefill_chunk_tokens. A model "
                        "with recurrent layers (model.layer_mixers: Mamba-2, KDA "
                        "or Gated DeltaNet layers beside attention layers) gives these pages "
                        "to its attention layers alone and keeps a state slot "
                        "a row (--max_batch of them) in every recurrent layer; "
                        "such a state-slot model is served without "
                        "--prefix_cache, --kv_checksum, --quantize and --spec_k")
    parser.add_argument("--block_size", type=int, default=64,
                        help="tokens per pool block (multiple of 8)")
    parser.add_argument("--steps_per_sched", type=int, default=8,
                        help="decode steps per device dispatch")
    parser.add_argument("--temperature", type=float, default=1.0,
                        help="0 = greedy")
    parser.add_argument("--top_k", type=int, default=None)
    parser.add_argument("--top_p", type=float, default=None)
    parser.add_argument("--min_p", type=float, default=None)
    parser.add_argument("--stop_token", type=int, default=None)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--ema", action="store_true",
                        help="serve from the EMA shadow params")
    parser.add_argument("--draft_model_path", default="",
                        help="draft checkpoint for SPECULATIVE serving "
                        "(k proposals per round verified in one target "
                        "forward; temperature-only sampling)")
    parser.add_argument("--spec_k", type=int, default=None,
                        help="draft proposals per speculative round (4 with "
                        "--draft_model_path). Without a draft checkpoint, "
                        "--spec_k 1 on a model with a multi-token-prediction "
                        "module (model.mtp_depth) makes the module its own "
                        "draft: no --draft_* option is needed")
    parser.add_argument("--no-pipeline", action="store_true",
                        help="disable the pipelined scheduler (fully "
                        "synchronous dispatch/reap baseline)")
    parser.add_argument("--pipeline_depth", type=int, default=0,
                        help="in-flight decode-window queue depth (0 = "
                        "config/engine default; 1 = classic double "
                        "buffering). Host scheduling only — greedy "
                        "outputs are identical at every depth")
    parser.add_argument("--admit_batch", type=int, default=0,
                        help="accumulate waiting prefills until this many "
                        "can be admitted in ONE batched admission (0/1 = "
                        "admit eagerly at every window boundary)")
    parser.add_argument("--prefix_cache", action="store_true",
                        help="cross-request prefix cache: finished requests "
                        "publish their KV blocks; new admissions reuse the "
                        "longest cached block-aligned prefix and prefill "
                        "only the suffix (greedy outputs unchanged)")
    parser.add_argument("--prefix_cache_min_blocks", type=int, default=0,
                        help="shortest cached prefix (in blocks) worth "
                        "mapping (0 = config default)")
    parser.add_argument("--prefill_chunk_tokens", type=int, default=0,
                        help="chunked prefill: stream prompts into the pool "
                        "in chunks of at most this many tokens, interleaved "
                        "with decode windows, instead of one monolithic "
                        "prefill per admission (0 = config default, which "
                        "is off; greedy outputs are identical either way)")
    parser.add_argument("--tokenizer", default=None,
                        help="override the checkpoint's tokenizer name")
    parser.add_argument("--output", default="",
                        help="results JSONL path (default: stdout)")
    parser.add_argument("--http", action="store_true",
                        help="serve an HTTP/SSE gateway instead of draining "
                        "an offline prompt file")
    parser.add_argument("--host", default=None,
                        help="gateway bind host (default: config)")
    parser.add_argument("--port", type=int, default=None,
                        help="gateway bind port, 0 = ephemeral (default: "
                        "config)")
    parser.add_argument("--max_queue_depth", type=int, default=None,
                        help="backpressure: max in-system requests before "
                        "429 (default: config)")
    parser.add_argument("--max_outstanding_tokens", type=int, default=None,
                        help="backpressure: outstanding prompt+max_new token "
                        "budget, 0 = unlimited (default: config)")
    parser.add_argument("--default_deadline_s", type=float, default=None,
                        help="deadline applied to requests that send none, "
                        "0 = none (default: config)")
    parser.add_argument("--events", default="",
                        help="(--http) request-lifecycle events JSONL path")
    parser.add_argument("--trace", default=None,
                        help="(--http) Chrome-trace JSON export path, "
                        "written at shutdown; implies --trace_sample 1.0 "
                        "unless set explicitly (default: config)")
    parser.add_argument("--trace_sample", type=float, default=None,
                        help="(--http) per-request tracing head-sample "
                        "fraction in [0, 1]; 0 = off (default: config)")
    parser.add_argument("--healthz_stale_after_s", type=float, default=None,
                        help="(--http) /healthz returns 503 once the engine "
                        "loop has not completed a scheduler turn for this "
                        "many seconds; 0 = disabled (default: config)")
    parser.add_argument("--replicas", type=int, default=None,
                        help="(--http) in-process engine replicas behind the "
                        "fleet router: prefix-affinity routing, health "
                        "ejection + relaunch, drain/redrive of in-flight "
                        "requests. 1 = plain single engine loop (default: "
                        "config)")
    parser.add_argument("--replica_mode", default=None,
                        choices=["inproc", "process"],
                        help="(--http) where replica engines live: "
                        "'inproc' = EngineLoop threads in this process; "
                        "'process' = one worker subprocess per replica "
                        "behind a socket (real kill -9 fault domain, "
                        "rolling weight upgrades). Router/gateway "
                        "behavior is identical (default: config)")
    parser.add_argument("--replica_roles", default=None,
                        help="(--http, replicas>1) comma-separated "
                        "disaggregation roles, one per replica (or one "
                        "value for all): prefill|decode|both, e.g. "
                        "'prefill,decode'. Prefill workers take no "
                        "client decode traffic; the router runs prompt "
                        "prefills on them and migrates the KV pages to "
                        "the decode target over the wire "
                        "(default: config)")
    parser.add_argument("--attach", default=None,
                        help="(--http, replica_mode=process) attach to "
                        "pre-spawned workers (worker.py --listen) instead "
                        "of spawning: comma-separated host:port list, one "
                        "address per replica. Attached workers are "
                        "detached, never killed, at teardown "
                        "(default: config frontend.worker_attach)")
    parser.add_argument("--attach_token", default=None,
                        help="(--http) shared secret for the attach "
                        "handshake; must match the worker's --token "
                        "(default: config)")
    parser.add_argument("--lease_s", type=float, default=None,
                        help="(--http) heartbeat lease: a worker that "
                        "hears nothing from the router for this long "
                        "stops admitting and parks; the router redrives "
                        "its in-flight work. 0 = disabled "
                        "(default: config)")
    parser.add_argument("--journal_path", default=None,
                        help="(--http) write-ahead fleet journal JSONL: "
                        "membership, fence generations, committed "
                        "frontiers — enough to restart the router "
                        "without losing or duplicating a request "
                        "(default: config)")
    parser.add_argument("--recover", action="store_true",
                        help="(--http) recover router state from "
                        "--journal_path before taking traffic: re-attach "
                        "survivors, fence the old generation, redrive "
                        "journaled in-flight requests from their last "
                        "committed frontier")
    parser.add_argument("--serving_faults", default=None,
                        help="(--http) serving fault plan, e.g. "
                        "'replica_crash@req3:r0,slow_window@req5' — a "
                        "deterministic failover drill (default: config)")
    parser.add_argument("--wedged_after_s", type=float, default=None,
                        help="(--http) watchdog: eject a replica whose loop "
                        "has active requests but no completed scheduler turn "
                        "for this long; 0 = disabled (default: config)")
    parser.add_argument("--quantize", default="",
                        choices=["", "none", "int8", "int8-kv"],
                        help="serving quantization: 'int8' = per-channel "
                        "int8 weights (attention/FFN projections, bf16 "
                        "accumulation); 'int8-kv' = int8 weights AND int8 "
                        "KV pool pages with bf16 per-token scales (~1.9x "
                        "block capacity at head_dim 64). Greedy outputs "
                        "are deterministic within the quantized graph but "
                        "differ from the bf16 graph (default: config)")
    parser.add_argument("--kv_checksum", action="store_true",
                        help="verify prefix-cache KV pages against digests "
                        "recorded at publish; a corrupted shared page is "
                        "dropped and the request re-prefills privately")
    parser.add_argument("--probe_interval_s", type=float, default=None,
                        help="(--http, replicas>1) golden-probe period: "
                        "inject pinned greedy probes per replica and "
                        "quarantine on output divergence; 0 = off "
                        "(default: config)")
    parser.add_argument("--probe_count", type=int, default=None,
                        help="(--http) distinct golden probes to pin "
                        "(default: config)")
    parser.add_argument("--probe_max_new", type=int, default=None,
                        help="(--http) tokens each probe decodes "
                        "(default: config)")
    parser.add_argument("--weight_fingerprint_interval_s", type=float,
                        default=None,
                        help="(--http) per-replica weight fingerprint "
                        "recompute period; the sentinel quarantines on "
                        "drift from the value pinned at launch; 0 = off "
                        "(default: config)")
    parser.add_argument("--no-slo", action="store_true",
                        help="(--http) disable the live SLO engine "
                        "(GET /slo returns 404, no burn-rate alerts)")
    parser.add_argument("--slo_ttft_s", type=float, default=2.0,
                        help="(--http) TTFT latency objective threshold "
                        "for the 'interactive' SLO class")
    parser.add_argument("--slo_e2e_s", type=float, default=30.0,
                        help="(--http) end-to-end latency objective "
                        "threshold for the 'interactive' SLO class")
    parser.add_argument("--slo_target", type=float, default=0.99,
                        help="(--http) success-fraction target shared by "
                        "the SLO objectives (error budget = 1 - target)")
    parser.add_argument("--slo_window_s", type=float, default=60.0,
                        help="(--http) rolling window for the live "
                        "latency percentile sketches")
    args = parser.parse_args()
    if not args.http and not args.input_file:
        parser.error("--input_file is required unless --http is set")

    from pretraining_llm_tpu.data.tokenizer import get_tokenizer
    from pretraining_llm_tpu.generation.generate import (
        cast_params_for_inference, load_config_for_inference,
        load_model_for_inference,
    )
    from pretraining_llm_tpu.generation.serving import ServingEngine

    texts = []
    if args.input_file:
        with open(args.input_file) as f:
            texts = [ln.rstrip("\r\n") for ln in f if ln.strip()]
        if not texts:
            raise SystemExit(f"no prompts in {args.input_file}")

    cfg = load_config_for_inference(args.model_path)
    enc = get_tokenizer(args.tokenizer or cfg.data.tokenizer_name)
    if args.http and (args.replica_mode or cfg.frontend.replica_mode) == "process":
        # One process for each chip: the workers load the checkpoint
        # themselves, and this parent must never initialise a device
        # backend — a parent that holds the chip leaves none for them.
        _serve_http(args, cfg, None, enc)
        return

    params, _ = load_model_for_inference(args.model_path, use_ema=args.ema)
    params = cast_params_for_inference(params, cfg.model)

    spec = {}
    if args.draft_model_path:
        d_params, d_cfg = load_model_for_inference(args.draft_model_path)
        spec = dict(
            draft_params=cast_params_for_inference(d_params, d_cfg.model),
            draft_cfg=d_cfg.model, spec_k=args.spec_k or 4,
        )
    elif args.spec_k:
        # self-drafting: the engine takes the model's own MTP module as the
        # draft, or says by name why it cannot
        spec = dict(spec_k=args.spec_k)

    quantize = args.quantize or cfg.serving.quantize

    # A factory, not an engine: the fleet path builds one engine per
    # replica, and a crashed replica relaunches with a FRESH engine.
    # With quantization on, quantize ONCE here (not per replica): every
    # replica then serves the same int8 codes + scales, so fleet-wide
    # fingerprint comparison and probe unanimity stay meaningful.
    if quantize != "none":
        from pretraining_llm_tpu.models import quantize as quantize_mod

        params = quantize_mod.quantize_params_for_serving(params, cfg.model)

    def make_engine():
        return ServingEngine(
            params, cfg.model,
            max_batch=args.max_batch, n_blocks=args.n_blocks,
            block_size=args.block_size, temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p, min_p=args.min_p,
            stop_token=args.stop_token, seed=args.seed,
            steps_per_sched=args.steps_per_sched,
            pipeline_depth=args.pipeline_depth or cfg.serving.pipeline_depth,
            admit_batch=args.admit_batch or cfg.serving.admit_batch,
            prefix_cache=args.prefix_cache or cfg.serving.prefix_cache,
            prefix_cache_min_blocks=(
                args.prefix_cache_min_blocks
                or cfg.serving.prefix_cache_min_blocks
            ),
            prefill_chunk_tokens=(
                args.prefill_chunk_tokens or cfg.serving.prefill_chunk_tokens
            ),
            kv_checksum=args.kv_checksum or cfg.serving.kv_checksum,
            quantize=quantize,
            **spec,
        )

    if args.http:
        _serve_http(args, cfg, make_engine, enc)
        return

    eng = make_engine()

    rids = {}
    rejected = []
    for i, text in enumerate(texts):
        try:
            rids[eng.submit(enc.encode_ordinary(text), args.max_new_tokens)] = i
        except ValueError as e:
            # One oversized prompt must not abort the other requests.
            rejected.append(i)
            print(f"[serve] rejected prompt {i}: {e}", file=sys.stderr)
    if not rids:
        raise SystemExit("every prompt was rejected")

    t0 = time.perf_counter()
    out = eng.run(pipeline=not args.no_pipeline)
    dt = time.perf_counter() - t0

    sink = open(args.output, "w") if args.output else sys.stdout
    try:
        for rid in sorted(rids, key=rids.get):
            toks = out[rid]
            record = {
                "index": rids[rid],
                "prompt": texts[rids[rid]],
                "output": enc.decode(toks),
                "tokens": [int(t) for t in toks],
                "n_tokens": len(toks),
            }
            # Per-request lifecycle latencies: how long the request sat in
            # the waiting queue, time to its first committed token, and
            # submit-to-finish — the offline view of the serving SLOs.
            record.update(eng.timing_summary(rid))
            sink.write(json.dumps(record) + "\n")
    finally:
        if sink is not sys.stdout:
            sink.close()
    n_tok = sum(len(out[r]) for r in rids)
    print(
        f"[serve] {len(texts)} requests, {n_tok} tokens in {dt:.2f}s "
        f"({n_tok / dt:.1f} tok/s) — stats {eng.stats}",
        file=sys.stderr,
    )


def _serve_http(args, cfg, make_engine, enc) -> None:
    """Run the online gateway until interrupted (Ctrl-C).

    ``--replicas 1`` (the default) keeps the original single
    EngineLoop wiring; ``--replicas N`` puts the fleet Router in front
    of N in-process replicas (each with its own engine, loop, admission
    and labeled registry) — same gateway, same endpoints, plus
    failover/drain/redrive semantics.
    """
    from pretraining_llm_tpu.frontend.admission import AdmissionController
    from pretraining_llm_tpu.frontend.engine_loop import EngineLoop
    from pretraining_llm_tpu.frontend.gateway import ServingGateway
    from pretraining_llm_tpu.frontend.replica import Replica
    from pretraining_llm_tpu.frontend.router import Router
    from pretraining_llm_tpu.observability.capacity import DecisionLog
    from pretraining_llm_tpu.observability.events import EventBus
    from pretraining_llm_tpu.observability.metrics import MetricsRegistry
    from pretraining_llm_tpu.observability.slo import (
        SLOEngine, default_slo_classes,
    )
    from pretraining_llm_tpu.observability.spans import get_recorder
    from pretraining_llm_tpu.observability.tracing import Tracer
    from pretraining_llm_tpu.resilience.faults import ServingFaultInjector

    fc = cfg.frontend

    def pick(cli_val, cfg_val):
        return cfg_val if cli_val is None else cli_val

    # The SLO engine is a pure bus subscriber, so enabling it forces a
    # bus into existence even without --events (in-memory, no JSONL).
    bus = None
    if args.events or not args.no_slo:
        bus = EventBus(jsonl_path=args.events)
    slo = None
    if not args.no_slo:
        slo = SLOEngine(
            classes=default_slo_classes(
                ttft_s=args.slo_ttft_s, e2e_s=args.slo_e2e_s,
                target=args.slo_target,
            ),
            bus=bus,
            decisions=DecisionLog(bus=bus),
            window_s=args.slo_window_s,
        )
    trace_path = pick(args.trace, fc.trace_path)
    trace_sample = pick(args.trace_sample, fc.trace_sample)
    if args.trace is not None and args.trace_sample is None:
        trace_sample = 1.0  # asking for an export implies sampling
    tracer = None
    if trace_sample > 0:
        tracer = Tracer(get_recorder(), sample=trace_sample, seed=args.seed)
    # quant_dtype rides every serving series as a const-label so dashboards
    # can split bf16 vs quantized fleets without a scrape-config change.
    quantize = args.quantize or cfg.serving.quantize
    registry = MetricsRegistry(
        prefix="pllm_serving_", const_labels={"quant_dtype": quantize}
    )
    n_replicas = pick(args.replicas, fc.replicas)
    replica_mode = pick(args.replica_mode, fc.replica_mode)
    fault_spec = pick(args.serving_faults, fc.serving_faults)
    attach = pick(args.attach, fc.worker_attach)
    attach_token = pick(args.attach_token, fc.attach_token)
    lease_s = pick(args.lease_s, fc.lease_s)
    journal_path = pick(args.journal_path, fc.journal_path)
    roles_raw = pick(args.replica_roles, getattr(fc, "replica_roles", ""))
    roles = (
        [r.strip() for r in str(roles_raw).split(",") if r.strip()]
        if roles_raw else []
    )
    if roles:
        if len(roles) == 1:
            roles = roles * n_replicas
        if len(roles) != n_replicas:
            raise SystemExit(
                f"--replica_roles lists {len(roles)} roles for "
                f"{n_replicas} replicas"
            )
        bad = [r for r in roles if r not in ("prefill", "decode", "both")]
        if bad:
            raise SystemExit(
                f"--replica_roles: unknown role(s) {bad}; expected "
                "prefill|decode|both"
            )
    attach_addrs = [a.strip() for a in attach.split(",")] if attach else []
    if attach_addrs:
        if replica_mode != "process":
            raise SystemExit("--attach needs --replica_mode process")
        if len(attach_addrs) != n_replicas:
            raise SystemExit(
                f"--attach lists {len(attach_addrs)} addresses for "
                f"{n_replicas} replicas"
            )
    if args.recover and not journal_path:
        raise SystemExit("--recover needs --journal_path")
    max_queue_depth = pick(args.max_queue_depth, fc.max_queue_depth)
    max_outstanding = pick(
        args.max_outstanding_tokens, fc.max_outstanding_tokens
    )

    def make_admission(reg, scope=""):
        return AdmissionController(
            max_queue_depth=max_queue_depth,
            max_outstanding_tokens=max_outstanding,
            retry_after_s=fc.retry_after_s,
            shed_infeasible=fc.shed_infeasible,
            registry=reg,
            scope=scope,
        )

    loop_kwargs = dict(
        idle_wait_s=fc.idle_wait_s, capacity_ring=fc.capacity_ring,
        weight_fingerprint_interval_s=pick(
            args.weight_fingerprint_interval_s,
            fc.weight_fingerprint_interval_s,
        ),
    )

    def make_router(replicas, extra_bus_faults_done=False):
        return Router(
            replicas,
            admission=make_admission(registry, scope="fleet"),
            bus=bus, registry=registry, tracer=tracer, slo=slo,
            affinity_tokens=fc.affinity_tokens,
            spill_margin=fc.spill_margin,
            wedged_after_s=pick(args.wedged_after_s, fc.wedged_after_s),
            eject_backoff_s=fc.eject_backoff_s,
            eject_backoff_max_s=fc.eject_backoff_max_s,
            backoff_seed=args.seed,
            redrive_max=fc.redrive_max_attempts,
            brownout_min_healthy_frac=fc.brownout_min_healthy_frac,
            brownout_min_priority=fc.brownout_min_priority,
            brownout_max_deadline_s=fc.brownout_max_deadline_s,
            probe_interval_s=pick(args.probe_interval_s, fc.probe_interval_s),
            probe_count=pick(args.probe_count, fc.probe_count),
            probe_max_new=pick(args.probe_max_new, fc.probe_max_new),
            journal_path=journal_path,
            journal_rotate_bytes=int(fc.journal_rotate_mb * 1024 * 1024),
            recover=args.recover,
        ).start()

    if replica_mode == "process":
        # One worker subprocess per replica. Workers load the checkpoint
        # themselves from the spec (same load/cast/quantize pipeline as
        # above); the fault plan splits into engine kinds (ride in the
        # worker spec, fire inside its scheduler) and process kinds
        # (worker_kill/worker_stall/conn_drop — executed by the parent,
        # the only party that can kill a process).
        from pretraining_llm_tpu.frontend.remote_replica import RemoteReplica
        from pretraining_llm_tpu.resilience.faults import split_serving_plan

        if args.draft_model_path or args.spec_k:
            raise SystemExit(
                "--replica_mode process does not support speculative "
                "serving (--draft_model_path, --spec_k): draft params "
                "cannot ride a JSON worker spec, and the worker spec "
                "carries no spec_k"
            )
        engine_plan, process_plan = (
            split_serving_plan(fault_spec) if fault_spec else ("", "")
        )
        proc_faults = (
            ServingFaultInjector(process_plan, bus=bus)
            if process_plan else None
        )
        worker_spec = dict(
            model_path=args.model_path,
            ema=bool(args.ema),
            quantize=quantize,
            engine=dict(
                max_batch=args.max_batch, n_blocks=args.n_blocks,
                block_size=args.block_size, temperature=args.temperature,
                top_k=args.top_k, top_p=args.top_p, min_p=args.min_p,
                stop_token=args.stop_token, seed=args.seed,
                steps_per_sched=args.steps_per_sched,
                pipeline_depth=(
                    args.pipeline_depth or cfg.serving.pipeline_depth
                ),
                admit_batch=args.admit_batch or cfg.serving.admit_batch,
                prefix_cache=args.prefix_cache or cfg.serving.prefix_cache,
                prefix_cache_min_blocks=(
                    args.prefix_cache_min_blocks
                    or cfg.serving.prefix_cache_min_blocks
                ),
                prefill_chunk_tokens=(
                    args.prefill_chunk_tokens
                    or cfg.serving.prefill_chunk_tokens
                ),
                kv_checksum=args.kv_checksum or cfg.serving.kv_checksum,
            ),
            admission=dict(
                max_queue_depth=max_queue_depth,
                max_outstanding_tokens=max_outstanding,
                retry_after_s=fc.retry_after_s,
                shed_infeasible=fc.shed_infeasible,
            ),
            loop=loop_kwargs,
            serving_faults=engine_plan,
        )
        def _rep_spec(i):
            # Attach mode: each replica gets its own pre-spawned worker
            # address (plus the shared token); spawn mode shares the spec
            # unless per-replica roles differentiate it.
            if not attach_addrs and not roles:
                return worker_spec
            s = dict(worker_spec)
            if roles:
                s["role"] = roles[i]
            if attach_addrs:
                s["attach"] = attach_addrs[i]
                if attach_token:
                    s["token"] = attach_token
            return s

        # All RemoteReplicas share the tracer's recorder (or the process
        # default): worker-exported spans land in the SAME buffer as the
        # router's own, so one shutdown export yields the merged
        # cross-host trace.
        replicas = [
            RemoteReplica(
                i, _rep_spec(i), bus=bus,
                registry_labels={"quant_dtype": quantize},
                fault_injector=proc_faults,
                backoff_seed=args.seed,
                lease_s=lease_s,
                recorder=tracer.recorder if tracer is not None else None,
            )
            for i in range(n_replicas)
        ]
        loop = make_router(replicas)
    elif n_replicas > 1:
        faults = (
            ServingFaultInjector(fault_spec, bus=bus) if fault_spec else None
        )
        replicas = [
            Replica(
                i, make_engine, bus=bus, tracer=tracer,
                registry_labels={"quant_dtype": quantize},
                admission_factory=make_admission, fault_injector=faults,
                loop_kwargs=loop_kwargs,
                role=roles[i] if roles else "both",
            )
            for i in range(n_replicas)
        ]
        loop = make_router(replicas)
    else:
        faults = (
            ServingFaultInjector(fault_spec, bus=bus) if fault_spec else None
        )
        eng = make_engine()
        if faults is not None:
            eng.pipeline_tick = faults.wrap_tick(0, eng.pipeline_tick)
        loop = EngineLoop(
            eng, admission=make_admission(registry), bus=bus,
            idle_wait_s=fc.idle_wait_s, tracer=tracer, registry=registry,
            capacity_ring=fc.capacity_ring,
        ).start()
    gateway = ServingGateway(
        loop,
        host=pick(args.host, fc.host),
        port=pick(args.port, fc.port),
        encode=enc.encode_ordinary,
        decode=enc.decode,
        default_deadline_s=pick(args.default_deadline_s, fc.default_deadline_s),
        healthz_stale_after_s=pick(
            args.healthz_stale_after_s, fc.healthz_stale_after_s
        ),
        retry_jitter_frac=fc.retry_jitter_frac,
        retry_jitter_seed=fc.retry_jitter_seed,
        slo=slo,
    )
    fleet = f" ({n_replicas} replicas)" if n_replicas > 1 else ""
    print(
        f"[serve] gateway{fleet} listening on "
        f"http://{gateway._server.server_address[0]}"
        f":{gateway.port} — POST /v1/generate, GET /healthz, GET /readyz, "
        f"GET /metrics, GET /slo, GET /metricsz, GET /debug/requests, "
        f"GET /debug/engine",
        file=sys.stderr,
    )
    # SIGTERM (a plain `kill`, the orchestrator's stop signal) must take
    # the same graceful path as ^C: without this the process dies before
    # the finally block and the whole trace export is lost. SIGTERM
    # additionally requests a fleet drain — stop admitting, let in-flight
    # requests finish (or redrive), THEN tear down — because the
    # orchestrator's kill is routine (rolling restart), not an emergency.
    graceful = {"drain": False}

    def _sigterm(signum, frame):
        graceful["drain"] = True
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _sigterm)
    try:
        gateway.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if graceful["drain"]:
            begin = getattr(loop, "begin_drain", None)
            if begin is not None:
                begin()
            deadline = time.monotonic() + 30.0
            while (
                getattr(loop, "active_requests", 0) > 0
                and time.monotonic() < deadline
            ):
                time.sleep(0.05)
            print("[serve] SIGTERM drain complete "
                  f"({getattr(loop, 'active_requests', 0)} still in flight)",
                  file=sys.stderr)
        gateway.stop()
        clean = loop.stop()
        if clean is False:
            print("[serve] WARNING: engine loop abandoned wedged at "
                  "shutdown; outstanding requests got error terminals",
                  file=sys.stderr)
        if bus is not None:
            bus.close()
        if tracer is not None and trace_path:
            path = tracer.recorder.export(trace_path)
            dropped = tracer.recorder.dropped
            extra = f" ({dropped} spans DROPPED)" if dropped else ""
            print(f"[serve] trace written to {path}{extra}", file=sys.stderr)
        counters = getattr(loop, "counters", {})
        print(f"[serve] shut down — {counters}", file=sys.stderr)


if __name__ == "__main__":
    main()
