"""Open-loop serving through ``EngineLoop`` (the gateway's path without HTTP).

One dispatcher (this thread) submits each request when it is due and records
how late it ran; nothing else is started besides the engine loop's own
thread. Arrivals begin ``lead_s`` before the window so it opens on a running
batch; every request due inside the window is measured from its due time and
drained after the window closes. Commit times are taken on the loop thread,
in a wrapper around the engine's ``on_token`` hook; a wrapper around
``pipeline_tick`` records the ``bench.engine_tick`` span.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from harness import loadgen, opcount, program, serving_check, weights
from harness.context import Ctx, RunResult, span


def build(ctx: Ctx) -> Tuple[Any, Any, Any]:
    """(params, model config, engine) with every prefill shape and the decode program run once."""
    arch, tr = ctx.arch, ctx.traffic
    with ctx.phase("weights"):
        params = weights.serving_params(arch, ctx.seed)
    with ctx.phase("state_build"):
        cfg = program.model_config(arch, tr["engine"]["max_seq"])
        eng = program.serving_engine(params, cfg, tr)
    with ctx.phase("warm_up"):
        rng = np.random.default_rng([int(ctx.seed), 2])
        vocab = opcount.dims(arch)["vocab"]
        # every prefill program (rows and pages are bucketed to powers of two), and at one
        # page the other row counts: the engine merges k admitted rows with eager ops shaped by k
        shapes = [(p, r) for p in tr["warm"]["pages"] for r in tr["warm"]["rows"]]
        shapes += [(1, r) for r in tr["warm"]["extra_rows_at_one_page"]]
        for pages, rows in shapes:
            for _ in range(rows):
                eng.submit(rng.integers(0, vocab, pages * eng.block_size).tolist(), 2)
            eng.run()
            eng.finished.clear()
            eng.req_timing.clear()
    return params, cfg, eng


def offer(ctx: Ctx, eng: Any, loop: Any, traffic: Dict[str, Any], seconds: float,
          window: Any = None) -> Dict[str, Any]:
    """Offer the schedule; returns the measured requests' timings and the window's counts."""
    lead = traffic["lead_s"]
    sched = loadgen.build_schedule(traffic, lead + seconds)
    rng = np.random.default_rng([int(ctx.seed), 1])
    vocab = opcount.dims(ctx.arch)["vocab"]
    prompts = [rng.integers(0, vocab, a.prompt_tokens).tolist() for a in sched]
    commits: Dict[int, List[float]] = {}
    st: Dict[str, Any] = {}
    inner_token = eng.on_token
    inner_tick = eng.pipeline_tick

    def on_token(rid: int, tok: int) -> None:
        t = time.perf_counter()
        rec = commits.get(rid)
        if rec is None:
            commits[rid] = [t, t, 1]
        else:
            rec[1] = t
            rec[2] += 1
        inner_token(rid, tok)

    # since the window opened: the longest engine tick and the longest pause of the loop between
    # two ticks, each as [seconds, started at]; what a stall of seconds is told apart by
    longest, pause, last_end = [0.0, 0.0], [0.0, 0.0], [time.perf_counter()]

    def tick() -> bool:
        t = time.perf_counter()
        with span("engine_tick"):
            busy = inner_tick()
        end = time.perf_counter()
        if "t_open" in st:
            if end - t > longest[0]:
                longest[:] = [end - t, t]
            if last_end[0] >= st["t_open"] and t - last_end[0] > pause[0]:
                pause[:] = [t - last_end[0], last_end[0]]
        last_end[0] = end
        return busy

    eng.on_token, eng.pipeline_tick = on_token, tick
    handles: List[Any] = [None] * len(sched)
    lags: List[float] = []
    t0 = time.perf_counter()

    def dispatch(arrivals) -> None:
        for a in arrivals:
            wait = t0 + a.due_s - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            handles[a.index] = loop.submit(prompts[a.index], a.output_tokens)
            lags.append(time.perf_counter() - (t0 + a.due_s))

    dispatch([a for a in sched if a.due_s < lead])
    time.sleep(max(0.0, t0 + lead - time.perf_counter()))
    st["t_open"] = time.perf_counter()
    st["stats0"] = dict(eng.stats)
    n_lead = len(lags)
    with (window() if window is not None else contextlib.nullcontext()):
        dispatch([a for a in sched if a.due_s >= lead])
        time.sleep(max(0.0, t0 + lead + seconds - time.perf_counter()))
        # read before the window's exit: stopping a trace takes seconds and the loop runs on
        st["stats1"] = dict(eng.stats)
        st["window_s"] = time.perf_counter() - st["t_open"]
        st["waiting_at_close"] = len(eng.waiting)
    ttft, tpot, queue_wait, failed = [], [], [], 0
    measured = [a for a in sched if a.due_s >= lead]
    # a few measured requests spread over the window, with what the engine emitted: checked afterwards
    k = traffic["check_requests"]
    keep = {measured[int((j + 0.5) * len(measured) / k)].index for j in range(k)} if measured else set()
    emitted = []
    for a in sched:
        status, tokens, info = handles[a.index].result(timeout=300)
        if a.due_s < lead:
            continue
        rec = commits.get(handles[a.index].rid)
        if status != "done" or rec is None or rec[2] != a.output_tokens or len(tokens) != a.output_tokens:
            failed += 1
            continue
        if a.index in keep:
            emitted.append((prompts[a.index], list(tokens)))
        ttft.append(rec[0] - (t0 + a.due_s))
        if rec[2] > 1:
            tpot.append((rec[1] - rec[0]) / (rec[2] - 1))
        queue_wait.append(info.get("queue_wait_s", 0.0))
    st["drain_s"] = time.perf_counter() - st["t_open"] - st["window_s"]
    eng.on_token, eng.pipeline_tick = inner_token, inner_tick
    st.update(measured=len(measured), failed=failed, ttft=ttft, tpot=tpot, emitted=emitted,
              queue_wait=queue_wait, lags=lags[n_lead:], t0=t0,
              longest_tick=(longest[0], longest[1] - st["t_open"]),
              longest_pause=(pause[0], pause[1] - st["t_open"]))
    return st


def run(ctx: Ctx) -> RunResult:
    from pretraining_llm_tpu.frontend.engine_loop import EngineLoop
    from pretraining_llm_tpu.observability.device import CompileWatcher

    tr = ctx.traffic
    watcher = CompileWatcher().start()
    params, cfg, eng = build(ctx)
    resident = 0 if ctx.rehearsal else ctx.devices[0].memory_stats()["bytes_in_use"]
    loop = EngineLoop(eng).start()
    opened = {}

    def window():
        watcher.mark_warm()
        opened["setup_s"] = time.perf_counter() - ctx.t_start
        return ctx.window()

    try:
        st = offer(ctx, eng, loop, tr, ctx.window_seconds, window)
        compiles = watcher.summary()["recompiles"]
        watcher.stop()
    finally:
        stopped = loop.stop()
    if not stopped or loop.failure is not None:
        raise RuntimeError(f"engine loop did not stop cleanly: {loop.failure!r}")
    s0, s1 = st["stats0"], st["stats1"]
    steps = s1["steps"] - s0["steps"]
    tokens = s1["tokens"] - s0["tokens"]
    ctx.log(
        f"window: requests {st['measured']} failed {st['failed']} ticks(steps) {steps} "
        f"admissions {s1['admissions'] - s0['admissions']} "
        f"prefill_tokens {s1['prefill_tokens'] - s0['prefill_tokens']} tokens_committed {tokens} "
        f"waiting_at_close {st['waiting_at_close']} drain {st['drain_s']:.2f}s "
        f"generator_lag_max {max(st['lags']) * 1e3:.3f}ms"
    )
    ctx.log(f"since the window opened: longest engine tick {st['longest_tick'][0] * 1e3:.1f} ms at "
            f"+{st['longest_tick'][1]:.3f}s, longest pause of the loop between ticks "
            f"{st['longest_pause'][0] * 1e3:.1f} ms at +{st['longest_pause'][1]:.3f}s "
            f"(a tick is one decode step, some 50 ms; seconds are a stall)")
    compared = {"logits_rel_err": serving_check.compare(ctx, eng, params, cfg)}
    compared["engine_token_regret"] = serving_check.compare_tokens(ctx, st["emitted"], tr["engine"]["max_seq"])
    compared["requests_failed"] = (float(st["failed"]), 0.0)
    ms = 1e3
    return RunResult(
        end_to_end={
            "tpot_p50_ms": ms * loadgen.percentile(st["tpot"], 0.50),
            "setup_s": opened["setup_s"],
        },
        attempted=st["measured"], failed=st["failed"],
        observed={
            "window_s": st["window_s"], "compiles_in_window": compiles, "steps": steps,
            "tokens_committed": tokens, "requests_measured": st["measured"],
            "prefill_tokens": s1["prefill_tokens"] - s0["prefill_tokens"],
            "preemptions": s1["preemptions"] - s0["preemptions"],
            "rows": eng.max_batch, "mean_rows_active": tokens / max(1, steps),
            "queue_wait_p50_ms": ms * loadgen.percentile(st["queue_wait"], 0.5),
            "generator_lag_p95_ms": ms * loadgen.percentile(st["lags"], 0.95),
            # which kind of host a run had (PERF.md section 2): a request sent tens of ms late, a tick of seconds
            "generator_lag_max_ms": ms * max(st["lags"]), "longest_tick_ms": ms * st["longest_tick"][0],
            "ttft_p50_ms": ms * loadgen.percentile(st["ttft"], 0.5),
            "ttft_p90_ms": ms * loadgen.percentile(st["ttft"], 0.9),
            # the tail: per layer since PR 53's refusal round (`ttft_p95_ms.chat`), spread too wide to judge
            "ttft_p95_ms": ms * loadgen.percentile(st["ttft"], 0.95),
            "ttft_mean_ms": ms * float(np.mean(st["ttft"])) if st["ttft"] else None,
            "bytes_in_use": resident, "emitted": st["emitted"],
        },
        compared=compared,
    )
