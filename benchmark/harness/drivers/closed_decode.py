"""Closed decode loop whose work is fixed by construction.

``B`` rows, one class: prompt ``P``, output ``O``, greedy, no stop token. Row
``i`` of the first wave gets a prompt of ``P + floor(i*O/B)`` tokens and a
budget of ``O - floor(i*O/B)``, so residency is staggered from the first
tick; every later request is ``(P, O)`` and is submitted from the
``on_finish`` hook at the tick that reaps its predecessor. This thread
drives ``ServingEngine.submit`` / ``pipeline_tick`` directly: no other
thread, no clock in any decision before the window closes. The trajectory
(ticks, admissions, prefill tokens, tokens committed) is a pure function of
the traffic file; ``--seed`` sets token contents and weights only.

The window opens at the first commit after ``warm_ticks`` ticks and closes
at the last commit before ``--seconds`` have passed; the rate is tokens
committed after the opening commit through the closing one, over the time
between the two.
"""

from __future__ import annotations

import collections
import csv
import os
import statistics
import time
from typing import Any, Dict, List

import numpy as np

from harness import opcount, program, serving_check, weights
from harness.context import Ctx, RunResult, span


def first_wave(rows: int, p: int, o: int) -> List[tuple]:
    """(prompt tokens, output budget) for each row of the staggered first wave."""
    return [(p + (i * o) // rows, o - (i * o) // rows) for i in range(rows)]


class TickLog:
    """One line per commit: tick, rows active, admissions, prefill tokens, tokens."""

    FIELDS = ("tick", "rows_active", "admissions", "prefill_tokens", "tokens_committed")

    def __init__(self, eng: Any) -> None:
        self.eng = eng
        self.tick = 0
        self.rows: List[tuple] = []
        self.times: List[float] = []
        self.blocks_peak = 0
        self.longest = (0.0, 0, 0.0, 0.0)  # (seconds, tick, started at, host_blocked_s inside) of the longest tick
        self._reaped = eng.stats["windows_reaped"]

    def step(self) -> bool:
        """One scheduler tick; True when it committed a window."""
        eng = self.eng
        t, blocked = time.perf_counter(), eng.stats["host_blocked_s"]
        with span("engine_tick"):
            eng.pipeline_tick()
        self.tick += 1
        took = time.perf_counter() - t
        if took > self.longest[0]:
            self.longest = (took, self.tick, t, eng.stats["host_blocked_s"] - blocked)
        used = eng.n_blocks - 1 - eng.alloc.available
        self.blocks_peak = max(self.blocks_peak, used)
        if eng.stats["windows_reaped"] == self._reaped:
            return False
        self._reaped = eng.stats["windows_reaped"]
        st = eng.stats
        self.rows.append((self.tick, eng.n_active, st["admissions"], st["prefill_tokens"], st["tokens"]))
        self.times.append(time.perf_counter())
        return True

    def write(self, path: str, upto: int, t_open: float) -> None:
        """The counts, and in a last column each commit's time since the window
        opened (the only column that differs between two runs of a seed)."""
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(self.FIELDS + ("t_since_open_s",))
            for row, t in zip(self.rows[:upto], self.times):
                w.writerow(row + (f"{t - t_open:.6f}",))


def run(ctx: Ctx) -> RunResult:
    import jax

    from pretraining_llm_tpu.observability.device import CompileWatcher

    arch, tr = ctx.arch, ctx.traffic
    rows, p, o = tr["rows"], tr["prompt_tokens"], tr["output_tokens"]
    vocab = opcount.dims(arch)["vocab"]
    watcher = CompileWatcher().start()
    with ctx.phase("weights"):
        params = weights.serving_params(arch, ctx.seed)
    with ctx.phase("state_build"):
        cfg = program.model_config(arch, tr["engine"]["max_seq"])
        eng = program.serving_engine(params, cfg, tr)
    rng = np.random.default_rng([int(ctx.seed), 1])
    live: Dict[int, tuple] = {}  # rid -> (prompt, budget)
    # the last few requests to finish, with what the engine emitted: checked after the window
    emitted: Any = collections.deque(maxlen=tr["check_requests"])

    def submit(n_prompt: int, n_out: int) -> None:
        prompt = rng.integers(0, vocab, n_prompt).tolist()
        live[eng.submit(prompt, n_out)] = (prompt, n_out)

    failed = [0]

    def on_finish(rid: int, out: List[int]) -> None:
        prompt, budget = live.pop(rid)
        if len(out) != budget:
            failed[0] += 1
        else:
            emitted.append((prompt, list(out)))
        eng.finished.pop(rid, None)
        eng.req_timing.pop(rid, None)
        submit(p, o)

    eng.on_finish = on_finish
    log = TickLog(eng)
    with ctx.phase("warm_up"):
        wave = first_wave(rows, p, o)
        group = tr["first_wave_group"]
        for g in range(0, rows, group):
            for n_prompt, n_out in wave[g : g + group]:
                submit(n_prompt, n_out)
            log.step()
        while log.tick < tr["warm_ticks"]:
            log.step()
        while not log.step():  # the opening commit
            pass
        jax.block_until_ready(eng.pools)
    resident = 0 if ctx.rehearsal else ctx.devices[0].memory_stats()["bytes_in_use"]
    watcher.mark_warm()
    log.longest = (0.0, 0, 0.0, 0.0)
    open_i = len(log.rows) - 1
    st0 = dict(eng.stats)
    t_open = log.times[open_i]
    setup_s = t_open - ctx.t_start
    seconds = ctx.window_seconds
    with ctx.window():
        while time.perf_counter() - t_open < seconds:
            log.step()
    st1 = dict(eng.stats)
    compiles = watcher.summary()["recompiles"]
    watcher.stop()
    close_i = max(i for i, t in enumerate(log.times) if t - t_open <= seconds)
    if close_i <= open_i:
        raise RuntimeError("no commit inside the window")
    tokens = log.rows[close_i][4] - log.rows[open_i][4]
    span_s = log.times[close_i] - t_open
    counts_path = os.path.join(ctx.out_dir, "counts", ctx.run_id() + ".csv")
    log.write(counts_path, close_i + 1, t_open)
    win = log.rows[open_i + 1 : close_i + 1]
    took, at_tick, at_t, blocked = log.longest
    ctx.log(f"longest tick in the window: {took * 1e3:.1f} ms at tick {at_tick} (+{at_t - t_open:.3f}s), "
            f"{blocked * 1e3:.1f} ms of it blocked on the device's results")
    commit_s = statistics.median(b - a for a, b in zip(log.times[open_i:close_i], log.times[open_i + 1 :]))
    if seconds - span_s > 2 * commit_s:
        ctx.log(f"window closed early: last commit +{span_s:.3f}s of {seconds:g}s; commits are "
                f"{commit_s * 1e3:.1f} ms apart, so a stall straddles the end and is left out of both "
                f"tokens and time")
    ctx.log(
        f"window: ticks {log.rows[open_i][0]}..{log.rows[close_i][0]} commits {close_i - open_i} "
        f"admissions {log.rows[close_i][2] - log.rows[open_i][2]} "
        f"prefill_tokens {log.rows[close_i][3] - log.rows[open_i][3]} tokens_committed {tokens} "
        f"first_commit +0.000000s last_commit +{span_s:.6f}s counts {counts_path}"
    )
    # stop the loop: no resubmission, release every row, then the pool is free
    eng.on_finish = None
    for req in [r for r in eng.rows if r is not None] + list(eng.waiting):
        eng.cancel(req.rid)
    attempted = st1["admissions"] - st0["admissions"] + rows
    pool_info = eng.pool_info()
    compared = {"logits_rel_err": serving_check.compare(ctx, eng, params, cfg)}
    compared["engine_token_regret"] = serving_check.compare_tokens(ctx, list(emitted), tr["engine"]["max_seq"])
    compared["requests_wrong_length"] = (float(failed[0]), 0.0)
    mean_rows = float(np.mean([r[1] for r in win]))
    return RunResult(
        end_to_end={"output_tokens_per_s": tokens / span_s, "setup_s": setup_s},
        attempted=attempted, failed=failed[0],
        observed={
            "window_s": span_s, "compiles_in_window": compiles,
            "rows": rows, "mean_rows_active": mean_rows,
            "host_blocked_s": st1["host_blocked_s"] - st0["host_blocked_s"],
            "preemptions": st1["preemptions"] - st0["preemptions"],
            "kv_blocks_peak": log.blocks_peak, "kv_blocks_total": eng.n_blocks - 1,
            "steps": st1["steps"] - st0["steps"], "tokens_committed": tokens,
            "prefill_tokens": st1["prefill_tokens"] - st0["prefill_tokens"],
            "resident_tokens": rows * (p + o / 2.0), "bytes_in_use": resident,
            "pool_bytes": pool_info["pool_bytes"], "counts_path": counts_path,
            "emitted": list(emitted),
        },
        compared=compared,
    )
