"""A pretraining job: the program's train step on batches the file loader
samples from a seeded token stream.

Set-up makes the train state on the device(s) in one jitted call from the
seed (sharded as the program shards it), writes the token stream, and runs
``warm_steps`` steps. The window is whole steps, timed from one sync to
another: steps are dispatched ``sync_every`` at a time, as the trainer syncs
at its log boundaries, and the window closes at the last sync that fits in
``--seconds``. Rate = tokens of those steps / time between the two syncs.
"""

from __future__ import annotations

import os
import time
from types import SimpleNamespace
from typing import Any

import numpy as np

from harness import opcount, program, training_check, weights
from harness.context import Ctx, RunResult, span


def build(ctx: Ctx, with_state: bool = True) -> Any:
    """Train state from the seed, the step function, the token stream and its
    loader; ``with_state=False`` (the controls) makes the loader alone."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from pretraining_llm_tpu.data import loader as data_loader
    from pretraining_llm_tpu.parallel.sharding import batch_pspec, named_sharding_tree
    from pretraining_llm_tpu.training import optimizer as opt
    from pretraining_llm_tpu.training import train_step as ts

    arch, tr = ctx.arch, ctx.traffic
    cfg, mesh = program.train_config(arch, tr, ctx.devices, ctx.seed)
    seq_len, batch = tr["sequence_length"], cfg.train.batch_size
    key = weights.seed_key(ctx.seed)

    def make_state(key: Any) -> Any:
        params = weights.program_params(arch, key, jnp.dtype(cfg.model.param_dtype))
        return {"params": params, "opt": opt.optimizer_init(params, cfg.train),
                "step": jnp.zeros((), jnp.int32)}

    state = step_fn = put = None
    with ctx.phase("weights"):
        if not with_state:
            pass
        elif mesh is None:
            state = jax.jit(make_state)(key)
            put = lambda b: (jnp.asarray(b[0]), jnp.asarray(b[1]))
        else:
            shardings = named_sharding_tree(
                mesh, ts.state_pspec_tree(jax.eval_shape(make_state, key), False, tensor_size=1))
            state = jax.jit(make_state, out_shardings=shardings)(key)
            b_sh = NamedSharding(mesh, batch_pspec(cfg.model.sequence_parallel))
            put = lambda b: jax.device_put((jnp.asarray(b[0]), jnp.asarray(b[1])), (b_sh, b_sh))
        jax.block_until_ready(state)
    with ctx.phase("state_build"):
        if with_state:
            step_fn = ts.build_train_step(cfg, mesh)
        path = os.path.join(ctx.out_dir, "data", ctx.run_id() + ".bin")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        rng = np.random.default_rng([int(ctx.seed), 3])
        rng.integers(0, opcount.dims(arch)["vocab"], tr["stream_tokens"], dtype=np.uint16).tofile(path)
        batches = data_loader.get_batch_iterator(path, batch, seq_len, seed=ctx.seed % (2 ** 31))
    return SimpleNamespace(state=state, step_fn=step_fn, put=put, batches=batches, cfg=cfg, mesh=mesh,
                           batch=batch, seq_len=seq_len, path=path)


def run(ctx: Ctx) -> RunResult:
    import jax

    from pretraining_llm_tpu.data import loader as data_loader
    from pretraining_llm_tpu.observability.device import CompileWatcher

    arch, tr = ctx.arch, ctx.traffic
    chips = len(ctx.devices)
    watcher = CompileWatcher().start()
    job = build(ctx)
    state, step_fn, put, batches, cfg, mesh = (job.state, job.step_fn, job.put, job.batches, job.cfg,
                                               job.mesh)
    batch, seq_len, path = job.batch, job.seq_len, job.path
    del job
    try:
        with ctx.phase("warm_up"):
            first = next(batches)
            state, metrics = step_fn(state, put(first))
            step1 = (float(metrics["loss"]), float(metrics["grad_norm"]))
            feed = data_loader.DevicePrefetcher(batches, put, depth=cfg.data.prefetch)
            for _ in range(tr["warm_steps"] - 1):
                state, metrics = step_fn(state, next(feed))
            jax.block_until_ready(metrics)
        resident = 0 if ctx.rehearsal else max(d.memory_stats()["bytes_in_use"] for d in ctx.devices)
        watcher.mark_warm()
        sync_every = tr.get("sync_every", 4)
        t_open = time.perf_counter()
        setup_s = t_open - ctx.t_start
        seconds = ctx.window_seconds
        steps, t_close, group_s = 0, t_open, 0.0
        with ctx.window():
            while steps == 0 or (time.perf_counter() - t_open) + group_s <= seconds:
                t_group = time.perf_counter()
                for _ in range(sync_every):
                    with span("next_batch"):
                        b = next(feed)
                    with span("train_step"):
                        state, metrics = step_fn(state, b)
                with span("sync"):
                    jax.block_until_ready(metrics)
                t_close = time.perf_counter()
                group_s = t_close - t_group
                steps += sync_every
        compiles = watcher.summary()["recompiles"]
        watcher.stop()
        last_loss = float(metrics["loss"])
        feed.close()
    finally:
        if os.path.exists(path):
            os.remove(path)
    span_s = t_close - t_open
    tokens = steps * batch * seq_len
    ctx.log(f"window: steps {steps} sequences {steps * batch} tokens {tokens} "
            f"first_sync +0.000000s last_sync +{span_s:.6f}s last_loss {last_loss:.4f}")
    del state, metrics, b
    compared = training_check.compare(arch, ctx.seed, cfg, mesh, ctx.devices, first[0], first[1],
                                      step1, tr["check_sequences"] * chips, ctx.log)
    compared["loss_not_finite"] = (0.0 if np.isfinite(last_loss) else 1.0, 0.0)
    rate = tokens / span_s / chips
    return RunResult(
        end_to_end={"train_tokens_per_s_chip": rate, "setup_s": setup_s},
        attempted=steps, failed=0 if np.isfinite(last_loss) else steps,
        observed={
            "window_s": span_s, "compiles_in_window": compiles, "steps": steps,
            "tokens_per_s_chip": rate, "bytes_in_use": resident,
            "sequences_per_chip_in_window": steps * batch / chips,
        },
        compared=compared,
    )
