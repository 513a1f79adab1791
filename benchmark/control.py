"""The comparisons behind `correct`, read on many seeds, with their controls.

``python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--control] ...``
runs on the chip, in one process:

* for every seed, the numbers ``correct`` compares for the sound program;
* with ``--control``, the same numbers for the control: the computation in
  the nearest precision below the one the configuration states.

Serving (bfloat16): each seed runs the cell's own driver for a window of
``--seconds``, so that the engine's path is the measured one; the control is
the same run on the program's own int8 weight path
(``models/quantize.quantize_params_for_serving``, made two layers at a time
so that the bf16 and int8 trees never stand side by side). Beside the
engine-token reading it prints that reading's own controls: every emitted
token held to the logits of the position before its own (an engine one off in
a length or a page table) and to the next request's prompt (a wrong row). ``--quants int8`` adds the
reference's logits with int8 matmul operands.

Training (bfloat16 matmuls on float32 state; the program has no lower path):
the reference with the matmul operands of ``--quants`` (``fp8``: float8 e4m3,
``int8``) in the program's place. ``--reference_only`` reads the controls
without building the program's train state (the sound readings are every
run's own).

A limit is set between the largest sound reading and the smallest control
reading (PERF.md section 2). ``benchmark/run.py`` never runs this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def int8_params(arch, seed, chunk=2):
    """The program's int8 serving tree from the seed, ``chunk`` layers at a time."""
    import jax
    import jax.numpy as jnp

    from harness import opcount, weights
    from pretraining_llm_tpu.models import quantize

    dtype = jnp.dtype(arch["serving_dtype"])
    key = weights.seed_key(seed)
    n = opcount.dims(arch)["layers"]
    cfg = type("C", (), {"n_experts": 0})()
    make = jax.jit(lambda k, idx: quantize.quantize_params_for_serving(
        weights.program_params(arch, k, dtype, layers=idx), cfg))
    parts = [make(key, jnp.arange(a, min(a + chunk, n))) for a in range(0, n, chunk)]
    params = dict(parts[0])
    params["blocks"] = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *[p["blocks"] for p in parts])
    return jax.block_until_ready(params)


def serving(ctx, seeds, args):
    import dataclasses

    import numpy as np

    from harness import opcount, registry, serving_check as sc, weights
    from references.common import QUANTS

    arch, tr = ctx.arch, ctx.traffic
    run = registry.driver(tr["kind"])
    sound_params = weights.serving_params
    for seed in seeds:
        c = dataclasses.replace(ctx, seed=seed, seconds=args.seconds, setup_split={})
        row = {}
        for name in ("sound",) + (("control_program_int8",) if args.control else ()):
            weights.serving_params = sound_params if name == "sound" else int8_params
            try:
                res = run(c)
            finally:
                weights.serving_params = sound_params
            row.update({f"{name}_{k}": v[0] for k, v in res.compared.items()})
            if name == "sound":
                emitted, pad_to = res.observed["emitted"], tr["engine"]["max_seq"]
                _, off = sc.token_regrets(arch, seed, emitted, pad_to)
                wrong, _ = sc.token_regrets(arch, seed, sc.wrong_rows(emitted), pad_to)
                for name, r in (("one_position_off", off), ("wrong_row", wrong)):
                    row[f"control_{name}_engine_token_regret"] = float(r.max())
                    row[f"control_{name}_median_regret"] = float(np.median(r))
            del res
        if args.control and args.quants:
            sample = [tuple(x) for x in tr["check_sample"]]
            seqs = sc.sample_tokens(seed, opcount.dims(arch)["vocab"], sample)
            ref = sc.reference_logits(arch, seed, sample, seqs)
            for q in args.quants:
                row[f"control_reference_{q}_logits_rel_err"] = sc.rel_err(
                    sc.reference_logits(arch, seed, sample, seqs, quant=QUANTS[q]), ref)
        ctx.log(f"seed {seed} " + " ".join(f"{k}={v:.6g}" for k, v in row.items()))


def training(ctx, seeds, args):
    import dataclasses

    import jax

    from harness import training_check as tc
    from harness.drivers import train_job
    from references.common import QUANTS

    n_check = ctx.traffic["check_sequences"] * len(ctx.devices)
    for seed in seeds:
        job = train_job.build(dataclasses.replace(ctx, seed=seed), with_state=not args.reference_only)
        first = next(job.batches)
        os.remove(job.path)
        x, y = first
        res = {}
        if not args.reference_only:
            out = job.step_fn(job.state, job.put(first))
            step1 = (float(out[1]["loss"]), float(out[1]["grad_norm"]))
            cfg, mesh = job.cfg, job.mesh
            del job, out  # frees the train state before the reference needs the memory
            res = {f"sound_{k}": v[0] for k, v in
                   tc.compare(ctx.arch, seed, cfg, mesh, ctx.devices, x, y, step1, n_check, ctx.log).items()}
        if args.control:
            ref = tc.Reference(ctx.arch, seed, ctx.devices)
            _, g_ref = ref.loss_and_grads(x[:n_check], y[:n_check])
            leaves = jax.tree.leaves
            den = sum(float((b ** 2).sum()) for b in leaves(g_ref))
            for q in args.quants or ["fp8"]:
                _, g_ctl = ref.loss_and_grads(x[:n_check], y[:n_check], quant=QUANTS[q])
                num = sum(float(((a - b) ** 2).sum()) for a, b in zip(leaves(g_ctl), leaves(g_ref)))
                res[f"control_{q}_grad_rel_err"] = (num / den) ** 0.5
                del g_ctl
            del g_ref, ref
        ctx.log(f"seed {seed} " + " ".join(f"{k}={v:.6g}" for k, v in res.items()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true", help="read the control too")
    ap.add_argument("--quants", type=lambda v: v.split(","), default=[],
                    help="operand precisions of the reference-side controls: fp8, int8")
    ap.add_argument("--reference_only", action="store_true",
                    help="training: the controls alone, without the program's train state")
    ap.add_argument("--seconds", type=float, default=3.0, help="serving: window of each run")
    args = ap.parse_args(argv)
    import run as bench_run
    from harness import registry
    from harness.context import Ctx

    cell = registry.cell(args.workload)
    import jax

    bench_run.use_compile_cache(jax)
    devices = jax.devices()[: cell["chips"]]
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("control.py reads its numbers on the chip", file=sys.stderr)
        return 3
    traffic = registry.load_traffic(cell["traffic"])
    ctx = Ctx(cell=cell, arch=registry.load_config(cell["config"]), traffic=traffic, seed=0,
              seconds=0, trace=False, devices=devices, out_dir=os.path.join(HERE, "out"),
              t_start=T_START, tag="control")
    seeds = [int(s) for s in args.seeds.split(",")]
    (training if traffic["kind"] == "train_job" else serving)(ctx, seeds, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
