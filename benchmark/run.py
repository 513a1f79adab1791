"""One run of one cell: ``--workload <cell> --seed <n> --seconds <s> --trace <0|1>``.

Finds the cell, its configuration, traffic file, driver, per-layer metrics
and readers by name (``harness/registry.py``); runs in this process on the
chips the cell asks for; prints the set-up split and the window's counts,
then as the last line the result object. Fails, printing no result, when
JAX finds no TPU or fewer chips than the cell needs.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tag", default="", help="label of a set of runs, for spread.py")
    return ap.parse_args(argv)


def use_compile_cache(jax) -> str:
    """JAX's persistent cache inside the checkout, at a fixed path; a path
    given from outside (JAX_COMPILATION_CACHE_DIR) is taken as it is."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_block(devices, trace_summary=None) -> dict:
    stats = [d.memory_stats() or {} for d in devices]
    out = {
        "platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
        "memory_peak_bytes": max(
            max(s.get("peak_bytes_in_use", 0), s.get("bytes_in_use", 0)) for s in stats
        ),
    }
    if trace_summary is not None:
        out["busy_s"] = trace_summary["busy_s"]
        out["window_s"] = trace_summary["window_s"]
    return out


def main(argv=None) -> int:
    args = parse(argv)
    from harness import registry
    from harness.context import Ctx, emit

    cell = registry.cell(args.workload)
    arch = registry.load_config(cell["config"])
    traffic = registry.load_traffic(cell["traffic"])

    import jax

    cache_dir = use_compile_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark needs a TPU; JAX found platform {devices[0].platform!r}", file=sys.stderr)
        return 3
    if len(devices) < cell["chips"]:
        print(f"cell needs {cell['chips']} chips; JAX found {len(devices)}", file=sys.stderr)
        return 3
    devices = devices[: cell["chips"]]
    import pretraining_llm_tpu  # noqa: F401  the system under test; absent = no result

    ctx = Ctx(
        cell=cell, arch=arch, traffic=traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices, out_dir=os.path.join(HERE, "out"),
        t_start=T_START, tag=args.tag,
    )
    ctx.log(f"cell {cell['name']} seed {args.seed} trace {args.trace} cache {cache_dir} "
            f"device {devices[0].device_kind} x{len(devices)}")
    result = registry.driver(traffic["kind"])(ctx)
    ctx.log("set-up split (s): " + " ".join(f"{k}={v:.2f}" for k, v in ctx.setup_split.items())
            + f" total={result.end_to_end['setup_s']:.2f}")

    correct = True
    for name, (value, limit) in result.compared.items():
        ok = value <= limit
        correct = correct and ok
        ctx.log(f"compared {name}: {value:.6g} limit {limit:.6g} {'ok' if ok else 'NOT CORRECT'}")

    summary = None
    metrics = {}
    if ctx.trace:
        from harness import reduce_trace

        summary = reduce_trace.summarize(reduce_trace.load(reduce_trace.find_xplane(ctx.trace_dir)))
        for spec in registry.metrics_for(cell["name"], trace=True):
            lm = registry.layer_metric_spec(spec["name"])
            value = registry.reader(lm["reader"])(result, summary, ctx, **lm.get("args", {}))
            if value is None:
                continue
            if ("roofline" in spec["name"] or "mfu" in spec["name"]) and value > 100.0:
                raise RuntimeError(
                    f"{spec['name']} = {value}% of a peak: the operations or bytes are counted "
                    f"too high, or the time leaves out part of the work")
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    else:
        for spec in registry.metrics_for(cell["name"], trace=False):
            if spec["name"] in result.end_to_end:
                metrics[spec["name"]] = {"value": result.end_to_end[spec["name"]], "unit": spec["unit"]}

    line = {
        "correct": correct, "attempted": result.attempted, "failed": result.failed,
        "metrics": metrics, "device": device_block(devices, summary),
    }
    if summary is not None:
        line["breakdown"] = {"device_ops": summary["device_ops"], "idle_gaps": summary["idle_gaps"]}
    record = dict(line, workload=cell["name"], seed=args.seed, trace=args.trace, tag=args.tag,
                  seconds=args.seconds, setup_split=ctx.setup_split,
                  compared={k: list(v) for k, v in result.compared.items()},
                  observed={k: v for k, v in result.observed.items() if isinstance(v, (int, float, str))})
    os.makedirs(ctx.out_dir, exist_ok=True)
    with open(os.path.join(ctx.out_dir, "records.jsonl"), "a") as f:
        f.write(json.dumps(record) + "\n")
    emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
