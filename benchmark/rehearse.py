"""CPU rehearsal of a cell's driver at a toy size: file discovery, control
flow and counts, and never a device metric.

``JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload <cell>`` swaps
the cell's configuration for ``tests/toy/<family>.json`` and its traffic
sizes for the traffic file's own ``rehearsal`` block, runs the same driver,
and prints the counts and the numbers compared. Times, rates, utilisations
and roofline shares are not printed: a CPU run cannot give them.
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

COUNT_KEYS = ("steps", "tokens_committed", "prefill_tokens", "preemptions", "compiles_in_window",
              "kv_blocks_peak", "kv_blocks_total", "requests_measured", "sequences_per_chip_in_window")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 11)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--tag", default="rehearsal")
    args = ap.parse_args(argv)
    from harness import registry
    from harness.context import Ctx

    cell = registry.cell(args.workload)
    if cell["chips"] > 1:
        flag = f"--xla_force_host_platform_device_count={cell['chips']}"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    import jax

    if jax.devices()[0].platform != "cpu":
        print("rehearse.py is for the CPU; run benchmark/run.py on the chip", file=sys.stderr)
        return 3
    real = registry.load_config(cell["config"])
    with open(os.path.join(HERE, "tests", "toy", f"{real['family']}.json")) as f:
        arch = json.load(f)
    arch["name"] = cell["config"]
    if "mesh" in real:
        arch["mesh"] = real["mesh"]
    traffic = registry.load_traffic(cell["traffic"])
    traffic.update(traffic.pop("rehearsal"))
    ctx = Ctx(
        cell=cell, arch=arch, traffic=traffic, seed=args.seed, seconds=args.seconds, trace=False,
        devices=jax.devices()[: cell["chips"]], out_dir=os.path.join(HERE, "out", "rehearsal"),
        t_start=T_START, tag=args.tag, rehearsal=True,
    )
    result = registry.driver(traffic["kind"])(ctx)
    ok = all(v <= limit for v, limit in result.compared.values())
    print(json.dumps({
        "rehearsal": True, "platform": "cpu", "workload": cell["name"],
        "attempted": result.attempted, "failed": result.failed,
        "counts": {k: result.observed[k] for k in COUNT_KEYS if k in result.observed},
        "counts_path": result.observed.get("counts_path"),
        "compared": {k: list(v) for k, v in result.compared.items()}, "within_limits": ok,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
