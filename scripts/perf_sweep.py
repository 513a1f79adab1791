#!/usr/bin/env python
"""Sweep bench.py configurations on the real chip; record and rank results.

One command to re-tune after kernel/schedule changes (or a new chip):
runs the grid sequentially through bench.py's resilient wrapper (fresh
subprocess per attempt, transient-backend retries), appends every result to
a JSONL log, and prints the ranked table + the single best flag set.

Usage:
  python scripts/perf_sweep.py                  # default grid, gpt2-124m
  python scripts/perf_sweep.py --quick          # 1 attempt, short budget
  python scripts/perf_sweep.py --out /tmp/sweep.jsonl
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The grid: remat policies x CE head x batch. Attention stays flash (naive
# is only a reference point; measured 25% vs 41% MFU).
GRID = {
    "remat": ["none", "save_attn", "save_attn_res", "save_qkv_attn",
              "save_big", "full"],
    "ce": ["chunked", "fused", "dense"],
    "batch": [8, 12, 16, 24, 32],
}

# Excluded combos, each with the reason the skip log prints. Two classes:
# wedge risk (a known or adjacent chip-wedge combo: probing one can cost
# the backend for HOURS — the round-2 0.0 mechanism) and capacity (points
# far past the AOT-estimated memory ceiling; OOM is a clean bounded
# failure, but the budget is better spent on points that can land).
EXCLUDE = [
    # fused CE is a WEDGE CLASS on this backend, not a single bad combo:
    # save_attn+fused hung the chip twice (2026-07-31), and save_big+fused
    # — which had TWO clean captures in round 3 — hung and wedged the
    # backend on 2026-08-01. The wedge is intermittent within the class,
    # so no fused point is safe to probe on-chip; fused CE also measured
    # a throughput LOSS at every shape it completed (BASELINE.md), so the
    # payoff is known-negative.
    ({"ce": "fused"},
     "fused-CE wedge class (hung save_attn twice 2026-07-31 and save_big "
     "2026-08-01 despite two prior clean captures); measured slower anyway"),
    ({"remat": "none", "batch": 24},
     "far past the remat=none memory ceiling (AOT r4): near-certain OOM"),
    ({"remat": "none", "batch": 32},
     "far past the remat=none memory ceiling (AOT r4): near-certain OOM"),
]


def _excluded(flags: dict) -> str:
    """The exclusion reason for this combo, or '' if it should be probed."""
    for ex, why in EXCLUDE:
        if all(flags.get(k) == v for k, v in ex.items()):
            return why
    return ""


def run_one(
    flags: dict, budget: float, preset: str, quick: bool = False,
    skip_canary: bool = False,
) -> dict:
    cmd = [
        sys.executable, os.path.join(REPO, "bench.py"),
        "--preset", preset,
        "--remat", flags["remat"],
        "--ce", flags["ce"],
        "--batch", str(flags["batch"]),
        "--timeout-budget", str(budget),
        "--attempt-timeout", str(min(400.0, budget)),
    ]
    if quick:
        cmd.append("--quick")
    if skip_canary:
        # The environment was proven alive by the first config's canary;
        # later configs skip it (a mid-sweep backend death still surfaces as
        # that config's structured bench error).
        cmd.append("--skip-canary")
    t0 = time.time()
    rec = {"flags": flags}
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=budget + 120
        )
    except subprocess.TimeoutExpired:
        # One wedged config must not abort the rest of the grid.
        rec.update({"value": 0.0, "error": f"harness timeout after {budget + 120:.0f}s"})
        rec["wall_s"] = round(time.time() - t0, 1)
        return rec
    rec["wall_s"] = round(time.time() - t0, 1)
    line = (proc.stdout or "").strip().splitlines()
    try:
        rec.update(json.loads(line[-1]))
    except (IndexError, json.JSONDecodeError):
        rec.update({"value": 0.0, "error": (proc.stderr or "no output")[-300:]})
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="gpt2-124m")
    ap.add_argument("--out", default=os.path.join(REPO, "sweep_results.jsonl"))
    ap.add_argument("--budget", type=float, default=700.0, help="seconds per config")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    budget = 300.0 if args.quick else args.budget

    combos = [
        dict(zip(GRID, vals)) for vals in itertools.product(*GRID.values())
    ]
    skipped = [(c, _excluded(c)) for c in combos if _excluded(c)]
    combos = [c for c in combos if not _excluded(c)]
    for c, why in skipped:
        print(f"[skip] {c}: {why}", flush=True)
    results = []
    with open(args.out, "a") as f:
        env_alive = False
        for i, flags in enumerate(combos):
            print(f"[{i + 1}/{len(combos)}] {flags}", flush=True)
            rec = run_one(
                flags, budget, args.preset, quick=args.quick, skip_canary=env_alive
            )
            if rec.get("value", 0) > 0 or not rec.get("environment_error"):
                env_alive = True
            f.write(json.dumps(rec) + "\n")
            f.flush()
            results.append(rec)
            print(f"    -> {rec.get('value', 0)} {rec.get('error', '')[:80]}", flush=True)

    ok = [r for r in results if r.get("value", 0) > 0]
    ok.sort(key=lambda r: -r["value"])
    print("\n=== ranked ===")
    for r in ok[:10]:
        print(f"{r['value']:.4f}  {r['flags']}  step_ms={r.get('step_ms')}")
    if ok:
        best = ok[0]
        print(
            f"\nbest: python bench.py --remat {best['flags']['remat']} "
            f"--ce {best['flags']['ce']} --batch {best['flags']['batch']}"
            f"  -> {best['value']:.4f} MFU"
        )


if __name__ == "__main__":
    main()
