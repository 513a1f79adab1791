#!/usr/bin/env python
"""Training entry point.

Mirror of the reference CLI (`/root/reference/scripts/train_transformer.py`),
redesigned: presets + dotted overrides instead of a mutable global dict, JAX
multi-host init instead of torchrun env vars, `--data synthetic` for a
zero-setup smoke run.

Examples:
  python scripts/train.py --preset tiny --data synthetic --override train.train_steps=100
  python scripts/train.py --preset gpt2-124m \
      --override data.train_path=data/train.bin data.val_path=data/val.bin
"""

from __future__ import annotations

import argparse
import ast
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pretraining_llm_tpu.utils.compile_cache import use_compile_cache

use_compile_cache()

from pretraining_llm_tpu.parallel.mesh import initialize_distributed

# Must run before anything touches a device (see mesh.initialize_distributed).
initialize_distributed()

import jax  # noqa: E402

from pretraining_llm_tpu.config import get_preset, list_presets  # noqa: E402
from pretraining_llm_tpu.training.trainer import Trainer  # noqa: E402


def parse_overrides(pairs):
    out = {}
    for pair in pairs:
        if "=" not in pair:
            raise SystemExit(f"override must be key=value, got {pair!r}")
        key, raw = pair.split("=", 1)
        try:
            out[key] = ast.literal_eval(raw)
        except (ValueError, SyntaxError):
            out[key] = raw  # plain string
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--preset", default="gpt2-124m", help=f"one of {list_presets()}")
    parser.add_argument(
        "--override", nargs="*", default=[], metavar="SECTION.KEY=VALUE",
        help="dotted config overrides, e.g. train.lr=1e-4",
    )
    parser.add_argument(
        "--data", default="files", choices=["files", "synthetic"],
        help="'synthetic' trains on a generated Markov stream (no files needed)",
    )
    parser.add_argument(
        "--obs-dir", default="", metavar="DIR",
        help="enable run-wide telemetry under DIR: events.jsonl (EventBus), "
        "spans.trace.json (Perfetto), metrics.prom (Prometheus textfile); "
        "analyze offline with scripts/obs_report.py. Explicit obs.* "
        "overrides win over the derived paths",
    )
    parser.add_argument("--no-resume", action="store_true", help="ignore existing checkpoints")
    parser.add_argument("--steps", type=int, default=None, help="override total steps")
    parser.add_argument(
        "--compile-only", action="store_true",
        help="compile the train step, print per-device memory analysis "
        "(size a big config BEFORE burning pod time on an OOM), and exit",
    )
    args = parser.parse_args()

    overrides = parse_overrides(args.override)
    if args.obs_dir:
        os.makedirs(args.obs_dir, exist_ok=True)
        for key, fname in (
            ("obs.events_path", "events.jsonl"),
            ("obs.spans_path", "spans.trace.json"),
            ("obs.prometheus_path", "metrics.prom"),
        ):
            overrides.setdefault(key, os.path.join(args.obs_dir, fname))
    config = get_preset(args.preset).with_overrides(overrides)
    if jax.process_index() == 0:
        print(f"preset={config.name} devices={jax.device_count()} "
              f"params={config.model.num_params()/1e6:.1f}M")
    if args.compile_only:
        compile_only(config)
        return
    trainer = Trainer(config, synthetic_data=(args.data == "synthetic"), resume=not args.no_resume)
    final = trainer.train(steps=args.steps)
    if jax.process_index() == 0:
        print("final:", final, f"exit_reason={trainer.exit_reason}")
    # Return-code contract for scripts/supervisor.py (see resilience/):
    # preemption means "checkpointed, relaunch me"; an exhausted rollback
    # budget means "systemic anomaly, stop relaunching". EXIT_WEDGED is
    # raised by the watchdog itself via os._exit.
    from pretraining_llm_tpu.resilience import EXIT_ANOMALY, EXIT_PREEMPTED

    rc = {
        "preempted": EXIT_PREEMPTED,
        "anomaly_budget": EXIT_ANOMALY,
        "anomaly_no_checkpoint": EXIT_ANOMALY,
    }.get(trainer.exit_reason, 0)
    if rc:
        sys.exit(rc)


def compile_only(config) -> None:
    """AOT-compile the exact training program from shape specs only — no
    params materialize, no data loads — and report XLA's per-device memory
    breakdown (donated/aliased state buffers counted once)."""
    import json as _json
    import time as _time

    from pretraining_llm_tpu.parallel.mesh import build_mesh, needs_mesh
    from pretraining_llm_tpu.training import train_step as ts

    mesh = build_mesh(config.mesh) if needs_mesh(config.mesh) else None
    t0 = _time.time()
    compiled = ts.lower_train_step(config, mesh).compile()
    dt = _time.time() - t0
    mem = compiled.memory_analysis()
    gib = 2**30
    alias = getattr(mem, "alias_size_in_bytes", 0)
    report = {
        "compile_s": round(dt, 1),
        "devices": jax.device_count(),
        "per_device_GiB": {
            "arguments": round(mem.argument_size_in_bytes / gib, 3),
            "outputs": round(mem.output_size_in_bytes / gib, 3),
            "aliased (donated state, counted once)": round(alias / gib, 3),
            "temps": round(mem.temp_size_in_bytes / gib, 3),
            "total_peak_estimate": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - alias) / gib, 3,
            ),
        },
    }
    if jax.process_index() == 0:
        print(_json.dumps(report))


if __name__ == "__main__":
    main()
