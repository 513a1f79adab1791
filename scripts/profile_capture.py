#!/usr/bin/env python
"""Capture a device trace of the train step and print the HLO-op time table.

Runs a few steps under jax.profiler.trace, then parses the captured
xplane.pb with the in-image xprof converter (no TensorBoard UI needed,
the machine is air-gapped) and prints the top ops by self time — the
ground truth for where the step time actually goes.

Usage:
  python scripts/profile_capture.py --preset gpt2-124m --batch 24 --remat save_attn
  python scripts/profile_capture.py --tool framework_op_stats --top 40
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pretraining_llm_tpu.utils.compile_cache import use_compile_cache

use_compile_cache()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="gpt2-124m")
    ap.add_argument(
        "--batch", type=int, default=0,
        help="0 = mode default (train: 24; decode: 8 — matching bench.py's "
        "decode default so the trace explains the benchmark number)",
    )
    ap.add_argument("--remat", default="")
    ap.add_argument("--attention", default="")
    ap.add_argument(
        "--mode", default="train", choices=["train", "decode"],
        help="decode: trace KV-cached generation (prefill + token scan) "
        "instead of the train step — the ground truth for serving opt",
    )
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument(
        "--out", default="",
        help="trace dir; default derives from --mode (/tmp/pllm_trace vs "
        "/tmp/pllm_trace_decode) so a failed decode trace can never be "
        "silently satisfied by a stale train xplane (ADVICE r3)",
    )
    ap.add_argument("--tool", default="hlo_stats")
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--parse-only", action="store_true")
    args = ap.parse_args()

    if not args.batch:
        args.batch = 8 if args.mode == "decode" else 24
    if not args.out:
        args.out = "/tmp/pllm_trace_decode" if args.mode == "decode" else "/tmp/pllm_trace"

    def _xplanes():
        return set(
            glob.glob(os.path.join(args.out, "**", "*.xplane.pb"), recursive=True)
        )

    pre_existing = _xplanes()
    if not args.parse_only:
        import jax
        import jax.numpy as jnp

        from pretraining_llm_tpu.config import get_preset
        from pretraining_llm_tpu.data import loader
        from pretraining_llm_tpu.training import train_step as ts

        cfg = get_preset(args.preset)
        model = cfg.model
        if args.attention:
            model = dataclasses.replace(model, attention_impl=args.attention)
        elif model.attention_impl == "ring":
            model = dataclasses.replace(model, attention_impl="flash", sequence_parallel=False)
        if args.remat:
            model = dataclasses.replace(model, remat=args.remat)
        cfg = cfg.replace(
            model=model, train=dataclasses.replace(cfg.train, batch_size=args.batch)
        )
        if args.mode == "decode":
            # Same trap bench.py guards against (its --attention check):
            # these flags shape the TRAIN step only; silently accepting
            # them would produce identical traces labeled differently.
            if args.remat or args.attention:
                raise ValueError(
                    "--remat/--attention have no effect on the cached "
                    "decode path; drop them for --mode decode"
                )
            from pretraining_llm_tpu.generation.generate import (
                decode_bench_workload, generate,
            )

            # The canonical decode-bench workload from the RAW preset model
            # (bench.py passes the raw model too — the train-oriented
            # ring->flash rewrite above must not leak in): the trace
            # explains exactly the shape `bench.py --mode decode` measures.
            mcfg, params, prompt, new_tokens = decode_bench_workload(
                get_preset(args.preset).model, args.batch
            )

            def run(seed):
                return jax.device_get(
                    generate(params, mcfg, prompt, new_tokens,
                             jax.random.key(seed), temperature=1.0)
                )

            run(0)  # compile + warm outside the trace window
            with jax.profiler.trace(args.out):
                for s in range(1, args.steps + 1):
                    run(s)
        else:
            state = ts.init_train_state(cfg, jax.random.key(0))
            step = ts.build_train_step(cfg, None)
            it = loader.synthetic_iterator(model.vocab_size, model.context_length, args.batch, seed=0)
            x, y = next(it)
            batch = (jnp.asarray(x), jnp.asarray(y))
            # Warm (compile) outside the trace window.
            state, m = step(state, batch)
            float(jax.device_get(m["loss"]))
            with jax.profiler.trace(args.out):
                for _ in range(args.steps):
                    state, m = step(state, batch)
                float(jax.device_get(m["loss"]))

    planes = sorted(_xplanes(), key=os.path.getmtime)
    if not planes:
        print(json.dumps({"error": f"no xplane.pb under {args.out}"}))
        sys.exit(1)
    if not args.parse_only and not (set(planes) - pre_existing):
        # The profiler ran but produced no NEW trace: parsing the
        # mtime-newest pre-existing file would print a stale trace (possibly
        # from the other mode) labeled as this run's. Fail loudly instead.
        print(json.dumps({
            "error": f"profiler produced no new xplane under {args.out}; "
            f"{len(planes)} stale file(s) present — refusing to parse them",
        }))
        sys.exit(1)
    from xprof.convert import raw_to_tool_data as rtd

    data, _ = rtd.xspace_to_tool_data([planes[-1]], args.tool, {})
    if isinstance(data, bytes):
        data = data.decode("utf-8", "replace")
    rows = _extract_rows(data, args.tool)

    # Persist the FULL table and end stdout with one JSON summary line:
    # a caller that keeps only the end of stdout once recorded a truncated
    # HTML fragment — the whole table must live on disk, not in a pipe.
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    table_dir = os.path.join(repo, "data", "captures")
    os.makedirs(table_dir, exist_ok=True)
    import time

    # Timestamped: successive captures must not overwrite the table a
    # previously-banked campaign record's table_path points at.
    stamp = time.strftime("%Y%m%d_%H%M%S")
    table_path = os.path.join(
        table_dir, f"profile_{args.mode}_{args.tool}_{stamp}.tsv"
    )
    with open(table_path, "w") as f:
        f.write(data if rows is None else "\n".join(rows))
    if rows is None:
        print(json.dumps({"table_path": table_path, "parsed": False}))
        return
    for r in rows[: args.top]:
        print(r)
    import re

    def clean(row: str) -> str:
        return re.sub(r"<[^>]+>", "", row)[:240]

    print(json.dumps({
        "table_path": table_path,
        "n_rows": len(rows),
        "header": clean(rows[0]) if rows else "",
        "top": [clean(r) for r in rows[1: min(9, len(rows))]],
    }))


def _extract_rows(data: str, tool: str):
    """hlo_stats/framework_op_stats come back as gviz JSON-ish or CSV."""
    try:
        obj = json.loads(data)
    except (json.JSONDecodeError, ValueError):
        lines = data.splitlines()
        return lines if lines else None
    # gviz DataTable: {"cols": [...], "rows": [{"c": [{"v": ...}, ...]}]}
    if isinstance(obj, dict) and "rows" in obj and "cols" in obj:
        labels = [c.get("label") or c.get("id") for c in obj["cols"]]
        out = ["\t".join(str(x) for x in labels)]
        for row in obj["rows"]:
            out.append("\t".join(str(c.get("v") if c else "") for c in row["c"]))
        return out
    return None


if __name__ == "__main__":
    main()
