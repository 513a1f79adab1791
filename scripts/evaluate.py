#!/usr/bin/env python
"""Evaluate a checkpoint on a validation set: loss / perplexity / bits-per-token.

Standalone counterpart of the trainer's periodic eval (the reference has no
eval entry point at all — its eval lives inline in the training loop,
scripts/train_transformer.py:51-62). Deterministic: the same seeded batches
every run, so numbers are comparable across checkpoints.

Usage:
  python scripts/evaluate.py --model_path checkpoints --data data/val.bin
  python scripts/evaluate.py --model_path checkpoints/step-4000 \
      --data data/val.bin --iters 100 --batch 16
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pretraining_llm_tpu.utils.compile_cache import use_compile_cache

use_compile_cache()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model_path", required=True, help="checkpoint dir (or step-N dir)")
    ap.add_argument("--data", required=True, help="uint16 token .bin")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--batch", type=int, default=0, help="0 = checkpoint's train batch")
    ap.add_argument("--ema", action="store_true",
                    help="evaluate the EMA shadow params (train.ema_decay runs)")
    ap.add_argument(
        "--seed", type=int, default=-1,
        help="-1 = the trainer's own eval seed (data.sample_seed + 104729), "
        "so the number matches the training log's val_loss exactly",
    )
    args = ap.parse_args()

    import jax.numpy as jnp

    from pretraining_llm_tpu.data import loader
    from pretraining_llm_tpu.generation.generate import load_model_for_inference
    from pretraining_llm_tpu.training import train_step as ts

    params, cfg = load_model_for_inference(args.model_path, use_ema=args.ema)
    batch = args.batch or cfg.train.batch_size
    seed = args.seed if args.seed >= 0 else cfg.data.sample_seed + 104729
    it = loader.get_batch_iterator(args.data, batch, cfg.model.context_length, seed=seed)
    # Same single-dispatch scan the trainer's periodic eval uses — one device
    # round trip for all iters, not one per batch.
    eval_loop = ts.build_eval_loop(cfg, mesh=None)
    xs, ys = zip(*(next(it) for _ in range(args.iters)))
    loss = float(
        eval_loop({"params": params}, (jnp.asarray(np.stack(xs)), jnp.asarray(np.stack(ys))))
    )
    n = args.iters
    print(
        json.dumps(
            {
                "val_loss": round(loss, 6),
                # inf past the float64 exp bound — never a silently-clamped
                # finite value (same convention as the trainer's metrics).
                "val_ppl": round(math.exp(loss), 3) if loss < 700 else float("inf"),
                "val_bits_per_token": round(loss / math.log(2), 4),
                "iters": n,
                "batch": batch,
                "context_length": cfg.model.context_length,
                "tokens_evaluated": n * batch * cfg.model.context_length,
                "checkpoint": os.path.abspath(args.model_path),
            }
        )
    )


if __name__ == "__main__":
    main()
