#!/usr/bin/env python
"""Sampling CLI — mirror of the reference's `scripts/generate_text.py`
interface (`--model_path --input_text --max_new_tokens`,
/root/reference/scripts/generate_text.py:49-58), extended with sampling knobs.

Example:
  python scripts/generate_text.py --model_path checkpoints \
      --input_text "Once upon a time" --max_new_tokens 100 --temperature 0.8 --top_k 50
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pretraining_llm_tpu.utils.compile_cache import use_compile_cache

use_compile_cache()

from pretraining_llm_tpu.generation.generate import generate_text


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model_path", required=True, help="checkpoint dir (or a step-N dir)")
    group = parser.add_mutually_exclusive_group(required=True)
    group.add_argument("--input_text")
    group.add_argument(
        "--input_file",
        help="file with one prompt per line: the whole batch decodes in ONE "
        "compiled ragged program (different prompt lengths supported)",
    )
    parser.add_argument("--max_new_tokens", type=int, default=100)
    parser.add_argument("--temperature", type=float, default=1.0, help="0 = greedy")
    parser.add_argument("--top_k", type=int, default=None)
    parser.add_argument("--top_p", type=float, default=None)
    parser.add_argument("--min_p", type=float, default=None,
                        help="keep tokens with prob >= min_p * max prob")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--tokenizer", default=None,
        help="override the tokenizer name stored in the checkpoint config",
    )
    parser.add_argument(
        "--stop_token", type=int, default=None,
        help="token id that ends a row's generation (output truncates there)",
    )
    parser.add_argument(
        "--draft_model_path", default=None,
        help="a smaller checkpoint sharing the vocab: enables speculative "
        "decoding (draft proposes --spec_k tokens/round, target verifies "
        "in one forward; greedy output equals target-only decoding)",
    )
    parser.add_argument("--spec_k", type=int, default=4,
                        help="speculative proposals per round")
    parser.add_argument("--ema", action="store_true",
                        help="decode from the EMA shadow params")
    args = parser.parse_args()

    if args.draft_model_path:
        from pretraining_llm_tpu.generation.generate import (
            generate_text_speculative,
        )

        if args.input_file:
            parser.error("--draft_model_path is the batch-1 latency path; "
                         "use --input_text")
        if args.stop_token is not None or args.top_k or args.top_p or args.ema:
            parser.error("--draft_model_path supports --temperature only "
                         "(no stop_token/top_k/top_p/ema yet)")
        print(generate_text_speculative(
            args.model_path, args.draft_model_path, args.input_text,
            args.max_new_tokens, k=args.spec_k,
            temperature=args.temperature, seed=args.seed,
            tokenizer=args.tokenizer,
        ))
        return

    if args.input_file:
        from pretraining_llm_tpu.generation.generate import generate_text_batch

        with open(args.input_file) as f:
            prompts = [line.rstrip("\r\n") for line in f if line.strip()]
        outs = generate_text_batch(
            args.model_path,
            prompts,
            args.max_new_tokens,
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            min_p=args.min_p,
            seed=args.seed,
            tokenizer=args.tokenizer,
            stop_token=args.stop_token,
            ema=args.ema,
        )
        for text in outs:
            print(text)
            print("---")
        return

    text = generate_text(
        args.model_path,
        args.input_text,
        args.max_new_tokens,
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        min_p=args.min_p,
        seed=args.seed,
        tokenizer=args.tokenizer,
        stop_token=args.stop_token,
        ema=args.ema,
    )
    print(text)


if __name__ == "__main__":
    main()
