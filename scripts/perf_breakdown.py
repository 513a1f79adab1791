#!/usr/bin/env python
"""Where does the step time go? Component-level timings on the real chip.

Times (a) forward loss only, (b) forward+backward, (c) the full train step
(adds optimizer), plus isolated attention and CE-head microbenches, using the
same scan-of-N-steps + slope protocol as bench.py (it cancels the fixed
per-dispatch cost). Prints one JSON line per component.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from pretraining_llm_tpu.config import get_preset
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.training import train_step as ts


def timed(body, init_carry, n2=12, n1=3):
    """ms per iteration of `body(carry) -> carry` via the two-length slope
    protocol (cancels the fixed dispatch/transfer overhead)."""

    def runner(n):
        def run(c):
            out, _ = jax.lax.scan(lambda c, _: (body(c), None), c, None, length=n)
            return out

        return jax.jit(run)

    def sync(tree):
        return jax.tree.leaves(jax.device_get(jax.tree.map(lambda x: x.ravel()[:1], tree)))[0]

    r1, r2 = runner(n1), runner(n2)
    sync(r1(init_carry))
    sync(r2(init_carry))
    t0 = time.perf_counter()
    sync(r1(init_carry))
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    sync(r2(init_carry))
    t2 = time.perf_counter() - t0
    return (t2 - t1) / (n2 - n1) * 1e3  # ms per iteration


def ragged_kernel_breakdown() -> None:
    """Decode-side component lane: the four ragged paged-attention
    variants (XLA gather, classic ragged, FA2 KV-split, AMLA rescale)
    through the same two-length-slope protocol. One JSON line each; the
    output feeds queries as next-round carry so iterations serialize.
    Off-TPU the kernel runs in interpret mode — labeled, not comparable
    to chip numbers.
    """
    import numpy as np

    from pretraining_llm_tpu.ops.pallas_ragged import (
        ragged_gather_attention,
        ragged_paged_attention,
    )

    interpret = jax.devices()[0].platform != "tpu"
    h, g, d, bs, b, t, pages = 4, 2, 32, 8, 4, 8, 8
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(pages * 3, bs, g, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(pages * 3, bs, g, d)), jnp.float32)
    tbl = jnp.asarray(rng.integers(1, pages * 3, size=(b, pages)), jnp.int32)
    seq = jnp.asarray(rng.integers(pages * bs // 2, pages * bs - t, size=(b,)), jnp.int32)
    ql = jnp.asarray([1 if i % 2 == 0 else t for i in range(b)], jnp.int32)

    variants = {
        "gather": lambda c: ragged_gather_attention(c, kp, vp, tbl, seq, ql),
        "ragged": lambda c: ragged_paged_attention(c, kp, vp, tbl, seq, ql, kv_splits=1),
        "ragged_split": lambda c: ragged_paged_attention(c, kp, vp, tbl, seq, ql, kv_splits=4),
        "ragged_amla": lambda c: ragged_paged_attention(c, kp, vp, tbl, seq, ql, kv_splits=1, amla=True),
    }
    for name, fn in variants.items():
        ms = timed(lambda c, fn=fn: fn(c).astype(c.dtype), q, n2=8, n1=2)
        print(json.dumps({
            "component": f"ragged_kernel_{name}", "ms": round(ms, 3),
            "cpu_interpret": interpret,
            "shape": {"B": b, "T": t, "pages": pages, "block_size": bs},
        }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="gpt2-124m")
    ap.add_argument("--batch", type=int, default=24)
    ap.add_argument("--remat", default="full")
    ap.add_argument(
        "--ragged-kernel", action="store_true",
        help="time the ragged paged-attention variants instead of the "
        "train-step components (runs anywhere; interpret-mode off-TPU)",
    )
    args = ap.parse_args()

    if args.ragged_kernel:
        ragged_kernel_breakdown()
        return

    cfg = get_preset(args.preset)
    model = dataclasses.replace(
        cfg.model,
        attention_impl="flash" if cfg.model.attention_impl == "ring" else cfg.model.attention_impl,
        sequence_parallel=False,
        remat=args.remat,
    )
    cfg = cfg.replace(model=model, train=dataclasses.replace(cfg.train, batch_size=args.batch))
    b, t = args.batch, model.context_length
    x = jnp.zeros((b, t), jnp.int32)
    y = jnp.zeros((b, t), jnp.int32)
    state = ts.init_train_state(cfg, jax.random.key(0))

    # (a) forward loss only: params ride the carry (closing over them would
    # bake 124M constants into the program);
    # the scalar slot chains iterations so they serialize.
    def fwd_body(c):
        params, prev = c
        return (params, transformer.loss_fn(params, x, y, model) + 0.0 * prev)

    ms_fwd = timed(fwd_body, (state["params"], jnp.zeros(())))
    print(json.dumps({"component": "forward_loss", "ms": round(ms_fwd, 2)}))

    # (b) forward+backward: carry a params-shaped tree (grads feed back in)
    gradfn = jax.grad(lambda p: transformer.loss_fn(p, x, y, model))
    ms_bwd = timed(gradfn, state["params"])
    print(json.dumps({"component": "forward_backward", "ms": round(ms_bwd, 2)}))

    # (c) full train step
    step = ts.build_train_step(cfg, None)
    ms_step = timed(lambda s: step(s, (x, y))[0], state)
    print(json.dumps({"component": "full_step", "ms": round(ms_step, 2),
                      "optimizer_ms": round(ms_step - ms_bwd, 2)}))

    # attention microbench: one layer's flash fwd+bwd at model shapes
    from pretraining_llm_tpu.ops.flash_attention import flash_attention

    h, dh, g = model.n_heads, model.head_dim, model.kv_heads
    q = jnp.zeros((b, t, h, dh), jnp.bfloat16)
    kv = jnp.zeros((b, t, g, dh), jnp.bfloat16)
    attn_g = jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v).astype(jnp.float32)), (0, 1, 2)
    )

    def attn_body(c):
        dq, dk, dv = attn_g(c[0], c[1], c[2])
        return (dq.astype(c[0].dtype), dk.astype(c[1].dtype), dv.astype(c[2].dtype))

    ms_attn = timed(attn_body, (q, kv, kv))
    print(json.dumps({"component": "flash_attn_fwd_bwd_per_layer", "ms": round(ms_attn, 2),
                      "all_layers_ms": round(ms_attn * model.n_layers, 2)}))

    # CE head microbench: hidden -> chunked CE fwd+bwd
    hid = jnp.zeros((b, t, model.d_model), jnp.bfloat16)
    w = jnp.zeros((model.d_model, model.vocab_size), jnp.float32)
    ce_g = jax.grad(
        lambda hdn, w: transformer._chunked_ce(hdn, w, None, y, model), (0, 1)
    )

    def ce_body(c):
        dh, dw = ce_g(c[0], c[1])
        return (dh.astype(c[0].dtype), dw)

    ms_ce = timed(ce_body, (hid, w))
    print(json.dumps({"component": "ce_head_fwd_bwd", "ms": round(ms_ce, 2)}))


if __name__ == "__main__":
    main()
