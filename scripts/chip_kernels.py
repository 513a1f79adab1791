#!/usr/bin/env python
"""Compile and run every Pallas attention kernel on the TPU against its XLA
reference, at the sizes serving and training use (Dh 64, 12 heads, bf16; the
paged decode step also at 32 heads of 128).

The CPU tests run these kernels in interpret mode at toy sizes; only the
chip hears Mosaic's refusals (tiling, unaligned slices, VMEM) and only there
do the compiled numerics exist. One line per case, then one JSON summary
line; exit 0 iff every case matched. A correctness run — it times nothing.

Usage (on the chip):  python scripts/chip_kernels.py [--only SUBSTR]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pretraining_llm_tpu.utils.compile_cache import use_compile_cache

use_compile_cache()

import jax
import jax.numpy as jnp
import numpy as np

H, DH = 12, 64  # the gpt2-124m head layout; G varies per case
N_BLOCKS = 256
CTX = 1024


def _err(got, want) -> float:
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))


def flash_case(t: int, g: int, seg: bool):
    from pretraining_llm_tpu.ops.attention import naive_attention
    from pretraining_llm_tpu.ops.pallas_flash import pallas_flash_attention

    b = 2
    ks = jax.random.split(jax.random.key(t + g), 4)
    q = jax.random.normal(ks[0], (b, t, H, DH), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, t, g, DH), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, t, g, DH), jnp.bfloat16)
    w = jax.random.normal(ks[3], (b, t, H, DH), jnp.float32)
    segments = None
    if seg:
        # Three documents per row, boundaries off the block grid.
        cuts = jnp.asarray([t // 3 + 5, 2 * t // 3 + 11])
        segments = jnp.sum(jnp.arange(t)[None, :, None] >= cuts[None, None, :], -1)
        segments = jnp.broadcast_to(segments, (b, t)).astype(jnp.int32)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    def kernel(q, k, v):
        return pallas_flash_attention(q, k, v, causal=True, segments=segments)

    def ref(q, k, v):
        return naive_attention(
            *(x.astype(jnp.float32) for x in (q, k, v)),
            causal=True, segments=segments,
        )

    out, grads = jax.jit(
        lambda q, k, v: (kernel(q, k, v), jax.grad(loss, (1, 2, 3))(kernel, q, k, v))
    )(q, k, v)
    out_r, grads_r = jax.jit(
        lambda q, k, v: (ref(q, k, v), jax.grad(loss, (1, 2, 3))(ref, q, k, v))
    )(q, k, v)
    errs = {"o": _err(out, out_r)}
    for name, a, r in zip(("dq", "dk", "dv"), grads, grads_r):
        # Gradients sum over T keys/queries: scale the bound by their size.
        errs[name] = _err(a, r) / max(1.0, float(jnp.max(jnp.abs(r))))
    return errs, 3e-2


def _pool(key, g: int, page: int, int8: bool, dh: int = DH):
    ks = jax.random.split(key, 4)
    shape = (N_BLOCKS, page, g, dh)
    if not int8:
        return (
            jax.random.normal(ks[0], shape, jnp.bfloat16),
            jax.random.normal(ks[1], shape, jnp.bfloat16),
            None, None,
        )
    codes = [
        jax.random.randint(k, shape, -127, 128, jnp.int32).astype(jnp.int8)
        for k in ks[:2]
    ]
    scales = [
        jax.random.uniform(k, shape[:-1] + (1,), jnp.float32, 0.5, 3.0).astype(jnp.bfloat16)
        for k in ks[2:]
    ]
    return codes[0], codes[1], scales[0], scales[1]


def _tables(b: int, page: int, t: int, rng: np.random.Generator):
    """Disjoint page lists per row, mixed committed lengths, 0-padded tails."""
    nb = CTX // page
    seq = rng.integers(0, CTX - t, size=b).astype(np.int32)
    seq[0] = 0  # a fresh row
    seq[-1] = CTX - t  # a full row
    tables = np.zeros((b, nb), np.int32)
    free = list(range(1, N_BLOCKS))
    rng.shuffle(free)
    for i in range(b):
        need = -(-(int(seq[i]) + t) // page)
        need = min(need, len(free) // (b - i))
        tables[i, :need] = [free.pop() for _ in range(need)]
        seq[i] = min(int(seq[i]), need * page - t)
    return jnp.asarray(tables), jnp.asarray(seq)


def paged_case(g: int, page: int, t: int, h: int = H, dh: int = DH):
    """Heads of 64 run the one-page-a-grid-step form whatever ``t``; one query
    a row over heads of 128 (the Mistral cells' layout) the form that copies a
    row's live pages in place (``pallas_paged.pages_copy_in_place``)."""
    from pretraining_llm_tpu.ops.pallas_paged import paged_decode_attention
    from pretraining_llm_tpu.ops.pallas_ragged import ragged_gather_attention

    b = 8
    rng = np.random.default_rng(page + t)
    k_pool, v_pool, _, _ = _pool(jax.random.key(1), g, page, False, dh)
    tables, seq = _tables(b, page, t, rng)
    q = jax.random.normal(jax.random.key(2), (b, t, h, dh), jnp.bfloat16)
    got = paged_decode_attention(
        q[:, 0] if t == 1 else q, k_pool, v_pool, tables, seq
    )
    if t == 1:
        got = got[:, None]
    want = jax.jit(ragged_gather_attention)(
        q, k_pool, v_pool, tables, seq, jnp.full((b,), t, jnp.int32)
    )
    return {"o": _err(got, want)}, 3e-2


def ragged_case(g: int, page: int, t: int, splits, amla: bool, int8: bool):
    from pretraining_llm_tpu.ops.pallas_ragged import (
        ragged_gather_attention, ragged_paged_attention,
    )

    b = 8
    rng = np.random.default_rng(page + t)
    k_pool, v_pool, k_scale, v_scale = _pool(jax.random.key(3), g, page, int8)
    tables, seq = _tables(b, page, t, rng)
    # Decode rows (1 query) ride with chunk rows of every length up to T.
    q_lens = jnp.asarray(np.minimum(t, rng.integers(1, t + 1, size=b)).astype(np.int32))
    q_lens = q_lens.at[0].set(t).at[1].set(1)
    q = jax.random.normal(jax.random.key(4), (b, t, H, DH), jnp.bfloat16)
    kw = dict(k_scale=k_scale, v_scale=v_scale)
    got = ragged_paged_attention(
        q, k_pool, v_pool, tables, seq, q_lens, kv_splits=splits, amla=amla, **kw
    )
    want = jax.jit(ragged_gather_attention)(
        q, k_pool, v_pool, tables, seq, q_lens, **kw
    )
    return {"o": _err(got, want)}, 3e-2


def cases():
    for t in (1024, 2048):  # one block (fused backward) / 2x2 blocks
        for g in (12, 4):
            for seg in (False, True):
                yield f"flash t{t} g{g}" + (" seg" if seg else ""), flash_case, (t, g, seg)
    for page in (64, 16):
        for g in (12, 4):
            for t in (1, 4):  # decode / speculative verify
                yield f"paged page{page} g{g} t{t}", paged_case, (g, page, t)
        for g in (8, 32):  # grouped and ungrouped heads of 128, in place
            yield f"paged page{page} g{g} t1 h32 dh128", paged_case, (g, page, 1, 32, 128)
    for page in (64, 16):
        for g in (12, 4):
            for t in (1, 128):  # decode-only launch / chunked-prefill launch
                for splits, amla, int8 in (
                    (1, False, False),
                    (None, False, False),  # model.ragged_kv_splits=0: auto
                    (4, False, False),
                    (1, True, False),
                    (1, False, True),
                    (4, True, True),
                ):
                    name = (
                        f"ragged page{page} g{g} t{t} splits{splits or 'auto'}"
                        + (" amla" if amla else "") + (" int8" if int8 else "")
                    )
                    yield name, ragged_case, (g, page, t, splits, amla, int8)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="", help="run cases whose name contains this")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(f"jax {jax.__version__} backend {jax.default_backend()} "
          f"{dev.device_kind} x{len(jax.devices())}", flush=True)
    if jax.default_backend() != "tpu":
        print("chip_kernels: no TPU backend; the CPU tests cover interpret mode",
              file=sys.stderr)
        return 1
    results = {}
    for name, fn, fn_args in cases():
        if args.only not in name:
            continue
        try:
            errs, tol = fn(*fn_args)
            ok = all(np.isfinite(e) and e <= tol for e in errs.values())
            results[name] = {"ok": ok, "max_err": errs}
            print(("ok   " if ok else "FAIL ") + name, errs, flush=True)
        except Exception as e:  # the refusal IS the finding: record, go on
            first = str(e).strip().splitlines()
            results[name] = {
                "ok": False, "error": type(e).__name__,
                "message": " | ".join(first[:6])[:1500],
            }
            print("ERR  " + name, type(e).__name__, results[name]["message"], flush=True)
            traceback.print_exc(limit=3, file=sys.stderr)
    n_ok = sum(r["ok"] for r in results.values())
    print(json.dumps({
        "ok": n_ok == len(results), "passed": n_ok, "cases": len(results),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "results": results,
    }))
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
