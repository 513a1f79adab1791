#!/usr/bin/env python
"""Compile and run every Pallas kernel on the TPU against its XLA reference,
at the sizes serving and training use (flash attention: Dh 64, 12 heads, bf16,
and the head layouts the tiled kernels read in place: 25 heads of 64, whose
last 128-lane block is half outside the array, and grouped heads of 128 and
256; the paged decode step at 32 heads of 128; the expert FFN at three serving
cells' expert shapes; the KDA step over the Ling cell's state pool and over
the Olmo-Hybrid cell's, 96 x 192 a head under one decay a head).

The CPU tests run these kernels in interpret mode at toy sizes; only the
chip hears Mosaic's refusals (tiling, unaligned slices, VMEM) and only there
do the compiled numerics exist. One line per case, then one JSON summary
line; exit 0 iff every case matched. A correctness run; ``--time-moe`` instead
times the expert kernel against ``ragged_dot`` alone, 2 to 128 rows an expert
(``--only`` then names a shape), and ``--time-moe-layer`` the whole expert
layer around it at the six expert cells' decode shapes (``--only`` names a cell).

Usage (on the chip):  python scripts/chip_kernels.py [--only SUBSTR] [--time-moe | --time-moe-layer]
"""

from __future__ import annotations

import argparse
import functools
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


def flash_case(t: int, g: int, seg: bool, h: int = H, dh: int = DH):
    from pretraining_llm_tpu.ops.attention import naive_attention
    from pretraining_llm_tpu.ops.pallas_flash import pallas_flash_attention

    b = 2
    ks = jax.random.split(jax.random.key(t + g), 4)
    q = jax.random.normal(ks[0], (b, t, h, dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, t, g, dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, t, g, dh), jnp.bfloat16)
    w = jax.random.normal(ks[3], (b, t, h, dh), jnp.float32)
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


def _pool(key, g: int, page: int, dh: int):
    ks = jax.random.split(key, 2)
    shape = (N_BLOCKS, page, g, dh)
    return tuple(jax.random.normal(k, shape, jnp.bfloat16) for k in ks)


def _tables(b: int, page: int, rng: np.random.Generator):
    """Disjoint page lists per row, mixed committed lengths (each with room
    for the step's own token), 0-padded tails."""
    nb = CTX // page
    seq = rng.integers(0, CTX - 1, size=b).astype(np.int32)
    seq[0] = 0  # a fresh row
    seq[-1] = CTX - 1  # a full row
    tables = np.zeros((b, nb), np.int32)
    free = list(range(1, N_BLOCKS))
    rng.shuffle(free)
    for i in range(b):
        need = -(-(int(seq[i]) + 1) // page)
        need = min(need, len(free) // (b - i))
        tables[i, :need] = [free.pop() for _ in range(need)]
        seq[i] = min(int(seq[i]), need * page - 1)
    return jnp.asarray(tables), jnp.asarray(seq)


def paged_case(g: int, page: int, h: int, dh: int):
    """One query a row over heads of 128 (the Mistral cells' layout): the
    kernel that copies a row's live pages in place against the gather form."""
    from pretraining_llm_tpu.ops.pallas_paged import gather_attention, paged_decode_attention

    b = 8
    rng = np.random.default_rng(page + 1)
    k_pool, v_pool = _pool(jax.random.key(1), g, page, dh)
    tables, seq = _tables(b, page, rng)
    q = jax.random.normal(jax.random.key(2), (b, 1, h, dh), jnp.bfloat16)
    got = paged_decode_attention(q[:, 0], k_pool, v_pool, tables, seq)[:, None]
    want = jax.jit(gather_attention)(
        q, k_pool, v_pool, tables, seq, jnp.ones((b,), jnp.int32)
    )
    return {"o": _err(got, want)}, 3e-2


# The serving cells' expert layers: (layers of the stack, experts held, D, F,
# sorted rows of a decode step, of which routed to experts held here, experts
# the router scores). Granite holds 18 of 72: three quarters of the sorted rows
# belong to experts held elsewhere and sort last.
MOE_SHAPES = {
    "ling": (3, 128, 2560, 768, 1024, 256, 512),
    "xing": (3, 64, 3584, 1024, 128, 128, 64),
    "granite": (3, 18, 4096, 768, 1280, 304, 72),
    # ungated relu^2 experts of two matrices, a width of 14.5 lane tiles (MOE_UNGATED)
    "nemotron": (3, 32, 2688, 1856, 768, 192, 128),
}
MOE_UNGATED = ("nemotron",)
MOE_MIXES = ("uniform", "skewed", "one-takes-all", "tile-edges", "all-elsewhere")


def _moe_inputs(shape: str, mix: str, rows_an_expert: int = 0, seed: int = 0):
    """(xs, w1, w2, sizes) at a cell's expert shapes. ``rows_an_expert`` > 0:
    that many sorted rows an expert the router scores on average, the held
    experts' share of them routed here and the rest sorted last. ``skewed``:
    the popularity of an expert runs from 0.4 to 1.6 of the mean, which with
    the draw's own noise makes the busiest about twice the mean at 17 rows an
    expert (the Granite cell's ``moe_load_max_over_mean``)."""
    from pretraining_llm_tpu.ops.pallas_moe import ROW_TILE

    n_stack, held, d, f, n, here, n_experts = MOE_SHAPES[shape]
    if rows_an_expert:
        n, here = rows_an_expert * n_experts, rows_an_expert * held
    rng = np.random.default_rng(seed)
    if mix == "uniform":
        sizes = np.bincount(rng.integers(0, held, here), minlength=held)
    elif mix == "skewed":
        liked = rng.permutation(np.linspace(0.4, 1.6, held))
        sizes = np.bincount(rng.choice(held, here, p=liked / liked.sum()), minlength=held)
    elif mix == "one-takes-all":
        sizes = np.zeros(held, np.int64)
        sizes[held // 3] = here
    elif mix == "tile-edges":  # groups of 0, 1, 2, 7, a row tile and one more, then the rest
        sizes = np.zeros(held, np.int64)
        sizes[1:12:2] = (1, 2, 7, ROW_TILE, ROW_TILE + 1, 0)
        sizes[-1] = here - sizes.sum()
    else:
        sizes = np.zeros(held, np.int64)
    ks = jax.random.split(jax.random.key(seed), 3)
    xs = jax.random.normal(ks[0], (n, d), jnp.bfloat16)

    def stack(key, shape, fan_in):  # a layer at a time: the generator's temporaries are float32
        one = jax.jit(lambda k: (jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5).astype(jnp.bfloat16))
        return jnp.stack([one(k) for k in jax.random.split(key, n_stack)])

    w1, w2 = stack(ks[1], (held, d, (1 if shape in MOE_UNGATED else 2) * f), d), stack(ks[2], (held, f, d), f)
    return xs, w1, w2, jnp.asarray(sizes, jnp.int32)


def moe_case(shape: str, mix: str, layer: int, clamp: bool, rows_an_expert: int = 0):
    """``ops/pallas_moe.py`` against the ``ragged_dot`` pair it replaces, over
    the rows routed here (what lies past them is promised by neither), a visit
    as wide as ``moe_mlp_dropless`` makes it at these rows an expert."""
    from pretraining_llm_tpu.models import moe
    from pretraining_llm_tpu.ops import pallas_moe

    xs, w1, w2, sizes = _moe_inputs(shape, mix, rows_an_expert)
    limit = jnp.float32(1.5) if clamp and shape not in MOE_UNGATED else None  # an ungated expert has no clamp
    w = pallas_moe.windows(xs.shape[0], MOE_SHAPES[shape][-1])
    got = jax.jit(moe.experts_kernel, static_argnums=6)(xs, w1, w2, sizes, jnp.int32(layer), limit, w)
    want = jax.jit(moe.experts_grouped)(xs, w1, w2, sizes, jnp.int32(layer), limit)
    here = int(sizes.sum())
    if not here:
        return {"o": 0.0 if got.shape == want.shape else float("inf")}, 3e-2
    scale = max(1.0, float(jnp.max(jnp.abs(want[:here].astype(jnp.float32)))))
    return {"o": _err(got[:here], want[:here]) / scale}, 3e-2


# Rows an expert at which ``time_moe`` times the two forms: the decode step's own
# mix (0), the decode cells' 2 to 8, and the band the rule stands in.
MOE_TIMED_ROWS = (0, 2, 8, 16, 24, 32, 48, 64, 128)


def time_moe(reps: int = 20, only: str = ""):
    """Milliseconds a layer of the kernel and of the ``ragged_dot`` pair alone,
    at the three expert shapes, under even and skewed routing: the decode
    step's own mix, then 2 to 128 rows an expert (PERF.md section 6, PR 44:
    where ``moe.KERNEL_ROWS_PER_EXPERT`` comes from). The kernel at a visit of
    two windows (``kernel_w2_ms``) and at the width ``pallas_moe.windows``
    gives these rows an expert (``kernel_ms``, ``w``), with the weight reads a
    touched expert of each. Each call runs every layer of the stack in turn."""
    import time

    from pretraining_llm_tpu.models import moe
    from pretraining_llm_tpu.ops import pallas_moe

    def ms_a_layer(form, xs, w1, w2, sizes):
        def every_layer(xs, w1, w2, sizes):
            def body(acc, layer):
                return acc + form(xs, w1, w2, sizes, layer, None)[0, 0].astype(jnp.float32), None
            return jax.lax.scan(body, jnp.float32(0), jnp.arange(w1.shape[0], dtype=jnp.int32))[0]
        fn = jax.jit(every_layer)
        fn(xs, w1, w2, sizes).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(xs, w1, w2, sizes)
        out.block_until_ready()
        return (time.perf_counter() - t0) / (reps * w1.shape[0]) * 1e3

    for shape, (_, held, d, f, _, _, n_experts) in MOE_SHAPES.items():
        if only not in shape:
            continue
        for mix in ("uniform", "skewed"):
            for rows in MOE_TIMED_ROWS:
                xs, w1, w2, sizes = _moe_inputs(shape, mix, rows)
                touched = int((sizes > 0).sum())
                w = pallas_moe.windows(xs.shape[0], n_experts)
                reads = lambda w: round(float(pallas_moe.group_visits(sizes, w)[0].sum()) / max(touched, 1), 3)
                line = {
                    "shape": shape, "mix": mix, "rows_an_expert": rows or "decode step", "rows": xs.shape[0],
                    "here": int(sizes.sum()), "touched": touched, "touched_mb": round(touched * (2 if shape in MOE_UNGATED else 3) * d * f * 2 / 1e6, 1),
                    "max_over_mean": round(float(sizes.max()) * held / max(int(sizes.sum()), 1), 2),
                    "w": w, "reads_a_touched": reads(w), "reads_a_touched_w2": reads(2),
                }
                forms = [("kernel_ms", functools.partial(moe.experts_kernel, windows=w)),
                         ("grouped_ms", moe.experts_grouped)]
                if w != 2:
                    forms.insert(1, ("kernel_w2_ms", moe.experts_kernel))
                for name, form in forms:
                    try:
                        line[name] = round(ms_a_layer(form, xs, w1, w2, sizes), 4)
                    except Exception as e:
                        line[name] = f"{type(e).__name__}: {str(e).splitlines()[0][:200]}"
                print(json.dumps(line), flush=True)
                del xs, w1, w2


# The six expert cells' decode steps: tokens a step (JoyAI's 64 rows bring two
# queries each) and what names the layer in a ModelConfig.
MOE_LAYERS = {
    "granite": (128, dict(d_model=4096, n_experts=72, n_experts_held=18, experts_per_token=10, d_expert=768,
                          n_shared_experts=2, moe_score="softmax")),
    "nemotron": (128, dict(d_model=2688, n_experts=128, n_experts_held=32, experts_per_token=6, d_expert=1856,
                           n_shared_experts=2, activation="relu2", moe_score_bias=True, moe_routed_scale=2.5)),
    "ling": (128, dict(d_model=2560, n_experts=512, n_experts_held=128, experts_per_token=8, d_expert=768,
                       moe_score_bias=True, moe_n_group=8, moe_topk_group=4, moe_routed_scale=2.5)),
    "joyai": (128, dict(d_model=2048, n_experts=256, n_experts_held=128, experts_per_token=8, d_expert=768,
                        moe_score_bias=True, moe_routed_scale=2.5)),
    "trinity": (64, dict(d_model=2048, n_experts=128, experts_per_token=8, d_expert=1024, moe_score_bias=True,
                         moe_routed_scale=2.826)),
    "xing": (32, dict(d_model=3584, n_experts=64, experts_per_token=4, d_expert=1024, moe_score_bias=True,
                      moe_routed_scale=2.0)),
}


def time_moe_layer(reps: int = 20, only: str = "", n_stack: int = 2, chain: int = 8):
    """Milliseconds a layer of the whole dropless expert layer (router to the
    sum with the shared expert: the five ``moe.*`` scopes) at the six expert
    cells' decode shapes, as it stood before PR 59 (``tests/test_moe.py`` keeps
    that layer as its oracle; read from there) and as it stands, beside the
    kernel alone over the same step's sorted rows (its visits planned outside
    the timed call): what is left between the two is the glue. Each call chains
    ``chain`` layers, a layer's output added to the next one's input, over a
    stack of ``n_stack`` layers' experts read where they lie."""
    import importlib.util
    import time

    from pretraining_llm_tpu.config import ModelConfig
    from pretraining_llm_tpu.models import moe, transformer
    from pretraining_llm_tpu.ops import pallas_moe

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location("oracle", os.path.join(root, "tests", "test_moe.py"))
    oracle = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(oracle)

    def ms(fn, *args):
        fn(*args).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        out.block_until_ready()
        return round((time.perf_counter() - t0) / (reps * chain) * 1e3, 4)

    layers = jnp.arange(chain, dtype=jnp.int32) % n_stack
    for cell, (tokens, fields) in MOE_LAYERS.items():
        if only not in cell:
            continue
        cfg = ModelConfig(**{**dict(
            vocab_size=64, context_length=64, n_heads=2, n_layers=1, activation="swiglu", norm="rmsnorm",
            mlp_bias=False, compute_dtype="bfloat16", param_dtype="bfloat16", moe_routing="dropless",
            moe_score="sigmoid", n_shared_experts=1), **fields})
        init = jax.jit(lambda k: moe.init_dropless_params(cfg, k, 0.02, jnp.bfloat16))
        made = [init(k) for k in jax.random.split(jax.random.key(0), n_stack)]
        mlp = dict(made[0], experts=jax.tree.map(lambda *a: jnp.stack(a), *[m["experts"] for m in made]))
        del made
        h = jax.random.normal(jax.random.key(1), (tokens, 1, cfg.d_model), jnp.bfloat16)
        dense = lambda shared, hh: transformer._dense_mlp(shared, hh, cfg)
        pairs = tokens * cfg.experts_per_token
        form = moe.experts_form(pairs, cfg, mlp["experts"])

        def chained(layer_fn):
            def run(mlp, h):
                def body(h, layer):
                    return h + layer_fn(dict(mlp, expert_layer=layer), h, cfg, dense)[0] * 0.125, None
                return jax.lax.scan(body, h, layers)[0]
            return jax.jit(run)

        before = chained(lambda m, x, c, dn: oracle._layer_before_pr59(m, x, c, dn, form))
        now = chained(moe.moe_mlp_dropless)
        line = {"cell": cell, "tokens": tokens, "pairs": pairs, "held": cfg.experts_held, "form": form,
                "same": bool(jnp.array_equal(before(mlp, h), now(mlp, h))),
                "layer_before_ms": ms(before, mlp, h), "layer_ms": ms(now, mlp, h)}
        if form == "kernel":
            # the kernel alone over this step's own sorted rows
            flat = jnp.minimum(moe.route_dropless(mlp, h[:, 0], cfg)[0].reshape(pairs), cfg.experts_held)
            sizes = jnp.bincount(flat, length=cfg.experts_held + 1)[: cfg.experts_held].astype(jnp.int32)
            xs = h[jnp.argsort(flat, stable=True) // cfg.experts_per_token, 0]
            xs = jnp.pad(xs, ((0, -pairs % pallas_moe.ROW_TILE), (0, 0)))
            w = pallas_moe.windows(pairs, cfg.n_experts)
            visits = tuple(pallas_moe.plan(sizes, xs.shape[0], w)[:3])

            @jax.jit
            def kernel(xs, w1, w2, sizes, visits):
                def body(acc, layer):
                    out = moe.experts_visits(xs, w1, w2, sizes, visits, layer, None, w)
                    return acc + out[0, 0].astype(jnp.float32), None
                return jax.lax.scan(body, jnp.float32(0), layers)[0]

            line.update(w=w, touched=int((sizes > 0).sum()), visits=int(visits[2][0]),
                        kernel_ms=ms(kernel, xs, mlp["experts"]["w1"], mlp["experts"]["w2"], sizes, visits))
            line.update(glue_before_ms=round(line["layer_before_ms"] - line["kernel_ms"], 4),
                        glue_ms=round(line["layer_ms"] - line["kernel_ms"], 4))
        print(json.dumps(line), flush=True)
        del mlp


def flash_qkv_case(t: int, h: int, dh: int):
    """``pallas_flash_attention_qkv`` (q, k and v out of one (B, 3, T, H*Dh)
    array, one d(qkv) back) against the three-array entry over slices of the
    same array: the same kernels on the same values, so equal bit for bit."""
    from pretraining_llm_tpu.ops.pallas_flash import pallas_flash_attention, pallas_flash_attention_qkv

    b = 2
    ks = jax.random.split(jax.random.key(t + h), 2)
    qkv = jax.random.normal(ks[0], (b, 3, t, h * dh), jnp.bfloat16)
    w = jax.random.normal(ks[1], (b, t, h, dh), jnp.float32)
    one = lambda x: pallas_flash_attention_qkv(x, h)
    three = lambda x: pallas_flash_attention(*(x[:, c].reshape(b, t, h, dh) for c in range(3)))
    both = lambda fn: jax.jit(
        lambda x: (fn(x), jax.grad(lambda x: jnp.sum(fn(x).astype(jnp.float32) * w))(x)))(qkv)
    (o1, g1), (o3, g3) = both(one), both(three)
    if not float(jnp.max(jnp.abs(g3.astype(jnp.float32)))) > 0:
        return {"dqkv": float("inf")}, 0.0
    return {"o": _err(o1, o3), "dqkv": _err(g1, g3)}, 0.0


def kda_case(rows: int, heads: int, kdim: int = 128, vdim: int = 128, gate_channels: bool = True):
    """``ops/pallas_kda.py`` against ``kda.recurrent_step``, the state donated
    to both as the decode programs donate the pools; the last row is dead
    (``g = 0``, ``beta = 0``) and must come back bit for bit. ``gate_channels``
    False: one decay a head, broadcast over K as ``models/gdn.py`` hands it."""
    from pretraining_llm_tpu.models import kda
    from pretraining_llm_tpu.ops import pallas_kda

    ks = jax.random.split(jax.random.key(rows + heads), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[1], (rows, heads, kdim))) * kdim ** -0.5
    k = unit(jax.random.normal(ks[2], (rows, heads, kdim)))
    v = jax.random.normal(ks[3], (rows, heads, vdim))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (rows, heads, kdim if gate_channels else 1)))
    g = jnp.broadcast_to(g, (rows, heads, kdim)).at[-1].set(0.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (rows, heads))).at[-1].set(0.0)
    state = lambda: jax.random.normal(ks[0], (rows, heads, kdim, vdim), jnp.float32)
    dead = np.asarray(state()[-1])
    got_o, got_s = jax.jit(pallas_kda.recurrent_step, donate_argnums=0)(state(), q, k, v, g, beta)
    want_o, want_s = jax.jit(kda.recurrent_step, donate_argnums=0)(state(), q, k, v, g, beta)
    return {"o": _err(got_o, want_o), "state": _err(got_s, want_s),
            "dead_row": 0.0 if np.array_equal(np.asarray(got_s[-1]), dead) else float("inf")}, 1e-4


def cases():
    for rows, heads in ((129, 32), (3, 4), (2, 12)):  # the Ling cell's pool; part groups of heads
        yield f"kda rows{rows} heads{heads}", kda_case, (rows, heads)
    # Gated DeltaNet: the Olmo-Hybrid cell's pool (V a lane tile and a half, 3 groups of heads and 6) and a toy's
    for rows, heads, kdim, vdim in ((129, 30, 96, 192), (3, 3, 8, 16)):
        yield f"kda rows{rows} heads{heads} state{kdim}x{vdim} scalar gate", kda_case, (rows, heads, kdim, vdim, False)
    for shape in MOE_SHAPES:
        for mix in MOE_MIXES:
            for layer, clamp in ((0, False), (MOE_SHAPES[shape][0] - 1, True)):
                name = f"moe {shape} {mix} layer{layer}" + (" clamp" if clamp else "")
                yield name, moe_case, (shape, mix, layer, clamp)
        for rows in (24, 48) + ((384,) if shape in MOE_UNGATED else ()):  # past a row tile an expert: the visit widens
            # (384: a prefill's rows, which a width of no whole lane tiles hands the kernel too)
            yield f"moe {shape} skewed rows{rows}", moe_case, (shape, "skewed", 1, False, rows)
    for t in (1024, 2048):  # one block (fused backward) / 2x2 blocks
        for g in (12, 4):
            for seg in (False, True):
                yield f"flash t{t} g{g}" + (" seg" if seg else ""), flash_case, (t, g, seg)
    # heads in place (pallas_flash.heads_in_place): an odd count of 64, grouped heads of 128, 256
    for h, g, dh in ((25, 25, 64), (8, 2, 128), (2, 1, 256)):
        yield f"flash t1024 h{h} g{g} dh{dh}", flash_case, (1024, g, False, h, dh)
    # q, k and v out of a fused projection's one array: the training cells' heads, heads of 128
    for t, h, dh in ((1024, 20, 64), (1024, 25, 64), (512, 8, 128)):
        yield f"flash qkv t{t} h{h} dh{dh}", flash_qkv_case, (t, h, dh)
    for page in (64, 16):
        for g in (8, 32):  # grouped and ungrouped heads of 128, in place
            yield f"paged page{page} g{g} t1 h32 dh128", paged_case, (g, page, 32, 128)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="", help="run cases whose name contains this")
    ap.add_argument("--time-moe", action="store_true",
                    help="time the expert kernel against ragged_dot instead (one JSON line a size)")
    ap.add_argument("--time-moe-layer", action="store_true",
                    help="time the whole expert layer, as before PR 59 and as it is, beside the kernel alone "
                         "at the six expert cells' decode shapes (one JSON line a cell)")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(f"jax {jax.__version__} backend {jax.default_backend()} "
          f"{dev.device_kind} x{len(jax.devices())}", flush=True)
    if jax.default_backend() != "tpu":
        print("chip_kernels: no TPU backend; the CPU tests cover interpret mode",
              file=sys.stderr)
        return 1
    if args.time_moe:
        time_moe(only=args.only)
        return 0
    if args.time_moe_layer:
        time_moe_layer(only=args.only)
        return 0
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
