"""The Pallas paged-attention kernel vs the gather+masked-softmax reference.

The kernel (ops/pallas_paged.py) reads pool pages directly through the
scalar-prefetched block table; the reference (``gather_attention`` beside it)
materializes pool[tables] and runs a masked softmax -- the two must agree to
accumulation-order tolerance for every (GQA, window, dtype, fragmentation)
combination. Interpret mode on CPU (same convention as test_pallas_flash),
heads of 128 (a page is a copy of its own); the at-size compile for a
described v5e sits with the latent kernel's, in tests/test_pallas_latent.py.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.config import ModelConfig
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import transformer
from pretraining_llm_tpu.ops import pallas_paged
from pretraining_llm_tpu.ops.pallas_paged import gather_attention, paged_decode_attention


def _gather_ref(q, kp, vp, tables, seq, window):
    ones = jnp.ones((q.shape[0],), jnp.int32)
    return gather_attention(
        q[:, None], kp, vp, jnp.asarray(tables), jnp.asarray(seq), ones, window=window
    )[:, 0]


def test_kernel_validation():
    q = jnp.zeros((2, 4, 128))
    kp = jnp.zeros((8, 8, 3, 128))
    with pytest.raises(ValueError, match="divide"):
        paged_decode_attention(
            q, kp, kp, jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32)
        )
    kp = jnp.zeros((8, 8, 2, 128))
    with pytest.raises(ValueError, match="batch"):
        paged_decode_attention(
            q, kp, kp, jnp.zeros((3, 2), jnp.int32), jnp.zeros((3,), jnp.int32)
        )


@pytest.mark.parametrize("q_shape,pool_shape", [
    ((2, 3, 4, 128), (8, 8, 2, 128)),  # several queries a row
    ((2, 4, 64), (8, 8, 2, 64)),  # heads half a lane tile wide
    ((2, 6, 128), (8, 8, 3, 128)),  # kv heads that neither fill nor divide 8
], ids=["several-queries-a-row", "heads-of-64", "three-kv-heads"])
def test_kernel_refuses_what_the_gather_form_serves(q_shape, pool_shape):
    kp = jnp.zeros(pool_shape)
    with pytest.raises(ValueError, match="gather form"):
        paged_decode_attention(
            jnp.zeros(q_shape), kp, kp, jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32)
        )


# -- one query a row: a row's live pages copied in place, several a step ---------------------


@dataclasses.dataclass(frozen=True)
class Rows:
    """Rows of the given lengths (None: an idle row, its table on the scratch
    block 0 and ``seq`` 0) over tables ``max_blocks`` wide."""

    seq: tuple
    max_blocks: int = 6
    bs: int = 8
    h: int = 8
    g: int = 2
    window: int = 0
    pages: int = 2  # pages a step of the in-row loop
    dtype: str = "float32"


DECODE_CASES = {
    "seq-0": Rows(seq=(0, 5)),
    "length-on-a-page-boundary": Rows(seq=(7, 8, 15, 16, 47)),
    "partly-filled-last-page": Rows(seq=(11, 29, 42)),
    "longer-and-shorter-than-a-group": Rows(seq=(45, 3, 20), pages=4),
    "one-page-a-step": Rows(seq=(45, 3, 20), pages=1),
    "more-pages-a-step-than-the-table-has": Rows(seq=(45, 3, 20), pages=8),
    "idle-rows-beside-full-ones": Rows(seq=(None, 47, None, None, 47)),
    "every-row-idle": Rows(seq=(None, None)),
    "a-row-past-its-capacity": Rows(seq=(48, 50, 13)),
    "heads-32-over-8": Rows(seq=(19, 40), h=32, g=8),
    "heads-4-over-4": Rows(seq=(19, 40), h=4, g=4),
    "one-kv-head": Rows(seq=(19, 40), h=4, g=1),
    "window-below-the-length": Rows(seq=(40, 33, 12, 5), window=12),
    "window-of-one-page": Rows(seq=(40, 16, 15), window=8),
    "window-above-the-length": Rows(seq=(40, 5), window=64),
    "window-past-a-row-at-capacity": Rows(seq=(50, 47), window=5),
    "block-64": Rows(seq=(0, 63, 64, 200, None), bs=64, max_blocks=4, h=8, g=8),
    "block-64-the-cells-heads": Rows(seq=(130, 1), bs=64, max_blocks=3, h=32, g=8, window=100, pages=8),
    "bf16": Rows(seq=(45, 3, 20, None), dtype="bfloat16"),
    # what the one-page-a-grid-step form's tests held, at a width this form takes
    "heads-8-over-8": Rows(seq=(37, 8, 22), max_blocks=5, g=8),
    "heads-8-over-2": Rows(seq=(37, 8, 22), max_blocks=5, g=2),
    "heads-8-over-4-window-12": Rows(seq=(37, 8, 22), max_blocks=5, g=4, window=12),
    "heads-8-over-1": Rows(seq=(37, 8, 22), max_blocks=5, g=1),
    "heads-8-over-8-window-12": Rows(seq=(37, 8, 22), max_blocks=5, g=8, window=12),
    "heads-8-over-2-window-12": Rows(seq=(37, 8, 22), max_blocks=5, g=2, window=12),
    "heads-8-over-1-window-12": Rows(seq=(37, 8, 22), max_blocks=5, g=1, window=12),
    "bf16-heads-4-over-2": Rows(seq=(13, 20), max_blocks=3, h=4, g=2, dtype="bfloat16"),
    "seq-0-beside-a-full-table": Rows(seq=(0, 15), max_blocks=2, h=4, g=4),
    **{
        f"page-16-seq-{n}-window-{w}": Rows(seq=(n, 40), bs=16, max_blocks=3, window=w)
        for n in (15, 16, 17) for w in (0, 12)
    },
}


def _rows_state(case: Rows, seed: int, d: int = 128):
    rng = np.random.default_rng(seed)
    b = len(case.seq)
    n_blocks = 1 + b * case.max_blocks
    free = rng.permutation(np.arange(1, n_blocks)).tolist()
    tables = np.zeros((b, case.max_blocks), np.int32)
    seq = np.zeros((b,), np.int32)
    for i, n in enumerate(case.seq):
        if n is None:
            continue
        own = min(case.max_blocks, n // case.bs + 1)
        tables[i, :own] = [free.pop() for _ in range(own)]
        seq[i] = n
    dtype = jnp.dtype(case.dtype)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    q = normal(b, case.h, d)
    kp, vp = normal(n_blocks, case.bs, case.g, d), normal(n_blocks, case.bs, case.g, d)
    return q, kp, vp, tables, seq


@pytest.mark.parametrize("case", DECODE_CASES.values(), ids=DECODE_CASES.keys())
def test_decode_kernel_matches_gather(case):
    q, kp, vp, tables, seq = _rows_state(case, seed=len(case.seq) + case.bs + case.h)
    assert pallas_paged.pages_copy_in_place(case.g, q.shape[-1])
    out = paged_decode_attention(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(seq), window=case.window, pages_per_step=case.pages
    )
    assert out.shape == q.shape and out.dtype == q.dtype
    ref = _gather_ref(q, kp, vp, tables, seq, case.window)
    atol = 2e-5 if case.dtype == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref), atol=atol)


@pytest.mark.parametrize("tail", [0, 3], ids=["tail-on-scratch", "tail-on-a-live-block"])
def test_decode_kernel_never_reads_a_dead_page_into_the_result(tail):
    """Whatever a table names past a row's last visible slot, and whatever
    the pages nobody names hold, the result is the same to the bit: dead
    pages are not copied, and what a partly live group leaves in the buffer
    meets zero weights."""
    case = Rows(seq=(3, 20, 9), pages=4)
    q, kp, vp, tables, seq = _rows_state(case, seed=5)
    base = paged_decode_attention(q, kp, vp, jnp.asarray(tables), jnp.asarray(seq), pages_per_step=4)
    named = np.unique(np.concatenate([tables[i, : seq[i] // case.bs + 1] for i in range(len(seq))]))
    dead = np.setdiff1d(np.arange(kp.shape[0]), named)
    kp2, vp2 = kp.at[dead].set(1e30), vp.at[dead].set(-1e30)
    t2 = tables.copy()
    for i in range(len(seq)):
        t2[i, seq[i] // case.bs + 1 :] = dead[tail] if tail else 0
    out = paged_decode_attention(q, kp2, vp2, jnp.asarray(t2), jnp.asarray(seq), pages_per_step=4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))


def test_wide_pages_take_fewer_a_step_so_that_two_groups_fit_vmem(monkeypatch):
    seen = []
    real = pallas_paged._decode_call
    monkeypatch.setattr(pallas_paged, "_decode_call", lambda *a: seen.append(a[6]) or real(*a))
    case = Rows(seq=(19, 40), h=4, g=4)
    q, kp, vp, tables, seq = _rows_state(case, seed=1)
    paged_decode_attention(q, kp, vp, jnp.asarray(tables), jnp.asarray(seq))
    monkeypatch.setattr(pallas_paged, "_GROUP_BYTES", 4 * 3 * kp[0].size * 4)  # room for three pages a group
    paged_decode_attention(q, kp, vp, jnp.asarray(tables), jnp.asarray(seq))
    assert seen == [pallas_paged.PAGES_PER_STEP, 3]


# -- the form is read from the input ---------------------------------------------------------

WIDE = ModelConfig(vocab_size=64, context_length=64, d_model=256, n_heads=2, n_layers=1, d_head=128,
                   pos_embed="rope")
MESH = object()  # any serving mesh: the pool may be sharded over it


@pytest.mark.parametrize("tq,quantized,backend,mesh,cfg,form", [
    (1, False, "tpu", None, WIDE, "kernel"),
    (1, False, "cpu", None, WIDE, "gather"),
    (1, False, "gpu", None, WIDE, "gather"),
    (4, False, "tpu", None, WIDE, "gather"),  # the verify, the chunk lane
    (1, True, "tpu", None, WIDE, "gather"),  # int8 pages
    (1, False, "tpu", MESH, WIDE, "gather"),
    (1, False, "tpu", None, dataclasses.replace(WIDE, d_head=64), "gather"),  # a page is no copy of its own
    (1, False, "tpu", None, dataclasses.replace(WIDE, n_heads=6, n_kv_heads=3), "gather"),  # nor here
    (1, False, "tpu", None, dataclasses.replace(WIDE, n_heads=16, n_kv_heads=8), "kernel"),
    (4, True, "tpu", None, WIDE, "gather"),  # int8 pages under several queries
    (1, True, "cpu", None, WIDE, "gather"),
    (2, False, "tpu", None, dataclasses.replace(WIDE, n_heads=16, n_kv_heads=8), "gather"),  # a round of k = 1
    (1, False, "tpu", None, dataclasses.replace(WIDE, n_heads=12, n_kv_heads=12), "kernel"),  # 12 kv heads: the
    # pool stores 16 (pool_kv_heads), where XLA would copy a pool of 12 into another layout
    (1, False, "tpu", None, dataclasses.replace(WIDE, n_heads=30, n_kv_heads=30), "kernel"),  # 30 as 32
    (1, False, "tpu", None, dataclasses.replace(WIDE, n_heads=12, n_kv_heads=6), "gather"),  # up to 8: no padding
])
def test_the_form_follows_the_input(tq, quantized, backend, mesh, cfg, form, monkeypatch):
    assert transformer.paged_attention_form(cfg, tq, quantized, backend=backend, mesh=mesh) == form
    # and without the argument, the backend is the process's own
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert transformer.paged_attention_form(cfg, tq, quantized, mesh=mesh) == form


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_the_engine_reports_the_form_of_its_decode_step(backend, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    eng = ServingEngine(transformer.init_params(WIDE, jax.random.key(0)), WIDE, max_batch=2, n_blocks=8,
                        block_size=8)
    form = transformer.paged_attention_form(WIDE, 1, False)
    assert form == ("kernel" if backend == "tpu" else "gather")
    assert eng.decode_attention == eng.pool_info()["decode_attention"] == form


def test_the_decode_program_takes_the_kernel_where_the_form_says_so(request):
    """The decode step through the kernel gives the gather form's logits."""
    from pretraining_llm_tpu.generation import paged

    cfg = dataclasses.replace(WIDE, compute_dtype="float32", n_heads=4, n_kv_heads=2, sliding_window=12)
    params = transformer.init_params(cfg, jax.random.key(0))
    pools = transformer.make_paged_kv_pool(cfg, 12, 8)
    rng = np.random.default_rng(0)
    pools = jax.tree.map(lambda x: jnp.asarray(rng.normal(size=x.shape), x.dtype), pools)
    tables = jnp.asarray([[3, 5, 7], [2, 0, 0], [0, 0, 0]], jnp.int32)
    seq, tok = jnp.asarray([20, 4, 0], jnp.int32), jnp.asarray([1, 2, 0], jnp.int32)

    def logits():
        copy = jax.tree.map(jnp.copy, pools)  # the program donates its pools
        return np.asarray(paged.paged_decode_logits(params, copy, tok, tables, seq, cfg=cfg)[0])

    want = logits()
    request.getfixturevalue("paged_kernel_forced")
    program = jax.make_jaxpr(lambda p: paged.paged_decode_logits(params, p, tok, tables, seq, cfg=cfg))(pools)
    assert "pallas_call" in str(program)
    np.testing.assert_allclose(logits(), want, atol=2e-4)
