"""ops/pallas_moe.py (interpreted here): the expert FFN of a handful of rows an
expert against the ``ragged_dot`` form it replaces and a dense per-expert
oracle, the rule that picks the form (``moe.experts_form``), and the programs
and the engine that follow it. The compiled kernel is heard on the chip
(``scripts/chip_kernels.py``) and, at the cells' sizes, by the at-size compile
in ``tests/test_pallas_latent.py``."""

import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.generation import paged
from pretraining_llm_tpu.generation.serving import ServingEngine
from pretraining_llm_tpu.models import moe, transformer
from pretraining_llm_tpu.ops import pallas_moe as pk

TILE = pk.ROW_TILE
E, D, F, STACK = 6, 128, 256, 3


def _weights(dtype, held=E, stack=None, seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    lead = (held,) if stack is None else (stack, held)
    w1 = (jax.random.normal(k1, lead + (D, 2 * F), jnp.float32) * D ** -0.5).astype(dtype)
    w2 = (jax.random.normal(k2, lead + (F, D), jnp.float32) * F ** -0.5).astype(dtype)
    return w1, w2


def _rows(n, dtype, seed=1):
    return jax.random.normal(jax.random.key(seed), (n, D), jnp.float32).astype(dtype)


def _oracle(xs, w1, w2, sizes, limit=None):
    """Each row through its own expert's dense SwiGLU, in float32; rows past
    the last group are zero."""
    xs, w1, w2 = (np.asarray(a, np.float32) for a in (xs, w1, w2))
    out, row = np.zeros_like(xs), 0
    for e, n in enumerate(np.asarray(sizes)):
        up = xs[row : row + n] @ w1[e]
        gate, up = up[:, :F], up[:, F:]
        if limit:
            gate, up = np.minimum(gate, limit), np.clip(up, -limit, limit)
        out[row : row + n] = (gate / (1 + np.exp(-gate)) * up) @ w2[e]
        row += n
    return out


def _tol(dtype):
    return 2e-5 if dtype == jnp.float32 else 6e-2


WIDE = 4  # the windows a visit holds at the Granite cell's 17.8 rows an expert: a span of 64 rows

GROUPS = {
    "empty-one-two-seven": [0, 1, 2, 7, 0, 3],
    "tile-and-one-more": [TILE, 0, TILE + 1, 1, 0, 2],
    "straddles-a-window": [TILE - 1, 2, 0, TILE + 1, TILE + 2, 1],
    "one-takes-every-row": [0, 0, 3 * TILE + 5, 0, 0, 0],
    "all-alone": [1, 1, 1, 1, 1, 1],
    "last-only": [0, 0, 0, 0, 0, 9],
    # from a window's last row: as many rows as a wide span still holds, then one more (span - 15 and past it)
    "wide-span-from-a-last-row": [TILE - 1, 3 * TILE + 1, 0, 5, 0, 1],
    "past-a-wide-span-from-a-last-row": [TILE - 1, 3 * TILE + 2, 0, 5, 0, 1],
    "crosses-two-wide-spans": [3, 0, 2 * WIDE * TILE + 3, 0, TILE + 2, 0],
    "the-cells-mix": [21, 9, 34, 17, 25, 12],  # about 18 rows an expert, the busiest twice the mean
}


@pytest.mark.parametrize("w", [2, WIDE], ids=["two-windows", "wide"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("sizes", GROUPS.values(), ids=GROUPS)
def test_kernel_is_the_grouped_form_and_the_dense_oracle(sizes, dtype, w):
    sizes = jnp.asarray(sizes, jnp.int32)
    n = int(sizes.sum())
    xs, (w1, w2) = _rows(n, dtype), _weights(dtype)
    got = pk.expert_ffn(xs, w1, w2, sizes, w=w)
    assert got.shape == xs.shape and got.dtype == dtype
    want = moe.experts_grouped(xs, w1, w2, sizes)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=_tol(dtype))
    np.testing.assert_allclose(np.asarray(got, np.float32), _oracle(xs, w1, w2, sizes), atol=_tol(dtype))


@pytest.mark.parametrize("w", [2, WIDE], ids=["two-windows", "wide"])
@pytest.mark.parametrize("sizes,elsewhere", [([2, 0, 5, 1], 9), ([0, 0, 0, 0], 13), ([TILE + 1, 0, 0, 3], TILE),
                                             ([20, 13, 0, 36], 3 * 69)],
                         ids=["some-here", "all-elsewhere", "a-window-of-strangers", "three-quarters-elsewhere"])
def test_rows_of_experts_held_elsewhere_cost_nothing_and_change_nothing(sizes, elsewhere, w):
    """``held < n_experts``: the rows past the last group belong to no expert
    here. The rows that do come out as without them."""
    sizes = jnp.asarray(sizes, jnp.int32)
    here = int(sizes.sum())
    xs, (w1, w2) = _rows(here + elsewhere, jnp.float32), _weights(jnp.float32, held=4)
    got = pk.expert_ffn(xs, w1, w2, sizes, w=w)
    assert got.shape == xs.shape
    np.testing.assert_allclose(got[:here], _oracle(xs, w1, w2, sizes)[:here], atol=2e-5)
    if here:
        np.testing.assert_array_equal(got[:here], pk.expert_ffn(xs[:here], w1, w2, sizes, w=w))


@pytest.mark.parametrize("clamp", [None, 0.0, 0.4], ids=["no-clamp", "clamp-off", "clamp-on"])
@pytest.mark.parametrize("layer", [0, 1, STACK - 1], ids=["first", "middle", "last"])
def test_a_stack_is_read_at_its_layer(layer, clamp):
    sizes = jnp.asarray([3, 0, TILE + 2, 1, 0, 2], jnp.int32)
    xs, (w1, w2) = _rows(int(sizes.sum()), jnp.float32), _weights(jnp.float32, stack=STACK)
    limit = None if clamp is None else jnp.float32(clamp)
    got = jax.jit(pk.expert_ffn)(xs, w1, w2, sizes, jnp.int32(layer), limit)
    np.testing.assert_allclose(got, _oracle(xs, w1[layer], w2[layer], sizes, clamp), atol=2e-5)
    np.testing.assert_allclose(got, moe.experts_grouped(xs, w1, w2, sizes, jnp.int32(layer), limit), atol=2e-5)
    if clamp:  # the clamp bites at this size, or the case shows nothing
        assert not np.allclose(got, _oracle(xs, w1[layer], w2[layer], sizes), atol=1e-3)


def test_what_no_row_chose_is_never_read():
    """NaN in every expert no row chose and in every other layer of the stack:
    the same finite result (the kernel visits touched experts only, as PR 30's
    kernel reads no dead page)."""
    sizes = np.asarray([0, 4, 0, TILE + 3, 0, 1])
    layer = 1
    xs, (w1, w2) = _rows(int(sizes.sum()) + 7, jnp.float32), _weights(jnp.float32, stack=STACK)
    clean = pk.expert_ffn(xs, w1, w2, jnp.asarray(sizes), jnp.int32(layer))
    dead = np.ones((STACK, E), bool)
    dead[layer, sizes > 0] = False
    poison = lambda w: jnp.where(dead.reshape(STACK, E, 1, 1), jnp.nan, w)
    got = pk.expert_ffn(xs, poison(w1), poison(w2), jnp.asarray(sizes), jnp.int32(layer))
    here = int(sizes.sum())
    assert np.isfinite(np.asarray(got[:here])).all()
    np.testing.assert_array_equal(got[:here], clean[:here])


@pytest.mark.parametrize("w", [2, 3, WIDE], ids=["two-windows", "three", "wide"])
@pytest.mark.parametrize("rows,held", [(16, 4), (256, 64), (1024, 128), (48, 300), (1280, 18), (8192, 128)])
def test_the_grid_holds_every_plan(rows, held, w):
    """``n_visits`` bounds the visits of the mixes that take most: every
    touched expert alone in a visit, groups that start on a window's last
    row, and groups one row longer than a visit holds from there (1,280 rows
    over 18 held: the Granite cell's decode step; 8,192 over 128: Ling's
    one-prompt admission)."""
    span = w * TILE
    first_on_a_last_row = np.bincount(np.arange(rows - TILE + 1) % min(held, 3), minlength=held)
    first_on_a_last_row[0] += TILE - 1
    crossing = np.zeros(held, int)  # TILE - 1 rows, then groups that each cross a span by one row
    crossing[0] = min(TILE - 1, rows)
    n_crossing = min(held - 1, (rows - crossing[0]) // (span - TILE))
    crossing[1 : 1 + n_crossing] = span - TILE
    crossing[-1] += rows - crossing.sum()
    for sizes in (
        np.bincount(np.arange(rows) % held, minlength=held),
        first_on_a_last_row,
        crossing,
        np.r_[rows, np.zeros(held - 1, int)],
    ):
        assert sizes.sum() == rows
        ends = np.cumsum(sizes)
        base = (ends - sizes) // TILE * TILE
        visits = np.where(sizes > 0, -(-(ends - base) // span), 0).sum()
        assert visits <= pk.n_visits(rows, held, w)
        np.testing.assert_array_equal(pk.group_visits(jnp.asarray(sizes, jnp.int32), w)[0].sum(), visits)
        expert, window, live, offset = pk.plan(jnp.asarray(sizes, jnp.int32), rows, w)
        assert int(live[0]) == visits and expert.shape == window.shape == (pk.n_visits(rows, held, w),)
        assert offset.shape == (held,)
        # the visits' (expert, first window) lists are what the two searches gave
        v_ends = np.cumsum(np.where(sizes > 0, -(-(ends - base) // span), 0))
        want_expert = np.minimum(np.searchsorted(v_ends, np.arange(len(expert)), side="right"), held - 1)
        np.testing.assert_array_equal(expert, want_expert)
        want_window = base[want_expert] // TILE + w * (np.arange(len(expert)) - (v_ends - np.diff(v_ends, prepend=0))[want_expert])
        np.testing.assert_array_equal(window, np.clip(want_window, 0, rows // TILE - 1))
        # row i of group e lies at offset[e] + i: every sorted row a place of its own inside the live visits
        of_row = np.repeat(np.arange(held), sizes)
        here = np.asarray(offset)[of_row] + np.arange(rows) - (ends - sizes)[of_row]
        assert len(set(here.tolist())) == len(here) and here.max(initial=0) < visits * span
        # a row lies in its visit's span at the place it has in the sorted rows
        first_window = np.asarray(window)[here // span]
        np.testing.assert_array_equal(first_window * TILE + here % span, np.arange(rows))
        np.testing.assert_array_equal(pk.sorted_positions(jnp.asarray(sizes, jnp.int32), offset, rows, visits * span), here)
    assert pk.n_visits(rows, held) == min(held, rows) + rows // TILE  # two windows: the bound the cells have had


@pytest.mark.parametrize("rows,n_experts,w", [
    (2 * 512, 512, 2), (1024 * 8, 512, 2),  # Ling: a decode step, a one-prompt admission at exactly a row tile an expert
    (32 * 4, 64, 2), (64 * 2 * 8, 256, 2), (64 * 8, 128, 2),  # Xing, JoyAI's two-query round, Trinity
    (128 * 10, 72, WIDE),  # Granite: 17.8 rows an expert, a span of 64 holds every group up to 49 rows
    (16 * 8 + 1, 8, 3), (24 * 8, 8, 4), (32 * 8, 8, 5), (48 * 8, 8, 7),
], ids=["ling-decode", "ling-admission", "xing-decode", "joyai-round", "trinity-decode", "granite-decode",
        "just-past-a-tile", "24", "32", "48"])
def test_a_visit_is_two_windows_up_to_a_row_tile_an_expert_and_holds_twice_the_mean_past_it(rows, n_experts, w):
    assert pk.windows(rows, n_experts) == w
    if w > 2:
        twice = -(-2 * rows // n_experts)
        assert (w - 1) * TILE + 1 >= twice > (w - 2) * TILE + 1  # the fewest windows that do


@pytest.mark.parametrize("rows,held,d,f,stack", [(1024, 128, 256, 384, 2), (128, 8, 128, 256, None), (8192, 16, 128, 128, 3)],
                         ids=["ling-like", "xing-like", "an-admission"])
def test_at_a_row_tile_an_expert_and_under_the_traced_call_is_what_it_was(rows, held, d, f, stack):
    """Up to ROW_TILE rows an expert the call is PR 32's, figure for figure:
    two 16-row windows of the sorted rows, a 32-row scratch, accumulator and
    output block, one output block a visit of ``min(held, rows) + rows // 16``,
    a dynamic first grid bound, and no VMEM limit of its own."""
    shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt)
    lead = () if stack is None else (stack,)
    w = pk.windows(rows, rows // 16)  # exactly a row tile an expert
    assert w == pk.windows(rows, 4 * rows) == 2
    args = [shape(rows, d), shape(*lead, held, d, 2 * f), shape(*lead, held, f, d), shape(held, dt=jnp.int32)]
    args += [shape(dt=jnp.int32)] * (stack is not None)
    jaxpr = jax.make_jaxpr(lambda *a: pk.expert_ffn(*a, w=w, interpret=False))(*args)

    def find(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                if (found := find(sub)) is not None:
                    return found

    call = find(jaxpr.jaxpr)
    grid = call.params["grid_mapping"]
    tf = pk.f_tile(d, f, 2)
    visits = min(held, rows) + rows // 16
    block = lambda bm: tuple(getattr(dim, "block_size", None) for dim in bm.block_shape)
    assert [block(bm) for bm in grid.block_mappings] == [(16, d), (16, d), (None, d, tf), (None, d, tf), (None, tf, d), (32, d)]
    assert grid.grid[1] == f // tf and not isinstance(grid.grid[0], int) and grid.num_index_operands == 3
    assert [(a.shape, a.dtype) for a in (s.inner_aval for s in grid.scratch_avals)] == [((32, d), jnp.bfloat16), ((32, d), jnp.float32)]
    assert [(a.shape, a.dtype) for a in call.params["out_avals"]] == [((visits * 32, d), jnp.bfloat16)]
    assert [v.aval.shape for v in call.invars[1:4]] == [(visits,), (visits,), (1,)]
    params = call.params["compiler_params"]["mosaic_tpu"]
    assert params.vmem_limit_bytes is None and params.dimension_semantics == ("arbitrary", "arbitrary")


def test_shapes_the_kernel_cannot_take_are_refused_by_name():
    xs, (w1, w2) = _rows(4, jnp.float32), _weights(jnp.float32)
    sizes = jnp.asarray([4, 0, 0, 0, 0, 0], jnp.int32)
    with pytest.raises(ValueError, match="128-lane"):
        pk.expert_ffn(xs[:, :96], w1[:, :96], w2[:, :, :96], sizes)
    with pytest.raises(ValueError, match="layer"):
        pk.expert_ffn(xs, w1, w2, sizes, jnp.int32(0))
    with pytest.raises(ValueError, match="one"):
        pk.expert_ffn(xs.astype(jnp.bfloat16), w1, w2, sizes)


# -- the form follows the input -------------------------------------------------------

CFG = transformer.ModelConfig(
    vocab_size=64, context_length=64, d_model=128, n_heads=2, n_layers=3, mlp_ratio=2.0,
    activation="swiglu", norm="rmsnorm", pos_embed="rope", tie_embeddings=False, mlp_bias=False,
    compute_dtype="bfloat16", param_dtype="bfloat16", n_experts=8, experts_per_token=2,
    moe_routing="dropless", moe_score="sigmoid", n_shared_experts=1, d_expert=128, n_dense_layers=1,
)


def _experts(dtype=jnp.bfloat16, d=128, f=128):
    return {"w1": jax.ShapeDtypeStruct((8, d, 2 * f), dtype), "w2": jax.ShapeDtypeStruct((8, f, d), dtype)}


ROWS = moe.KERNEL_ROWS_PER_EXPERT  # the rule's bound, in sorted rows an expert

# what the cells' programs hand the rule: (sorted rows, experts the router scores)
CELLS = {
    "ling-decode": (128 * 8, 512, "kernel"), "ling-one-prompt-prefill": (1024 * 8, 512, "kernel"),  # 2 and exactly 16
    "ling-first-wave-prefill": (8 * 1024 * 8, 512, "grouped"),  # 128
    "xing-decode": (32 * 4, 64, "kernel"), "xing-prefill": (8192 * 4, 64, "grouped"),  # 2, 512
    "joyai-round": (64 * 2 * 8, 256, "kernel"), "joyai-prefill": (2048 * 8, 256, "grouped"),  # 4, 64
    "trinity-decode": (64 * 8, 128, "kernel"), "trinity-prefill": (1024 * 8, 128, "grouped"),  # 4, 64
    "granite-decode": (128 * 10, 72, "kernel"), "granite-prefill": (1024 * 10, 72, "grouped"),  # 17.8, 142
}


@pytest.mark.parametrize("rows,n_experts,backend,experts,mesh,compute,form", [
    (2 * 8, 8, "tpu", _experts(), None, "bfloat16", "kernel"),
    (ROWS * 8, 8, "tpu", _experts(), None, "bfloat16", "kernel"),
    (ROWS * 8 + 1, 8, "tpu", _experts(), None, "bfloat16", "grouped"),  # a prefill
    (512 * 8, 8, "tpu", _experts(), None, "bfloat16", "grouped"),
    (2 * 8, 8, "cpu", _experts(), None, "bfloat16", "grouped"),
    (2 * 8, 8, "gpu", _experts(), None, "bfloat16", "grouped"),
    (2 * 8, 8, "tpu", _experts(jnp.int8), None, "bfloat16", "grouped"),  # quantized for serving
    (2 * 8, 8, "tpu", _experts(jnp.float32), None, "float32", "grouped"),
    (2 * 8, 8, "tpu", _experts(), None, "float32", "grouped"),  # a cast copy of the stack is no place to stream from
    (2 * 8, 8, "tpu", _experts(), "a mesh", "bfloat16", "grouped"),
    (2 * 8, 8, "tpu", _experts(d=192), None, "bfloat16", "grouped"),
    (2 * 8, 8, "tpu", _experts(f=64), None, "bfloat16", "grouped"),
] + [(rows, n_experts, "tpu", _experts(), None, "bfloat16", form) for rows, n_experts, form in CELLS.values()],
    ids=["decode", "at-the-constant", "past-it", "prefill", "cpu", "gpu", "int8", "float32", "float32-compute",
         "mesh", "d-not-lanes", "f-not-lanes"] + list(CELLS))
def test_form_is_read_from_rows_backend_dtype_mesh_and_lanes(rows, n_experts, backend, experts, mesh, compute, form):
    cfg = dataclasses.replace(CFG, compute_dtype=compute, n_experts=n_experts)
    assert moe.experts_form(rows, cfg, experts, mesh=mesh, backend=backend) == form
    if backend == "cpu":  # what this process runs on, unasked
        assert moe.experts_form(rows, cfg, experts, mesh=mesh) == form


def test_the_rule_stands_between_the_decode_steps_and_the_prefills():
    """One bound, in rows an expert: past a row tile (the Granite cell's decode
    step stands at 17.8) and under the 64 at which the cells' prefills begin."""
    assert 17.8 < ROWS < 64


@pytest.fixture
def on_a_tpu(monkeypatch):
    """``moe.experts_form`` answers as on a TPU (the kernel is interpreted
    here). A jitted program keeps the form it was traced with, so the caches
    go before and after."""
    monkeypatch.setattr(moe, "experts_form", functools.partial(moe.experts_form, backend="tpu"))
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


def _decode(p, n_steps=2):
    pools = transformer.make_paged_kv_pool(CFG, 16, 8)
    tables = jnp.asarray(np.arange(1, 9).reshape(2, 4), jnp.int32)
    return functools.partial(
        paged.paged_decode_steps, p, pools, jnp.asarray([3, 5], jnp.int32), tables,
        jnp.asarray([4, 9], jnp.int32), jax.random.key(1), CFG, n_steps=n_steps,
    )


def _prefill(p, tokens=64, rows=8):
    pools = transformer.make_paged_kv_pool(CFG, 1 + rows * tokens // 8, 8)
    pages = jnp.arange(1, 1 + rows * tokens // 8, dtype=jnp.int32).reshape(rows, -1)
    return functools.partial(
        paged._prefill_scatter_sample, p, pools, jnp.zeros((rows, tokens), jnp.int32),
        jnp.asarray([tokens, 11] * (rows // 2), jnp.int32), pages, jax.random.key(2), CFG, tokens, tokens // 8,
    )


def _primitives(fn):
    """{primitive name: [name stacks]} over the whole jaxpr of ``fn()``."""
    found = {}

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            path = f"{outer}/{eqn.source_info.name_stack}"  # a call's body names its scopes from the call on
            found.setdefault(eqn.primitive.name, []).append(path)
            if eqn.primitive.name == "pallas_call":
                continue  # the kernel's own body
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, path)

    walk(jax.make_jaxpr(fn)().jaxpr, "")
    return found


def test_decode_traces_the_kernel_under_moe_experts_and_prefill_keeps_ragged_dot(params, on_a_tpu):
    decode = _primitives(_decode(params))
    assert "ragged_dot" not in decode and "ragged_dot_general" not in decode
    assert decode["pallas_call"] and all("moe.experts" in path for path in decode["pallas_call"])
    prefill = _primitives(_prefill(params))  # 8 x 64 tokens x 2 choices over 8 experts: 128 rows an expert
    assert "pallas_call" not in prefill
    assert any(name.startswith("ragged_dot") for name in prefill)


def test_off_the_tpu_every_program_keeps_the_grouped_form(params):
    assert "pallas_call" not in _primitives(_decode(params))


def test_decode_through_the_kernel_is_decode_through_the_grouped_form(params, on_a_tpu, monkeypatch):
    tokens, _ = _decode(params, n_steps=4)()
    monkeypatch.setattr(moe, "experts_form", lambda *a, **k: "grouped")
    jax.clear_caches()
    want, _ = _decode(params, n_steps=4)()
    for got_leaf, want_leaf in zip(jax.tree.leaves(tokens), jax.tree.leaves(want)):
        np.testing.assert_array_equal(got_leaf, want_leaf)


def test_engine_reports_the_decode_steps_form(params, on_a_tpu, caplog):
    eng = ServingEngine(params, CFG, max_batch=2, n_blocks=16, block_size=8)
    assert eng.decode_experts == eng.pool_info()["decode_experts"] == "kernel"
    eng.submit([1, 2, 3, 4, 5], 4)
    with caplog.at_level(logging.INFO, logger="pretraining_llm_tpu.serving"):
        eng.run()
    lines = [r.getMessage() for r in caplog.records if "engine empty" in r.getMessage()]
    assert len(lines) == 1 and "experts (kernel) took" in lines[0]
    # a batch whose decode step brings a prefill's rows an expert keeps the grouped form
    wide = ServingEngine(params, CFG, max_batch=8 * moe.KERNEL_ROWS_PER_EXPERT, n_blocks=16, block_size=8)
    assert wide.pool_info()["decode_experts"] == "grouped"
    # and one past a row tile an expert, under the rule's bound, the kernel with a wider visit
    assert ServingEngine(params, CFG, max_batch=4 * 18, n_blocks=16, block_size=8).pool_info()["decode_experts"] == "kernel"


def test_engine_off_the_tpu_and_without_experts(params):
    assert ServingEngine(params, CFG, max_batch=2, n_blocks=16, block_size=8).pool_info()["decode_experts"] == "grouped"
    dense = dataclasses.replace(CFG, n_experts=0, n_shared_experts=0, n_dense_layers=0, moe_routing="capacity")
    eng = ServingEngine(transformer.init_params(dense, jax.random.key(0)), dense, max_batch=2, n_blocks=16, block_size=8)
    assert "decode_experts" not in eng.pool_info()


def test_gradient_through_the_layer_is_the_grouped_forms(on_a_tpu, monkeypatch):
    """``loss_fn`` differentiates ``moe_mlp_dropless``: at a size the rule
    hands to the kernel the VJP is the grouped form's."""
    mlp = moe.init_dropless_params(CFG, jax.random.key(3), 0.02, jnp.bfloat16)
    h = jax.random.normal(jax.random.key(4), (2, 4, CFG.d_model), jnp.bfloat16)
    dense = lambda shared, hh: transformer._dense_mlp(shared, hh, CFG)

    def grads():
        loss = lambda m, x: jnp.sum(moe.moe_mlp_dropless(m, x, CFG, dense)[0].astype(jnp.float32) ** 2)
        assert ("pallas_call" in str(jax.make_jaxpr(loss)(mlp, h))) == (moe.experts_form(0, CFG, mlp["experts"]) == "kernel")
        return jax.grad(loss, (0, 1))(mlp, h)

    got = grads()
    monkeypatch.setattr(moe, "experts_form", lambda *a, **k: "grouped")
    want = grads()
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(g, np.float32)).all()
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(w, np.float32), atol=2e-2, rtol=5e-2)


@pytest.mark.parametrize("form", ["kernel", "grouped"])
def test_the_layer_plans_its_rows_once(monkeypatch, form):
    """The mechanism of PR 59, read off the jaxpr of one decode-shaped layer
    (128 rows x 2 choices over 8 experts of which 4 are held): a pair's place
    is counted once, in token order, and each row is gathered once on the way
    in (``x[order // k]``, the sorted rows the kernel's windows read) and once
    on the way out (each pair's row straight from the experts' output, visit
    order or sorted, weighted in token order). One sort is left (the order of
    the way in); no second sort inverts it, no ``searchsorted`` finds again the
    expert a pair chose (a ``while`` of log2 steps, each a gather), and nothing
    restores a sorted order only to undo it. The layer before did two sorts,
    two searches and three (N, D) gathers in the kernel's form."""
    cfg = dataclasses.replace(CFG, n_experts_held=4)
    mlp = jax.eval_shape(lambda: moe.init_dropless_params(cfg, jax.random.key(0), 0.02, jnp.bfloat16))
    h = jax.ShapeDtypeStruct((128, 1, cfg.d_model), jnp.bfloat16)
    monkeypatch.setattr(moe, "experts_form", lambda *a, **kw: form)
    n, d = 128 * cfg.experts_per_token, cfg.d_model
    found = {"sort": 0, "while": 0, "row gathers": 0, "pallas_call": 0}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in found:
                found[name] += 1
            if name == "pallas_call":
                continue  # the kernel's own body
            if name == "gather":
                shapes = [eqn.invars[0].aval.shape, eqn.outvars[0].aval.shape]
                found["row gathers"] += any(len(sh) == 2 and sh[0] >= n and sh[1] == d for sh in shapes)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(lambda m, x: moe.moe_mlp_dropless(m, x, cfg, lambda shared, hh: transformer._dense_mlp(shared, hh, cfg)))(mlp, h).jaxpr)
    assert found == {"sort": 1, "while": 0, "row gathers": 2, "pallas_call": form == "kernel"}, found


# What Mosaic is handed for a SwiGLU expert at the five expert cells' decode
# shapes (stack, held, experts scored, D, F, sorted rows, clamp): the hash of
# the kernel's assembly without source locations. PR 58 put a second expert
# form (two matrices, relu^2) into the same kernel body and pinned these from
# the parent's tree: an edit for one form that leaves the other's cells as
# they were leaves these as they are; one that moves them says so here, and
# owes those cells' rates.
SWIGLU_CELLS = {
    "ling": ((4, 128, 512, 2560, 768, 1024, False), "eb9a28296186"),
    "xing": ((5, 64, 64, 3584, 1024, 128, True), "85267eae5d17"),
    "trinity": ((4, 128, 128, 2048, 1024, 512, False), "87d03b0c40a2"),
    "granite": ((10, 18, 72, 4096, 768, 1280, False), "b31b3df94d85"),
    "joyai": ((4, 128, 256, 2048, 768, 1024, False), "a28ac67faaa7"),
}


@pytest.mark.parametrize("cell", list(SWIGLU_CELLS))
def test_the_swiglu_kernel_handed_to_mosaic_is_the_pinned_one(cell):
    import base64
    import hashlib
    import re

    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    (stack, held, n_experts, d, f, rows, clamp), pinned = SWIGLU_CELLS[cell]
    w = pk.windows(rows, n_experts)
    tf = pk.f_tile(d, f, 2, w)
    s = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype)
    args = [s((rows, d)), s((stack, held, d, 2 * f)), s((stack, held, f, d)), s((held,), jnp.int32), s((), jnp.int32)]
    if clamp:
        args.append(s((), jnp.float32))
    call = lambda xs, w1, w2, sizes, layer, *lim: pk._moe_call(xs, w1, w2, sizes, layer, *(lim or (None,)), tf, w, False)
    text = jax.jit(call).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    (body,) = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)
    ctx = jmlir.make_ir_context()
    tpu.register_dialect(ctx)
    with ctx:
        ctx.allow_unregistered_dialects = True
        asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(enable_debug_info=False)
    assert hashlib.sha256(asm.encode()).hexdigest()[:12] == pinned
