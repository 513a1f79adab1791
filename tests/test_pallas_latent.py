"""The latent-pool decode kernel (ops/pallas_latent.py) against the gather form.

The oracle is ``models/mla.py::_absorbed``'s middle on hand-built tables:
``pool[tables]``, masked scores in float32, softmax, ``p . latents``. The
kernel reads the same pages of both pools through the block table, several a
step of its in-row loop, and must agree to accumulation-order tolerance
whatever the rows' lengths, the fill of a last page, the division of the page
count by the step's pages, the fold of a page's slots, and whatever a table's
dead tail points at. Interpret mode on CPU (same convention as
test_pallas_paged); one at-size compile for a described v5e says what Mosaic
would refuse. The other kernels of the serving path compile at size here too
(``ops/pallas_paged.py``'s decode form, ``ops/pallas_moe.py``), and so do the
tiled flash kernels of the training cells (``ops/pallas_flash.py``, alone and
in a GPT-2 large layer): one file, so that one test worker loads the TPU
compiler.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pretraining_llm_tpu.models import mla
from pretraining_llm_tpu.ops import pallas_latent as pk

H, C, R, BS, N_BLOCKS = 4, 32, 8, 8, 48
SCALE = 0.23


def gather_form(q, q_rope, pool, rpool, tables, seq):
    """softmax(([q | q_rope] . [latents | ropes]) * SCALE) . latents, query i
    of a row over its slots 0..seq + i, from gathered copies of (n_blocks, BS,
    width) pools, in float32."""
    b, t = q.shape[:2]
    kv = tables.shape[1] * BS
    f32 = jnp.float32
    lat = pool[tables].reshape(b, kv, C).astype(f32)
    rope = rpool[tables].reshape(b, kv, R).astype(f32)
    s = jnp.einsum("bthc,bkc->bthk", q.astype(f32), lat) + jnp.einsum("bthr,bkr->bthk", q_rope.astype(f32), rope)
    pos = jnp.asarray(seq)[:, None] + jnp.arange(t)[None, :]
    mask = jnp.arange(kv)[None, None, None, :] <= pos[:, :, None, None]
    p = jax.nn.softmax(jnp.where(mask, s * SCALE, -jnp.inf), axis=-1)
    return jnp.einsum("bthk,bkc->bthc", p, lat)


def through_the_kernel(q, q_rope, pool, rpool, tables, seq, pages, fold=2):
    """The same pools with ``fold`` slots side by side in a row of a page."""
    return pk.latent_decode_attention(
        q, q_rope, pool.reshape(N_BLOCKS, BS // fold, fold * C), rpool.reshape(N_BLOCKS, BS // fold, fold * R),
        jnp.asarray(tables), jnp.asarray(seq), scale=SCALE, pages_per_step=pages,
    )


def state(seed, seq, max_blocks, dtype=jnp.float32, t=1):
    """Rows of the given lengths, ``t`` queries each, on disjoint, shuffled
    pages that reach the last query's slot; a table's dead tail is 0 (the
    scratch block)."""
    rng = np.random.default_rng(seed)
    b = len(seq)
    perm = rng.permutation(np.arange(1, N_BLOCKS)).tolist()
    tables = np.zeros((b, max_blocks), np.int32)
    for i, n in enumerate(seq):
        own = min(max_blocks, (n + t - 1) // BS + 1)
        tables[i, :own] = [perm.pop() for _ in range(own)]
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    return (normal(b, t, H, C), normal(b, t, H, R), normal(N_BLOCKS, BS, C), normal(N_BLOCKS, BS, R),
            tables, np.asarray(seq, np.int32))


CASES = {
    # seq_lens, max_blocks, pages a step
    "rows-of-unlike-lengths": ((3, 17, 30, 44), 6, 2),
    "length-0-on-the-scratch-block": ((0, 21), 4, 2),
    "last-page-holds-one-slot": ((BS, 2 * BS), 4, 2),  # slot seq is the page's first
    "last-page-one-slot-short-of-full": ((BS - 2, 3 * BS - 2), 4, 2),  # one short of full
    "last-page-full": ((BS - 1, 3 * BS - 1), 4, 2),  # a second query's slot opens a page
    "last-group-full": ((2 * BS - 1, 4 * BS - 1, 4 * BS - 2), 6, 2),  # and a group of pages
    "pages-not-divisible-by-the-step": ((5, 37, 20), 5, 3),
    "one-page-a-step": ((5, 37, 20), 5, 1),
    "a-step-wider-than-the-table": ((5, 37, 20), 5, 8),
    # the last two wrote to scratch; further queries of the first and the fourth do:
    # a query past the capacity sees every slot and not its own token
    "row-at-capacity": ((4 * BS - 1, 4 * BS, 4 * BS + 5, 4 * BS - 2), 4, 2),
}
QUERIES = pytest.mark.parametrize("t", [1, 2, 3], ids=["one-query", "two-queries", "three-queries"])


@QUERIES
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_the_gather_form(case, t):
    seq, max_blocks, pages = CASES[case]
    q, q_rope, pool, rpool, tables, seq = state(len(case), seq, max_blocks, t=t)
    if case == "length-0-on-the-scratch-block":
        tables[0] = 0  # an idle engine row: every entry the scratch block
    got = through_the_kernel(q, q_rope, pool, rpool, tables, seq, pages)
    want = gather_form(q, q_rope, pool, rpool, tables, seq)
    assert got.shape == want.shape == (len(seq), t, H, C) and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@QUERIES
@pytest.mark.parametrize("tail", ["live-blocks-of-another-row", "poisoned-blocks"])
def test_a_dead_tail_is_never_read_into_the_result(tail, t):
    seq = (11, 42, 3)
    q, q_rope, pool, rpool, tables, seq = state(5, seq, 6, t=t)
    want = gather_form(q, q_rope, pool, rpool, tables, seq)
    if tail == "live-blocks-of-another-row":
        tables[0, 2:] = tables[1, :4]
        tables[2, 1:] = tables[1, 1:]
    else:
        # blocks nobody owns, holding values that would swamp any sum they entered
        free = [i for i in range(1, N_BLOCKS) if i not in set(tables.ravel().tolist())][:5]
        pool = pool.at[jnp.asarray(free)].set(1e4)
        rpool = rpool.at[jnp.asarray(free)].set(1e4)
        tables[0, 2:] = free[:4]
        tables[2, 1:] = free
    got = through_the_kernel(q, q_rope, pool, rpool, tables, seq, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # and the gather form itself does not care either: the oracle is sound
    np.testing.assert_allclose(
        np.asarray(gather_form(q, q_rope, pool, rpool, tables, seq)), np.asarray(want), atol=1e-5
    )


@QUERIES
def test_kernel_bf16(t):
    q, q_rope, pool, rpool, tables, seq = state(7, (13, 40, 0, 29), 6, jnp.bfloat16, t=t)
    got = through_the_kernel(q, q_rope, pool, rpool, tables, seq, 2)
    assert got.dtype == jnp.bfloat16
    want = gather_form(q, q_rope, pool, rpool, tables, seq)
    # the tolerance of the bfloat16 cases of tests/test_pallas_paged.py::test_decode_kernel_matches_gather
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=3e-2)


@QUERIES
@pytest.mark.parametrize("fold", [1, 4, 8])
def test_any_fold_of_a_page_gives_the_same_numbers(fold, t):
    """Slots side by side in a row change the order of a class's columns, not
    what a softmax over them sums to (fold 2 is every other test's)."""
    q, q_rope, pool, rpool, tables, seq = state(3, (19, 33, 0, 39), 5, t=t)
    got = through_the_kernel(q, q_rope, pool, rpool, tables, seq, 2, fold)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(gather_form(q, q_rope, pool, rpool, tables, seq)), atol=1e-5
    )


@pytest.mark.parametrize("block,rope,fold", [(64, 64, 2), (16, 64, 2), (8, 8, 8), (64, 32, 4), (64, 128, 1), (64, 96, 4)])
def test_page_fold_fills_whole_lane_tiles_where_the_block_allows(block, rope, fold):
    assert mla.page_fold(block, rope) == fold
    assert block % fold == 0 and (fold * rope % 128 == 0 or fold == block)


@pytest.mark.parametrize("t", [1, 3], ids=["one-token-a-row", "a-chunk-a-row"])
def test_a_written_slot_is_the_slot_the_gather_form_reads(t):
    """``mla._write_slots`` into a folded pool (whole rows rewritten for one
    token a row, windows for a chunk), then the pool read as (slots, width):
    every token lies at its slot and nothing else moved."""
    fold = 4
    before = np.random.default_rng(t).normal(size=(N_BLOCKS, BS, C)).astype(np.float32)
    pool = jnp.asarray(before).reshape(N_BLOCKS, BS // fold, fold * C)
    blk = np.asarray([[5, 5, 9], [7, 0, 0], [2, 2, 2]])[:, :t]
    slots = np.asarray([[6, 7, 0], [3, 0, 0], [1, 2, 3]])[:, :t]  # row 1's last two went to scratch
    vals = np.arange(3 * t * C, dtype=np.float32).reshape(3, t, C) + 1
    after = np.asarray(mla._write_slots(pool, jnp.asarray(blk), jnp.asarray(slots), jnp.asarray(vals), fold))
    want = before.copy()
    for r in range(3):
        for i in range(t):
            want[blk[r, i], slots[r, i]] = vals[r, i]
    # block 0 is scratch: rows past their capacity all write there, in no promised order
    np.testing.assert_array_equal(after.reshape(N_BLOCKS, BS, C)[1:], want[1:])


@pytest.mark.parametrize("wrong", ["width", "dtype", "batch", "rope-pool", "no-query-axis", "query-counts"])
def test_kernel_validation(wrong):
    q, q_rope, pool, rpool, tables, seq = state(1, (5, 9), 4, t=2)
    lat, rope = pool.reshape(N_BLOCKS, BS // 2, 2 * C), rpool.reshape(N_BLOCKS, BS // 2, 2 * R)
    args = [q, q_rope, lat, rope, jnp.asarray(tables), jnp.asarray(seq)]
    match = {"batch": "batch", "no-query-axis": "queries a row"}.get(wrong, "do not match the pools")
    if wrong == "width":
        args[0] = q[..., :24]
    elif wrong == "dtype":
        args[0] = q.astype(jnp.bfloat16)
    elif wrong == "batch":
        args[5] = jnp.zeros((3,), jnp.int32)
    elif wrong == "rope-pool":
        args[3] = rpool  # pages of another fold than the latents'
    elif wrong == "no-query-axis":
        args[0], args[1] = q[:, 0], q_rope[:, 0]  # (B, H, C): the single-query kernel's operands
    else:
        args[1] = q_rope[:, :1]  # two latent queries a row, one rope slice
    with pytest.raises(ValueError, match=match):
        pk.latent_decode_attention(*args, scale=SCALE, pages_per_step=3)


# -- what Mosaic says, at the serving cell's size, without a chip ----------------------


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("b,t,n_blocks,nb", [(32, 1, 4097, 129), (64, 2, 4161, 65), (64, mla.KERNEL_QUERIES, 4161, 65)],
                         ids=["xing-decode-step", "joyai-mtp-round", "the-most-queries-a-row"])
def test_kernel_compiles_for_the_chip_at_the_cells_size(one_chip, b, t, n_blocks, nb):
    """32 rows x 1 query x 32 heads over 129 pages of the folded (4097, 32,
    1024) and (4097, 32, 128) bfloat16 pools (``serve_xing_decode_7k``), 64 rows
    x 2 queries x 32 heads over 65 pages of (4161, 32, 1024) and (4161, 32, 128)
    (``serve_joyai_mtp_decode_2k``'s round), and the latter at the most queries
    ``mla.decode_form`` sends here: Mosaic takes it, both pools enter the custom
    call as they lie (no copy, slice or relayout of either), and nothing of the
    size of a gathered copy is made."""
    from jax.experimental.compilation_cache import compilation_cache

    h, c, r, bs = 32, 512, 64, 64
    fold = mla.page_fold(bs, r)
    page = (n_blocks, bs // fold)
    shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    fn = lambda q, qr, lat, rope, t, n: pk._latent_call(q, qr, lat, rope, t, n, 0.1, pk.PAGES_PER_STEP, False)
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable without a chip
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(fn).lower(
            shape((b, t, h, c)), shape((b, t, h, r)), shape(page + (fold * c,)), shape(page + (fold * r,)),
            shape((b, nb), jnp.int32), shape((b,), jnp.int32),
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    ops = [line.split(" = ", 1)[1] for line in text.splitlines() if " = " in line]
    pool_sized = [op for op in ops if op.startswith(f"bf16[{n_blocks},") and " parameter(" not in op]
    assert not pool_sized, pool_sized
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20  # block-diagonal queries, a padded table


@pytest.mark.parametrize("b,n_blocks,window", [(64, 9281, 0), (64, 2177, 2048)], ids=["full-layer", "window-layer"])
def test_per_head_decode_kernel_compiles_for_the_chip_at_the_trinity_cells_size(one_chip, b, n_blocks, window):
    """``ops/pallas_paged.py``'s single-query form at ``serve_trinity_decode_1k_8k``'s
    two pools (64 rows, 32 query heads over 4 kv heads of 128: 8 a group, tables
    of 145 pages; the full layer's 9,281 pages unclipped, a window layer's 2,177
    under the 2,048-token clip): Mosaic takes both, the flat view of each pool is
    a bitcast and no pool-sized copy stands beside the custom call."""
    from jax.experimental.compilation_cache import compilation_cache

    from pretraining_llm_tpu.ops import pallas_paged as pp

    heads, kv_heads, d, bs, max_blocks = 32, 4, 128, 64, 145
    shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    pool = shape((n_blocks, bs, kv_heads, d))
    pages = pp._pages_a_step(pool, pp.PAGES_PER_STEP)
    fn = lambda q, k, v, t, n: pp._decode_call(q, k, v, t, n, window, pages, False)
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable without a chip
    compilation_cache.reset_cache()
    try:
        assert pp.pages_copy_in_place(kv_heads, d)
        compiled = jax.jit(fn).lower(shape((b, heads, d)), pool, pool, shape((b, max_blocks), jnp.int32),
                                     shape((b,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    ops = [line.split(" = ", 1)[1] for line in text.splitlines() if " = " in line]
    pool_sized = [op for op in ops if op.startswith(f"bf16[{n_blocks},") and " parameter(" not in op]
    assert len(pool_sized) == 2 and all(" bitcast(" in op for op in pool_sized), pool_sized
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("max_blocks,heads,kv_heads,ok", [(17, 32, 8, True), (22, 32, 8, True), (22, 32, 32, True),
                                                          (17, 12, 12, False)],
                         ids=["decode-cell", "chat-cell", "ungrouped-heads", "heads-of-64"])
def test_per_head_decode_kernel_compiles_for_the_chip_at_the_cells_size(one_chip, max_blocks, heads, kv_heads, ok):
    """``ops/pallas_paged.py``'s single-query form at the Mistral cells' size
    (40 rows, 32 query heads over 8 kv heads of 128, 641 pages of 64 slots,
    tables of 17 and 22 pages, window 4,096): Mosaic takes it, the flat
    (641, 512, 128) view of each pool is a bitcast, and no pool-sized copy
    stands beside the custom call. Ungrouped heads make a page four times as
    wide, so fewer pages a step keep two groups inside VMEM. Heads of 64 are
    what ``pages_copy_in_place`` exists to keep away: Mosaic refuses to copy
    a page that is half a lane tile wide."""
    from jax.experimental.compilation_cache import compilation_cache

    from pretraining_llm_tpu.ops import pallas_paged as pp

    b, d, bs, n_blocks = 40, 128 if ok else 64, 64, 641
    shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    pool = shape((n_blocks, bs, kv_heads, d))
    pages = pp._pages_a_step(pool, pp.PAGES_PER_STEP)
    fn = lambda q, k, v, t, n: pp._decode_call(q, k, v, t, n, 4096, pages, False)
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable without a chip
    compilation_cache.reset_cache()
    try:
        lowered = jax.jit(fn).lower(shape((b, heads, d)), pool, pool, shape((b, max_blocks), jnp.int32),
                                    shape((b,), jnp.int32))
        assert pp.pages_copy_in_place(kv_heads, d) == ok
        if not ok:
            with pytest.raises(Exception, match="aligned to tiling"):
                lowered.compile()
            return
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    ops = [line.split(" = ", 1)[1] for line in text.splitlines() if " = " in line]
    pool_sized = [op for op in ops if op.startswith(f"bf16[{n_blocks},") and " parameter(" not in op]
    assert len(pool_sized) == 2 and all(" bitcast(" in op for op in pool_sized), pool_sized
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("stack,held,n_experts,d,f,rows,clamp,windows", [
    (4, 128, 512, 2560, 768, 1024, False, 2), (5, 64, 64, 3584, 1024, 128, True, 2), (4, 128, 128, 2048, 1024, 512, False, 2),
    (4, 128, 512, 2560, 768, 8192, False, 2), (10, 18, 72, 4096, 768, 1280, False, 4), (10, 18, 72, 4096, 768, 48 * 72, False, 7),
    (4, 128, 512, 2560, 768, 2 * 8192, False, 5),
], ids=["ling-decode-step", "xing-decode-step", "trinity-decode-step", "ling-one-prompt-admission",
        "granite-decode-step", "granite-at-the-rules-bound", "ling-two-prompt-admission"])
def test_expert_kernel_compiles_for_the_chip_at_the_cells_size(one_chip, stack, held, n_experts, d, f, rows, clamp, windows):
    """``ops/pallas_moe.py`` at the serving cells' expert layers (a stack of
    128 experts of 2560 x 768 under 1,024 sorted rows, of 64 experts of 3584 x
    1024 under 128, of 18 of 72 experts of 4096 x 768 under 1,280 with a visit
    of four windows, whose rows leave the weight tiles half the columns): Mosaic
    takes it with its tiles inside the default scoped VMEM, both stacks enter the custom call as
    they lie (the flat (L * E, ...) views are bitcasts; no copy, slice or
    relayout of either), and the temporaries are the visits' output, not a
    stack's size. Up to a row tile an expert the visit is two windows and the
    tiles are what they were before PR 44."""
    from jax.experimental.compilation_cache import compilation_cache

    from pretraining_llm_tpu.ops import pallas_moe as pm

    shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    w = pm.windows(rows, n_experts)
    tf = pm.f_tile(d, f, 2, w)
    assert w == windows and tf <= pm.f_tile(d, f, 2) and (w > 2 or tf == pm.f_tile(d, f, 2))
    fn = lambda xs, w1, w2, sizes, layer, *limit: pm._moe_call(xs, w1, w2, sizes, layer, *(limit or (None,)), tf, w, False)
    args = [shape((rows, d)), shape((stack, held, d, 2 * f)), shape((stack, held, f, d)),
            shape((held,), jnp.int32), shape((), jnp.int32)] + [shape((), jnp.float32)] * clamp
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable without a chip
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "ragged-dot" not in text
    ops = [line.split(" = ", 1)[1] for line in text.splitlines() if " = " in line]
    # (the flat views are (L * E, d, 2f) and (L * E, f, d); Trinity's 512 sorted rows are as many as its L * E)
    stack_sized = [op for op in ops
                   if op.startswith((f"bf16[{stack},{held},", f"bf16[{stack * held},{d},", f"bf16[{stack * held},{f},"))
                   and " parameter(" not in op]
    assert len(stack_sized) == 2 and all(" bitcast(" in op for op in stack_sized), stack_sized
    assert 2 * 3 * d * tf * 2 <= pm.WEIGHT_TILE_BYTES
    out_bytes = pm.n_visits(rows, held, w) * w * pm.ROW_TILE * d * 2  # what the most visits these rows can take write
    assert compiled.memory_analysis().temp_size_in_bytes < max(64 << 20, out_bytes + (16 << 20))


@pytest.mark.parametrize("rows,windows", [(128 * 6, 2), (1024 * 6, 7), (8192 * 6, 7)],
                         ids=["nemotron-decode-step", "nemotron-one-prompt-admission", "nemotron-8192-token-admission"])
def test_ungated_expert_kernel_compiles_for_the_chip_at_the_cells_size(one_chip, rows, windows):
    """``ops/pallas_moe.py``'s two-matrix form at the Nemotron-H cell's expert
    layers (a stack of 11 x 32 experts of 2,688 x 1,856, a width of 14.5 lane
    tiles): Mosaic takes F tiles of 464 rows of both matrices, the up-projection
    contracting the lane axes of both operands; the device keeps ``w1`` with D
    minor-most, so its (.., F, D) view is a bitcast and neither stack is copied,
    sliced or re-laid (the grouped form re-lays all 3.4 GB of ``w1`` a call). A
    prefill's rows take the same kernel at the widest visit, whose rows and the
    two tiles' buffers (14.2 MB) still stand inside ``VMEM_BYTES``."""
    from jax.experimental.compilation_cache import compilation_cache

    from pretraining_llm_tpu.ops import pallas_moe as pm

    stack, held, n_experts, d, f = 11, 32, 128, 2688, 1856
    shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    w = pm.windows(rows, n_experts)
    tf = pm.f_tile(d, f, 2, w, gated=False)
    assert (w, tf) == (windows, 464)
    fn = lambda xs, w1, w2, sizes, layer: pm._moe_call(xs, w1, w2, sizes, layer, None, tf, w, False)
    args = [shape((rows, d)), shape((stack, held, d, f)), shape((stack, held, f, d)), shape((held,), jnp.int32),
            shape((), jnp.int32)]
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable without a chip
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "ragged-dot" not in text
    ops = [line.split(" = ", 1)[1] for line in text.splitlines() if " = " in line]
    stack_sized = [op for op in ops if op.startswith((f"bf16[{stack},{held},", f"bf16[{stack * held},"))
                   and " parameter(" not in op]
    assert len(stack_sized) == 2 and all(" bitcast(" in op for op in stack_sized), stack_sized
    out_bytes = pm.n_visits(rows, held, w) * w * pm.ROW_TILE * d * 2
    assert compiled.memory_analysis().temp_size_in_bytes < out_bytes + rows * d * 2 + (16 << 20)


# mixer, preset, what the cell's size replaces in it, (rows, heads, K, V) of the pool
_STATE_STEPS = {
    "ling-kda-128x128": ("kda", "ling-mini", dict(d_model=2560, n_heads=32, kda_head_dim=128), (128, 32, 128, 128)),
    "olmo-hybrid-gdn-96x192": ("gdn", "olmo-hybrid-toy", dict(d_model=3840, n_heads=30, n_kv_heads=30, gdn_heads=30,
                                                              gdn_key_dim=96, gdn_value_dim=192), (128, 30, 96, 192)),
}


@pytest.mark.parametrize("case", sorted(_STATE_STEPS))
def test_kda_step_compiles_for_the_chip_over_the_cells_pools_as_they_lie(one_chip, monkeypatch, case):
    """``recurrent.mixer_block`` of a decode step at ``serve_ling_decode_4k``'s size
    (KDA: 128 rows over 129 state slots of 32 heads x 128 x 128 float32, d_model
    2560) and at ``serve_olmo_hybrid_decode_512_2k``'s (Gated DeltaNet: 30 heads x
    96 x 192, d_model 3840, heads that fill no whole group of eight and V a lane
    tile and a half), the pools donated as the decode programs donate them:
    Mosaic takes ``ops/pallas_kda.py`` with a row's 2 MB (2.95 MB as the
    96 x 192 state lies, V padded to two lane tiles) of state a block each way,
    the state pool enters the custom call as it lies and the new pool is the
    same buffer (no copy, no second array of the pool's size), and XLA's two
    fusions over the state are gone."""
    import dataclasses
    import functools

    from jax.experimental.compilation_cache import compilation_cache

    from pretraining_llm_tpu.config import get_preset
    from pretraining_llm_tpu.models import kda, layers, recurrent, transformer
    from pretraining_llm_tpu.ops import pallas_kda

    name, preset, sized, (rows, heads, n, nv) = _STATE_STEPS[case]
    mixer = recurrent.MIXERS[name]
    d = sized["d_model"]
    cfg = dataclasses.replace(get_preset(preset).model, param_dtype="bfloat16", compute_dtype="bfloat16", **sized)
    monkeypatch.setattr(mixer, "step_form", functools.partial(kda.step_form, backend="tpu"))
    monkeypatch.setattr(pallas_kda, "recurrent_step", functools.partial(pallas_kda.recurrent_step, interpret=False))
    placed = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    blk = placed(jax.eval_shape(lambda: {
        "ln1": layers.init_norm("rmsnorm", d, jnp.bfloat16),
        "attn": mixer.init_params(cfg, jax.random.key(0), 0.02, jnp.bfloat16),
    }))
    pools = placed({key + "_pool": jax.ShapeDtypeStruct(*spec) for key, spec in mixer.state_shapes(cfg, rows + 1).items()})
    assert pools["state_pool"].shape == (rows + 1, heads, n, nv) and mixer.step_form(pools["state_pool"]) == "kernel"

    def fn(blk, pools, x, tables, seq_lens):
        return recurrent.mixer_block(name, blk, x, cfg, pools, paged=transformer.PagedInfo(tables, seq_lens))

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable without a chip
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(fn, donate_argnums=1).lower(
            blk, pools, placed(jax.ShapeDtypeStruct((rows, 1, d), jnp.bfloat16)),
            placed(jax.ShapeDtypeStruct((rows, 65), jnp.int32)), placed(jax.ShapeDtypeStruct((rows,), jnp.int32)),
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "output_to_operand_aliasing={{1}: (5, {})}" in text
    ops = [line.split(" = ", 1)[1] for line in text.splitlines() if " = " in line]
    pool_sized = [op for op in ops if f"f32[{rows + 1},{heads},{n},{nv}]" in op.split("(", 1)[0] and " parameter(" not in op]
    made = [op for op in pool_sized if not any(f" {kind}(" in op for kind in ("custom-call", "get-tuple-element", "tuple"))]
    assert pool_sized and not made, made
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def _compiled_for_the_chip(fn, *shapes):
    from jax.experimental.compilation_cache import compilation_cache

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable without a chip
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


# (B, T, H, G, Dh), the lanes of the arrays the custom calls are handed (0: the heads folded first)
_FLASH_TILED = {
    "gpt2-large-step": ((12, 1024, 20, 20, 64), 1280),
    "gpt2-xl-step-an-odd-head-count": ((12, 1024, 25, 25, 64), 1600),
    "ling-latent-admission": ((1, 1024, 32, 32, 256), 8192),
    "grouped-heads-of-128": ((4, 1024, 32, 8, 128), 4096),
    "grouped-heads-of-64-folded-first": ((2, 1024, 12, 4, 64), 0),
}


@pytest.mark.parametrize("case", sorted(_FLASH_TILED))
def test_tiled_flash_kernels_compile_for_the_chip_at_the_training_cells_size(one_chip, case):
    """Forward and fused backward of a lone causal block a head at T 1,024, as both training
    cells call them three times a layer a step (and Ling's one-row latent admission the
    forward): Mosaic takes the 128-lane column blocks, the masked selects of bfloat16
    operands, the half-outside last block of 25 heads and, with the heads folded first,
    blocks 64 lanes wide; the calls are handed (B, T, H*Dh) arrays where the rule says so."""
    import functools

    from pretraining_llm_tpu.ops import pallas_flash

    (b, t, h, g, d), lanes = _FLASH_TILED[case]
    assert bool(pallas_flash.heads_in_place(d, h, g, t // pallas_flash.CAUSAL_TILE)) == bool(lanes)
    attn = functools.partial(pallas_flash.pallas_flash_attention, interpret=False)
    loss = lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)
    shape = lambda heads: jax.ShapeDtypeStruct((b, t, heads, d), jnp.bfloat16, sharding=one_chip)
    text = _compiled_for_the_chip(jax.grad(loss, (0, 1, 2)), shape(h), shape(g), shape(g)).as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    in_place = f"bf16[{b},{t},{lanes}]"
    assert all((in_place in line) == bool(lanes) for line in calls), calls


# (B, T, H, Dh) of a fused projection's (B, 3, T, H*Dh) handed to the kernels whole
_FLASH_ONE_ARRAY = {
    "gpt2-large-step": (12, 1024, 20, 64),
    "gpt2-xl-step-an-odd-head-count": (12, 1024, 25, 64),
    "heads-of-128": (4, 1024, 8, 128),
}


@pytest.mark.parametrize("case", sorted(_FLASH_ONE_ARRAY))
def test_tiled_flash_kernels_compile_for_the_chip_on_the_projections_one_array(one_chip, case):
    """The same two kernels with q, k and v taken out of one (B, 3, T, H*Dh) array and d(qkv)
    written as one: Mosaic takes the squeezed plane of the operands' blocks, the three views of
    the backward's one (1, 3, T, 128) output block and, at 25 heads, that block half outside
    the array; the calls are handed the one array and the backward returns one of its shape."""
    import functools

    from pretraining_llm_tpu.ops import pallas_flash

    b, t, h, d = _FLASH_ONE_ARRAY[case]
    attn = functools.partial(pallas_flash.pallas_flash_attention_qkv, n_heads=h, interpret=False)
    loss = lambda qkv: jnp.sum(attn(qkv).astype(jnp.float32) ** 2)
    qkv = jax.ShapeDtypeStruct((b, 3, t, h * d), jnp.bfloat16, sharding=one_chip)
    text = _compiled_for_the_chip(jax.grad(loss), qkv).as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    one = f"bf16[{b},3,{t},{h * d}]"
    assert all(line.split(" custom-call(")[1].count(one) >= 3 or one in line.split(" = ")[1].split(" custom-call(")[0]
               for line in calls), calls
    assert sum(line.split(" = ")[1].split(" custom-call(")[0].startswith(one) for line in calls) == 1  # d(qkv)


def test_no_copy_stands_beside_the_flash_calls_of_a_gpt2_large_layer(one_chip, monkeypatch):
    """One layer of ``train_gpt2large_1chip``'s step (12 x 1,024 tokens, 20 heads of 64, remat
    ``full``), forward and backward, compiled for the chip: the projections hand q, k, v and
    the cotangent of o to the custom calls where their dots leave them and take o, dq, dk, dv
    from them the same way - no copy of an activation (what a change of layout compiles to) is
    left in the program (the parent had twelve of bf16[12,20,1024,64] a layer and one of the
    padded lse). Since PR 55 q, k and v reach the calls as the fused projection's one array,
    and no fusion slices that array or puts d(qkv) together beside them."""
    import functools

    from pretraining_llm_tpu.config import ModelConfig
    from pretraining_llm_tpu.models import transformer
    from pretraining_llm_tpu.ops import flash_attention, pallas_flash

    b, t = 12, 1024
    cfg = ModelConfig(
        vocab_size=256, context_length=t, d_model=1280, n_heads=20, n_layers=1, activation="gelu",
        norm="layernorm", pos_embed="learned", tie_embeddings=True, qkv_bias=True, mlp_bias=True,
        attention_impl="flash", remat="full",
    )
    monkeypatch.setattr(flash_attention, "_pallas_available", lambda: True)
    monkeypatch.setattr(pallas_flash, "pallas_flash_attention_qkv",
                        functools.partial(pallas_flash.pallas_flash_attention_qkv, interpret=False))
    placed = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = placed(jax.eval_shape(lambda k: transformer.init_params(cfg, k), jax.random.key(0)))
    tokens = placed(jax.ShapeDtypeStruct((b, t), jnp.int32))
    grad = jax.grad(lambda p, x, y: transformer.loss_fn(p, x, y, cfg))
    text = _compiled_for_the_chip(grad, params, tokens, tokens).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3  # forward, its recompute, the fused backward
    moved = [line.strip()[:120] for line in text.splitlines()
             if " copy(" in line
             and any(f"[{shape}]" in line.split(" = ", 1)[-1].split("(", 1)[0]
                     for shape in ("12,1024,1280", "12,1024,20,64", "12,20,1024,64", "12,1,1024,20,64", "240,1024,1",
                                   "12,3,1024,1280", "12,3,1024,1,1280"))]
    assert not moved, moved
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    whole = "bf16[12,3,1024,1280]"
    assert all(line.split(" custom-call(")[1].count(whole) >= 3 for line in calls), calls
    assert sum(line.split(" = ")[1].startswith(whole) for line in calls) == 1  # the backward's one d(qkv)
    # the parent's three slice copies a forward pass were fusions named for what they did
    assert "slice_bitcast_fusion" not in text
    # and its d(qkv) was three pads of the kernels' gradients, added up
    assert not [line.strip()[:100] for line in text.splitlines() if " pad(" in line and "bf16[12,3,1024" in line]
