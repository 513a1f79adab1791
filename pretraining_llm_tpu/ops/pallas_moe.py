"""Pallas TPU expert FFN for a handful of rows an expert: weights read once.

A decode step hands an expert layer two to a few dozen rows an expert. XLA's
TPU expansion of ``jax.lax.ragged_dot`` is built for prefill (row tiles of
hundreds) and pays a fixed ~25 us a touched group whatever the group's bytes
(PERF.md section 6, PR 32). This kernel is the other end of that trade: it is
bound by the bytes of the experts some row chose, and does the two grouped
matmuls and the SwiGLU between them in one pass over those bytes.

  - The rows arrive sorted by expert (``models/moe.py::moe_mlp_dropless``),
    ``sizes[e]`` of them for held expert ``e``; rows past the last group
    belong to experts held elsewhere.
  - A **visit** is one expert and a span of ``w * ROW_TILE`` consecutive
    sorted rows: the ``w`` ``ROW_TILE``-aligned windows from the one that holds
    the expert's first row. A group of up to ``(w - 1) * ROW_TILE + 1`` rows is
    one visit wherever it starts; a larger one takes a visit a span, its
    weights read again for each. ``w`` is static and read from the call's rows
    an expert (``windows``): two under a decode step's handful, and past
    ``ROW_TILE`` rows an expert as many as hold a group of twice the mean, so
    that a skewed step still reads each touched expert once (PERF.md section 6,
    PR 44). A prefill's hundreds of rows an expert keep ``ragged_dot``.
  - Grid ``(visits, F tiles)``. The list of visits (expert, first window) is
    scalar prefetch, built from ``sizes`` outside; the weight stacks stay whole
    in HBM and a step's tiles are addressed ``(layer * E + expert, ...)`` by
    the block index maps: gate columns ``(D, tf)``, up columns ``(D, tf)``
    (``w1`` is passed twice: gate columns come before up columns in ``2F``)
    and the matching ``(tf, D)`` rows of ``w2``, double buffered by the
    pipeline. ``silu(gate) * up`` stays in VMEM; the ``(rows, D)`` output
    accumulates in float32 across the F tiles.
  - The grid is as long as the visits of this call (a dynamic grid bound, at
    least one step: with nothing routed here that step computes nothing). An
    expert no row chose, another layer's expert and the rows of experts held
    elsewhere cost no grid step, no copy and no MXU pass.
  - Neighbouring groups share a sublane tile of the sorted rows, so a visit
    cannot write its rows where they lie. It computes its whole windows
    (the neighbours' rows through its own weights are wasted MXU passes, of
    which a byte-bound kernel has plenty) into an output block of its own. The
    layer gathers each pair's row from there straight into token order
    (``expert_visits``: the visits' output and ``plan``'s ``offset``); the sorted
    form (``expert_ffn``: timing, tests, the VJP) brings the sorted order back
    with one row gather.

Same arithmetic as the grouped form: operands in the compute dtype, float32
accumulation, gate / up and the hidden each rounded to the compute dtype once.
Forward only here; ``models/moe.py`` gives the call the grouped form's VJP.

**Two expert forms, read from the shapes.** ``w1 (.., D, 2F)`` is the SwiGLU
above, three weight tiles a grid step. ``w1 (.., D, F)`` is an **ungated**
expert of two matrices, ``w2 . relu(x . w1)^2`` (Nemotron-H), two tiles a step,
and its F need not be whole lane tiles (1,856 = 14.5 of them). The TPU lays an
array of (.., 2688, 1856) out with the 2,688 minor-most, the axis that fills
whole lane tiles (every stored byte a real one, no padding to 1,920), so the
kernel takes ``w1`` as that layout's own view, ``(.., F, D)`` (the ``swapaxes``
in ``_visits_call`` is a bitcast there, and an at-size compile shows no copy of
the stack), cuts F tiles of whole 16-row sublane tiles from it (464 = 16 x 29)
exactly as it cuts them from ``w2 (.., F, D)``, and the up-projection contracts
the lane axes of both operands. Same grid, same visits, same accumulator.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows of a window of the sorted rows: one packed sublane tile of bfloat16.
ROW_TILE = 16
# Mosaic's default scoped VMEM is 16 MB. The pipeline's two buffers of (gate, up,
# down) tiles stay under the first figure; with what a visit's rows take (two
# buffers of its windows and of its output block, the row scratch, the float32
# accumulator) they stay under the second, which leaves the compiler's own
# temporaries their room. At two windows and the cells' widths the first binds;
# a wide visit at D 4,096 takes narrower tiles, which time the same (PERF.md
# section 6, PR 44).
WEIGHT_TILE_BYTES = 12 << 20
VMEM_BYTES = 14 << 20
# The widest visit: what the rule's bound asks for (48 rows an expert: twice the
# mean from a window's last row). A call of more rows an expert (only an ungated
# expert whose F is no whole number of lane tiles makes one: ``moe.experts_form``)
# keeps it and takes more visits an expert.
MAX_WINDOWS = 7


def ungated(w1: jax.Array, w2: jax.Array) -> bool:
    """Whether these experts are the two-matrix form: ``w1`` ([L,] E, D, F) as
    wide as ``w2`` ([L,] E, F, D) is tall, not a SwiGLU's (.., D, 2F). The one
    place that reads it from the shapes; ``models/moe.py`` asks here too."""
    return w1.shape[-1] == w2.shape[-2]


def f_tile(d: int, f: int, itemsize: int, w: int = 2, gated: bool = True) -> int:
    """Columns of F a grid step takes under a visit of ``w`` windows: the
    largest whole number of 128-lane tiles that divides F and keeps two buffers
    of the three weight tiles under ``WEIGHT_TILE_BYTES``, and under
    ``VMEM_BYTES`` with the visit's rows (at least one lane tile). An ungated
    expert (``gated=False``) has two weight tiles a step and F on the sublanes
    of both: whole 16-row tiles of it (at least one; 1,856 = 64 x 29 gives 464
    at every width of visit)."""
    # a row of a visit: two buffers in, two out, the scratch, the float32 accumulator
    rows = w * ROW_TILE * d * (5 * itemsize + 4)
    unit, tiles = (128, 3) if gated else (ROW_TILE, 2)
    fits = [
        tf for tf in range(unit, f + 1, unit)
        if f % tf == 0 and 2 * tiles * d * tf * itemsize <= min(WEIGHT_TILE_BYTES, VMEM_BYTES - rows)
    ]
    return max(fits, default=unit)


def windows(n_rows: int, n_experts: int) -> int:
    """ROW_TILE windows a visit holds when ``n_rows`` sorted rows are routed
    over ``n_experts`` (all the router scores, held here or not): two up to
    ROW_TILE rows an expert; past that the fewest that hold a group of twice
    the mean in one visit wherever it starts (a group may start on a window's
    last row), and never more than ``MAX_WINDOWS``."""
    if n_rows <= ROW_TILE * n_experts:
        return 2
    twice = -(-2 * n_rows // n_experts)
    return min(-(-(twice + ROW_TILE - 1) // ROW_TILE), MAX_WINDOWS)


def n_visits(n_rows: int, held: int, w: int = 2) -> int:
    """The most visits of ``w`` windows ``n_rows`` sorted rows (a multiple of
    ROW_TILE) over ``held`` experts can take: one a touched expert, and one
    more for every ``(w - 1) * ROW_TILE`` rows a group holds (a group of n rows
    takes at most 1 + n // ((w - 1) * ROW_TILE))."""
    return min(held, n_rows) + n_rows // ((w - 1) * ROW_TILE)


def group_visits(sizes: jax.Array, w: int = 2):
    """``sizes`` (..., E) rows a held expert, in sorted order along the last
    axis -> (visits of ``w`` windows each group takes, the window-aligned row
    each group starts in, the row each ends before), all (..., E) int32. What
    ``plan`` lays out and what the engine counts as weight reads."""
    span = w * ROW_TILE
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes, axis=-1)
    base = (ends - sizes) // ROW_TILE * ROW_TILE  # the window a group starts in
    visits = jnp.where(sizes > 0, (ends - base + span - 1) // span, 0)
    return visits, base, ends


def plan(sizes: jax.Array, n_rows: int, w: int = 2):
    """``sizes`` (E,) rows a held expert -> the visits and where each group lies
    in their output.

    (expert (V,), first window (V,), live visits (1,), offset (E,)); V is
    ``n_visits``, what lies past the live visits is never read. The i-th row of
    group ``e`` (in sorted order) lies at row ``offset[e] + i`` of the visits'
    output: a visit writes its whole span, so a row keeps the place it has in
    its group's first window, ``(first + rel // span) * span + rel % span`` for
    the row ``rel`` rows past that window's start, which is ``first * span +
    rel``. Every lookup is a compare against all E groups summed (V x E: a few
    thousand), no search and no gather."""
    held = sizes.shape[0]
    span = w * ROW_TILE
    visits, base, ends = group_visits(sizes, w)
    v_ends = jnp.cumsum(visits)
    first = v_ends - visits
    v = jnp.arange(n_visits(n_rows, held, w), dtype=jnp.int32)
    # a visit's group: as many groups as end at or before it (the last past the live visits)
    expert = jnp.minimum(jnp.sum(v_ends[None, :] <= v[:, None], axis=1, dtype=jnp.int32), held - 1)
    of_visit = expert[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :]
    window = w * v + jnp.sum(jnp.where(of_visit, (base // ROW_TILE - w * first)[None, :], 0), axis=1)
    window = jnp.clip(window, 0, n_rows // ROW_TILE - 1).astype(jnp.int32)
    offset = first * span + ends - sizes.astype(jnp.int32) - base
    return expert, window, v_ends[-1].reshape(1), offset


def sorted_positions(sizes: jax.Array, offset: jax.Array, n: int, out_rows: int) -> jax.Array:
    """(n,) the row of the visits' output (``out_rows`` long) that holds each of
    the first ``n`` sorted rows; a row past the last group gets some row inside
    it, which nobody reads. The sorted form's way back, and the VJP's."""
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    of_row = (ends - sizes <= rows) & (rows < ends)
    position = rows[:, 0] + jnp.sum(jnp.where(of_row, (offset - ends + sizes)[None, :], 0), axis=1)
    return jnp.clip(position, 0, out_rows - 1)


def _moe_kernel(
    exp_ref,  # (V,) int32 scalar prefetch: the visit's group, layer * E + expert
    win_ref,  # (V,) int32: its first window
    live_ref,  # (1,) int32: visits that do anything
    *refs,
    clamp: bool,
    gated: bool = True,
):
    if clamp:
        lim_ref, *refs = refs  # (1,) float32 in SMEM
    *refs, wu_ref, wd_ref, o_ref, x_scr, acc_scr = refs
    if gated:
        *refs, wg_ref = refs
    x_refs = refs  # the visit's windows
    v, j = pl.program_id(0), pl.program_id(1)
    cdt = x_scr.dtype

    @pl.when(v < live_ref[0])
    def _visit():
        @pl.when(j == 0)
        def _rows():
            for i, x_ref in enumerate(x_refs):
                x_scr[i * ROW_TILE : (i + 1) * ROW_TILE] = x_ref[...]
            acc_scr[...] = jnp.zeros_like(acc_scr)

        x = x_scr[...]
        dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
        # rounded to the compute dtype as ragged_dot's preferred_element_type does
        if gated:
            gate = dot(x, wg_ref[...]).astype(cdt).astype(jnp.float32)
            up = dot(x, wu_ref[...]).astype(cdt).astype(jnp.float32)
            if clamp:
                lim = lim_ref[0]
                gate, up = jnp.minimum(gate, lim), jnp.clip(up, -lim, lim)
            hidden = (gate * jax.nn.sigmoid(gate)).astype(cdt).astype(jnp.float32) * up
        else:
            # the (tf, D) tile of w1 as it lies: the lane axes of both operands contract
            up = jax.lax.dot_general(
                x, wu_ref[...], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            up = jnp.maximum(up.astype(cdt).astype(jnp.float32), 0.0)
            hidden = up * up
        acc_scr[...] += dot(hidden.astype(cdt), wd_ref[...])

        @pl.when(j == pl.num_programs(1) - 1)
        def _out():
            o_ref[...] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tf", "w", "interpret"))
def _visits_call(xs, w1, w2, visits, layer, limit, tf, w, interpret):
    """The kernel over ``plan``'s visits of the sorted rows ``xs`` (whole row
    tiles) -> the visits' output, a span a visit: (V * w * ROW_TILE, D)."""
    n_rows, d = xs.shape
    held, f = w1.shape[-3], w2.shape[-2]
    gated = not ungated(w1, w2)
    w1 = w1.reshape(-1, d, w1.shape[-1])  # a stack's (L, E) as L * E groups: a bitcast
    w2 = w2.reshape(-1, f, d)
    nf, span = f // tf, w * ROW_TILE
    expert, window, live = visits
    n_windows = n_rows // ROW_TILE

    def x_window(i):  # the visit's i-th window of the sorted rows
        if i == 0:  # plan has clipped it: the index map two-window calls have always had
            return pl.BlockSpec((ROW_TILE, d), lambda v, j, exp, win, live: (win[v], 0))
        return pl.BlockSpec(
            (ROW_TILE, d), lambda v, j, exp, win, live: (jnp.minimum(win[v] + i, n_windows - 1), 0)
        )

    clamp = limit is not None
    lim = ()
    if clamp:
        lim = (jnp.where(limit > 0, limit, jnp.inf).astype(xs.dtype).astype(jnp.float32).reshape(1),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(jnp.maximum(live[0], 1), nf),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM) for _ in lim] + [x_window(i) for i in range(w)] + ([
            pl.BlockSpec((None, d, tf), lambda v, j, exp, win, live: (exp[v], 0, j)),
            pl.BlockSpec((None, d, tf), lambda v, j, exp, win, live: (exp[v], 0, nf + j)),
        ] if gated else [
            pl.BlockSpec((None, tf, d), lambda v, j, exp, win, live: (exp[v], j, 0)),  # of w1 as (.., F, D)
        ]) + [
            pl.BlockSpec((None, tf, d), lambda v, j, exp, win, live: (exp[v], j, 0)),
        ],
        out_specs=pl.BlockSpec((span, d), lambda v, j, exp, win, live: (v, 0)),
        scratch_shapes=[pltpu.VMEM((span, d), xs.dtype), pltpu.VMEM((span, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_moe_kernel, clamp=clamp, gated=gated),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((expert.shape[0] * span, d), xs.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(expert + layer * held, window, live, *lim, *[xs] * w, *((w1, w1) if gated else (jnp.swapaxes(w1, 1, 2),)), w2)


@functools.partial(jax.jit, static_argnames=("tf", "w", "interpret"))
def _moe_call(xs, w1, w2, sizes, layer, limit, tf, w, interpret):
    n = xs.shape[0]
    xs = jnp.pad(xs, ((0, -n % ROW_TILE), (0, 0)))
    *visits, offset = plan(sizes, xs.shape[0], w)
    out = _visits_call(xs, w1, w2, visits, layer, limit, tf, w, interpret)
    return out[sorted_positions(sizes, offset, n, out.shape[0])]


def _checked(xs, w1, w2, sizes, layer, limit, w, interpret):
    """The static arguments of a call, (layer, F tile, windows, interpret), or
    a ValueError that names what the kernel cannot take."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    n, d = xs.shape
    held, f = w1.shape[-3], w2.shape[-2]
    stacked = w1.ndim == 4
    two = ungated(w1, w2)
    if (
        w1.shape[-3:] not in ((held, d, 2 * f), (held, d, f))
        or w2.shape != w1.shape[:-2] + (f, d)
        or w1.ndim != 3 + stacked
        or not xs.dtype == w1.dtype == w2.dtype
        or sizes.shape != (held,)
        or stacked != (layer is not None)
        or d % 128 or f % (ROW_TILE if two else 128)
        or (two and limit is not None)
        or w < 2
    ):
        raise ValueError(
            f"rows {xs.shape} {xs.dtype}, w1 {w1.shape} {w1.dtype}, w2 {w2.shape} {w2.dtype}, sizes "
            f"{sizes.shape}, layer {layer}, {w} windows a visit: want (N, D), ([L,] E, D, 2F) or an ungated "
            f"([L,] E, D, F) without a clamp, ([L,] E, F, D), (E,) in one dtype, D whole 128-lane tiles and F "
            f"too (ungated: whole 16-row tiles), a layer exactly for a stack, two windows or more"
        )
    layer = jnp.zeros((), jnp.int32) if layer is None else jnp.asarray(layer, jnp.int32)
    return layer, f_tile(d, f, xs.dtype.itemsize, int(w), not two), int(w), bool(interpret)


def expert_ffn(
    xs: jax.Array,  # (N, D) rows sorted by expert, the compute dtype
    w1: jax.Array,  # (E, D, 2F) gate columns then up columns, or a stack (L, E, D, 2F)
    w2: jax.Array,  # (E, F, D) or (L, E, F, D)
    sizes: jax.Array,  # (E,) int32 rows of each held expert, in order
    layer: Optional[jax.Array] = None,  # the layer of a stack, a scalar
    limit: Optional[jax.Array] = None,  # SwiGLU clamp, a scalar, 0 = off
    *,
    w: int = 2,  # ROW_TILE windows a visit holds: ``windows`` of the call's rows an expert
    interpret: Optional[bool] = None,
) -> jax.Array:
    """(silu(x . gate_e) * (x . up_e)) . down_e for each sorted row x of held
    expert e: (N, D) in ``xs``'s dtype; with ``w1`` ([L,] E, D, F), as wide as
    ``w2`` is tall, the ungated relu(x . w1_e)^2 . w2_e (F whole 16-row tiles,
    no clamp). Rows past ``sum(sizes)`` (experts held
    elsewhere) come back as something finite or not: the caller selects them
    away, as after ``ragged_dot``. ``interpret=None``: compiled on TPU, the
    interpreter elsewhere (tests)."""
    layer, *static = _checked(xs, w1, w2, sizes, layer, limit, w, interpret)
    return _moe_call(xs, w1, w2, sizes, layer, limit, *static)


def expert_visits(
    xs: jax.Array, w1: jax.Array, w2: jax.Array, sizes: jax.Array, visits, layer: Optional[jax.Array] = None,
    limit: Optional[jax.Array] = None, *, w: int = 2, interpret: Optional[bool] = None,
) -> jax.Array:
    """``expert_ffn`` without the way back: ``xs`` (whole row tiles of sorted
    rows) under ``visits``, the first three of ``plan(sizes, xs.shape[0], w)``
    -> the visits' output (V * w * ROW_TILE, D), where row i of group e lies at
    ``offset[e] + i`` (the plan's fourth). What a caller that gathers from the
    output anyway (``models/moe.py``) takes, so that no pass restores a sorted
    order only to undo it."""
    if xs.shape[0] % ROW_TILE:
        raise ValueError(f"rows {xs.shape}: the visits read whole tiles of {ROW_TILE} rows")
    layer, *static = _checked(xs, w1, w2, sizes, layer, limit, w, interpret)
    return _visits_call(xs, w1, w2, visits, layer, limit, *static)
