"""Pallas TPU kernel for one token of the KDA recurrence: a row's state read
once and written once, in place.

``models/kda.py::recurrent_step`` is four ``jnp`` lines over a float32 state
of (H, K, V) a row. XLA compiles them to two fusions a layer, and the state
crosses HBM about three and a half times where the recurrence needs it twice
(PERF.md section 6, PR 40). This kernel is the same four lines with the
state in VMEM between them. Per (row, head), everything float32::

    D   = exp(g)[:, None] * S
    kTs = sum_K  k[:, None] * D            # (V,)
    u   = beta * (v - kTs)
    S'  = D + k[:, None] * u[None, :]      # stored as it is made
    o   = sum_K  q[:, None] * S'           # (V,)

Two mixers step through it: KDA (a square state of whole lane tiles, 128 x 128,
a decay a channel) and Gated DeltaNet (``models/gdn.py``: 96 x 192, the head's
one decay broadcast over its K channels by the caller). One recurrence; what
differs is read from the state's shape.

  - Grid ``(rows,)``. A grid step brings all heads of one row: the state block
    (1, H, K, V) — 2 MB at 32 heads of 128 x 128 — comes in through the
    pipeline, the new state leaves through the output block of the same index,
    and ``input_output_aliases`` makes both the same HBM buffer. A smaller
    block would pay a grid step's fixed ~0.35 us on too few bytes; in, out and
    their second buffers are four blocks of VMEM (``vmem_limit_bytes``). On the
    v5e the call stands on the ceiling of such a pass, not on its arithmetic:
    a bare in-place copy through the same blocks takes 4.27 ms for five pools
    of 129 rows where this kernel takes 4.28 (650 GB/s of the chip's 819;
    three rows a step, hand-made DMAs in up to eight chunks and a third buffer
    all read the same: PERF.md section 6, PR 40).
  - **The layout stays (rows, H, K, V)**: ``kda.chunked`` (prefill),
    ``paged._scatter_pages``, the benchmark's check and its byte count all read
    the pool so, and a decode program that donates its pools hands this call
    the pool itself. V lies on lanes, K on sublanes: a head of 128 x 128 is 16
    vregs of 8 values of K each. The block's last two dimensions are the
    array's own, so K need only be whole 8-sublane tiles and V may be any
    width: the chip stores V padded to whole 128-lane tiles, and **a block is
    reckoned at its padded lanes** (``_block_bytes``), against
    ``STATE_BLOCK_BYTES`` and in ``vmem_limit_bytes``. A 96 x 192 head is 12
    sublane tiles by two lane tiles, the second half empty: 30 heads are
    2.95 MB a row as they lie (2.21 MB of numbers), and the pass moves the
    padding with them. Six pools of 129 rows take 7.06 ms where the bare copy
    takes 7.00 (647 and 652 GB/s of padded bytes, 485 GB/s of the numbers
    alone; XLA's two fusions 10.03 ms: PERF.md section 6, PR 57). Whole tiles
    (all heads' values side by side) would need the pool in another layout.
  - q, k and exp(g) index K, so the state's tiles want them down the sublanes,
    and they arrive as rows (K on lanes). Eight heads' three vectors are
    stacked to one (128, Kp) tile (24 rows of it used) and transposed on the
    XLU, once a group of eight heads; head ``hh``'s vectors are then columns
    ``hh``, ``8 + hh``, ``16 + hh`` of the transposed tile, each broadcast over
    the lanes as it meets the state. Nothing is pre-broadcast in HBM. The
    transpose wants whole tiles, so the wrapper pads the three operands' K to
    whole lane tiles (Kp; 1.5 MB a layer at 96 beside 760 MB of state) and the
    kernel takes the first K sublanes of the transposed tile.
  - Inside a step a ``fori_loop`` walks the groups of eight heads; the eight
    heads of a group are unrolled, which is what lets the scheduler overlap one
    head's loads with another's sums (a loop of one head a turn was timed at
    twice the time, bound by its own chain and no longer by the bytes). Heads
    that fill no whole group (30 = 3 groups and 6) follow the loop as one
    shorter group. The sums over K add the K / 8 vregs and then the 8
    sublanes; on the chip they came out bit-equal to XLA's at both cells'
    shapes.
  - A dead row or a padded one (``g = 0``, ``beta = 0``) keeps its arithmetic:
    ``S * 1 + k * 0`` is written back over ``S``. No select over states, no
    skipped row: the bytes are the same for every row, as the benchmark counts.

Forward only (decode never differentiates).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
GROUP = 8  # heads a transposed tile serves: one float32 sublane tile of each vector
# The most bytes of state a row may bring: in, out and their second buffers are
# four such blocks of VMEM.
STATE_BLOCK_BYTES = 4 << 20


def _block_bytes(h: int, k: int, v: int) -> int:
    """A row's heads as they lie on the chip: V padded to whole 128-lane tiles."""
    return h * k * (v + -v % LANES) * 4


def takes(state_shape: Tuple[int, ...], dtype) -> bool:
    """Whether the kernel takes a state of this shape and dtype: float32,
    (rows, H, K, V) with K whole 8-sublane tiles and V any width, a row's heads
    at their padded lanes inside ``STATE_BLOCK_BYTES``."""
    if len(state_shape) != 4 or jnp.dtype(dtype) != jnp.float32:
        return False
    _, h, k, v = state_shape
    return k % 8 == 0 and 0 < _block_bytes(h, k, v) <= STATE_BLOCK_BYTES


def _kda_kernel(q_ref, k_ref, g_ref, v_ref, beta_ref, s_ref, o_ref, so_ref, *, heads: int):
    # q/k/g (1, Hp, Kp), v (1, Hp, V), beta (1, Hp, 1): heads padded to whole groups,
    # K to whole lane tiles; s/so (1, H, K, V); o (1, Hp, V)
    kdim = s_ref.shape[2]
    spare = jnp.zeros((LANES - 3 * GROUP, q_ref.shape[-1]), jnp.float32)

    def group(gi, n: int):
        """Heads gi * GROUP .. + n, n static."""
        h0 = pl.multiple_of(gi * GROUP, GROUP)
        rows = pl.ds(h0, GROUP)
        decay = jnp.exp(g_ref[0, rows, :])
        stacked = jnp.concatenate([decay, k_ref[0, rows, :], q_ref[0, rows, :], spare], axis=0)
        cols = stacked.T  # (Kp, 128): vector j of head hh down column j * GROUP + hh
        v, beta = v_ref[0, rows, :], beta_ref[0, rows, :]
        outs = []
        for hh in range(n):
            dc, kc, qc = (cols[:kdim, j * GROUP + hh : j * GROUP + hh + 1] for j in range(3))
            d = s_ref[0, h0 + hh] * dc
            kts = jnp.sum(d * kc, axis=0, keepdims=True)
            u = beta[hh : hh + 1, :] * (v[hh : hh + 1, :] - kts)
            s = d + kc * u
            so_ref[0, h0 + hh] = s
            outs.append(jnp.sum(s * qc, axis=0, keepdims=True))
        outs += [jnp.zeros_like(outs[0])] * (GROUP - n)
        o_ref[0, rows, :] = jnp.concatenate(outs, axis=0)

    full, rest = divmod(heads, GROUP)
    if full:
        jax.lax.fori_loop(0, full, lambda gi, c: (group(gi, GROUP), c)[1], 0)
    if rest:
        group(full, rest)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_call(state, q, k, v, g, beta, interpret):
    n, h, kdim, vdim = state.shape
    hp, kp = h + -h % GROUP, kdim + -kdim % LANES
    pad = lambda a, lanes=0: jnp.pad(a, ((0, 0), (0, hp - h), (0, lanes)))  # heads to whole groups, K to whole tiles
    small = lambda width: pl.BlockSpec((1, hp, width), lambda i: (i, 0, 0))
    big = pl.BlockSpec((1, h, kdim, vdim), lambda i: (i, 0, 0, 0))
    block = _block_bytes(h, kdim, vdim)
    o, new_state = pl.pallas_call(
        functools.partial(_kda_kernel, heads=h),
        grid=(n,),
        in_specs=[small(kp), small(kp), small(kp), small(vdim), small(1), big],
        out_specs=[small(vdim), big],
        out_shape=[jax.ShapeDtypeStruct((n, hp, vdim), jnp.float32), jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # the state's block in and out, each double buffered, and the small operands
            vmem_limit_bytes=4 * block + (8 << 20),
        ),
        interpret=interpret,
    )(pad(q, kp - kdim), pad(k, kp - kdim), pad(g, kp - kdim), pad(v), pad(beta[..., None]), state)
    return o[:, :h], new_state


def recurrent_step(
    state: jax.Array,  # (N, H, K, V) float32; the new state takes its buffer
    q: jax.Array,  # (N, H, K)
    k: jax.Array,  # (N, H, K)
    v: jax.Array,  # (N, H, V)
    g: jax.Array,  # (N, H, K) log decay, <= 0
    beta: jax.Array,  # (N, H)
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """``models/kda.py::recurrent_step`` as one kernel: (o (N, H, V), new state),
    both float32. ``interpret=None``: compiled on TPU, the interpreter elsewhere
    (tests)."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    n, h, kdim, vdim = state.shape if state.ndim == 4 else (0, 0, 0, 0)
    if (
        not takes(state.shape, state.dtype)
        or q.shape != (n, h, kdim) or k.shape != q.shape or g.shape != q.shape
        or v.shape != (n, h, vdim) or beta.shape != (n, h)
    ):
        raise ValueError(
            f"state {state.shape} {state.dtype}, q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, beta "
            f"{beta.shape}: want a float32 state (N, H, K, V) with K whole 8-sublane tiles and a row's heads, V "
            f"at its padded lanes, within {STATE_BLOCK_BYTES} bytes, q/k/g (N, H, K), v (N, H, V), beta (N, H)"
        )
    f32 = lambda a: a.astype(jnp.float32)
    return _kda_call(state, f32(q), f32(k), f32(v), f32(g), f32(beta), bool(interpret))
