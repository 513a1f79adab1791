"""Serving `correct` for a model that keeps two cache lifetimes (window and
full attention layers, ``cfg.two_lifetimes``): the logits of prefill-then-decode
through BOTH page pools, against the plain reference's full forward.

What ``serving_check.compare`` is to a model with one block list a row, for
one with two. ``serving_check.program_logits`` builds one table from one
allocator; here each sampled sequence owns a list in the full layers' pool
(every page of its length) and one in the window layers' (``paged.
window_first_block``: the pages that hold positions a next query can see), and
the check keeps the window list as the engine does, step by step: before each
teacher-forced decode step every page that now lies wholly behind the window
goes back to the window pool's free list (and is handed to whichever row next
opens a page: the free list is last-in first-out), and the page the step
writes is opened. So a sample whose prompt is longer than the window is
prefilled into its last window pages only, one that crosses the window while
decoding returns pages, and all of them decode at the cell's batch width
through ``paged.paged_decode_logits(window_tables=...)``.

A prompt is prefilled as ``serving_check`` prefills it (``paged.
prefill_into_pool``, which hands back the logits of its last position), with
its window pages named beside its pages; the engine's own batched admission
program, which names them the same way, is what the engine-token comparison
and the tier-1 tests hold.

Two numbers (PERF.md section 2: on an expert model a routing flip under
bfloat16 moves a whole row of logits, so the root mean square over all rows is
a tail statistic): ``logits_rel_err``, ``||program - reference|| /
||reference||`` over all compared rows, which every run reports in its log, and
``logits_row_median_err``, the median over rows of the row's own relative
error, which a flipped row does not move. `correct` holds the one the
configuration's ``check_limits`` names; both are logged. The engine's own
tokens are held as in every serving cell (``mtp_check.compare_tokens``:
``serving_check``'s regret with the head run on the compared rows only).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import mtp_check, opcount, serving_check

Sample = serving_check.Sample
compare_tokens = mtp_check.compare_tokens


class WindowRows:
    """The window layers' table and free list for the sampled rows, kept as
    ``ServingEngine._release_window_pages`` / ``_grow_window_pages`` keep them."""

    def __init__(self, alloc: Any, shape: Tuple[int, int], window: int, block_size: int) -> None:
        self.alloc, self.window, self.bs = alloc, window, block_size
        self.tables = np.zeros(shape, np.int32)
        self.first = np.zeros((shape[0],), np.int64)  # the row's first live page
        self.next = np.zeros((shape[0],), np.int64)  # one past its last opened page
        self.released = 0

    def admit(self, row: int, prompt: int) -> None:
        from pretraining_llm_tpu.generation import paged

        self.first[row] = self.next[row] = paged.window_first_block(prompt, self.window, self.bs)
        self.open(row, prompt)

    def open(self, row: int, position: int) -> None:
        """Pages up to the one that holds ``position``."""
        while self.next[row] <= position // self.bs:
            got = self.alloc.alloc(1)
            if got is None:
                raise RuntimeError("the window pool has no room for the correctness sample")
            self.tables[row, self.next[row]] = got[0]
            self.next[row] += 1

    def release(self, row: int, seq_len: int) -> None:
        """Give back the pages wholly behind the window of the query at ``seq_len``."""
        from pretraining_llm_tpu.generation import paged

        first = paged.window_first_block(seq_len, self.window, self.bs)
        while self.first[row] < min(first, self.next[row]):
            j = self.first[row]
            self.alloc.free([int(self.tables[row, j])])
            self.tables[row, j] = 0
            self.first[row] += 1
            self.released += 1


def program_logits(params: Any, cfg: Any, eng: Any, sample: Sample,
                   seqs: List[np.ndarray]) -> Tuple[List[np.ndarray], Dict[str, int]]:
    """For each sequence, the logits after the prompt and after each forced
    token, through the engine's two pools and allocators; and what the window
    rows did (pages released, the most held)."""
    from pretraining_llm_tpu.generation import paged

    bs, b = eng.block_size, eng.max_batch
    tables = np.zeros((b, eng.max_blocks), np.int32)
    seq_lens = np.zeros((b,), np.int32)
    own = WindowRows(eng.w_alloc, tables.shape, cfg.sliding_window, bs)
    for r, (p, k) in enumerate(sample):
        ids = eng.alloc.alloc(paged.required_blocks(p + k + 1, bs))
        if ids is None:
            raise RuntimeError("pool has no room for the correctness sample")
        tables[r, : len(ids)] = ids
        seq_lens[r] = p
        own.admit(r, p)
    out: List[List[np.ndarray]] = []
    for r, ((p, _), toks) in enumerate(zip(sample, seqs)):
        n_pre = paged.required_blocks(p, bs)
        last, eng.pools = paged.prefill_into_pool(
            params, cfg, eng.pools, toks[:p].tolist(), tables[r, :n_pre].tolist(),
            window_block_ids=own.tables[r, :n_pre].tolist(),
        )
        out.append([np.asarray(last, np.float32)])
    held = 0
    for j in range(max(k for _, k in sample)):
        tok = np.zeros((b,), np.int32)
        for r, ((p, k), toks) in enumerate(zip(sample, seqs)):
            if j < k:
                tok[r] = toks[p + j]
                own.release(r, int(seq_lens[r]))
                own.open(r, int(seq_lens[r]))
        held = max(held, int(np.max(own.next - own.first)))
        logits, eng.pools = paged.paged_decode_logits(
            params, eng.pools, jnp.asarray(tok), jnp.asarray(tables), jnp.asarray(seq_lens), cfg=cfg,
            window_tables=jnp.asarray(own.tables.copy()),
        )
        host = np.asarray(logits, np.float32)
        for r, (p, k) in enumerate(sample):
            if j < k:
                out[r].append(host[r])
                seq_lens[r] += 1
    return [np.stack(o) for o in out], {"window_pages_released": own.released, "window_pages_held_most": held}


def row_errors(prog: List[np.ndarray], ref: List[np.ndarray]) -> np.ndarray:
    """Each compared row's own ``||program - reference|| / ||reference||``."""
    a, b = np.concatenate(prog).astype(np.float64), np.concatenate(ref).astype(np.float64)
    return np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)


def reference_logits(arch: Dict[str, Any], seed: int, sample: Sample, seqs: List[np.ndarray],
                     quant: Any = None, pad_to: int = 0) -> List[np.ndarray]:
    """Rows p-1 .. p+k-1 of the reference's logits for each whole sequence,
    every sequence padded to one length (one compiled program a layer kind)."""
    ref = mtp_check.reference(arch, seed, pad_to or max(len(t) for t in seqs), quant)
    return [np.asarray(ref.logits(ref.hidden(toks), p - 1, k + 1), np.float32)
            for (p, k), toks in zip(sample, seqs)]


def compare(ctx: Any, eng: Any, params: Any, cfg: Any) -> Dict[str, Tuple[float, float]]:
    """{name: (error, limit)} of the comparison ``check_limits`` names, on an
    engine whose rows have been released; logs both; frees the engine's pools,
    and on the chip the program's weights, before the reference runs."""
    if not getattr(eng, "two_lifetimes", False):
        raise RuntimeError("the engine keeps one block list a row: this check is for a model of "
                           "window and full attention layers")
    t0 = time.perf_counter()
    sample = [tuple(s) for s in ctx.traffic["check_sample"]]
    seqs = serving_check.sample_tokens(ctx.seed, opcount.dims(ctx.arch)["vocab"], sample)
    prog, did = program_logits(params, cfg, eng, sample, seqs)
    del eng.pools
    t1 = time.perf_counter()
    if not ctx.rehearsal:
        # the program's part is over: the reference gets the chip (mtp_check.compare)
        for leaf in jax.tree.leaves(params):
            leaf.delete()
    ref = reference_logits(ctx.arch, ctx.seed, sample, seqs, pad_to=ctx.traffic["engine"]["max_seq"])
    rows = row_errors(prog, ref)
    found = {"logits_rel_err": serving_check.rel_err(prog, ref), "logits_row_median_err": float(np.median(rows))}
    ctx.log(f"logits through both pools: {rows.size} rows, rel err {found['logits_rel_err']:.6g}, per-row median "
            f"{found['logits_row_median_err']:.6g}, {int(np.sum(rows > 0.1))} rows over 0.1, largest "
            f"{rows.max():.6g}; the sampled rows gave back {did['window_pages_released']} window pages and held "
            f"at most {did['window_pages_held_most']}; the program's side {t1 - t0:.1f} s, the reference's "
            f"{time.perf_counter() - t1:.1f} s")
    limits = ctx.arch["check_limits"]
    return {name: (value, limits[name]) for name, value in found.items() if name in limits}
