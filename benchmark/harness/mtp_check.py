"""Serving `correct` for a model that drafts for itself with its own
multi-token-prediction module: the logits of the round's two forwards, stack
and module, through the page pool, against the plain reference's full forward.

What ``serving_check.compare`` is to the decode step, for the speculative
round (``generation/paged.py``): each sampled sequence is prefilled by the
engine's own admission program with the module (``prefill_into_pool_batched(
with_draft=True)``: the stack's and the module's pages, the first token, the
first draft), then ``k / 2`` teacher-forced rounds of the two-query verify
program (``paged_mtp_logits``: what ``paged_mtp_round`` runs, without its
decisions) at the cell's batch width give

- ``verify_logits_rel_err``: the stack's logits at positions ``p .. p+k-1``;
- ``draft_logits_rel_err``: the module's logits at the same positions, the
  program's module fed the program's own hidden states, the reference's module
  the reference's own.

Both are ``||program - reference|| / ||reference||`` over all compared rows.
The token at position ``p`` is the one the program's prefill sampled (the
module's prefill consumed it), every other one is drawn from the seed, so
rounding never forks a sequence. A module that read the wrong hidden state,
the wrong token or another layer's pages would go unnoticed in the engine's
tokens at chance acceptance; here it reads near 1 (the control:
``reference_logits(hidden_shift=1)``). Limits: the configuration's
``check_limits``; PERF.md section 2 gives the readings each stands between.
"""

from __future__ import annotations

import functools
import importlib
import json
import time
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import opcount, serving_check, weights

Sample = serving_check.Sample


def sample_tokens(seed: int, vocab: int, sample: Sample) -> List[np.ndarray]:
    """As ``serving_check.sample_tokens``, one token longer: the module's row
    at the last compared position reads the token behind it."""
    return serving_check.sample_tokens(seed, vocab, [(p, k + 1) for p, k in sample])


def program_logits(params: Any, cfg: Any, pools: Any, alloc: Any, max_batch: int, max_blocks: int,
                   block_size: int, sample: Sample, seqs: List[np.ndarray], prefill_rows: int = 0):
    """(the stack's logits, the module's logits, pools): for each sequence the
    rows of positions p .. p+k-1, (k, V) each. Writes the token the prefill
    sampled into ``seqs[r][p]``."""
    from pretraining_llm_tpu.generation import paged

    tables = np.zeros((max_batch, max_blocks), np.int32)
    seq_lens = np.zeros((max_batch,), np.int32)
    prefill_ids = []
    for r, ((p, k), toks) in enumerate(zip(sample, seqs)):
        if k % 2:
            raise ValueError("check_sample's steps are whole rounds of two")
        ids = alloc.alloc(paged.required_blocks(p + k + 2, block_size))
        if ids is None:
            raise RuntimeError("pool has no room for the correctness sample")
        prefill_ids.append(ids[: paged.required_blocks(p, block_size)])
        tables[r, : len(ids)] = ids
        seq_lens[r] = p
    # Every prompt in one admission program. ``prefill_rows``: filled up to that many rows with
    # one-token prompts on a page each, so that the program is the one the engine's own
    # admissions of that many rows compiled (the first wave's longest bucket), not a new one.
    prompts = [toks[:p].tolist() for (p, _), toks in zip(sample, seqs)]
    for _ in range(prefill_rows - len(prompts)):
        page = alloc.alloc(1)
        if page is None:
            raise RuntimeError("pool has no room for the correctness sample")
        prompts.append([0])
        prefill_ids.append(page)
    first, pools = paged.prefill_into_pool_batched(
        params, cfg, pools, prompts, prefill_ids, jax.random.PRNGKey(0), with_draft=True)  # the engine's kind of key
    for (p, _), toks, tok in zip(sample, seqs, np.asarray(first)[:, 0]):
        toks[p] = int(tok)
    verify: List[List[np.ndarray]] = [[] for _ in sample]
    draft: List[List[np.ndarray]] = [[] for _ in sample]
    n = len(sample)
    for j in range(0, max(k for _, k in sample), 2):
        tok = np.zeros((max_batch, 2), np.int32)
        nxt = np.zeros((max_batch, 2), np.int32)
        for r, ((p, k), toks) in enumerate(zip(sample, seqs)):
            if j < k:
                tok[r], nxt[r] = toks[p + j : p + j + 2], toks[p + j + 1 : p + j + 3]
        t_logits, m_logits, pools = paged.paged_mtp_logits(
            params, pools, jnp.asarray(tok), jnp.asarray(nxt), jnp.asarray(tables), jnp.asarray(seq_lens), cfg=cfg)
        t_host, m_host = np.asarray(t_logits[:n], np.float32), np.asarray(m_logits[:n], np.float32)
        for r, (p, k) in enumerate(sample):
            if j < k:
                verify[r].append(t_host[r])
                draft[r].append(m_host[r])
                seq_lens[r] += 2
    return [np.concatenate(v) for v in verify], [np.concatenate(d) for d in draft], pools


_rows_from = jax.jit(jax.lax.dynamic_slice_in_dim, static_argnums=(2,))  # n rows from a traced start


@jax.jit
def _regrets(rows, toks):
    """How far each row's logit of its token lies below the row's best, in the row's standard deviations."""
    got = jnp.take_along_axis(rows, toks[:, None], axis=-1)[:, 0]
    return (jnp.max(rows, axis=-1) - got) / jnp.std(rows, axis=-1)


_REFERENCES: Dict[Any, "Reference"] = {}


def reference(arch: Dict[str, Any], seed: int, pad_to: int, quant: Any = None) -> "Reference":
    """One ``Reference`` a configuration, seed, length and precision: the logit
    and the token comparison of a run share its weights and compiled programs."""
    key = (json.dumps(arch, sort_keys=True, default=str), int(seed), int(pad_to), quant)
    if key not in _REFERENCES:
        _REFERENCES[key] = Reference(arch, seed, pad_to, quant)
    return _REFERENCES[key]


class Reference:
    """The plain reference on a seed's weights, every sequence padded to one
    length (the model is causal: padding changes nothing before it), so that
    each of its programs compiles once a run: ``hidden`` (tokens -> the
    stack's normed output, (pad_to, d)), ``mtp_hidden`` and ``logits`` (the
    head on the rows asked for, nothing else)."""

    def __init__(self, arch: Dict[str, Any], seed: int, pad_to: int, quant: Any = None) -> None:
        self.ref = importlib.import_module(f"references.{arch['family']}")
        self.arch, self.quant, self.pad_to = arch, quant, pad_to
        key = weights.seed_key(seed)
        dtype = jnp.dtype(arch["serving_dtype"])
        make_layer = jax.jit(lambda k, l: weights.layer(arch, k, l, dtype))
        # each layer made once and kept: with the program's weights gone the chip holds them
        # all (four of 1.27 GB beside 2.5 GB of globals at the cell's size)
        self.layer = functools.lru_cache(maxsize=None)(lambda l: make_layer(key, l))
        self.gw = jax.jit(lambda k: weights.globals_(arch, k, dtype))(key)

    def padded(self, toks: Any) -> Any:
        toks = np.asarray(toks, np.int32)
        if len(toks) > self.pad_to:
            raise ValueError(f"a compared sequence of {len(toks)} tokens is longer than pad_to={self.pad_to}")
        return jnp.asarray(np.concatenate([toks, np.zeros((self.pad_to - len(toks),), np.int32)]))

    def hidden(self, toks: Any) -> Any:
        return self.ref.hidden(self.padded(toks), self.layer, self.gw, self.arch, self.quant)

    def mtp_hidden(self, toks: Any, h: Any, hidden_shift: int = 0) -> Any:
        return self.ref.mtp_hidden(self.padded(toks), h, self.gw, self.arch, self.quant, hidden_shift)

    def logits(self, h: Any, start: int, n: int) -> Any:
        """The head on rows ``start .. start + n - 1`` of ``h``: one program a row count, wherever they start."""
        return self.ref.head(_rows_from(h, start, n), self.gw, self.quant, on_host=False)


def reference_logits(arch: Dict[str, Any], seed: int, sample: Sample, seqs: List[np.ndarray],
                     quant: Any = None, hidden_shift: int = 0, pad_to: int = 0):
    """(the stack's, the module's) reference logits at positions p .. p+k-1 of
    each sequence. ``hidden_shift`` 1 wires the module one position off."""
    ref = reference(arch, seed, pad_to or max(len(t) for t in seqs), quant)
    verify, draft = [], []
    for (p, k), toks in zip(sample, seqs):
        h = ref.hidden(toks)
        verify.append(np.asarray(ref.logits(h, p, k), np.float32))
        draft.append(np.asarray(ref.logits(ref.mtp_hidden(toks, h, hidden_shift), p, k), np.float32))
    return verify, draft


def compare(ctx: Any, eng: Any, params: Any, cfg: Any) -> Dict[str, Tuple[float, float]]:
    """{name: (relative logit error, limit)} for the stack and the module, on
    an engine whose rows have been released; frees its pool, and on the chip
    the program's weights, before the reference runs."""
    t0 = time.perf_counter()
    sample = [tuple(s) for s in ctx.traffic["check_sample"]]
    seqs = sample_tokens(ctx.seed, opcount.dims(ctx.arch)["vocab"], sample)
    verify, draft, eng.pools = program_logits(
        params, cfg, eng.pools, eng.alloc, eng.max_batch, eng.max_blocks, eng.block_size, sample, seqs,
        prefill_rows=ctx.traffic.get("first_wave_group", 0))
    del eng.pools
    t1 = time.perf_counter()
    if not ctx.rehearsal:
        # The program's part is over: give its weights' memory back too, so that the
        # reference (2.5 GB of embedding, head, dense layer and module, a 1.3 GB layer
        # at a time, float32 attention over 4 k tokens) has the chip; nothing after
        # this reads them.
        for leaf in jax.tree.leaves(params):
            leaf.delete()
    ref_verify, ref_draft = reference_logits(
        ctx.arch, ctx.seed, sample, seqs, pad_to=ctx.traffic["engine"]["max_seq"])
    ctx.log(f"round's logits: the program's side {t1 - t0:.1f} s, the reference's {time.perf_counter() - t1:.1f} s")
    limits = ctx.arch["check_limits"]
    return {
        "verify_logits_rel_err": (serving_check.rel_err(verify, ref_verify), limits["verify_logits_rel_err"]),
        "draft_logits_rel_err": (serving_check.rel_err(draft, ref_draft), limits["draft_logits_rel_err"]),
    }


def token_regrets(arch: Dict[str, Any], seed: int, emitted: serving_check.Emitted, pad_to: int) -> np.ndarray:
    """``serving_check.token_regrets``'s number for every emitted token (how far
    the reference's logit of the token lies below its best at that position, in
    standard deviations of the row), with the head run on the compared rows
    only (those that score an emitted token) and the stack at the length the
    logit comparison compiled it for."""
    ref = reference(arch, seed, pad_to)
    # one window of rows for every request (one compiled head): as long as the longest output,
    # ending at the row that scores the request's last token
    n_rows = min(pad_to - 1, max(len(tokens) for _, tokens in emitted))
    out = []
    for prompt, tokens in emitted:
        seq = np.asarray(list(prompt) + list(tokens), np.int32)
        start = max(len(seq) - 1 - n_rows, 0)  # row t scores token t + 1
        scored = np.zeros((n_rows,), np.int32)
        scored[: len(seq) - 1 - start] = seq[start + 1 :]
        r = np.asarray(_regrets(ref.logits(ref.hidden(seq), start, n_rows), jnp.asarray(scored)), np.float64)
        out.append(r[len(prompt) - 1 - start : len(seq) - 1 - start])
    return np.concatenate(out)


def compare_tokens(ctx: Any, emitted: serving_check.Emitted, pad_to: int) -> Tuple[float, float]:
    """(largest regret of the tokens the engine emitted for the sampled
    requests, limit): ``serving_check.compare_tokens`` at a fraction of its
    cost; run after ``compare`` has freed the pool and the weights."""
    if not emitted:
        raise RuntimeError("no finished request to check the engine's tokens on")
    t0 = time.perf_counter()
    regrets = token_regrets(ctx.arch, ctx.seed, emitted, pad_to)
    ctx.log(f"engine tokens: {len(emitted)} requests, {regrets.size} tokens compared, "
            f"{int(np.sum(regrets > 0))} not the reference's argmax, largest regret {regrets.max():.6g} "
            f"({time.perf_counter() - t0:.1f} s)")
    return float(regrets.max()), ctx.arch["check_limits"]["engine_token_regret"]
