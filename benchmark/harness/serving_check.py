"""Serving `correct`, against the plain reference's full forward pass: the
logits of prefill-then-decode through the paged cache (the precision check),
and the tokens the engine itself emitted in the measured window (the check
of the engine's own path: page allocation, admission, merge, the scanned
decode program and its fused sampling).

**Logits.** The program side uses the functions the engine's lanes are built from
(``paged.prefill_into_pool``, ``paged.paged_decode_logits``) on the engine's
own parameters and pool, with the decode batch at the cell's width. Tokens
are teacher-forced from the seed, so rounding never forks the sequence.
The number compared is the relative error of all compared logits,
``||program - reference|| / ||reference||``: an average over some 10**5
values, steady from seed to seed.

**Engine tokens.** The drivers keep a few requests that finished in the
window, prompt and emitted tokens. The reference runs over each whole
sequence (teacher-forced on what the engine emitted, so a near-tie never
forks it) and gives every emitted token its *regret*: how far the reference's
logit of that token lies below the reference's best logit at that position,
in standard deviations of that row of logits. A greedy engine in bfloat16
picks the reference's own argmax or a near-tie (regret of a few hundredths); a
token read through a wrong page, row or length is a draw from the vocabulary
(regret of several). The number compared is the largest regret of all sampled
tokens, so that one wrong token in thousands shows. It is a check of meaning,
not of precision: PERF.md section 2 says what the int8 path reads on it.

Limits: the configuration file's ``check_limits``; PERF.md section 2 gives the
readings each stands between (sound bf16 runs below, the control above).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np



Sample = List[Tuple[int, int]]  # (prompt tokens, teacher-forced decode steps) per sequence


def sample_tokens(seed: int, vocab: int, sample: Sample) -> List[np.ndarray]:
    rng = np.random.default_rng([int(seed), 7])
    return [rng.integers(0, vocab, p + k, dtype=np.int32) for p, k in sample]


def program_logits(params: Any, cfg: Any, pools: Any, alloc: Any, max_batch: int,
                   max_blocks: int, block_size: int, sample: Sample,
                   seqs: List[np.ndarray]) -> Tuple[List[np.ndarray], Any]:
    """For each sequence, the logits after the prompt and after each forced token."""
    import jax.numpy as jnp

    from pretraining_llm_tpu.generation import paged

    tables = np.zeros((max_batch, max_blocks), np.int32)
    seq_lens = np.zeros((max_batch,), np.int32)
    out: List[List[np.ndarray]] = []
    for r, ((p, k), toks) in enumerate(zip(sample, seqs)):
        ids = alloc.alloc(paged.required_blocks(p + k + 1, block_size))
        if ids is None:
            raise RuntimeError("pool has no room for the correctness sample")
        n_pre = paged.required_blocks(p, block_size)
        last, pools = paged.prefill_into_pool(params, cfg, pools, toks[:p].tolist(), ids[:n_pre])
        tables[r, : len(ids)] = ids
        seq_lens[r] = p
        out.append([np.asarray(last, np.float32)])
    steps = max(k for _, k in sample)
    for j in range(steps):
        tok = np.zeros((max_batch,), np.int32)
        for r, ((p, k), toks) in enumerate(zip(sample, seqs)):
            tok[r] = toks[p + j] if j < k else 0
        logits, pools = paged.paged_decode_logits(
            params, pools, jnp.asarray(tok), jnp.asarray(tables), jnp.asarray(seq_lens), cfg=cfg
        )
        host = np.asarray(logits, np.float32)
        for r, (p, k) in enumerate(sample):
            if j < k:
                out[r].append(host[r])
                seq_lens[r] += 1
    return [np.stack(o) for o in out], pools


def reference_forward(arch: Dict[str, Any], seed: int, quant: Any = None):
    """``tokens -> logits (T, V)`` of the plain reference on the seed's weights,
    made one layer at a time in the served dtype and computed in float32."""
    import importlib

    import jax
    import jax.numpy as jnp

    from harness import weights

    ref = importlib.import_module(f"references.{arch['family']}")
    key = weights.seed_key(seed)
    dtype = jnp.dtype(arch["serving_dtype"])
    make_layer = jax.jit(lambda k, l: weights.layer(arch, k, l, dtype))
    layer = lambda l: make_layer(key, l)
    gw = jax.jit(lambda k: weights.globals_(arch, k, dtype))(key)
    return lambda toks: ref.forward(jnp.asarray(toks), layer, gw, arch, quant=quant)


def reference_logits(arch: Dict[str, Any], seed: int, sample: Sample, seqs: List[np.ndarray],
                     quant: Any = None) -> List[np.ndarray]:
    """Rows p-1 .. p+k-1 of the reference's logits for each whole sequence."""
    forward = reference_forward(arch, seed, quant)
    return [np.asarray(forward(toks)[p - 1 : p + k], np.float32)
            for (p, k), toks in zip(sample, seqs)]


def rel_err(prog: List[np.ndarray], ref: List[np.ndarray]) -> float:
    num = sum(float(np.sum((a.astype(np.float64) - b) ** 2)) for a, b in zip(prog, ref))
    den = sum(float(np.sum(b.astype(np.float64) ** 2)) for b in ref)
    return (num / den) ** 0.5


def compare(ctx: Any, eng: Any, params: Any, cfg: Any) -> Tuple[float, float]:
    """(relative logit error, limit) on an engine whose rows have been
    released; frees the engine's pool before the reference runs."""
    from harness import opcount

    sample = [tuple(s) for s in ctx.traffic["check_sample"]]
    seqs = sample_tokens(ctx.seed, opcount.dims(ctx.arch)["vocab"], sample)
    prog, eng.pools = program_logits(
        params, cfg, eng.pools, eng.alloc, eng.max_batch, eng.max_blocks, eng.block_size,
        sample, seqs,
    )
    del eng.pools
    ref = reference_logits(ctx.arch, ctx.seed, sample, seqs)
    return rel_err(prog, ref), ctx.arch["check_limits"]["logits_rel_err"]


Emitted = List[Tuple[List[int], List[int]]]  # (prompt, tokens the engine emitted) per request


def token_regrets(arch: Dict[str, Any], seed: int, emitted: Emitted,
                  pad_to: int) -> Tuple[np.ndarray, np.ndarray]:
    """The regret of every emitted token of every request (see the module's
    text), and beside it the control's: each token read against the logits of
    the position before its own, what an engine one off in a length or a page
    table would be held to. Sequences are padded to ``pad_to`` (one compiled
    shape; the model is causal, so the padding changes nothing before it)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def regrets(logits, toks):
        def against(rows):
            got = jnp.take_along_axis(rows, jnp.roll(toks, -1)[:, None], axis=-1)[:, 0]
            return (jnp.max(rows, axis=-1) - got) / jnp.std(rows, axis=-1)

        return against(logits), against(jnp.roll(logits, 1, axis=0))

    forward = reference_forward(arch, seed)
    own, off = [], []
    for prompt, tokens in emitted:
        seq = np.zeros((pad_to,), np.int32)
        n = len(prompt) + len(tokens)
        if n > pad_to:
            raise ValueError("a sampled request is longer than the engine's max_seq")
        seq[:n] = list(prompt) + list(tokens)
        r, r_off = regrets(forward(seq), jnp.asarray(seq))
        rows = slice(len(prompt) - 1, n - 1)  # row t scores token t + 1
        own.append(np.asarray(r, np.float64)[rows])
        off.append(np.asarray(r_off, np.float64)[rows])
    return np.concatenate(own), np.concatenate(off)


def wrong_rows(emitted: Emitted) -> Emitted:
    """The control of a wrong row: each request's emitted tokens behind the
    next request's prompt (cut or filled to the same length), what an engine
    reading another row's pages would be held to."""
    out = []
    for i, (prompt, tokens) in enumerate(emitted):
        other = emitted[(i + 1) % len(emitted)][0]
        out.append((other[: len(prompt)] + prompt[len(other):], tokens))
    return out


def compare_tokens(ctx: Any, emitted: Emitted, pad_to: int) -> Tuple[float, float]:
    """(largest regret of the tokens the engine emitted for the sampled
    requests, limit); run after ``compare`` has freed the pool."""
    if not emitted:
        raise RuntimeError("no finished request to check the engine's tokens on")
    regrets, _ = token_regrets(ctx.arch, ctx.seed, emitted, pad_to)
    ctx.log(f"engine tokens: {len(emitted)} requests, {regrets.size} tokens compared, "
            f"{int(np.sum(regrets > 0))} not the reference's argmax, largest regret {regrets.max():.6g}")
    return float(regrets.max()), ctx.arch["check_limits"]["engine_token_regret"]
