"""Output-integrity primitives: golden probes, fingerprints, digests.

PR 9 made the XLA gather fallback the single source of numeric truth for
the ragged Pallas kernel at TEST time. This module extends that idea to
LIVE serving: a replica that still answers health checks can nonetheless
be silently wrong — a bit-flipped weight shard, a corrupted shared
prefix-cache page, a miscompiled kernel — and nothing in the crash/hang
fleet machinery (PR 8) notices, because the loop keeps turning. The
detectors here all compare CURRENT state against something pinned while
the replica was known-good:

  golden probes       seeded prompts whose greedy continuations are pinned
                      once at startup from the reference ``generate`` path
                      (the same oracle every bit-identity test uses); the
                      router re-runs them per replica through the normal
                      admission lane and any token mismatch is proof of
                      divergence, whatever the root cause;
  weight fingerprint  one cheap device-side reduction over the param tree,
                      pinned at loop start and recomputed on an interval —
                      catches in-place weight corruption without hashing
                      gigabytes host-side;
  KV page digests     blake2b over a pool block's bytes, recorded when the
                      block is published into the cross-request prefix
                      cache and re-verified on acquire — a corrupted
                      shared page re-prefills privately instead of
                      poisoning every future hit;
  array digests       content checksums for checkpoint leaves, verified on
                      restore like the existing torn/truncated fallback.

Everything is gated off by default and costs nothing when off; the probe
comparison itself happens host-side on already-materialized token lists.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np


class IntegrityError(RuntimeError):
    """A detector fired: observed state contradicts pinned reference state."""


# ---------------------------------------------------------------------------
# Golden probes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GoldenProbe:
    """One pinned probe: a prompt and its reference greedy continuation."""

    prompt: Tuple[int, ...]
    expected: Tuple[int, ...]


def probe_prompts(
    n_probes: int, probe_len: int, vocab_size: int, seed: int = 20260805
) -> List[List[int]]:
    """Deterministic probe prompts. Every probe shares the first
    ``probe_len - 1`` tokens and differs in its LAST token only: with the
    prefix cache on, probe #0 publishes the shared prefix blocks and every
    later probe re-acquires them — so the probes continuously exercise the
    cached-KV read path and a corrupted shared page shows up as probe
    divergence, not just as a checksum event."""
    if n_probes < 1:
        raise ValueError(f"n_probes must be >= 1, got {n_probes}")
    if probe_len < 2:
        raise ValueError(f"probe_len must be >= 2, got {probe_len}")
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, vocab_size, size=probe_len - 1).tolist()
    return [
        prefix + [int(rng.randint(0, vocab_size))] for _ in range(n_probes)
    ]


def build_probe_set(
    params: Any,
    cfg: Any,
    *,
    n_probes: int = 2,
    probe_len: int = 9,
    max_new: int = 4,
    seed: int = 20260805,
) -> List[GoldenProbe]:
    """Pin the probe set: greedy continuations from the reference
    ``generate`` path (batch-1 fixed-count decode — deliberately NOT the
    serving engine, so the pin is independent of the machinery it later
    judges). Call once at startup, before traffic."""
    import jax
    import jax.numpy as jnp

    from pretraining_llm_tpu.generation.generate import generate

    probes = []
    for prompt in probe_prompts(n_probes, probe_len, cfg.vocab_size, seed):
        toks = generate(
            params, cfg, jnp.asarray([prompt], jnp.int32), max_new,
            jax.random.key(7), temperature=0.0,
        )
        probes.append(
            GoldenProbe(tuple(prompt), tuple(np.asarray(toks)[0].tolist()))
        )
    return probes


# ---------------------------------------------------------------------------
# Weight fingerprint
# ---------------------------------------------------------------------------


def weight_fingerprint(params: Any) -> float:
    """One device-side reduction over every floating AND integer leaf ->
    one scalar pull. Position-weighted sums (not abs) so both value
    corruption and leaf swaps move it; float32 accumulation is
    deterministic for a fixed tree on a fixed platform, which is all the
    pinned-vs-current and fleet-wide comparisons need. Integer leaves are
    the int8 codes of quantized serving params (models/quantize.py) —
    excluding them would leave most of a quantized replica's weight bytes
    outside the detector. Cost: one fused reduce + ONE host sync — cheap
    enough for an interval loop, never on the per-token path."""
    import jax
    import jax.numpy as jnp

    leaves = [
        leaf for leaf in jax.tree_util.tree_leaves(params)
        if hasattr(leaf, "dtype")
        and (
            jnp.issubdtype(leaf.dtype, jnp.floating)
            or jnp.issubdtype(leaf.dtype, jnp.integer)
        )
    ]
    total = _fingerprint_reduce(leaves)
    return float(np.asarray(total))


_REDUCE_JIT = None  # lazily-built module-level jit: one trace per tree shape


def _fingerprint_reduce(leaves: Sequence[Any]):
    global _REDUCE_JIT
    import jax
    import jax.numpy as jnp

    if _REDUCE_JIT is None:

        def _reduce(ls):
            acc = jnp.float32(0.0)
            for i, leaf in enumerate(ls):
                acc = acc + jnp.float32(i + 1) * jnp.sum(
                    leaf.astype(jnp.float32)
                )
            return acc

        _REDUCE_JIT = jax.jit(_reduce)
    return _REDUCE_JIT(list(leaves))


# ---------------------------------------------------------------------------
# KV page + array digests
# ---------------------------------------------------------------------------


def kv_block_digest(pools: Any, block: int) -> str:
    """Content digest of ONE pool block across every pool leaf (K, V, and
    quantization scales alike). This is a device pull per leaf, so callers
    gate it behind the ``kv_checksum`` knob — it runs at publish/acquire
    boundaries, never inside the decode window."""
    import jax

    h = hashlib.blake2b(digest_size=16)
    for leaf in jax.tree_util.tree_leaves(pools):
        # a pool leaf is (n_blocks, ...): see make_paged_kv_pool
        arr = np.ascontiguousarray(jax.device_get(leaf[block]))
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def array_digest(arr: np.ndarray) -> str:
    """Content checksum for a checkpoint leaf: dtype + shape + bytes, so a
    silently truncated or bit-flipped ``.npy`` cannot verify."""
    arr = np.ascontiguousarray(arr)
    h = hashlib.blake2b(digest_size=16)
    h.update(str(arr.dtype).encode())
    h.update(repr(arr.shape).encode())
    h.update(arr.tobytes())
    return h.hexdigest()


def verify_array(arr: np.ndarray, expected: Optional[str], name: str) -> None:
    """Raise IntegrityError unless ``arr`` digests to ``expected``.
    ``expected=None`` (a pre-checksum checkpoint) verifies vacuously —
    old checkpoints stay restorable."""
    if expected is None:
        return
    got = array_digest(arr)
    if got != expected:
        raise IntegrityError(
            f"checksum mismatch for {name}: expected {expected}, got {got} "
            f"(corrupted checkpoint leaf)"
        )
