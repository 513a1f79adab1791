"""KV-page migration: cached prefix chains serialized into wire frames.

The fleet machinery so far only moves REQUESTS between hosts — a decode
worker re-prefills every prefix some other worker already computed. This
module makes KV state itself migratable, page by page:

  snapshot    ``snapshot_chain`` pulls the longest cached block chain for
              a prompt out of a sender engine's pool: per pool leaf (K,
              V, and quantization-scale leaves alike, every layer's) one
              contiguous byte string per page, plus a content digest
              computed with exactly the ``kv_block_digest`` algorithm —
              the same digest ``kv_checksum`` verifies at acquire, so a
              migrated page carries its integrity identity with it;
  framing     ``split_frames``/``join_frames`` batch pages into bounded
              ``kv_page`` wire frames (base64 inside the JSON framing of
              frontend/wire.py). Frames carry ``seq``/``n_frames`` so a
              torn transfer (missing or duplicated frame) is rejected as
              a unit, and ride the same ``g`` fence stamp as every other
              worker frame so stale-generation pages are dropped by the
              existing fence filters;
  adoption    ``adopt_chain`` inserts received pages into a receiver
              engine's pool BEHIND the prefix-cache publish path: verify
              each page's digest against its transported bytes, stop the
              chain at the first corrupt page (drop + count, never a
              wrong token — the request re-prefills what was dropped),
              scatter the accepted prefix into freshly reserved blocks,
              publish via ``PrefixCache.release_row`` (first writer
              wins: duplicate chains are freed back), and record the
              digest via ``set_checksum`` so verify-on-acquire guards
              migrated pages exactly like locally published ones.

Threading contract: ``snapshot_chain`` may run on any thread — it reads
only COMMITTED shared pages, pinned against eviction by an acquire-side
refcount, and pool arrays are immutable (a concurrent decode turn swaps
``engine.pools`` to a new array whose bytes at published blocks are
unchanged). ``adopt_chain`` WRITES ``engine.pools`` and must run on the
engine's loop thread (``EngineLoop.run_on_loop``) or a lost-update race
with the scheduler's own pools swap would corrupt live state.

Bit-identity story: every admission commits pool bytes through the
suffix-prefill lane as a pure function of the token's prompt prefix
(see ServingEngine._admit — int8-KV engines route even full misses
through it for exactly this reason), so a page computed on the prefill
tier is byte-identical to the page the decode tier would have computed
itself, and greedy outputs are unchanged by migration.
"""

from __future__ import annotations

import base64
import hashlib
from typing import Any, Dict, List, Optional

import numpy as np

# Per-frame payload budget for page data (pre-base64 bytes). Well under
# wire.MAX_FRAME_BYTES even after base64's 4/3 expansion plus JSON
# overhead; a single page larger than the budget still travels (one page
# per frame) — the hard frame cap in wire.encode_frame is the backstop.
KV_FRAME_BUDGET_BYTES = 8 * 1024 * 1024

# Transfer payload schema revision (inside the frames; the frame kinds
# themselves are negotiated via wire.PROTO_VERSION >= 3).
XFER_VERSION = 1


def _np_dtype(name: str) -> np.dtype:
    """Resolve a dtype name, including the ml_dtypes extensions
    (bfloat16 scale pools) plain numpy does not know."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _page_digest(arrays: List[np.ndarray]) -> str:
    """Content digest over one page's per-leaf arrays — byte-for-byte
    the ``resilience.integrity.kv_block_digest`` algorithm (dtype string
    then raw bytes, per leaf in tree order), computed host-side so one
    device pull serves both serialization and integrity."""
    h = hashlib.blake2b(digest_size=16)
    for arr in arrays:
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def snapshot_chain(
    engine: Any,
    prompt: List[int],
    *,
    max_pages: Optional[int] = None,
) -> Optional[Dict[str, Any]]:
    """Serialize the longest cached block chain covering ``prompt`` from
    ``engine``'s pool. Returns a transfer dict (see module docstring) or
    None when the engine has no prefix cache or no cached coverage.

    Safe from any thread: the chain's blocks are refcount-pinned via
    ``PrefixCache.acquire`` for the duration of the pull and released
    before returning, and only committed (published/shared) pages are
    ever read."""
    import jax

    cache = getattr(engine, "prefix_cache", None)
    if cache is None:
        return None
    cached_tokens, acquired = cache.acquire(prompt)
    if not acquired:
        return None
    try:
        blocks = acquired if max_pages is None else acquired[:max_pages]
        pools = engine.pools  # one read; see threading contract above
        leaves = jax.tree_util.tree_leaves(pools)
        bs = int(engine.block_size)
        layout: List[Dict[str, Any]] = []
        pages: List[Dict[str, Any]] = []
        for j, b in enumerate(blocks):
            arrays: List[np.ndarray] = []
            for leaf in leaves:
                arrays.append(np.ascontiguousarray(jax.device_get(leaf[b])))
            digest = _page_digest(arrays)
            expected = cache.checksum_of(b)
            if expected is not None and digest != expected:
                # The source page itself is corrupt: ship only the clean
                # prefix; the engine's own verify-on-acquire will deal
                # with the bad block on its next local hit.
                break
            if not layout:
                layout = [
                    {"dtype": str(a.dtype), "shape": list(a.shape)}
                    for a in arrays
                ]
            pages.append({
                "digest": digest,
                "leaves": [
                    base64.b64encode(a.tobytes()).decode("ascii")
                    for a in arrays
                ],
            })
    finally:
        cache.release_shared(acquired)
    if not pages:
        return None
    return {
        "v": XFER_VERSION,
        "block_size": bs,
        "tokens": [int(t) for t in prompt[: len(pages) * bs]],
        "layout": layout,
        "pages": pages,
    }


def transfer_bytes(xfer: Dict[str, Any]) -> int:
    """Decoded page-payload bytes of a transfer (the migrated-bytes
    accounting the fleet counters report)."""
    total = 0
    for page in xfer.get("pages", ()):
        for data in page["leaves"]:
            total += (len(data) * 3) // 4  # base64 -> raw, ignoring pad
    return total


def split_frames(
    xfer: Dict[str, Any], *, budget: int = KV_FRAME_BUDGET_BYTES
) -> List[Dict[str, Any]]:
    """Batch a transfer's pages into bounded frames. Frame 0 carries the
    header (tokens, layout, block size); every frame carries
    ``seq``/``n_frames`` so the receiver can detect a torn transfer.
    The caller adds routing fields (op, transfer id, fence stamp)."""
    if budget < 1:
        raise ValueError(f"frame budget must be >= 1, got {budget}")
    groups: List[List[Dict[str, Any]]] = []
    cur: List[Dict[str, Any]] = []
    cur_bytes = 0
    for page in xfer["pages"]:
        pb = sum((len(d) * 3) // 4 for d in page["leaves"])
        if cur and cur_bytes + pb > budget:
            groups.append(cur)
            cur, cur_bytes = [], 0
        cur.append(page)
        cur_bytes += pb
    groups.append(cur)  # header frame exists even for an empty transfer
    frames: List[Dict[str, Any]] = []
    for i, pgs in enumerate(groups):
        frame: Dict[str, Any] = {
            "seq": i, "n_frames": len(groups), "pages": pgs,
        }
        if i == 0:
            frame["v"] = xfer["v"]
            frame["block_size"] = xfer["block_size"]
            frame["tokens"] = xfer["tokens"]
            frame["layout"] = xfer["layout"]
        frames.append(frame)
    return frames


def join_frames(frames: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Reassemble a transfer from its frames (any arrival order).
    Raises ``ValueError`` on a torn transfer: missing/duplicate seq,
    inconsistent ``n_frames``, or a missing header."""
    if not frames:
        raise ValueError("torn kv transfer: no frames")
    n = frames[0].get("n_frames")
    by_seq: Dict[int, Dict[str, Any]] = {}
    for f in frames:
        if f.get("n_frames") != n:
            raise ValueError(
                f"torn kv transfer: inconsistent n_frames "
                f"({f.get('n_frames')} vs {n})"
            )
        seq = f.get("seq")
        if not isinstance(seq, int) or seq < 0 or seq >= n:
            raise ValueError(f"torn kv transfer: bad seq {seq!r} of {n}")
        if seq in by_seq:
            raise ValueError(f"torn kv transfer: duplicate seq {seq}")
        by_seq[seq] = f
    if len(by_seq) != n:
        missing = sorted(set(range(n)) - set(by_seq))
        raise ValueError(f"torn kv transfer: missing frames {missing}")
    head = by_seq[0]
    for key in ("v", "block_size", "tokens", "layout"):
        if key not in head:
            raise ValueError(f"torn kv transfer: header missing {key!r}")
    pages: List[Dict[str, Any]] = []
    for i in range(n):
        pages.extend(by_seq[i]["pages"])
    return {
        "v": head["v"],
        "block_size": head["block_size"],
        "tokens": head["tokens"],
        "layout": head["layout"],
        "pages": pages,
    }


def corrupt_first_page(xfer: Dict[str, Any]) -> bool:
    """Fault-injection hook (``corrupt_kv_migration``): flip one byte in
    the first page's first leaf, leaving the transported digest claiming
    the ORIGINAL bytes — the receiver must detect the mismatch and drop
    the page. Returns False when the transfer has no pages to corrupt."""
    pages = xfer.get("pages") or []
    if not pages:
        return False
    raw = bytearray(base64.b64decode(pages[0]["leaves"][0]))
    if not raw:
        return False
    raw[0] ^= 0xFF
    pages[0]["leaves"][0] = base64.b64encode(bytes(raw)).decode("ascii")
    return True


def adopt_chain(engine: Any, xfer: Dict[str, Any]) -> Dict[str, Any]:
    """Insert a received transfer's pages into ``engine``'s pool behind
    the prefix-cache publish path. MUST run on the engine's loop thread
    (``EngineLoop.run_on_loop``) — this swaps ``engine.pools``.

    Every page's digest is verified against its TRANSPORTED bytes before
    anything touches the pool; the chain is adopted up to the first
    corrupt page and the remainder dropped (the re-prefill fallback:
    requests simply miss the cache for what was dropped, so corruption
    can cost latency but never a wrong token). Returns
    ``{"inserted", "rejected", "published", "reason"}``."""
    import jax

    n_pages = len(xfer.get("pages") or [])

    def _bump(adopted: int, dropped: int) -> None:
        stats = getattr(engine, "stats", None)
        if isinstance(stats, dict):
            stats["kv_pages_adopted"] = (
                stats.get("kv_pages_adopted", 0) + adopted
            )
            stats["kv_pages_rejected"] = (
                stats.get("kv_pages_rejected", 0) + dropped
            )

    def _reject_all(reason: str) -> Dict[str, Any]:
        _bump(0, n_pages)
        return {
            "inserted": 0, "rejected": n_pages,
            "published": 0, "reason": reason,
        }

    cache = getattr(engine, "prefix_cache", None)
    if cache is None:
        return _reject_all("no_prefix_cache")
    if n_pages == 0:
        return _reject_all("empty")
    if int(xfer.get("v", -1)) != XFER_VERSION:
        return _reject_all("version_mismatch")
    bs = int(engine.block_size)
    if int(xfer["block_size"]) != bs:
        return _reject_all("block_size_mismatch")
    tokens = [int(t) for t in xfer["tokens"]]
    if len(tokens) < n_pages * bs:
        return _reject_all("short_tokens")
    leaves = jax.tree_util.tree_leaves(engine.pools)
    layout = xfer["layout"]
    if len(layout) != len(leaves):
        return _reject_all("layout_mismatch")
    for spec, leaf in zip(layout, leaves):
        if (
            tuple(spec["shape"]) != tuple(leaf.shape[1:])
            or str(spec["dtype"]) != str(leaf.dtype)
        ):
            return _reject_all("layout_mismatch")

    # Decode + verify host-side BEFORE touching the pool: a corrupt page
    # truncates the adoptable chain (pages after it would be unreachable
    # index entries — their digests chain through the dropped block).
    decoded: List[List[np.ndarray]] = []
    rejected_reason = ""
    for page in xfer["pages"]:
        if len(page["leaves"]) != len(layout):
            rejected_reason = "layout_mismatch"
            break
        arrays: List[np.ndarray] = []
        ok = True
        for spec, data in zip(layout, page["leaves"]):
            dtype = _np_dtype(spec["dtype"])
            raw = base64.b64decode(data)
            count = int(np.prod(spec["shape"], dtype=np.int64))
            if len(raw) != count * dtype.itemsize:
                ok = False
                break
            arrays.append(
                np.frombuffer(raw, dtype=dtype).reshape(spec["shape"])
            )
        if not ok or _page_digest(arrays) != page["digest"]:
            rejected_reason = rejected_reason or "checksum_mismatch"
            break
        decoded.append(arrays)
    k = len(decoded)
    if k == 0:
        return _reject_all(rejected_reason or "checksum_mismatch")

    blocks = engine.reserve_migration_blocks(k)
    if blocks is None:
        _bump(0, n_pages)
        return {
            "inserted": 0, "rejected": n_pages,
            "published": 0, "reason": "capacity",
        }
    # Scatter accepted pages into the reserved blocks, one functional
    # update per leaf (pool arrays are immutable; this is the write that
    # pins adopt_chain to the loop thread).
    pool_leaves, treedef = jax.tree_util.tree_flatten(engine.pools)
    for j, leaf in enumerate(pool_leaves):
        for i, b in enumerate(blocks):
            pool_leaves[j] = pool_leaves[j].at[b].set(
                decoded[i][j].astype(leaf.dtype)
            )
    engine.pools = jax.tree_util.tree_unflatten(treedef, pool_leaves)

    # Publish behind the normal path: n_shared=0, publish_len = the full
    # adopted span, so release_row indexes every block (duplicates of
    # chains this engine already holds go straight back to the
    # allocator — first writer wins) and returns the newly published
    # ids, which get the transported digest as their acquire-side
    # checksum exactly like a locally computed publish would.
    published = cache.release_row(tokens[: k * bs], blocks, 0, k * bs)
    digest_by_block = {
        b: page["digest"] for b, page in zip(blocks, xfer["pages"])
    }
    for b in published:
        cache.set_checksum(b, digest_by_block[b])
    _bump(k, n_pages - k)
    return {
        "inserted": k,
        "rejected": n_pages - k,
        "published": len(published),
        "reason": rejected_reason,
    }
